"""The plain reference's building blocks."""
import jax.numpy as jnp
import numpy as np
import pytest

from harness import plain


@pytest.mark.parametrize("kernel", [(3, 3), (1, 1)])
def test_a_strided_conv_keeps_every_stride_th_pixel_from_the_first(kernel):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(-1, 2, (2, 8, 8, 3)), jnp.float32)
    t = jnp.asarray(rng.integers(-1, 2, (*kernel, 3, 4)), jnp.int8)
    full = plain.conv(x, t, jnp.float32)
    assert full.shape == (2, 8, 8, 4)
    np.testing.assert_array_equal(plain.conv(x, t, jnp.float32, 2), full[:, ::2, ::2])
