"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; kernels must match them allclose (bit-exact for
ternary integer data).  Tests sweep shapes/dtypes against these.  Every dot
runs at ``HIGHEST`` precision: on TPU the default rounds f32 operands to
bf16, which would make the oracle itself inexact on real-valued inputs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ternary import unpack_ternary


def ternary_matmul_ref(x: jax.Array, w_packed: jax.Array, scale: jax.Array) -> jax.Array:
    """y = x @ unpack(w_packed) * scale   (scale broadcast over N)."""
    w = unpack_ternary(w_packed, axis=0).astype(jnp.float32)
    y = jnp.dot(x.astype(jnp.float32), w, precision=jax.lax.Precision.HIGHEST)
    y = y * scale.reshape(1, -1).astype(jnp.float32)
    return y.astype(x.dtype)


def ternary_conv2d_ref(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    *,
    fuse_ternary: bool = False,
    threshold=0.5,
    fuse_pool: int = 0,
    out_dtype=None,
) -> jax.Array:
    """SAME conv with ternary packed weights [KH,KW,C_in/4,C_out] + scale.
    ``threshold`` is a scalar or per-channel [C_out] vector (broadcast over
    pixels); ``fuse_pool`` > 1 appends a window/stride ``fuse_pool``
    max-pool after the optional ternarization — the oracle for the fused
    kernel epilogue."""
    w = unpack_ternary(w_packed, axis=2).astype(jnp.float32)
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.float32),
        w,
        window_strides=(1, 1),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    ) * scale.reshape(1, 1, 1, -1).astype(jnp.float32)
    if fuse_ternary:
        y = jnp.where(jnp.abs(y) > jnp.asarray(threshold, jnp.float32), jnp.sign(y), 0.0)
    if fuse_pool > 1:
        p = fuse_pool
        y = jax.lax.reduce_window(
            y, -jnp.inf, jax.lax.max, (1, p, p, 1), (1, p, p, 1), "VALID"
        )
    return y.astype(out_dtype or x.dtype)
