"""Per-kernel shape/dtype sweeps against the pure-jnp oracles in ref.py."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.ternary import pack_ternary, select_decode, select_masks, unpack_ternary
from repro.kernels import (
    quantize_pack_conv_weights,
    quantize_pack_matmul_weights,
    ternary_conv2d,
    ternary_matmul,
)
from repro.kernels.ref import ternary_conv2d_ref, ternary_matmul_ref


class TestSelectDecode:
    """The in-kernel packed-byte decode: 2-bit fields -> add/sub selects."""

    @pytest.mark.parametrize("axis", [0, 1, -1])
    def test_decode_matches_unpack(self, axis):
        rng = np.random.RandomState(11)
        t = jnp.asarray(rng.randint(-1, 2, (12, 8, 20)).astype(np.int8))
        p = pack_ternary(t, axis=axis)
        np.testing.assert_array_equal(
            np.asarray(select_decode(p, axis=axis)),
            np.asarray(unpack_ternary(p, axis=axis)),
        )

    @pytest.mark.parametrize("shape", [(24, 96), (3, 3, 8, 40)])
    def test_kernel_decode_matches_unpack(self, shape):
        """The decode inside both kernel bodies (int32-widened bit algebra,
        row interleave along the packed axis -2) equals unpack_ternary."""
        from repro.kernels.ternary_matmul import select_tile

        rng = np.random.RandomState(13)
        t = jnp.asarray(rng.randint(-1, 2, shape).astype(np.int8))
        p = pack_ternary(t, axis=-2)
        got = select_tile(p, jnp.float32)
        assert got.dtype == jnp.float32 and got.shape == t.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(t, np.float32))

    def test_masks_one_hot_per_trit(self):
        """plus/minus select lines are never both asserted (the OCU either
        adds, subtracts, or skips) and reproduce the trit as plus - minus."""
        rng = np.random.RandomState(12)
        t = jnp.asarray(rng.randint(-1, 2, (64,)).astype(np.int8))
        plus, minus = select_masks(pack_ternary(t, axis=0), axis=0)
        plus, minus = np.asarray(plus), np.asarray(minus)
        assert ((plus + minus) <= 1).all()
        np.testing.assert_array_equal(
            plus.astype(np.int8) - minus.astype(np.int8), np.asarray(t)
        )


class TestImplDispatch:
    """native / pallas(interpret) are one semantics: bit-equal on trit data."""

    def test_matmul_native_equals_interpret_bit_exact(self):
        rng = np.random.RandomState(21)
        x = jnp.asarray(rng.randint(-1, 2, (64, 128)).astype(np.float32))
        t = jnp.asarray(rng.randint(-1, 2, (128, 40)).astype(np.int8))
        wp = pack_ternary(t, axis=0)
        sc = jnp.asarray(np.abs(rng.randn(40)).astype(np.float32) + 0.1)
        y_nat = ternary_matmul(x, wp, sc, impl="native")
        y_int = ternary_matmul(x, wp, sc, impl="interpret")
        np.testing.assert_array_equal(np.asarray(y_nat), np.asarray(y_int))

    def test_conv_fused_pool_native_equals_interpret_bit_exact(self):
        rng = np.random.RandomState(22)
        x = jnp.asarray(rng.randint(-1, 2, (2, 8, 8, 16)).astype(np.float32))
        t = jnp.asarray(rng.randint(-1, 2, (3, 3, 16, 24)).astype(np.int8))
        wp = pack_ternary(t, axis=2)
        sc = jnp.asarray(np.abs(rng.randn(24)).astype(np.float32) + 0.1)
        kw = dict(fuse_ternary=True, threshold=0.3, fuse_pool=2,
                  out_dtype=jnp.int8)
        y_nat = ternary_conv2d(x, wp, sc, impl="native", **kw)
        y_int = ternary_conv2d(x, wp, sc, impl="interpret", **kw)
        assert y_nat.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(y_nat), np.asarray(y_int))

    def test_unknown_impl_raises(self):
        x = jnp.zeros((4, 8))
        wp = pack_ternary(jnp.zeros((8, 4), jnp.int8), axis=0)
        with pytest.raises(ValueError, match="unknown impl"):
            ternary_matmul(x, wp, jnp.ones((4,)), impl="cuda")


class TestBlockShapeErrors:
    """Raggedness at the wrapper level pads; at the kernel level it is a
    contract violation with an actionable ValueError (was: bare assert)."""

    def test_conv_pallas_non_dividing_block_raises(self):
        from repro.kernels.ternary_conv2d import ternary_conv2d_pallas

        rng = np.random.RandomState(31)
        t = jnp.asarray(rng.randint(-1, 2, (3, 3, 8, 10)).astype(np.int8))
        wp = pack_ternary(t, axis=2)
        x = jnp.zeros((1, 8, 8, 8))
        sc, th = jnp.ones((10,)), jnp.full((10,), 0.5)
        with pytest.raises(ValueError, match="cannot tile C_out"):
            ternary_conv2d_pallas(x, wp, sc, th, block_cout=8, interpret=True)

    def test_conv_wrapper_pads_non_dividing_block(self):
        """The public wrapper accepts the same geometry the kernel rejects."""
        rng = np.random.RandomState(32)
        x = jnp.asarray(rng.randint(-1, 2, (1, 8, 8, 8)).astype(np.float32))
        t = jnp.asarray(rng.randint(-1, 2, (3, 3, 8, 10)).astype(np.int8))
        wp = pack_ternary(t, axis=2)
        sc = jnp.ones((10,), jnp.float32)
        got = ternary_conv2d(x, wp, sc, block_cout=8, impl="interpret")
        want = ternary_conv2d_ref(x, wp, sc)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_matmul_pallas_block_errors(self):
        from repro.kernels.ternary_matmul import ternary_matmul_pallas

        x = jnp.zeros((64, 64))
        wp = pack_ternary(jnp.zeros((64, 64), jnp.int8), axis=0)
        sc = jnp.ones((64,))
        with pytest.raises(ValueError, match="block_k"):
            ternary_matmul_pallas(x, wp, sc, block_m=64, block_n=64,
                                  block_k=48, interpret=True)
        with pytest.raises(ValueError, match="must divide M"):
            ternary_matmul_pallas(x, wp, sc, block_m=48, block_n=64,
                                  block_k=64, interpret=True)

    def test_matmul_truncating_pack_raises(self):
        x = jnp.zeros((4, 16))
        wp = pack_ternary(jnp.zeros((8, 4), jnp.int8), axis=0)  # K=8 < 16
        with pytest.raises(ValueError, match="never truncates"):
            ternary_matmul(x, wp, jnp.ones((4,)))


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-4)


class TestTernaryMatmulKernel:
    @pytest.mark.parametrize("m,k,n", [
        (128, 512, 128),      # exactly one block
        (256, 1024, 384),     # multi-block every axis
        (8, 512, 128),        # M smaller than block
        (100, 100, 70),       # nothing aligned
        (1, 2048, 512),       # decode-like single row
        (384, 4, 128),        # K smaller than packing word
    ])
    def test_shapes_match_ref(self, m, k, n):
        x = jax.random.normal(jax.random.PRNGKey(m + n), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(k), (k, n), jnp.float32)
        wp, sc = quantize_pack_matmul_weights(w)
        got = ternary_matmul(x, wp, sc)
        k_pad = 4 * wp.shape[0]
        x_ref = jnp.pad(x, ((0, 0), (0, k_pad - k)))
        want = ternary_matmul_ref(x_ref, wp, sc)
        assert got.shape == (m, n)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(jnp.float32))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = jax.random.normal(jax.random.PRNGKey(0), (128, 512)).astype(dtype)
        w = jax.random.normal(jax.random.PRNGKey(1), (512, 128))
        wp, sc = quantize_pack_matmul_weights(w)
        got = ternary_matmul(x, wp, sc.astype(dtype))
        want = ternary_matmul_ref(x, wp, sc.astype(dtype))
        assert got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
        )

    def test_batch_dims(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 64, 256))
        w = jax.random.normal(jax.random.PRNGKey(3), (256, 96))
        wp, sc = quantize_pack_matmul_weights(w)
        got = ternary_matmul(x, wp, sc)
        want = ternary_matmul_ref(x.reshape(-1, 256), wp, sc).reshape(2, 3, 64, 96)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)

    def test_ternary_inputs_bit_exact(self):
        """All-ternary data must be exact (integer arithmetic)."""
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randint(-1, 2, (128, 512)).astype(np.float32))
        t = jnp.asarray(rng.randint(-1, 2, (512, 128)).astype(np.int8))
        wp = pack_ternary(t, axis=0)
        sc = jnp.ones((128,), jnp.float32)
        got = ternary_matmul(x, wp, sc)
        want = x @ jnp.asarray(t, jnp.float32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @given(
        m=st.integers(1, 40),
        kg=st.integers(1, 64),
        n=st.integers(1, 40),
        seed=st.integers(0, 10**6),
    )
    @settings(max_examples=15, deadline=None)
    def test_property_random_shapes(self, m, kg, n, seed):
        k = 4 * kg
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(m, k).astype(np.float32))
        t = jnp.asarray(rng.randint(-1, 2, (k, n)).astype(np.int8))
        wp = pack_ternary(t, axis=0)
        sc = jnp.asarray(np.abs(rng.randn(n)).astype(np.float32) + 0.1)
        got = ternary_matmul(x, wp, sc)
        want = ternary_matmul_ref(x, wp, sc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("impl", ["native", "interpret"])
    def test_block_size_invariance(self, impl):
        """Different BlockSpec tilings must give identical results (the
        native impl ignores block args entirely — same answer either way)."""
        x = jax.random.normal(jax.random.PRNGKey(4), (256, 1024))
        w = jax.random.normal(jax.random.PRNGKey(5), (1024, 256))
        wp, sc = quantize_pack_matmul_weights(w)
        y1 = ternary_matmul(x, wp, sc, block_m=128, block_n=128, block_k=512, impl=impl)
        y2 = ternary_matmul(x, wp, sc, block_m=64, block_n=256, block_k=256, impl=impl)
        y3 = ternary_matmul(x, wp, sc, block_m=256, block_n=64, block_k=1024, impl=impl)
        # different K-split orders differ only by f32 reduction-order noise
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y3), rtol=1e-4, atol=1e-4)


class TestTernaryConv2dKernel:
    @pytest.mark.parametrize("b,h,w,cin,cout", [
        (1, 8, 8, 16, 32),
        (2, 16, 16, 96, 96),    # CUTIE native layer
        (1, 64, 64, 96, 96),    # CUTIE max feature map
        (2, 32, 32, 3, 96),     # CIFAR input layer (c_in padded to 4)
        (1, 24, 1, 96, 96),     # mapped TCN layer, D=1
        (1, 3, 8, 96, 96),      # mapped TCN layer, D=8
    ])
    def test_shapes_match_ref(self, b, h, w, cin, cout):
        x = jax.random.normal(jax.random.PRNGKey(h * w), (b, h, w, cin))
        wt = jax.random.normal(jax.random.PRNGKey(cout), (3, 3, cin, cout))
        wp, sc = quantize_pack_conv_weights(wt)
        got = ternary_conv2d(x, wp, sc)
        x_ref = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 4 * wp.shape[2] - cin)))
        want = ternary_conv2d_ref(x_ref, wp, sc)
        assert got.shape == (b, h, w, cout)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 8, 32)).astype(dtype)
        wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 32, 64))
        wp, sc = quantize_pack_conv_weights(wt)
        got = ternary_conv2d(x, wp, sc.astype(dtype))
        want = ternary_conv2d_ref(x, wp, sc.astype(dtype))
        assert got.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
        )

    def test_fused_ternarization(self):
        """The fused epilogue = CUTIE's in-OCU thresholding; outputs ternary."""
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randint(-1, 2, (2, 12, 12, 32)).astype(np.float32))
        wt = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 32, 32))
        wp, sc = quantize_pack_conv_weights(wt)
        got = ternary_conv2d(x, wp, sc, fuse_ternary=True, threshold=0.3)
        want = ternary_conv2d_ref(x, wp, sc, fuse_ternary=True, threshold=0.3)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert set(np.unique(np.asarray(got))).issubset({-1.0, 0.0, 1.0})

    def test_all_ternary_bit_exact(self):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randint(-1, 2, (1, 16, 16, 96)).astype(np.float32))
        t = jnp.asarray(rng.randint(-1, 2, (3, 3, 96, 96)).astype(np.int8))
        wp = pack_ternary(t, axis=2)
        sc = jnp.ones((96,), jnp.float32)
        got = ternary_conv2d(x, wp, sc)
        want = jax.lax.conv_general_dilated(
            x, t.astype(jnp.float32), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_mapped_tcn_through_conv_kernel(self):
        """End-to-end paper §4 path: dilated 1-D conv -> 2-D mapping -> the
        Pallas conv kernel must equal the dilated reference exactly."""
        from repro.core.tcn import (
            dilated_causal_conv1d, project_weights_to_2d, wrap_time_axis,
            unwrap_time_axis,
        )
        rng = np.random.RandomState(7)
        tc = 96
        x = jnp.asarray(rng.randint(-1, 2, (1, 24, tc)).astype(np.float32))
        w1d = jnp.asarray(rng.randint(-1, 2, (3, tc, tc)).astype(np.float32))
        for d in (1, 2, 4, 8):
            y_ref = dilated_causal_conv1d(x, w1d, d)
            z = wrap_time_axis(x, d)
            k2d = project_weights_to_2d(w1d)
            # causal row padding (2,0) is part of the mapping; the Pallas
            # kernel is SAME-padded (1,1), so pre-pad one extra top row and
            # keep the first Q output rows.
            zp = jnp.pad(z, ((0, 0), (1, 0), (0, 0), (0, 0)))
            wp = pack_ternary(k2d.astype(jnp.int8), axis=2)
            sc = jnp.ones((tc,), jnp.float32)
            y2d = ternary_conv2d(zp, wp, sc)[:, : z.shape[1], :, :]
            got = unwrap_time_axis(y2d, 24)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(y_ref))
