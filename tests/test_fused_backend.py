"""The fused conv->ternarize(->pool) backend: bit-exact vs the ref oracle.

The "fused" backend keeps the wide accumulator inside the kernel (CUTIE's
OPU -> ThFU -> pooling pipeline) and emits int8 ternary activations.  On
these nets every inter-layer tensor is ternary (or a dyadic mean of ternary
values), so fused and ref accumulate exactly in float32 and apply the same
per-channel scale + threshold: agreement must be *exact*, not allclose —
every assertion here is bit-equality.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.api.program import CutieProgram, check_backend
from repro.kernels.ops import ternary_conv2d
from repro.kernels.ref import ternary_conv2d_ref
from repro.kernels import quantize_pack_conv_weights


def _exact(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _deployed(graph, seed=0, calib=None):
    prog = CutieProgram(graph)
    params = prog.init(jax.random.PRNGKey(seed))
    return prog, prog.quantize(params, calib=calib)


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------

class TestFusedKernel:
    @pytest.mark.parametrize("hw", [(7, 5), (5, 9), (8, 8)])
    def test_odd_spatial_sizes(self, hw):
        h, w = hw
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(0), (2, h, w, 8)))
        wt = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16))
        wp, sc = quantize_pack_conv_weights(wt)
        got = ternary_conv2d(x, wp, sc, fuse_ternary=True, out_dtype=jnp.int8)
        want = ternary_conv2d_ref(x, wp, sc, fuse_ternary=True)
        assert got.dtype == jnp.int8
        _exact(got, want)

    def test_cout_not_divisible_by_block(self):
        """C_out=10 with block_cout=8: ops.py pads the weight tile and slices
        the valid channels back out — fused epilogue included."""
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(2), (1, 6, 6, 4)))
        wt = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 4, 10))
        wp, sc = quantize_pack_conv_weights(wt)
        got = ternary_conv2d(
            x, wp, sc, block_cout=8, fuse_ternary=True, fuse_pool=2,
            out_dtype=jnp.int8,
        )
        want = ternary_conv2d_ref(x, wp, sc, fuse_ternary=True, fuse_pool=2)
        assert got.shape == (1, 3, 3, 10)
        _exact(got, want)

    def test_fused_pool_matches_ternarize_then_pool(self):
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(4), (2, 8, 8, 8)))
        wt = jax.random.normal(jax.random.PRNGKey(5), (3, 3, 8, 8))
        wp, sc = quantize_pack_conv_weights(wt)
        fused = ternary_conv2d(x, wp, sc, fuse_ternary=True, fuse_pool=2,
                               out_dtype=jnp.int8)
        unpooled = ternary_conv2d_ref(x, wp, sc, fuse_ternary=True)
        pooled = jax.lax.reduce_window(
            unpooled, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
        )
        assert fused.shape == (2, 4, 4, 8)
        _exact(fused, pooled)


# ---------------------------------------------------------------------------
# program level
# ---------------------------------------------------------------------------

class TestFusedProgram:
    def test_pooled_and_unpooled_layers(self):
        """Graph mixing conv->pool (fused into the kernel epilogue) and a
        bare conv (no pool metadata): forward must equal ref exactly, and the
        quantize() tables must carry the per-layer fusion plan."""
        g = api.CutieGraph(
            name="mix", input_hw=(8, 8), input_ch=3, n_classes=4,
            layers=(api.conv2d(3, 8), api.pool(),        # fused pool
                    api.conv2d(8, 8),                    # unpooled
                    api.conv2d(8, 8), api.pool(),        # fused pool
                    api.flatten(), api.fc(2 * 2 * 8, 4)),
        )
        assert g.conv_pool_plan() == (2, 0, 2)
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(6), (3, 8, 8, 3)))
        _, dep = _deployed(g, calib=x)
        assert [e["pool"] for e in dep.tables["conv"]] == [2, 0, 2]
        _exact(dep.forward(x, backend="fused"), dep.forward(x, backend="ref"))

    def test_odd_spatial_program(self):
        """Odd input sizes (no pool layers divide them) run unfused-pool
        convs through the fused backend."""
        g = api.CutieGraph(
            name="odd", input_hw=(7, 5), input_ch=2, n_classes=3,
            layers=(api.conv2d(2, 8), api.conv2d(8, 8),
                    api.global_pool(), api.fc(8, 3)),
        )
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(7), (2, 7, 5, 2)))
        _, dep = _deployed(g, calib=x)
        _exact(dep.forward(x, backend="fused"), dep.forward(x, backend="ref"))

    def test_registry_cifar_exact(self):
        prog = api.get_net("cifar10_tnn_smoke")
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(8), (2, 16, 16, 3)))
        dep = prog.quantize(prog.init(jax.random.PRNGKey(0)), calib=x)
        _exact(dep.forward(x, backend="fused"), dep.forward(x, backend="ref"))

    def test_registry_dvs_exact_and_stream_equals_batch(self):
        """Temporal net: fused forward matches ref exactly, and streaming
        frame-by-frame through the TCN ring on the fused backend equals the
        batched window forward."""
        prog = api.get_net("dvs_cnn_tcn_smoke")
        frames = (jax.random.uniform(jax.random.PRNGKey(9), (2, 5, 32, 32, 2))
                  < 0.05).astype(jnp.float32)
        dep = prog.quantize(prog.init(jax.random.PRNGKey(0)), calib=frames)
        batch_fused = dep.forward(frames, backend="fused")
        _exact(batch_fused, dep.forward(frames, backend="ref"))
        session = dep.stream(batch=2, backend="fused")
        for t in range(frames.shape[1]):
            logits = session.step(frames[:, t])
        _exact(logits, batch_fused)

    @pytest.mark.parametrize("unknown", ["cuda", "pallas", "interpret"])
    def test_fused_in_backends_tuple(self, unknown):
        assert "fused" in api.BACKENDS
        check_backend("fused")
        with pytest.raises(ValueError, match="unknown backend"):
            check_backend(unknown)
