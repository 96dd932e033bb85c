"""Plan-driven kernel autotuning (`repro.kernels.autotune`).

Pins: the selection rule (plan-derived block == TileAssign width on uniform
<=3x3 layers, measured fallback elsewhere), determinism, the deploy/executor
threading (`kernel_block_plan` over `DeployedProgram.plan`, artifact-loaded
execution), and
the end-to-end bit-exactness of the fallback path on the 5x5-stem net the
plan cannot schedule uniformly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.kernels.autotune import (
    KernelBlock,
    block_for_layer,
    kernel_block_plan,
    tpu_legal,
)
from repro.sim.plan import lower


def _deploy(name, batch=2, seed=0):
    prog = api.get_net(name)
    g = prog.graph
    rng = np.random.RandomState(seed)
    if g.is_temporal:
        x = jnp.asarray(rng.randint(-1, 2, (batch, g.tcn_steps, *g.input_hw,
                                            g.input_ch)).astype(np.float32))
    else:
        x = jnp.asarray(rng.randint(-1, 2, (batch, *g.input_hw,
                                            g.input_ch)).astype(np.float32))
    return prog.quantize(prog.init(jax.random.PRNGKey(seed)), calib=x), x


class TestSelectionRule:
    def test_uniform_small_window_layers_are_plan_derived(self):
        """Every <=3x3 conv/tcn layer with one TPU-legal tile width gets
        that width; every block, plan or fallback, is one the TPU tiles."""
        for name in api.list_nets():
            plan = lower(api.get_graph(name))
            for lp in plan.layers:
                if lp.kind not in ("conv2d", "tcn"):
                    continue
                kb = block_for_layer(lp)
                widths = lp.cout_tile_widths
                if (len(widths) == 1 and lp.kh <= 3 and lp.kw <= 3
                        and tpu_legal(widths[0], lp.c_out)):
                    assert kb == KernelBlock(widths[0], "plan"), (name, lp.index)
                else:
                    assert kb.source == "fallback", (name, lp.index)
                assert lp.c_out % kb.block_cout == 0, (name, lp.index)
                assert tpu_legal(kb.block_cout, lp.c_out), (name, lp.index)

    def test_wide_stem_uses_fallback(self):
        """cifar10_tnn_wide's 5x5 stem — the analytic_schedulable=False net —
        leaves the plan-derived regime; the fallback must still divide and
        be a block the TPU accepts (192 = one whole-width block)."""
        plan = lower(api.get_graph("cifar10_tnn_wide"))
        stem = next(lp for lp in plan.layers if lp.kind == "conv2d")
        assert (stem.kh, stem.kw) == (5, 5)
        kb = block_for_layer(stem)
        assert kb.source == "fallback"
        assert kb.block_cout == stem.c_out == 192
        assert tpu_legal(kb.block_cout, stem.c_out)

    def test_fallback_prefers_largest_dividing_block(self):
        from repro.kernels.autotune import _fallback_block

        assert _fallback_block(192) == 192  # 96 would not tile on TPU
        assert _fallback_block(256) == 128
        assert _fallback_block(96) == 96
        assert _fallback_block(8) == 8
        # no lane multiple divides -> one ragged block, no padding in ops
        assert _fallback_block(10) == 10

    def test_wide_net_96_tiles_are_not_tpu_blocks(self):
        """cifar10_tnn_wide's 3x3 layers tile 192 channels in two 96-wide
        OCU passes; 96 does not tile a 192-wide array on TPU, so they fall
        back to one whole-width block."""
        plan = lower(api.get_graph("cifar10_tnn_wide"))
        convs = [lp for lp in plan.layers if lp.kind == "conv2d"][1:]
        assert convs
        for lp in convs:
            assert lp.cout_tile_widths == (96,)
            assert block_for_layer(lp) == KernelBlock(192, "fallback")

    def test_non_conv_layer_raises(self):
        plan = lower(api.get_graph("cifar10_tnn_smoke"))
        fc = next(lp for lp in plan.layers if lp.kind == "fc")
        with pytest.raises(ValueError, match="no conv kernel block"):
            block_for_layer(fc)


class TestDeterminism:
    def test_same_graph_same_blocks(self):
        """Autotuning is a pure function of the plan: two independent
        lowerings of the same graph yield identical TileAssigns and blocks."""
        for name in ("cifar10_tnn_smoke", "dvs_cnn_tcn_smoke"):
            g = api.get_graph(name)
            p1, p2 = lower(g), lower(g)
            for l1, l2 in zip(p1.layers, p2.layers):
                assert l1.tiles == l2.tiles
            assert kernel_block_plan(p1) == kernel_block_plan(p2)


class TestDeployThreading:
    def test_kernel_blocks_structure(self):
        dep, _ = _deploy("dvs_cnn_tcn_smoke")
        blocks = kernel_block_plan(dep.plan)
        assert set(blocks) == {"conv", "tcn"}
        assert len(blocks["conv"]) == len(dep.tables["conv"])
        assert len(blocks["tcn"]) == len(dep.tables["tcn"])
        assert all(isinstance(b, KernelBlock) for bs in blocks.values()
                   for b in bs)

    def test_fallback_net_fused_bit_exact(self):
        """The designed fallback exerciser end-to-end: fused (autotuned
        blocks) and bitsim must stay bit-equal to the ref oracle."""
        dep, x = _deploy("cifar10_tnn_wide_smoke")
        assert any(b.source == "fallback"
                   for b in kernel_block_plan(dep.plan)["conv"])
        ref = np.asarray(dep.forward(x, backend="ref"))
        for backend in ("fused", "bitsim"):
            got = np.asarray(dep.forward(x, backend=backend))
            np.testing.assert_array_equal(got, ref)

    def test_loaded_artifact_uses_plan_blocks(self, tmp_path):
        """An artifact round-trip keeps the autotuned packed path bit-exact
        — the loader derives blocks from the shipped plan, no graph."""
        from repro.artifact import load, save

        dep, x = _deploy("cifar10_tnn_wide_smoke", seed=1)
        path = tmp_path / "wide.cutie"
        save(dep, str(path))
        loaded = load(str(path))
        ref = np.asarray(dep.forward(x, backend="ref"))
        got = np.asarray(loaded.forward(x, backend="fused"))
        np.testing.assert_array_equal(got.astype(ref.dtype), ref)
