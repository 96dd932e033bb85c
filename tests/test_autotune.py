"""Plan-driven kernel autotuning (`repro.kernels.autotune`).

Pins: the selection rule (plan-derived block == TileAssign width on uniform
<=3x3 layers, measured fallback elsewhere), determinism, the deploy/executor
threading (`kernel_block_plan` over `DeployedProgram.plan`, artifact-loaded
execution), and
the end-to-end bit-exactness of the fallback path on the 5x5-stem net the
plan cannot schedule uniformly.  The conv kernel's batch block
(`batch_block`): its bounds, and its picks in every conv launch of the
benchmarked steps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.kernels.autotune import (
    ROW_TARGET,
    VMEM_BUDGET,
    KernelBlock,
    batch_block,
    block_for_layer,
    cell_vmem_bytes,
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


# (B, h, w, c_in, block_cout, input bytes): every conv map the registry nets
# launch, at batches with few and with many divisors
BATCH_SHAPES = [
    (b, h, w, c, n, ib)
    for b in (1, 3, 8, 12, 64, 256)
    for (h, w, c, n, ib) in [
        (32, 32, 4, 96, 4), (32, 32, 96, 96, 1), (16, 16, 96, 96, 1),
        (8, 8, 96, 96, 1), (8, 8, 64, 64, 1), (64, 64, 4, 64, 4),
        (4, 4, 96, 96, 1), (25, 1, 96, 96, 1), (13, 2, 96, 96, 1),
        (7, 4, 96, 96, 1), (4, 8, 96, 96, 1),
    ]
]


def _conv_launches(jaxpr):
    """Per `pallas_call` in ``jaxpr`` (nested jaxprs included), in launch
    order: (input shape, images per grid cell)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            shape = eqn.invars[0].aval.shape
            out.append((shape, shape[0] // eqn.params["grid_mapping"].grid[0]))
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                if isinstance(sub, ClosedJaxpr):
                    out += _conv_launches(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    out += _conv_launches(sub)
    return out


def _step_launches(name, batch, monkeypatch):
    """The conv launches of the step a benchmark cell times, traced for the
    chip's Pallas path: a one-shot net's fused forward over ``batch``
    images, or a DVS pool's step over ``batch`` slots."""
    import repro.kernels.ops as ops
    from repro.serving import SessionPool

    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    prog = api.get_net(name)
    dep = prog.quantize(prog.init(jax.random.PRNGKey(0)))
    g = dep.graph
    if g.is_temporal:
        pool = SessionPool(dep, batch, backend="fused")
        jx = jax.make_jaxpr(pool._step)(
            pool.state, jax.ShapeDtypeStruct((batch, *pool.frame_shape), jnp.float32),
            jnp.zeros((batch,), jnp.int8))
    else:
        jx = jax.make_jaxpr(lambda x: dep.forward(x, backend="fused"))(
            jax.ShapeDtypeStruct((batch, *g.input_hw, g.input_ch), jnp.float32))
    return _conv_launches(jx.jaxpr)


class TestBatchBlock:
    @pytest.mark.parametrize("b,h,w,c_in,block,in_bytes", BATCH_SHAPES)
    def test_divides_and_stays_within_bounds(self, b, h, w, c_in, block, in_bytes):
        bb = batch_block(b, h, w, c_in, block, in_bytes=in_bytes)
        assert b % bb == 0
        if bb > 1:
            assert bb * h * w <= ROW_TARGET
            assert cell_vmem_bytes(bb, h, w, c_in, block,
                                   in_bytes=in_bytes) <= VMEM_BUDGET
        # the largest such divisor: the next one up breaks a bound
        nxt = next((d for d in range(bb + 1, b + 1) if b % d == 0), None)
        if nxt is not None:
            assert (nxt * h * w > ROW_TARGET
                    or cell_vmem_bytes(nxt, h, w, c_in, block,
                                       in_bytes=in_bytes) > VMEM_BUDGET)

    def test_one_image_per_cell_for_one_image_and_for_the_64x64_stem(self):
        for shape in [(32, 32, 96, 96), (8, 8, 96, 96), (25, 1, 96, 96)]:
            assert batch_block(1, *shape) == 1
        for b in (8, 64, 256):
            assert batch_block(b, 64, 64, 4, 64, in_bytes=4) == 1

    def test_deterministic(self):
        picks = [batch_block(*s[:5], in_bytes=s[5]) for s in BATCH_SHAPES]
        assert picks == [batch_block(*s[:5], in_bytes=s[5]) for s in BATCH_SHAPES]

    # images per grid cell of every conv launch, in launch order, in the
    # steps the benchmark's cells time (cifar_b256, resnet20_b256,
    # dvs_pool8 / dvs_pool32_x4 per chip, dvs_pool64)
    PICKS = {
        ("cifar10_tnn", 256): [2, 2, 8, 8, 8, 32, 32, 32],
        ("resnet20_tnn", 256): [2] * 8 + [8] * 6 + [32] * 5,
        ("dvs_cnn_tcn", 8): [1, 2, 8, 8, 8, 8, 8, 8, 8],
        ("dvs_cnn_tcn", 64): [1, 2, 8, 32, 64, 16, 32, 32, 64],
    }

    @pytest.mark.parametrize("name,batch", list(PICKS))
    def test_picks_in_the_benchmarked_steps(self, name, batch, monkeypatch):
        launches = _step_launches(name, batch, monkeypatch)
        assert [bb for _, bb in launches] == self.PICKS[name, batch]
        assert all(shape[0] == batch for shape, _ in launches)
