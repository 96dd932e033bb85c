"""`repro.sim`: plan lowering, bitsim bit-exactness, counters, reconciliation.

The simulator's contracts, pinned:

  * lowering round-trips through JSON losslessly and is THE lowering path
    (`export_conv_layers` is a view over it);
  * ``backend="bitsim"`` is bit-exact vs the ``ref`` oracle (and ``fused``)
    on odd sizes, non-divisible C_out, pooled graphs, per-channel threshold
    vectors, forced tiling, and streamed-vs-batch temporal execution;
  * per-layer cycle counters respect the physical utilization bound and
    reconcile with the analytic model within the gated tolerance — except
    on the wide/5x5 net, where the analytic formula is *documented* to
    underprice the schedule (``analytic_schedulable=False``);
  * `silicon_report(source="sim")` reproduces the paper's calibrated
    2.72 uJ / 3200 inf/s CIFAR-10 corner.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api.program import CutieProgram
from repro.core.cutie_arch import PAPER, CutieHW
from repro.sim import (
    ExecutionPlan,
    PlanExecutor,
    SimParams,
    WeightMemory,
    count_plan,
    counters,
    lower,
    reconcile,
)
from repro.sim.counters import analytic_schedulable, inference_counts


def _exact(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _deployed(graph, seed=0, calib=None, **init_kw):
    prog = CutieProgram(graph)
    params = prog.init(jax.random.PRNGKey(seed), **init_kw)
    return prog, prog.quantize(params, calib=calib)


def _mixed_graph():
    return api.CutieGraph(
        name="mix", input_hw=(8, 8), input_ch=3, n_classes=4,
        layers=(api.conv2d(3, 8), api.pool(),
                api.conv2d(8, 8),
                api.conv2d(8, 10), api.pool(),   # C_out not divisible by 8
                api.flatten(), api.fc(2 * 2 * 10, 4)),
    )


def _strided_graph():
    """Strided + pointwise (1x1) convs — the KWS-frontend layer kinds."""
    return api.CutieGraph(
        name="strided", input_hw=(8, 8), input_ch=3, n_classes=4,
        layers=(api.conv2d(3, 8, stride=2),
                api.conv2d(8, 8, kernel=(1, 1)),
                api.conv2d(8, 8, stride=2),
                api.flatten(), api.fc(2 * 2 * 8, 4)),
    )


# ---------------------------------------------------------------------------
# plan lowering
# ---------------------------------------------------------------------------

class TestPlan:
    def test_round_trip_through_json(self):
        for name in ("cifar10_tnn_smoke", "dvs_cnn_tcn_smoke", "cifar10_tnn_wide_smoke"):
            plan = lower(api.get_graph(name))
            wire = json.loads(json.dumps(plan.to_dict()))
            assert ExecutionPlan.from_dict(wire) == plan

    def test_pool_absorption_matches_conv_pool_plan(self):
        g = _mixed_graph()
        plan = lower(g)
        conv_pools = tuple(
            lp.pool for lp in plan.layers if lp.kind == "conv2d"
        )
        assert conv_pools == g.conv_pool_plan() == (2, 0, 2)
        # absorbed pools do not appear as standalone plan steps
        assert not any(lp.kind == "pool" for lp in plan.layers)

    def test_tiling_under_small_array(self):
        """A 2x2-OCU / 8-channel array forces the full tile grid."""
        g = _mixed_graph()
        hw = CutieHW(n_ocu=4, max_cin=4)
        plan = lower(g, hw)
        conv2 = [lp for lp in plan.layers if lp.kind == "conv2d"][1]
        # 8 c_out / 4 ocu x 8 c_in / 4 max_cin = 4 tiles
        assert len(conv2.tiles) == 4
        spans = {(t.cout_lo, t.cout_hi, t.cin_lo, t.cin_hi) for t in conv2.tiles}
        assert spans == {(0, 4, 0, 4), (0, 4, 4, 8), (4, 8, 0, 4), (4, 8, 4, 8)}

    def test_max_cin_must_be_pack_aligned(self):
        with pytest.raises(ValueError, match="multiple of 4"):
            lower(_mixed_graph(), CutieHW(max_cin=6))

    def test_export_conv_layers_is_a_plan_view(self):
        for name in ("cifar10_tnn", "dvs_cnn_tcn", "cifar10_tnn_wide"):
            g = api.get_graph(name)
            assert api.export_conv_layers(g) == lower(g).to_arch_layers()

    def test_stride_and_pointwise_lowering(self):
        """stride subsamples AFTER ternarization: the plan records the
        pre-stride input extent but prices only the kept output pixels."""
        plan = lower(_strided_graph())
        convs = [lp for lp in plan.layers if lp.kind == "conv2d"]
        assert [(c.stride, (c.kh, c.kw)) for c in convs] == \
            [(2, (3, 3)), (1, (1, 1)), (2, (3, 3))]
        # pre-stride extents, post-stride pricing
        assert (convs[0].h, convs[0].w, convs[0].out_pixels) == (8, 8, 16)
        assert (convs[1].h, convs[1].w, convs[1].out_pixels) == (4, 4, 16)
        assert (convs[2].h, convs[2].w, convs[2].out_pixels) == (4, 4, 4)
        assert convs[0].macs == 16 * 3 * 3 * 3 * 8  # kept pixels only

    def test_strided_conv_never_absorbs_pool(self):
        """Fusing a pool into a strided conv would pool the subsampled
        grid; the pool must stay a standalone plan step instead."""
        g = api.CutieGraph(
            name="sp", input_hw=(8, 8), input_ch=3, n_classes=4,
            layers=(api.conv2d(3, 8, stride=2), api.pool(),
                    api.flatten(), api.fc(2 * 2 * 8, 4)),
        )
        plan = lower(g)
        conv = next(lp for lp in plan.layers if lp.kind == "conv2d")
        assert conv.stride == 2 and conv.pool == 0
        pool = next(lp for lp in plan.layers if lp.kind == "pool")
        assert (pool.h, pool.w) == (4, 4)  # pools the strided output

    def test_stride_round_trips_and_defaults_to_one(self):
        """New plans serialize stride losslessly; dicts written before the
        field existed deserialize to stride=1 (the old semantics)."""
        plan = lower(_strided_graph())
        wire = json.loads(json.dumps(plan.to_dict()))
        assert ExecutionPlan.from_dict(wire) == plan
        for lp in wire["layers"]:
            del lp["stride"]  # a pre-stride-schema plan dict
        old = ExecutionPlan.from_dict(wire)
        assert all(lp.stride == 1 for lp in old.layers)

    def test_export_conv_layers_legacy_shapes(self):
        """The projected rows keep the legacy geometry (paper networks)."""
        rows = api.export_conv_layers(api.get_graph("cifar10_tnn"))
        assert len(rows) == 9
        assert (rows[0].h_out, rows[0].w_out, rows[0].c_in, rows[0].c_out) == (32, 32, 3, 96)
        assert rows[-1].is_fc and (rows[-1].kh, rows[-1].kw) == (4, 4)
        dvs = api.export_conv_layers(api.get_graph("dvs_cnn_tcn"))
        # 5 frontend passes x 5 convs + 4 tcn + fc
        assert len(dvs) == 5 * 5 + 4 + 1
        assert [(r.h_out, r.w_out) for r in dvs[25:29]] == [(24, 1), (12, 2), (6, 4), (3, 8)]


# ---------------------------------------------------------------------------
# bitsim bit-exactness
# ---------------------------------------------------------------------------

class TestBitsimExact:
    def test_backend_registered(self):
        assert "bitsim" in api.BACKENDS
        api.check_backend("bitsim")

    def test_mixed_graph_pool_and_ragged_cout(self):
        g = _mixed_graph()
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(0), (3, 8, 8, 3)))
        _, dep = _deployed(g, calib=x)
        want = dep.forward(x, backend="ref")
        _exact(dep.forward(x, backend="bitsim"), want)
        _exact(dep.forward(x, backend="fused"), want)

    def test_odd_spatial_sizes(self):
        g = api.CutieGraph(
            name="odd", input_hw=(7, 5), input_ch=2, n_classes=3,
            layers=(api.conv2d(2, 8), api.conv2d(8, 8),
                    api.global_pool(), api.fc(8, 3)),
        )
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(1), (2, 7, 5, 2)))
        _, dep = _deployed(g, calib=x)
        _exact(dep.forward(x, backend="bitsim"), dep.forward(x, backend="ref"))

    def test_kernel5_stem(self):
        g = api.get_graph("cifar10_tnn_wide_smoke")
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16, 3)))
        _, dep = _deployed(g, calib=x)
        want = dep.forward(x, backend="ref")
        _exact(dep.forward(x, backend="bitsim"), want)
        _exact(dep.forward(x, backend="fused"), want)

    def test_strided_and_pointwise_exact(self):
        """Post-ternarize subsampling is the SAME arithmetic in every
        backend — strided/1x1 graphs must stay bit-exact across the
        matrix."""
        g = _strided_graph()
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(11), (3, 8, 8, 3)))
        _, dep = _deployed(g, calib=x)
        want = dep.forward(x, backend="ref")
        _exact(dep.forward(x, backend="bitsim"), want)
        _exact(dep.forward(x, backend="fused"), want)

    def test_kws_tcn_smoke_batch_exact(self):
        """The 1-channel KWS TCN (strided stem + pointwise mixers) through
        the full backend matrix, batch mode."""
        prog = api.get_net("kws_tcn_smoke")
        g = prog.graph
        x = (jax.random.uniform(jax.random.PRNGKey(12),
                                (2, 4, *g.input_hw, g.input_ch))
             < 0.1).astype(jnp.float32)
        dep = prog.quantize(prog.init(jax.random.PRNGKey(0)), calib=x)
        want = dep.forward(x, backend="ref")
        _exact(dep.forward(x, backend="bitsim"), want)
        _exact(dep.forward(x, backend="fused"), want)

    def test_registry_smoke_nets_batch(self):
        for name in ("cifar10_tnn_smoke", "dvs_cnn_tcn_smoke"):
            prog = api.get_net(name)
            g = prog.graph
            key = jax.random.PRNGKey(3)
            if g.is_temporal:
                x = (jax.random.uniform(key, (2, 4, *g.input_hw, g.input_ch))
                     < 0.05).astype(jnp.float32)
            else:
                x = jnp.sign(jax.random.normal(key, (2, *g.input_hw, g.input_ch)))
            dep = prog.quantize(prog.init(jax.random.PRNGKey(0)), calib=x)
            _exact(dep.forward(x, backend="bitsim"), dep.forward(x, backend="ref"))

    def test_temporal_stream_equals_batch(self):
        prog = api.get_net("dvs_cnn_tcn_smoke")
        frames = (jax.random.uniform(jax.random.PRNGKey(4), (2, 5, 32, 32, 2))
                  < 0.05).astype(jnp.float32)
        dep = prog.quantize(prog.init(jax.random.PRNGKey(0)), calib=frames)
        batch = dep.forward(frames, backend="bitsim")
        session = dep.stream(batch=2, backend="bitsim")
        for t in range(frames.shape[1]):
            logits = session.step(frames[:, t])
        _exact(logits, batch)
        _exact(batch, dep.forward(frames, backend="ref"))

    def test_forced_tiling_stays_exact(self):
        """A tiny OCU array splits every layer into many tile passes; the
        partial-sum accumulation across C_in tiles must not change a bit."""
        g = _mixed_graph()
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(5), (2, 8, 8, 3)))
        _, dep = _deployed(g, calib=x)
        plan = lower(g, CutieHW(n_ocu=4, max_cin=4))
        mem = WeightMemory.from_tables(plan, dep.tables, g.act_threshold)
        ex = PlanExecutor(plan, mem)
        _exact(ex.spatial_forward(x), dep.forward(x, backend="ref"))

    def test_per_channel_threshold_vector(self):
        """The fused epilogue takes a per-OCU threshold vector; bitsim reads
        the same vector from the tables — both must equal ref exactly."""
        g = _mixed_graph()
        prog = CutieProgram(g)
        params = prog.init(jax.random.PRNGKey(0), learn_thresholds="per_channel")
        # make the vectors non-uniform so a scalar path cannot fake it
        params["thresh"]["conv"] = [
            t + jnp.linspace(-0.2, 0.4, t.shape[0]) for t in params["thresh"]["conv"]
        ]
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(6), (3, 8, 8, 3)))
        dep = prog.quantize(params, calib=x)
        assert dep.tables["conv"][0]["threshold"].shape == (8,)
        want = dep.forward(x, backend="ref")
        _exact(dep.forward(x, backend="fused"), want)
        _exact(dep.forward(x, backend="bitsim"), want)

    def test_per_channel_threshold_gradient(self):
        """The STE threshold surrogate reduces to the vector shape and is
        non-zero (trainable), leaving the scalar path untouched."""
        g = _mixed_graph()
        prog = CutieProgram(g)
        params = prog.init(jax.random.PRNGKey(0), learn_thresholds="per_channel")
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(7), (3, 8, 8, 3)))
        grads = jax.grad(lambda p: prog.forward_qat(p, x).sum())(params)
        gt = grads["thresh"]["conv"][0]
        assert gt.shape == (8,)
        assert float(jnp.abs(gt).sum()) > 0.0

    def test_serialized_plan_executes_identically(self):
        """lower -> serialize -> deserialize -> execute == direct execute."""
        g = _mixed_graph()
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(8), (2, 8, 8, 3)))
        _, dep = _deployed(g, calib=x)
        direct = dep.forward(x, backend="bitsim")
        plan = lower(g)
        mem = WeightMemory.from_tables(plan, dep.tables, g.act_threshold)
        wire = json.loads(json.dumps(
            {"plan": plan.to_dict(), "memory": mem.to_dict()}
        ))
        ex = PlanExecutor(
            ExecutionPlan.from_dict(wire["plan"]),
            WeightMemory.from_dict(wire["memory"]),
        )
        _exact(ex.spatial_forward(x), direct)


# ---------------------------------------------------------------------------
# counters + reconciliation
# ---------------------------------------------------------------------------

class TestCounters:
    def test_cycles_respect_utilization_bound(self):
        """No layer may beat the physical array: cycles >= macs/(array/2)."""
        hw = CutieHW()
        for name in ("cifar10_tnn", "dvs_cnn_tcn", "cifar10_tnn_wide"):
            for c in count_plan(lower(api.get_graph(name), hw), hw):
                if c.macs:
                    assert c.cycles >= c.macs / (hw.ops_per_cycle / 2), c.label
                    assert 0 < c.util <= 1.0, c.label

    def test_sim_cycles_upper_bound_analytic(self):
        """For schedulable nets the sim only adds fill/drain: divergence in
        [0, 15%] — the gate `check_bench_regression.py --silicon` applies."""
        for name in ("cifar10_tnn", "dvs_cnn_tcn",
                      "cifar10_tnn_smoke", "dvs_cnn_tcn_smoke",
                      "kws_tcn", "kws_tcn_smoke"):
            rec = reconcile(api.get_graph(name))
            assert rec["analytic_schedulable"], name
            assert 0.0 <= rec["divergence"] <= 0.15, (name, rec["divergence"])

    def test_wide_net_not_analytically_schedulable(self):
        """The 5x5-stem / 192-channel net is the counterexample: the sim
        schedules it (extra window passes, full tile grid) and diverges far
        beyond the gate — which is why such nets are exempt-but-reported."""
        rec = reconcile(api.get_graph("cifar10_tnn_wide"))
        assert not rec["analytic_schedulable"]
        assert rec["divergence"] > 0.5

    def test_drain_is_the_only_3x3_overhead(self):
        """With zero drain cycles, sim == analytic exactly on 3x3 nets —
        the two models share one schedule by construction."""
        g = api.get_graph("cifar10_tnn")
        hw = CutieHW()
        counts = inference_counts(lower(g, hw), hw, SimParams(pipeline_drain_cycles=0))
        sim_cycles = sum(c.cycles for c in counts)
        from repro.core.cutie_arch import evaluate_network

        analytic = evaluate_network(g.name, api.export_conv_layers(g), hw, 0.5)
        assert sim_cycles == analytic.cycles

    def test_window_passes_on_kernel5(self):
        plan = lower(api.get_graph("cifar10_tnn_wide"))
        hw = CutieHW()
        stem = [c for c in count_plan(plan, hw) if c.kind == "conv2d"][0]
        assert stem.window_passes == 4  # ceil(5/3)^2
        assert not analytic_schedulable(plan, hw)

    def test_weight_bytes_match_packed_tables(self):
        g = api.get_graph("cifar10_tnn_smoke")
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(9), (1, 16, 16, 3)))
        _, dep = _deployed(g, calib=x)
        plan = lower(g)
        counted = {
            c.index: c.wmem_bytes for c in count_plan(plan) if c.kind == "conv2d"
        }
        convs = [lp for lp in plan.layers if lp.kind == "conv2d"]
        for lp, entry in zip(convs, dep.tables["conv"]):
            assert counted[lp.index] == entry["packed"].size

    def test_ring_schedule(self):
        rec = reconcile(api.get_graph("dvs_cnn_tcn"))
        assert rec["ring"] == {
            "steps": PAPER["tcn_steps"], "channels": 96, "pushes_per_inference": 5
        }
        # 24 x 96 x 2 bit = 576 B — the paper's TCN memory
        from repro.sim import RingBufferSchedule

        ring = RingBufferSchedule(**rec["ring"])
        assert ring.nbytes == PAPER["tcn_mem_bytes"]


# ---------------------------------------------------------------------------
# silicon_report(source="sim")
# ---------------------------------------------------------------------------

class TestSimSiliconReport:
    def test_calibrated_cifar_corner_pinned(self):
        """The acceptance pin: the sim schedule, calibrated at 0.5 V,
        reproduces the paper's measured 2.72 uJ / 3200 inf/s."""
        rep = api.silicon_report(api.get_graph("cifar10_tnn"), v=0.5, source="sim")
        assert rep.source == "sim"
        assert abs(rep.energy_uj - PAPER["cifar_energy_uj"]) < 1e-6
        assert abs(rep.inf_per_s - PAPER["cifar_inf_per_s"]) < 1e-3
        assert rep.calibration.consistent

    def test_sources_reconcile_at_half_volt(self):
        a = api.silicon_report(api.get_graph("cifar10_tnn"), v=0.5)
        s = api.silicon_report(api.get_graph("cifar10_tnn"), v=0.5, source="sim")
        assert a.source == "analytic"
        assert 0.0 <= s.ideal.cycles / a.ideal.cycles - 1.0 <= 0.15

    def test_unknown_source_rejected(self):
        with pytest.raises(ValueError, match="unknown silicon source"):
            api.silicon_report(api.get_graph("cifar10_tnn"), source="magic")

    def test_deployed_program_source_plumbing(self):
        g = api.get_graph("cifar10_tnn_smoke")
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(10), (1, 16, 16, 3)))
        _, dep = _deployed(g, calib=x)
        rep = dep.silicon_report(v=0.5, source="sim")
        assert rep.source == "sim" and "sim schedule" in rep.summary()
        # the plan the report priced is the plan the bitsim backend runs
        assert dep.plan.graph_name == g.name


def test_counters_module_alias():
    """`repro.sim.counters` is importable as a module (docs reference it)."""
    assert hasattr(counters, "count_plan")
