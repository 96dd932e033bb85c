"""`repro.sim` — CUTIE compiler + cycle-approximate microarchitecture simulator.

The analytical silicon model (`core.cutie_arch`) reduces a network to one
closed formula over aggregate op counts.  This package replaces that formula
with an *inspectable schedule*: `lower()` compiles a `CutieGraph` into an
`ExecutionPlan` — per-layer OCU/C_in tile assignments, trit-packed
weight-memory images, double-buffered feature-memory traffic, and the TCN
ring-buffer schedule — which is then

  * **executed** by `PlanExecutor` — the one deploy interpreter behind
    every backend of `DeployedProgram.forward`/`stream`/`serve`, with
    ``backend="bitsim"`` walking the tile passes themselves — and
  * **counted** by `counters.count_plan` into per-layer cycle/access numbers
    that `core.cutie_arch.evaluate_network_counts` turns into the same
    `NetReport` the analytic model produces — `silicon_report(source="sim")`.

The two models must reconcile: `reconcile()` reports the cycle divergence,
gated in CI (``sim-smoke``) and in `scripts/check_bench_regression.py
--silicon`.  See docs/simulator.md for the plan format and the
reconciliation contract.

    from repro.sim import lower, count_plan, reconcile
    plan   = lower(graph)                  # schedule only (no weights)
    counts = count_plan(plan)              # per-layer cycles/accesses
    logits = deployed.forward(x, backend="bitsim")   # executes the plan
"""
from repro.sim.plan import ExecutionPlan, LayerPlan, TileAssign, lower
from repro.sim.memory import FeatureMemory, RingBufferSchedule, WeightMemory
from repro.sim.execute import PlanExecutor
from repro.sim.counters import (
    LayerCounters,
    SimParams,
    count_plan,
    evaluate_plan,
    evaluate_sim,
    inference_counts,
    reconcile,
)

__all__ = [
    "ExecutionPlan",
    "LayerPlan",
    "TileAssign",
    "lower",
    "WeightMemory",
    "FeatureMemory",
    "RingBufferSchedule",
    "PlanExecutor",
    "LayerCounters",
    "SimParams",
    "count_plan",
    "evaluate_plan",
    "evaluate_sim",
    "inference_counts",
    "reconcile",
]
