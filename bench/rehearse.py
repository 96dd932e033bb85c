"""Compile each cell's timed device programs for a described TPU v5e, with
no chip attached: what the chip's compiler would refuse costs no chip time.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <cell> ...]

Each cell's driver (``bench/drivers/<driver>.py``) lowers its timed
program with ``lower``: the pool step at the cell's pool size, sharded
over the cell's chips of a ``v5e:2x2``, or the jitted fused forward at the
cell's batch.  Dispatch is steered to the compiled Pallas path the chip
takes.  Nothing runs: a compile that passes says nothing about results or
times.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

_BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]


def compile_cell(bench, name: str, topo) -> str:
    from harness import program, weights

    cell = bench.cell(name)
    cfg, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    prog = program.deploy(cfg, weights.make(cfg["assumed"]["weight_seed"], cfg))
    chips = cell["chips"]
    lowered = bench.driver(traffic["driver"]).lower(prog, cfg, traffic, chips, topo)
    compiled = lowered.compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise SystemExit(f"{name}: no tpu_custom_call in the compiled program")
    return f"{name}: compiled for {chips} chip(s); {compiled.memory_analysis()}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append", help="a cell (default: all)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    import repro.kernels.ops as ops
    from harness import spec

    jax.config.update("jax_enable_compilation_cache", False)
    # steer dispatch to the compiled Pallas path the chip takes, and leave
    # arrays where they are: nothing can live on a described chip
    ops._on_cpu = lambda: False
    jax.device_put = lambda a, s=None: a
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bench = spec.Bench()
    for name in args.workload or [w["name"] for w in bench.doc["workloads"]]:
        print(compile_cell(bench, name, topo), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
