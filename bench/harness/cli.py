"""One run of one cell: set up, measure, compare, print the result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``, from the start of the process): the
configuration's seeded weights on the device, the program deployed over
them, the traffic driver's inputs (from ``--seed``) and its warm-up.  Then
the measured window, traced or not.
Then, outside the window and outside set-up: the device's memory peak, the
program's state released, the plain reference over every input the window
used, and the comparison.  The last line of standard output is the result;
the last lines of standard error are the compared numbers beside their
limits.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import tempfile
import time
from typing import Dict, Optional

import numpy as np

from harness import compare, counts, device, peaks, spec
from harness import trace as trace_mod
from harness.device import NoChip, log
from harness.window import Window

TRACER_CAPACITY = 1 << 21  # the program's span ring buffer, traced runs


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""

    cell: Dict
    config: Dict
    traffic: Dict
    peaks: Dict
    chips: int
    rows: int  # rows one step of the timed program computes, over all chips
    setup_s: float
    window: Window
    trace: Optional[trace_mod.DeviceTrace] = None


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def traffic_rng(seed: int) -> np.random.Generator:
    """The traffic's random stream, from a seed of any size."""
    return np.random.default_rng(np.random.SeedSequence(abs(seed)))


def run_cell(bench: spec.Bench, args, t_start: float, require_chip=device.require_tpu,
             lookup_peaks=peaks.lookup, control: bool = False) -> Dict:
    """One run; returns the result object.  ``require_chip`` and
    ``lookup_peaks`` let the benchmark's tests run it without a chip;
    ``control`` adds the control's reading (``bench/readings.py``): the
    reference computed in bfloat16, put in the program's place."""
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    module = bench.config_module(cell["config"])
    group = "per_layer" if args.trace else "end_to_end"
    wanted = bench.metrics_for(cell["name"], group)
    readers = {m["name"]: bench.reader(m["name"]) for m in wanted}
    if counts.required_ops(cfg) != cfg["required_ops_per_classification"]:
        raise spec.SpecError(f"{cfg['name']}: the layer table requires "
                             f"{counts.required_ops(cfg)} ops, the file states "
                             f"{cfg['required_ops_per_classification']}")

    device.use_cache()
    import jax

    from harness import program, weights

    phases = {"start": time.time() - t_start}
    devices = require_chip(cell["chips"])
    phases["chip"] = time.time() - t_start
    kind = devices[0].device_kind
    table = lookup_peaks(kind)
    compiles = device.CompileCounter()

    # The program closes over its weight tables as constants of its jitted
    # step, so weights that changed with --seed would make every run a new
    # program and a new compile.  Each configuration fixes its own.
    w = weights.make(cfg["assumed"]["weight_seed"], cfg)
    prog = program.deploy(cfg, w)
    phases["weights"] = time.time() - t_start
    tracer = None
    if args.trace:
        from repro.obs import Tracer

        tracer = Tracer(capacity=TRACER_CAPACITY)
    rng = traffic_rng(args.seed)
    gen = dict(traffic["inputs"])
    generate = bench.inputs(gen.pop("kind")).generate

    def make_inputs(lead):
        return generate(rng, lead, cfg, **gen)

    driver = bench.driver(traffic["driver"]).Driver(prog, cfg, traffic, rng, make_inputs,
                                                     tracer=tracer)
    del prog
    phases["inputs"] = time.time() - t_start
    driver.warm_up()
    # What set-up made lives through the window; frozen, the collector's
    # full passes in the window walk only what the window makes.
    gc.collect()
    gc.freeze()
    setup_s = time.time() - t_start
    log(f"{cell['name']}: set-up {setup_s:.3f} s ({compiles.n} programs built, "
        f"{compiles.n - compiles.hits} compiled; cumulative s: "
        + ", ".join(f"{k} {v:.3f}" for k, v in phases.items())
        + f"); window {args.seconds} s, trace {args.trace}")

    before = compiles.n
    trace = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            with jax.profiler.trace(trace_dir, profiler_options=opts):
                window = driver.measure(args.seconds)
            trace = trace_mod.load(trace_dir, window.t0_epoch_ns, window.seconds)
            trace_mod.add_host(trace, window.marks, window.t0_ns)
            trace_mod.add_host(trace, [(e.name, e.ts, e.dur) for e in window.spans or ()
                                       if e.phase == "X"], window.spans_t0_ns)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        window = driver.measure(args.seconds)
    window_compiles = compiles.n - before
    gc.unfreeze()
    used = driver.devices()
    mem_peak = device.memory_peak_bytes(used)
    library, rows = driver.library, driver.rows
    driver.release()
    del driver
    gc.collect()

    t_ref = time.perf_counter()
    ref = module.reference(w, library, cfg, jax.numpy.float32)
    checks = compare.check(window, ref, cfg["limits"])
    wrong = int((compare.logit_gaps(window.keys, window.logits, ref)
                 > cfg["limits"]["logit_err"]).sum())
    log(f"reference over {library.shape[0]} library items: "
        f"{time.perf_counter() - t_ref:.3f} s; {window_compiles} programs built in the window")

    run = Run(cell=cell, config=cfg, traffic=traffic, peaks=table, chips=cell["chips"],
              rows=rows, setup_s=setup_s, window=window, trace=trace)
    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = {"platform": devices[0].platform, "kind": kind, "count": len(used),
           "memory_peak_bytes": mem_peak}
    result = {
        "correct": compare.passed(checks),
        "attempted": int(window.attempted),
        "failed": int(window.unmatched) + wrong,
        "metrics": metrics,
        "device": dev,
    }
    if trace is not None:
        dev["busy_s"] = trace_mod.mean_busy_s(trace)
        dev["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace_mod.top_ops(trace),
                               "idle_gaps": trace_mod.idle_gaps(trace)}
    result["window"] = {"seconds": window.seconds, "rounds": window.rounds,
                        "longest_s": float(window.latencies_s.max(initial=0.0)),
                        "compiles": window_compiles}
    if control:
        low = module.reference(w, library, cfg, jax.numpy.bfloat16)
        result["control"] = {"logit_err": float(compare.logit_gaps(
            window.keys, low[window.keys[:, 0], window.keys[:, 1]], ref).max())}
    result["checks"] = checks
    return result


def main(argv, t_start: float) -> int:
    args = parse(argv)
    try:
        result = run_cell(spec.Bench(), args, t_start)
    except NoChip as e:
        log(f"FAIL: {e}")
        return 1
    except (peaks.UnknownDevice, spec.SpecError) as e:
        log(f"FAIL: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0
