"""Backend benchmark: ref/interpret/pallas/fused/bitsim across registry nets.

The harness behind ``BENCH_backends.json`` (repo root) — the perf trajectory
for the deploy backends.  For every (net, workload, batch, backend) cell it

  * times the jitted whole-network forward (median of ``--repeats``, after a
    compile+warmup call),
  * checks logit agreement against the ``ref`` oracle backend — **exact**
    (bit-equal) for ``fused`` and ``bitsim``, allclose(1e-4) for the float
    backends — and
    exits non-zero on disagreement, which is what the CI ``bench-smoke`` job
    gates on.

Workloads: spatial nets run one ``forward`` cell; temporal nets run both the
per-frame CNN ``spatial`` frontend (the serving hot path) and the full-clip
``forward``.

On a CPU host the Pallas backends execute in interpreter mode, so their
wall-clock is *directional only* (the JSON's ``meta.jax_backend`` records
the host); the ref-vs-fused agreement check is exact everywhere.

    python benchmarks/backend_bench.py                  # full registry nets
    python benchmarks/backend_bench.py --smoke          # tiny nets, CI gate
    python benchmarks/backend_bench.py --nets cifar10_tnn --batches 1 4 8
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT / "src"))

from repro import api  # noqa: E402

FULL_NETS = ("cifar10_tnn", "dvs_cnn_tcn")
SMOKE_NETS = ("cifar10_tnn_smoke", "dvs_cnn_tcn_smoke")


def _inputs(graph, batch: int, frames: int, key) -> jax.Array:
    """Ternary-valued spatial batch / sparse event clip, like the real data."""
    if graph.is_temporal:
        shape = (batch, frames, *graph.input_hw, graph.input_ch)
        return (jax.random.uniform(key, shape) < 0.05).astype(jnp.float32)
    shape = (batch, *graph.input_hw, graph.input_ch)
    return jnp.sign(jax.random.normal(key, shape))


def _time(fn, x, repeats: int):
    """(median seconds, output) — the warmup output is reused for the
    agreement check so no cell pays an extra forward."""
    out = fn(x)
    jax.block_until_ready(out)  # compile + warmup
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        samples.append(time.perf_counter() - t0)
    return statistics.median(samples), out


def _agreement(out: np.ndarray, ref: np.ndarray) -> dict:
    diff = float(np.max(np.abs(out.astype(np.float64) - ref.astype(np.float64))))
    return {
        "max_abs_diff_vs_ref": diff,
        "exact_vs_ref": bool((out == ref).all()),
        "allclose_vs_ref": bool(np.allclose(out, ref, rtol=1e-4, atol=1e-4)),
    }


def bench_cell(deployed, workload: str, x, backends, repeats: int):
    """One (net, workload, batch) cell: every backend vs the ref oracle."""
    fwd = deployed.spatial_forward if workload == "spatial" else deployed.forward
    fns = {b: jax.jit(lambda v, _b=b: fwd(v, backend=_b)) for b in backends}
    timed = {b: _time(fns[b], x, repeats) for b in backends}
    ref_out = np.asarray(timed["ref"][1])
    rows = []
    for b in backends:
        wall, out = timed[b]
        row = {"backend": b, "wall_ms": wall * 1e3}
        row.update(_agreement(np.asarray(out), ref_out))
        rows.append(row)
    ref_ms = next(r["wall_ms"] for r in rows if r["backend"] == "ref")
    for r in rows:
        r["speedup_vs_ref"] = ref_ms / r["wall_ms"] if r["wall_ms"] else float("nan")
    return rows


def check_row(row: dict, net: str, workload: str, batch: int) -> list:
    """The CI gate: fused/bitsim must be bit-exact, float backends allclose."""
    where = f"{net}/{workload}/batch{batch}/{row['backend']}"
    if row["backend"] in ("fused", "bitsim") and not row["exact_vs_ref"]:
        return [f"{where}: {row['backend']} logits differ from ref "
                f"(max_abs_diff={row['max_abs_diff_vs_ref']:.3e})"]
    if not row["allclose_vs_ref"]:
        return [f"{where}: logits not allclose to ref "
                f"(max_abs_diff={row['max_abs_diff_vs_ref']:.3e})"]
    return []


def run(args) -> int:
    nets = args.nets or (SMOKE_NETS if args.smoke else FULL_NETS)
    batches = args.batches or ([2] if args.smoke else [1, 4])
    frames = args.frames or (4 if args.smoke else 5)
    repeats = args.repeats or (2 if args.smoke else 3)
    backends = args.backends or list(api.BACKENDS)
    if "ref" not in backends:
        backends = ["ref", *backends]

    results, failures = [], []
    for net in nets:
        prog = api.get_net(net)
        g = prog.graph
        key = jax.random.PRNGKey(0)
        params = prog.init(key)
        calib = _inputs(g, max(batches), frames, jax.random.PRNGKey(1))
        deployed = prog.quantize(params, calib=calib)
        workloads = ["spatial", "forward"] if g.is_temporal else ["forward"]
        for workload in workloads:
            for batch in batches:
                if workload == "spatial":
                    x = _inputs(g, batch, frames, jax.random.PRNGKey(2))[:, 0]
                else:
                    x = _inputs(g, batch, frames, jax.random.PRNGKey(2))
                rows = bench_cell(deployed, workload, x, backends, repeats)
                for row in rows:
                    failures += check_row(row, net, workload, batch)
                    results.append({"net": net, "workload": workload,
                                    "batch": batch, **row})
                    print(f"[bench] {net:>18s} {workload:>8s} b{batch} "
                          f"{row['backend']:>9s}: {row['wall_ms']:9.2f} ms  "
                          f"x{row['speedup_vs_ref']:.2f} vs ref  "
                          f"exact={row['exact_vs_ref']}")

    payload = {
        "schema": 1,
        "meta": {
            "smoke": bool(args.smoke),
            "jax_backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "repeats": repeats,
            "frames": frames,
            "generated_unix": int(time.time()),
            "note": ("Pallas backends run in interpreter mode on non-TPU hosts; "
                     "wall-clock there is directional, the agreement columns are "
                     "exact everywhere."),
        },
        "results": results,
    }
    # smoke runs write next to, not over, the committed full-run trajectory.
    # BENCH_backends.smoke.json is the COMMITTED bench-regression baseline
    # (refresh it by re-running --smoke --repeats 5 and committing); CI
    # writes its fresh measurement to BENCH_backends.fresh.json via --out
    # and gates it with scripts/check_bench_regression.py
    default_name = "BENCH_backends.smoke.json" if args.smoke else "BENCH_backends.json"
    out = Path(args.out) if args.out else REPO_ROOT / default_name
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"[bench] wrote {out} ({len(results)} cells)")
    if failures:
        for f in failures:
            print(f"[bench] FAIL {f}", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny registry nets, one batch size — the CI gate")
    ap.add_argument("--nets", nargs="*", default=None)
    ap.add_argument("--backends", nargs="*", default=None,
                    choices=list(api.BACKENDS))
    ap.add_argument("--batches", nargs="*", type=int, default=None)
    ap.add_argument("--frames", type=int, default=None,
                    help="clip length for temporal nets")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: <repo>/BENCH_backends.json)")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main())
