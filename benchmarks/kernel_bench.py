"""Kernel microbenchmarks: packed select-decode kernels vs dense unpacked.

The harness behind ``BENCH_kernels.json`` (repo root) and the CI
``kernel-bench`` lane.  Per cell it times three implementations of the same
layer math:

  * **dense** — XLA on float weights (``unpack(packed) * scale``
    materialized dense): the unpacked baseline every packed claim is
    measured against.
  * **packed** — `kernels.ops` default dispatch (the deploy path: the
    native select-decode datapath on CPU hosts, compiled Pallas on TPU),
    loading the trit-packed uint8 table bytes verbatim.
  * **pallas_interp** — the Pallas kernel under the interpreter, pinned so
    the CI lane always exercises the Pallas machinery regardless of host.

Timing is **interleaved**: one warmup per impl, then round-robin samples
(dense, packed, interp, dense, ...) with the median reported — back-to-back
loops read drift (turbo, page cache) as impl differences; interleaving
spreads it evenly.

Each cell also carries the correctness gate CI fails on: ``bit_exact`` is
packed-vs-ref **bit equality on ternary inputs** (the deploy regime — trit
activations make every partial sum integer-valued and exact in f32), and
``max_err_float`` is the float-input allclose error.  Weight-traffic columns
(``weight_bytes_*``) record the 8x table-size reduction that is the packed
format's point.

    python benchmarks/kernel_bench.py                 # full cells -> BENCH_kernels.json
    python benchmarks/kernel_bench.py --smoke         # tiny cells, the CI gate
    python benchmarks/kernel_bench.py --smoke --out BENCH_kernels.fresh.json
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
from jax import lax

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT / "src"))

from repro.core.ternary import packed_nbytes, unpack_ternary  # noqa: E402
from repro.kernels import (  # noqa: E402
    quantize_pack_conv_weights,
    quantize_pack_matmul_weights,
    ternary_conv2d,
    ternary_matmul,
)
from repro.kernels.ref import ternary_conv2d_ref, ternary_matmul_ref  # noqa: E402

# (m, k, n) matmul / (b, hw, c_in, c_out, pool) conv cells.  Full cells are
# the paper nets' working set (96 = the OCU count); smoke cells keep the
# interpreter lane's grid tiny so the CI gate stays fast.
FULL_MATMULS = [(512, 2048, 512)]
SMOKE_MATMULS = [(128, 512, 128)]
FULL_CONVS = [(1, 32, 96, 96, 0), (4, 32, 96, 96, 0), (1, 32, 96, 96, 2)]
SMOKE_CONVS = [(1, 16, 8, 8, 0), (2, 16, 8, 8, 2)]


def _interleaved_time(fns: dict, repeats: int) -> dict:
    """Median seconds per impl, samples taken round-robin across impls."""
    for fn in fns.values():
        jax.block_until_ready(fn())  # compile + warmup
    samples = {name: [] for name in fns}
    for _ in range(repeats):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            samples[name].append(time.perf_counter() - t0)
    return {name: statistics.median(s) for name, s in samples.items()}


def _traffic(shape, axis: int) -> dict:
    dense = int(np.prod(shape)) * 2  # bf16 dense table
    packed = packed_nbytes(shape, axis=axis)
    return {
        "weight_bytes_dense_bf16": dense,
        "weight_bytes_packed": packed,
        "bytes_reduction": dense / packed,
    }


def _row(name, kind, times, bit_exact, max_err_float, traffic) -> dict:
    return {
        "name": name,
        "kind": kind,
        "dense_us": times["dense"] * 1e6,
        "packed_us": times["packed"] * 1e6,
        "pallas_interp_us": times["interp"] * 1e6,
        "speedup_packed_vs_unpacked": times["dense"] / times["packed"],
        "bit_exact": bit_exact,
        "max_err_float": max_err_float,
        **traffic,
    }


def bench_matmul(m: int, k: int, n: int, repeats: int) -> dict:
    kf = jax.random.PRNGKey(0)
    x = jax.random.normal(kf, (m, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    wp, sc = quantize_pack_matmul_weights(w)
    wf = unpack_ternary(wp, axis=0)[:k].astype(jnp.float32) * sc  # dense unpacked

    dense = jax.jit(lambda x, wf: x @ wf)
    packed = jax.jit(lambda x, wp, sc: ternary_matmul(x, wp, sc))
    interp = jax.jit(lambda x, wp, sc: ternary_matmul(x, wp, sc, impl="interpret"))
    times = _interleaved_time({
        "dense": lambda: dense(x, wf),
        "packed": lambda: packed(x, wp, sc),
        "interp": lambda: interp(x, wp, sc),
    }, repeats)

    # the deploy regime: ternary inputs must be bit-equal to the ref oracle
    xt = jnp.sign(jax.random.normal(jax.random.PRNGKey(2), (m, k)))
    bit_exact = bool(np.array_equal(
        np.asarray(packed(xt, wp, sc)), np.asarray(ternary_matmul_ref(xt, wp, sc))
    ))
    err = float(jnp.max(jnp.abs(packed(x, wp, sc) - ternary_matmul_ref(x, wp, sc))))
    return _row(f"ternary_matmul_{m}x{k}x{n}", "matmul",
                times, bit_exact, err, _traffic((k, n), axis=0))


def bench_conv(b: int, hw: int, cin: int, cout: int, pool: int, repeats: int) -> dict:
    x = jax.random.normal(jax.random.PRNGKey(3), (b, hw, hw, cin))
    w = jax.random.normal(jax.random.PRNGKey(4), (3, 3, cin, cout))
    wp, sc = quantize_pack_conv_weights(w)
    wf = unpack_ternary(wp, axis=2)[:, :, :cin].astype(jnp.float32)
    fused = pool > 0  # fused cells time the whole CUTIE layer epilogue

    def dense_fn(x):
        y = lax.conv_general_dilated(
            x, wf, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
        ) * sc.reshape(1, 1, 1, -1)
        if not fused:
            return y
        t = jnp.where(jnp.abs(y) > 0.5, jnp.sign(y), 0.0)
        return lax.reduce_window(
            t, -jnp.inf, lax.max, (1, pool, pool, 1), (1, pool, pool, 1), "VALID"
        ).astype(jnp.int8)

    kw = dict(fuse_ternary=True, fuse_pool=pool, out_dtype=jnp.int8) if fused else {}
    dense = jax.jit(dense_fn)
    packed = jax.jit(lambda x: ternary_conv2d(x, wp, sc, **kw))
    interp = jax.jit(lambda x: ternary_conv2d(x, wp, sc, impl="interpret", **kw))
    times = _interleaved_time({
        "dense": lambda: dense(x),
        "packed": lambda: packed(x),
        "interp": lambda: interp(x),
    }, repeats)

    xt = jnp.sign(jax.random.normal(jax.random.PRNGKey(5), x.shape))
    if fused:
        ref = dense_fn(xt)  # dense path doubles as the fused oracle
    else:
        ref = ternary_conv2d_ref(xt, wp, sc)
    bit_exact = bool(np.array_equal(np.asarray(packed(xt)), np.asarray(ref)))
    if fused:
        err = 0.0 if bit_exact else float("inf")  # int8 outputs: exactness only
    else:
        err = float(jnp.max(jnp.abs(packed(x) - ternary_conv2d_ref(x, wp, sc))))
    tag = f"ternary_conv2d_{b}x{hw}x{hw}x{cin}->{cout}" + (f"_fused_pool{pool}" if fused else "")
    return _row(tag, "conv2d_fused" if fused else "conv2d",
                times, bit_exact, err, _traffic((3, 3, cin, cout), axis=2))


def run(args) -> int:
    matmuls = SMOKE_MATMULS if args.smoke else FULL_MATMULS
    convs = SMOKE_CONVS if args.smoke else FULL_CONVS
    repeats = args.repeats or (7 if args.smoke else 30)

    results = []
    for m, k, n in matmuls:
        results.append(bench_matmul(m, k, n, repeats))
    for b, hw, cin, cout, pool in convs:
        results.append(bench_conv(b, hw, cin, cout, pool, repeats))

    failures = []
    for r in results:
        print(f"[kbench] {r['name']:>42s}: dense {r['dense_us']:9.1f} us  "
              f"packed {r['packed_us']:9.1f} us  x{r['speedup_packed_vs_unpacked']:.2f}  "
              f"bit_exact={r['bit_exact']}")
        if not r["bit_exact"]:
            failures.append(f"{r['name']}: packed output differs from ref on "
                            "ternary inputs (bit-exactness contract broken)")

    payload = {
        "schema": 1,
        "meta": {
            "smoke": bool(args.smoke),
            "jax_backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "repeats": repeats,
            "generated_unix": int(time.time()),
            "note": ("dense = XLA on unpacked float weights; packed = "
                     "kernels.ops default dispatch (native select-decode on "
                     "CPU, Pallas on TPU); pallas_interp pins the interpreter "
                     "and is directional only.  Interleaved-median timing."),
        },
        "results": results,
    }
    # BENCH_kernels.smoke.json is the COMMITTED kernel-bench baseline
    # (refresh: re-run --smoke and commit); CI writes its fresh measurement
    # to BENCH_kernels.fresh.json via --out and gates it with
    # scripts/check_bench_regression.py --kernels
    default_name = "BENCH_kernels.smoke.json" if args.smoke else "BENCH_kernels.json"
    out = Path(args.out) if args.out else REPO_ROOT / default_name
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"[kbench] wrote {out} ({len(results)} cells)")
    if failures:
        for f in failures:
            print(f"[kbench] FAIL {f}", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny kernel cells, fewer repeats — the CI gate")
    ap.add_argument("--repeats", type=int, default=None,
                    help="interleaved timing rounds (default 30, smoke 7)")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: <repo>/BENCH_kernels.json)")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main())
