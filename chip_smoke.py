"""Bring-up check: the fused serving path on a TPU, at full published width.

    python chip_smoke.py                # one chip: both phases below
    python chip_smoke.py --four-chips   # four chips: the sharded pool only

One process drives the chip; nothing here starts a child that touches JAX.
Every graph, weight and input is built from the committed tree and ``SEED``.

  * ``dvs_cnn_tcn`` served: get_net -> init -> quantize(calib) ->
    serve(pool_size=8, backend="fused"), fed by a `ContinuousBatcher` with
    16 sensors x 30 `DVSEventPipeline` frames — the 24-step ring wraps and
    freed slots refill.  Every result must be finite, the pool step must
    trace once, and pooled logits must equal a lone `StreamSession` bit for
    bit (first and refilled slots alike).
  * ``cifar10_tnn`` one-shot: forward(backend="fused") on a batch of 8
    `CifarLikePipeline` images must equal backend="ref" bit for bit.
  * ``--four-chips``: the same dvs_cnn_tcn pool with its pool axis over 4
    chips (``sharding=4``) against an unsharded pool on one chip; the state
    must span 4 devices and every stream's logits must match bit for bit.

Each fused step's lowered HLO must hold a ``tpu_custom_call`` — the Pallas
kernel, not an XLA fallback.  The times printed are bring-up figures, not a
benchmark.  Any failure exits non-zero; success ends stdout with exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

SEED = 0
DVS_NET, CIFAR_NET = "dvs_cnn_tcn", "cifar10_tnn"
POOL_SIZE, SENSORS, FRAMES = 8, 16, 30
CIFAR_BATCH = 8
REPLAYED = (0, 1, 8, 15)  # two first-wave slots, two refilled ones
LABEL = "(bring-up, not a benchmark)"


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip-smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def require_tpu(n_chips: int):
    """The TPU devices, or a non-zero exit: no CPU fallback, ever."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"no TPU found: {e}")
    if devices[0].platform != "tpu":
        fail(f"no TPU found: JAX runs on {devices[0].platform!r}")
    if len(devices) < n_chips:
        fail(f"needs {n_chips} TPU chips, JAX sees {len(devices)}")
    return devices


def assert_kernel(lowered, what: str) -> None:
    """The Pallas kernel, compiled for the chip, is what the step runs."""
    if "tpu_custom_call" not in lowered.as_text():
        raise AssertionError(f"{what}: no tpu_custom_call in the lowered HLO")
    log(f"{what}: tpu_custom_call present")


def deploy(net: str, calib):
    import jax
    from repro import api

    prog = api.get_net(net)
    return prog.quantize(prog.init(jax.random.PRNGKey(SEED)), calib=calib)


def dvs_clips(net: str):
    from repro import api
    from repro.data.pipeline import DVSEventPipeline

    g = api.get_graph(net)
    pipe = DVSEventPipeline(SENSORS, steps=FRAMES, hw=g.input_hw[0],
                            n_classes=g.n_classes, seed=SEED)
    clips, labels = pipe.next_batch()
    return clips, np.asarray(labels)


def serve(dep, clips, labels, sharding=None):
    """Run every clip through a fresh fused pool; returns the pool, the
    results by stream id, and the wall time of each tick (device synced)."""
    import jax
    from repro.serving import ContinuousBatcher, StreamRequest

    pool = dep.serve(POOL_SIZE, backend="fused", sharding=sharding)
    batcher = ContinuousBatcher(pool)
    host_clips = np.asarray(clips)
    for i in range(SENSORS):
        batcher.submit(StreamRequest(stream_id=f"sensor-{i}", frames=host_clips[i],
                                     label=int(labels[i]), arrival=i))
    tick_s = []
    while batcher.pending:
        t0 = time.perf_counter()
        batcher.tick()
        jax.block_until_ready(pool.state)
        tick_s.append(time.perf_counter() - t0)
    return pool, {r.stream_id: r for r in batcher.results}, tick_s


def check_pool(pool, results, what: str) -> list:
    failures = []
    if len(results) != SENSORS:
        failures.append(f"{what}: {len(results)}/{SENSORS} streams completed")
    if not all(np.isfinite(r.logits).all() for r in results.values()):
        failures.append(f"{what}: non-finite logits")
    if pool.trace_count != 1:
        failures.append(f"{what}: pool step traced {pool.trace_count} times")
    return failures


def pool_lowering(pool):
    import jax.numpy as jnp

    batch, active = pool.prepare({})
    lanes = active.astype(np.int8)  # the lane code `step_prepared` sends
    return pool._step.lower(pool.state, jnp.asarray(batch), jnp.asarray(lanes))


def report_ticks(what: str, tick_s) -> None:
    steady = tick_s[1:]
    log(f"{what}: compile + first tick {tick_s[0]:.3f} s; steady "
        f"{1e3 * sum(steady) / len(steady):.3f} ms/tick over {len(steady)} "
        f"ticks {LABEL}")


def serve_phase() -> list:
    """dvs_cnn_tcn through an 8-slot fused pool vs lone sessions."""
    from repro import api

    g = api.get_graph(DVS_NET)
    clips, labels = dvs_clips(DVS_NET)
    dep = deploy(DVS_NET, clips)
    log(f"{DVS_NET}: c={g.feature_channels}, {g.input_hw[0]}x{g.input_hw[1]} "
        f"frames, {g.tcn_steps}-step ring; {SENSORS} sensors x {FRAMES} frames "
        f"through a {POOL_SIZE}-slot pool")
    pool, results, tick_s = serve(dep, clips, labels)
    failures = check_pool(pool, results, DVS_NET)
    log(f"{DVS_NET}: pool {pool.pool_size}, {len(results)} streams, "
        f"{len(tick_s)} ticks, trace_count {pool.trace_count}")
    report_ticks(DVS_NET, tick_s)

    # the serving contract: each pooled stream == a lone StreamSession
    session = dep.stream(batch=1, backend="fused")
    mismatches = 0
    host_clips = np.asarray(clips)
    for i in REPLAYED:
        session.reset()
        for t in range(FRAMES):
            want = session.step(host_clips[i, t][None])
        got = results.get(f"sensor-{i}")
        if got is None or not np.array_equal(got.logits, np.asarray(want)[0]):
            mismatches += 1
            failures.append(f"{DVS_NET}: sensor-{i} pooled logits != lone session")
    log(f"{DVS_NET}: pool vs session: {len(REPLAYED)} streams replayed, "
        f"{mismatches} mismatches")
    assert_kernel(pool_lowering(pool), f"{DVS_NET} fused pool step")
    return failures


def forward_phase() -> list:
    """cifar10_tnn one-shot: fused must equal ref bit for bit."""
    import jax
    from repro import api
    from repro.data.pipeline import CifarLikePipeline

    g = api.get_graph(CIFAR_NET)
    x, _ = CifarLikePipeline(CIFAR_BATCH, seed=SEED, n_classes=g.n_classes,
                             hw=g.input_hw[0], ch=g.input_ch).next_batch()
    dep = deploy(CIFAR_NET, x)
    fused = jax.jit(functools.partial(dep.forward, backend="fused"))
    ref = jax.jit(functools.partial(dep.forward, backend="ref"))
    t0 = time.perf_counter()
    y = jax.block_until_ready(fused(x))
    compile_s = time.perf_counter() - t0
    reps = 20
    t0 = time.perf_counter()
    for _ in range(reps):
        y = fused(x)
    jax.block_until_ready(y)
    steady_ms = 1e3 * (time.perf_counter() - t0) / reps
    y, y_ref = np.asarray(y), np.asarray(ref(x))
    log(f"{CIFAR_NET}: c={dep.graph.layers[0].c_out}, batch {CIFAR_BATCH}: compile + "
        f"first call {compile_s:.3f} s; steady {steady_ms:.3f} ms/batch {LABEL}")
    failures = []
    if y.shape != (CIFAR_BATCH, dep.graph.n_classes) or not np.isfinite(y).all():
        failures.append(f"{CIFAR_NET}: fused logits {y.shape}, finite="
                        f"{np.isfinite(y).all()}")
    if np.ptp(y) == 0:
        failures.append(f"{CIFAR_NET}: constant logits — the comparison is vacuous")
    equal = np.array_equal(y, y_ref)
    if not equal:
        failures.append(f"{CIFAR_NET}: fused != ref (max|diff| "
                        f"{np.abs(y - y_ref).max():.3e})")
    log(f"{CIFAR_NET}: fused == ref: {equal}")
    assert_kernel(fused.lower(x), f"{CIFAR_NET} fused forward")
    return failures


def four_chip_phase() -> list:
    """The dvs_cnn_tcn pool over 4 chips vs the same pool on one chip."""
    clips, labels = dvs_clips(DVS_NET)
    dep = deploy(DVS_NET, clips)
    sharded, res_4, ticks_4 = serve(dep, clips, labels, sharding=4)
    plain, res_1, ticks_1 = serve(dep, clips, labels)
    failures = check_pool(sharded, res_4, "4-chip pool")
    failures += check_pool(plain, res_1, "1-chip pool")
    spans = {d.id for d in sharded.state.buf.sharding.device_set}
    log(f"{DVS_NET}: 4-chip pool state spans devices {sorted(spans)}; 1-chip "
        f"pool on {sorted(d.id for d in plain.state.buf.sharding.device_set)}")
    if len(spans) != 4:
        failures.append(f"4-chip pool state spans {len(spans)} devices, not 4")
    mismatches = sum(
        not np.array_equal(res_4[s].logits, res_1[s].logits)
        for s in res_1 if s in res_4
    )
    if mismatches or set(res_4) != set(res_1):
        failures.append(f"4-chip vs 1-chip pool: {mismatches} stream mismatches")
    log(f"{DVS_NET}: 4-chip vs 1-chip pool: {len(res_1)} streams compared, "
        f"{mismatches} mismatches")
    report_ticks(f"{DVS_NET} 4-chip pool", ticks_4)
    report_ticks(f"{DVS_NET} 1-chip pool", ticks_1)
    assert_kernel(pool_lowering(sharded), f"{DVS_NET} 4-chip fused pool step")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pool sharded over 4 chips vs 1 chip")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        fail(f"the repro package is not next to this script: {e}")
    use_compile_cache()
    devices = require_tpu(4 if args.four_chips else 1)
    kind = devices[0].device_kind
    log(f"device: {kind} ({devices[0].platform}) x{len(devices)}")

    if args.four_chips:
        failures = four_chip_phase()
    else:
        failures = serve_phase() + forward_phase()
    if failures:
        for msg in failures:
            print(f"[chip-smoke] FAIL: {msg}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
