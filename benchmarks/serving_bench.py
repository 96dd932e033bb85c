"""Serving benchmark: pool sizes x backends for the multi-sensor pool.

The harness behind ``BENCH_serving.json`` (repo root) — the throughput
trajectory for `repro.serving.SessionPool` continuous batching.  For every
(net, pool_size, backend) cell it

  * drives a full arrival/departure simulation (2x pool_size sensor
    streams, staggered arrivals) through `ContinuousBatcher` and measures
    frames/s and mean pool occupancy (compile excluded via a warmup tick),
  * measures the sequential baseline — the same streams served one at a
    time by a single batch-1 `StreamSession` — and reports the pool's
    speedup over it,
  * spot-checks one stream's pooled logits against an independent
    `StreamSession` replay (bit-exact) and exits non-zero on mismatch,
    mirroring the backend bench's CI contract,
  * samples per-tick wall latency and reports p50/p99 percentiles
    (compile excluded via warmup), per cell and — in the multi-tenant
    fleet cell (>= 3 distinct nets on one `FleetRouter`, measured on a
    pre-warmed second round) — per net and per bucket pool size,
  * runs an activity-gated cell (schema 3): the same pool under an
    `ActivityGate` on a bursty duty-cycle trace, gated per-stream logits
    checked bit-exact against lone sessions fed exactly the frames
    `ActivityGate.plan` selects, and the skipped frames priced in uJ via
    `repro.serving.energy_summary` — energy-per-classification must land
    strictly below the ungated baseline,
  * runs an observability cell (schema 4): the largest pool scenario
    re-driven under a `repro.obs.Tracer`, reporting how each tick's wall
    time splits across the batcher phases (admit/assemble/step, from
    `repro.obs.phase_breakdown`) — and asserting the observer effect is
    nil: the traced run's final logits must be byte-identical to an
    untraced run of the same scenario, with the step still traced once.

On a CPU host the Pallas backends run in interpreter mode, so wall-clock is
directional (the JSON's ``meta.jax_backend`` records the host); the
bit-exactness column is meaningful everywhere.

    python benchmarks/serving_bench.py                    # full net sweep
    python benchmarks/serving_bench.py --smoke            # tiny net, CI cell
    python benchmarks/serving_bench.py --pools 2 4 8 --backends fused ref
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT / "src"))

from repro import api  # noqa: E402
from repro.serving import (  # noqa: E402
    ActivityGate,
    ContinuousBatcher,
    FleetRouter,
    StreamRequest,
    energy_summary,
)

FULL_NET = "dvs_cnn_tcn"
SMOKE_NET = "dvs_cnn_tcn_smoke"
# the multi-tenant cell: >= 3 distinct temporal registry nets per fleet
FLEET_NETS_FULL = ("dvs_cnn_tcn", "dvs_cnn_tcn_micro", "dvs_cnn_tcn_nano")
FLEET_NETS_SMOKE = ("dvs_cnn_tcn_smoke", "dvs_cnn_tcn_micro", "dvs_cnn_tcn_nano")


def _event_clips(graph, n_streams: int, frames: int, key) -> jax.Array:
    shape = (n_streams, frames, *graph.input_hw, graph.input_ch)
    return (jax.random.uniform(key, shape) < 0.05).astype(jnp.float32)


def _run_pool(deployed, clips, pool_size: int, backend: str):
    """(wall seconds, stats dict, final logits by stream index)."""
    pool = deployed.serve(pool_size, backend=backend)
    warm = deployed.graph  # warmup: compile the fixed-shape step once
    pool.admit("__warm__")
    pool.step({"__warm__": np.zeros((*warm.input_hw, warm.input_ch), np.float32)})
    pool.evict("__warm__")

    batcher = ContinuousBatcher(pool)
    for i in range(clips.shape[0]):
        batcher.submit(
            StreamRequest(stream_id=f"s{i}", frames=clips[i], arrival=i)
        )
    t0 = time.perf_counter()
    results = batcher.run()
    jax.block_until_ready(pool.state.buf)
    wall = time.perf_counter() - t0
    stats = batcher.stats()
    stats["trace_count"] = pool.trace_count
    finals = {int(r.stream_id[1:]): r.logits for r in results}
    return wall, stats, finals


def _run_sequential(deployed, clips, backend: str):
    """The no-batching baseline: one batch-1 session, streams end to end."""
    session = deployed.stream(batch=1, backend=backend)
    session.step(np.zeros((1, *clips.shape[2:]), np.float32))  # compile
    session.reset()
    finals = {}
    t0 = time.perf_counter()
    for i in range(clips.shape[0]):
        session.reset()
        for t in range(clips.shape[1]):
            logits = session.step(clips[i : i + 1, t])
        finals[i] = np.asarray(logits)[0]
    jax.block_until_ready(logits)
    return time.perf_counter() - t0, finals


def bench_cell(deployed, clips, pool_size: int, backend: str):
    pool_wall, stats, pool_finals = _run_pool(deployed, clips, pool_size, backend)
    seq_wall, seq_finals = _run_sequential(deployed, clips, backend)
    n_frames = clips.shape[0] * clips.shape[1]
    check_idx = 0
    exact = bool((pool_finals[check_idx] == seq_finals[check_idx]).all())
    return {
        "pool_size": pool_size,
        "backend": backend,
        "streams": int(clips.shape[0]),
        "frames_per_stream": int(clips.shape[1]),
        "pool_wall_s": pool_wall,
        "pool_frames_per_s": n_frames / pool_wall,
        "sequential_wall_s": seq_wall,
        "sequential_frames_per_s": n_frames / seq_wall,
        "speedup_vs_sequential": seq_wall / pool_wall,
        "mean_occupancy": stats["mean_occupancy"],
        "ticks": stats["ticks"],
        "trace_count": stats["trace_count"],
        "exact_vs_single_session": exact,
        # per-tick wall latency over the simulation (compile excluded by
        # the warmup tick) — the serving-regression gate's percentiles
        "latency_ms_p50": stats["latency_ms_p50"],
        "latency_ms_p99": stats["latency_ms_p99"],
    }


def bench_fleet(net_names, backend: str, pool_cap: int, streams: int,
                frames: int):
    """The multi-tenant cell: >= 3 distinct nets on one `FleetRouter`,
    staggered arrivals interleaved across buckets, ladder autoscaling.

    Two rounds through the SAME router: round 1 warms every ladder rung
    the scenario visits (compile ticks land here), then each bucket's
    latency trace is cleared and round 2 is measured — so the percentiles
    price steady-state serving while the trace audit still spans both
    rounds (a rung re-traced in round 2 fails the zero-retrace contract).
    """
    router = FleetRouter(backend=backend, max_pool_size=pool_cap)
    deps, clips = {}, {}
    for idx, name in enumerate(net_names):
        prog = api.get_net(name)
        deps[name] = prog.quantize(prog.init(jax.random.PRNGKey(idx)))
        router.register(name, deps[name])

    def submit_round(tag: str, base_tick: int):
        for idx, name in enumerate(net_names):
            cs = _event_clips(deps[name].graph, streams, frames,
                              jax.random.PRNGKey(100 + idx))
            for s in range(streams):
                sid = f"{tag}/{name}/{s}"
                clips[sid] = np.asarray(cs[s])
                router.submit(StreamRequest(
                    stream_id=sid, frames=clips[sid],
                    arrival=base_tick + idx + s * len(net_names), net=name,
                ))

    submit_round("warm", router.tick_index)
    router.run()
    for bucket in router.buckets.values():
        bucket.batcher.latency_trace.clear()
    submit_round("meas", router.tick_index)
    t0 = time.perf_counter()
    results = router.run()
    wall = time.perf_counter() - t0
    stats = router.stats()
    router.close()

    # bit-exactness: replay one measured stream per net through a lone
    # batch-1 session (the same contract the single-pool cells gate)
    exact = True
    for r in results:
        if not r.stream_id.startswith("meas/") or not r.stream_id.endswith("/0"):
            continue
        session = deps[r.net].stream(batch=1, backend=backend)
        clip = clips[r.stream_id]
        for t in range(clip.shape[0]):
            ref = session.step(clip[t][None])
        exact = exact and bool((np.asarray(ref)[0] == r.logits).all())

    zero_retrace = all(
        tc <= 1
        for s in stats["nets"].values()
        for tc in s["pools_traced"].values()
    )
    per_net = {
        name: {
            "latency_ms_p50": s["latency_ms_p50"],
            "latency_ms_p99": s["latency_ms_p99"],
            "latency_by_pool_size": s["latency_by_pool_size"],
            "mean_occupancy": s["mean_occupancy"],
            "completed": s["completed"],
            "pools_traced": s["pools_traced"],
            "scale_events": len(s["scale_events"]),
        }
        for name, s in stats["nets"].items()
    }
    return {
        "nets": list(net_names),
        "backend": backend,
        "pool_cap": pool_cap,
        "streams_per_net": streams,
        "frames_per_stream": frames,
        "measured_wall_s": wall,
        "completed": sum(r.stream_id.startswith("meas/") for r in results),
        "per_net": per_net,
        "latency_ms_p50": stats["aggregate"]["latency_ms_p50"],
        "latency_ms_p99": stats["aggregate"]["latency_ms_p99"],
        "exact_vs_single_session": exact,
        "zero_retrace": zero_retrace,
    }


def bench_gated(deployed, backend: str, pool_size: int, streams: int,
                frames: int, duty: float, seed: int = 5):
    """The schema-3 activity-gated cell: bursty duty-cycle traces through
    a gated `ContinuousBatcher`, differentially verified against lone
    sessions fed exactly the `ActivityGate.plan`-selected frames, with the
    skipped frames priced in uJ on the sim counters."""
    from repro.data.pipeline import DVSEventPipeline, KWSSpectrogramPipeline

    g = deployed.graph
    pipe_cls = DVSEventPipeline if g.input_ch == 2 else KWSSpectrogramPipeline
    pipe = pipe_cls(streams, steps=frames, hw=g.input_hw[0],
                    n_classes=g.n_classes, seed=seed, duty_cycle=duty)
    clips = np.asarray(pipe.next_batch()[0])
    gate = ActivityGate()

    pool = deployed.serve(pool_size, backend=backend)
    pool.admit("__warm__")
    pool.step({"__warm__": np.zeros((*g.input_hw, g.input_ch), np.float32)})
    pool.evict("__warm__")
    batcher = ContinuousBatcher(pool, gate=gate)
    for i in range(streams):
        batcher.submit(StreamRequest(stream_id=f"s{i}", frames=clips[i],
                                     arrival=i))
    t0 = time.perf_counter()
    results = batcher.run()
    jax.block_until_ready(pool.state.buf)
    wall = time.perf_counter() - t0
    stats = batcher.stats()

    # the differential contract, every stream: processed set == the gate
    # plan, logits == a lone session fed exactly those frames
    exact = len(results) == streams
    for r in results:
        clip = clips[int(r.stream_id[1:])]
        plan = gate.plan([ActivityGate.activity(f) for f in clip])
        proc = [t for t, p in enumerate(plan) if p]
        if r.frames_processed != len(proc):
            exact = False
            continue
        if not proc:
            exact = exact and r.logits is None
            continue
        session = deployed.stream(batch=1, backend=backend)
        for t in proc:
            ref = session.step(clip[t][None])
        exact = exact and r.logits is not None and bool(
            (np.asarray(ref)[0] == r.logits).all()
        )

    sg = stats["gating"]
    energy = energy_summary(
        deployed,
        frames_processed=sg["frames_processed"],
        frames_total=sg["frames_processed"] + sg["frames_skipped"],
        completed=sum(1 for r in results if r.logits is not None),
    )
    return {
        "pool_size": pool_size,
        "backend": backend,
        "streams": streams,
        "frames_per_stream": frames,
        "trace_duty_cycle": duty,
        "gate": {"wake_threshold": gate.wake_threshold,
                 "park_threshold": gate.park_threshold,
                 "park_after": gate.park_after},
        "wall_s": wall,
        "parks": sg["parks"],
        "wakes": sg["wakes"],
        "trace_count": pool.trace_count,
        "exact_vs_gate_plan": exact,
        **energy,
    }


def bench_phases(deployed, clips, pool_size: int, backend: str):
    """The schema-4 observability cell: the same staggered-arrival pool
    scenario driven twice — once untraced, once under a `repro.obs.Tracer`
    — with the traced run's final logits checked byte-identical against
    the untraced run (tracing must observe, never alter), then the trace
    attributed into per-tick phase fractions via `phase_breakdown`."""
    from repro.obs import Tracer, phase_breakdown, to_chrome

    g = deployed.graph

    def drive(tracer):
        pool = deployed.serve(pool_size, backend=backend)
        pool.admit("__warm__")
        pool.step({"__warm__": np.zeros((*g.input_hw, g.input_ch), np.float32)})
        pool.evict("__warm__")
        batcher = ContinuousBatcher(pool, tracer=tracer)
        for i in range(clips.shape[0]):
            batcher.submit(StreamRequest(stream_id=f"s{i}", frames=clips[i],
                                         arrival=i))
        results = batcher.run()
        jax.block_until_ready(pool.state.buf)
        finals = {r.stream_id: np.asarray(r.logits) for r in results}
        return batcher, pool, finals

    _, _, plain = drive(None)
    tracer = Tracer()
    batcher, pool, traced = drive(tracer)
    exact = set(plain) == set(traced) and all(
        (plain[sid] == traced[sid]).all() for sid in plain
    )

    lane = phase_breakdown(to_chrome(tracer)).get(batcher.track, {})
    fractions = {
        name: round(cell["fraction"], 4)
        for name, cell in lane.get("phases", {}).items()
    }
    return {
        "pool_size": pool_size,
        "backend": backend,
        "streams": int(clips.shape[0]),
        "frames_per_stream": int(clips.shape[1]),
        "ticks": lane.get("ticks", 0),
        "tick_total_us": round(lane.get("tick_total_us", 0.0), 1),
        "trace_events": len(tracer),
        "trace_count": pool.trace_count,
        "exact_vs_untraced": exact,
        "phase_fraction": fractions,
    }


def run(args) -> int:
    net = args.net or (SMOKE_NET if args.smoke else FULL_NET)
    pools = args.pools or ([2, 4] if args.smoke else [2, 4, 8])
    backends = args.backends or ["fused", "ref"]
    frames = args.frames or (4 if args.smoke else 6)

    prog = api.get_net(net)
    g = prog.graph
    params = prog.init(jax.random.PRNGKey(0))
    calib = _event_clips(g, 2, frames, jax.random.PRNGKey(1))
    deployed = prog.quantize(params, calib=calib)

    results, failures = [], []
    for pool_size in pools:
        clips = _event_clips(
            g, 2 * pool_size, frames, jax.random.PRNGKey(2 + pool_size)
        )
        for backend in backends:
            row = bench_cell(deployed, clips, pool_size, backend)
            results.append({"net": net, **row})
            if not row["exact_vs_single_session"]:
                failures.append(
                    f"{net}/pool{pool_size}/{backend}: pooled logits != "
                    f"single-session logits"
                )
            if row["trace_count"] != 1:
                failures.append(
                    f"{net}/pool{pool_size}/{backend}: step retraced "
                    f"{row['trace_count']}x (continuous batching broken)"
                )
            print(
                f"[serving-bench] {net:>18s} pool{pool_size} {backend:>6s}: "
                f"{row['pool_frames_per_s']:8.1f} frames/s "
                f"(x{row['speedup_vs_sequential']:.2f} vs sequential), "
                f"occupancy {row['mean_occupancy']:.2f}, "
                f"p50 {row['latency_ms_p50']:.1f} ms / "
                f"p99 {row['latency_ms_p99']:.1f} ms, "
                f"exact={row['exact_vs_single_session']}"
            )

    fleet = None
    if not args.no_fleet:
        fleet_nets = tuple(args.fleet_nets) if args.fleet_nets else (
            FLEET_NETS_SMOKE if args.smoke else FLEET_NETS_FULL
        )
        fleet = bench_fleet(
            fleet_nets, backend=backends[0],
            pool_cap=max(pools), streams=2 * max(pools), frames=frames,
        )
        if not fleet["exact_vs_single_session"]:
            failures.append("fleet: pooled logits != single-session logits")
        if not fleet["zero_retrace"]:
            failures.append("fleet: a bucket pool retraced (ladder broken)")
        print(
            f"[serving-bench] {'fleet':>18s} {len(fleet_nets)} nets "
            f"{fleet['backend']:>6s}: p50 {fleet['latency_ms_p50']:.1f} ms / "
            f"p99 {fleet['latency_ms_p99']:.1f} ms per tick, "
            f"{fleet['completed']} streams, exact="
            f"{fleet['exact_vs_single_session']}, "
            f"zero_retrace={fleet['zero_retrace']}"
        )

    gated = None
    if not args.no_gate:
        gated = bench_gated(
            deployed, backend=backends[0], pool_size=max(pools),
            streams=2 * max(pools), frames=frames, duty=args.duty_cycle,
        )
        if not gated["exact_vs_gate_plan"]:
            failures.append("gated: pooled logits != gate-plan lone session")
        if gated["trace_count"] != 1:
            failures.append(
                f"gated: step retraced {gated['trace_count']}x")
        if gated["frames_skipped"] > 0 and not gated["energy_uj_saved"] > 0:
            failures.append(
                f"gated: skipped {gated['frames_skipped']} frames but saved "
                f"{gated['energy_uj_saved']:.3f} uJ")
        print(
            f"[serving-bench] {'gated':>18s} pool{gated['pool_size']} "
            f"{gated['backend']:>6s}: duty {gated['duty_cycle']:.2f}, "
            f"{gated['frames_skipped']}/{gated['frames_total']} frames "
            f"skipped, {gated['energy_uj_saved']:.2f} uJ saved, "
            f"{gated['energy_uj_per_classification']:.3f} uJ/cls "
            f"(ungated {gated['energy_uj_per_classification_ungated']:.3f}), "
            f"exact={gated['exact_vs_gate_plan']}"
        )

    phases = None
    if not args.no_phases:
        phases = bench_phases(
            deployed,
            _event_clips(g, 2 * max(pools), frames,
                         jax.random.PRNGKey(2 + max(pools))),
            pool_size=max(pools), backend=backends[0],
        )
        if not phases["exact_vs_untraced"]:
            failures.append(
                "phases: traced logits != untraced logits (tracing "
                "perturbed serving — zero-overhead contract broken)"
            )
        if phases["trace_count"] != 1:
            failures.append(
                f"phases: step retraced {phases['trace_count']}x under "
                f"tracing"
            )
        if not phases["phase_fraction"].get("step", 0.0) > 0.0:
            failures.append("phases: no step time attributed in the trace")
        frac = phases["phase_fraction"]
        print(
            f"[serving-bench] {'phases':>18s} pool{phases['pool_size']} "
            f"{phases['backend']:>6s}: {phases['ticks']} ticks, "
            f"step {frac.get('step', 0.0):.1%} / "
            f"assemble {frac.get('assemble', 0.0):.1%} / "
            f"admit {frac.get('admit', 0.0):.1%} / "
            f"other {frac.get('other', 0.0):.1%}, "
            f"exact_vs_untraced={phases['exact_vs_untraced']}"
        )

    payload = {
        "schema": 4,
        "meta": {
            "smoke": bool(args.smoke),
            "net": net,
            "jax_backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "frames_per_stream": frames,
            "generated_unix": int(time.time()),
            "note": (
                "Pool frames/s is host wall-clock over a staggered-arrival "
                "continuous-batching simulation; Pallas backends interpret "
                "on non-TPU hosts, so absolute numbers there are "
                "directional.  exact_vs_single_session and trace_count==1 "
                "are the serving correctness contract.  latency_ms_p50/p99 "
                "are per-tick wall percentiles with compile excluded "
                "(warmup tick / warmup round); the fleet cell measures "
                "round 2 through pre-warmed bucket pools.  Schema 3 adds "
                "the activity-gated cell: exact_vs_gate_plan is the "
                "differential gated-vs-ungated contract and the energy_* "
                "fields price skipped frames via repro.serving "
                "energy_summary (sim counters, deterministic).  Schema 4 "
                "adds the phases cell: the largest pool scenario re-driven "
                "under a repro.obs.Tracer, phase_fraction splitting tick "
                "wall time across admit/assemble/step, exact_vs_untraced "
                "the traced-vs-untraced byte-identity contract."
            ),
        },
        "results": results,
        "fleet": fleet,
        "gated": gated,
        "phases": phases,
    }
    default_name = "BENCH_serving.smoke.json" if args.smoke else "BENCH_serving.json"
    out = Path(args.out) if args.out else REPO_ROOT / default_name
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"[serving-bench] wrote {out} ({len(results)} cells)")
    if failures:
        for f in failures:
            print(f"[serving-bench] FAIL {f}", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny registry net, small pools — the CI cell")
    ap.add_argument("--net", default=None)
    ap.add_argument("--pools", nargs="*", type=int, default=None)
    ap.add_argument("--backends", nargs="*", default=None,
                    choices=list(api.BACKENDS))
    ap.add_argument("--frames", type=int, default=None,
                    help="frames per sensor stream")
    ap.add_argument("--fleet-nets", nargs="*", default=None,
                    help="nets for the multi-tenant FleetRouter cell "
                         "(default: 3 distinct temporal registry nets)")
    ap.add_argument("--no-fleet", action="store_true",
                    help="skip the fleet cell (single-pool sweep only)")
    ap.add_argument("--no-gate", action="store_true",
                    help="skip the activity-gated cell")
    ap.add_argument("--no-phases", action="store_true",
                    help="skip the traced phase-breakdown cell")
    ap.add_argument("--duty-cycle", type=float, default=0.4,
                    help="active-frame fraction of the gated cell's traces")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: <repo>/BENCH_serving.json)")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main())
