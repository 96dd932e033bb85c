"""Serving launcher: batched prefill + decode loop with continuous batching.

Two serving paths:
  * LM serving (``--arch``): prefill a batch of prompts, then decode
    autoregressively with a KV/SSM cache — the decode_32k / long_500k cells
    run exactly this step function on the production mesh.
  * CUTIE multi-sensor streaming (``--dvs``): the paper's autonomous mode
    scaled out — an arrival/departure simulation of many DVS sensor
    streams continuously batched onto one `repro.serving.SessionPool`.
    Sensors come online staggered, stream their event frames through the
    ternary CNN into slot-masked TCN ring memory, and finished streams free
    their slot for the next arrival without retracing the jitted step.
    Reports frames/s, pool occupancy, and streaming accuracy against the
    pipeline's ground-truth labels; verifies the pool against independent
    single-stream `StreamSession`s (bit-exact) and exits non-zero on any
    mismatch or non-finite logits — the CI ``serve-smoke`` gate.

  * CUTIE fleet serving (``--fleet``): the multi-tenant version — >= 3
    distinct registry TCN nets registered on one
    `repro.serving.FleetRouter`, staggered arrivals interleaved across
    buckets, ladder autoscaling, async ingestion, and the same per-stream
    bit-exactness gate plus the zero-retrace pool audit — the CI
    ``fleet-smoke`` gate.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke --tokens 16
    PYTHONPATH=src python -m repro.launch.serve --dvs --pool 4 --frames 6 --backend fused
    PYTHONPATH=src python -m repro.launch.serve --fleet --pool 4 --frames 5 --out fleet.json

    The DVS default backend is "fused": conv+threshold(+pool) in one kernel
    launch per layer, int8 ternary activations between layers — the
    silicon's 2-bit activation memory model (see benchmarks/backend_bench.py
    for measured speedups vs the unfused backends).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_local_mesh
from repro.launch.sharding import ShardingRules
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.model import init_params


def serve_lm(args):
    cfg = get_config(args.arch, quant=args.quant, smoke=args.smoke)
    mesh = make_local_mesh()
    rules = ShardingRules(mesh)
    shard = rules.make_shard_fn()
    key = jax.random.PRNGKey(args.seed)
    with mesh:
        params = init_params(cfg, key, dtype=jnp.float32)
        prefill = jax.jit(make_prefill_step(
            cfg, args.prompt_len + args.tokens, shard=shard, cache_dtype=jnp.float32
        ))
        decode = jax.jit(make_decode_step(cfg, shard=shard), donate_argnums=(2,))

        batch = {
            "tokens": jax.random.randint(
                key, (args.batch, args.prompt_len), 0, cfg.vocab_size
            )
        }
        if cfg.frontend == "vision":
            batch["frontend_embeds"] = jax.random.normal(
                key, (args.batch, cfg.frontend_seq, cfg.d_model))
        if cfg.is_encdec:
            batch["enc_embeds"] = jax.random.normal(
                key, (args.batch, cfg.enc_seq_len, cfg.d_model))

        t0 = time.time()
        logits, cache = prefill(params, batch)
        t_pf = time.time() - t0
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out_tokens = [tok]
        t0 = time.time()
        for _ in range(args.tokens - 1):
            logits, cache = decode(params, tok, cache)
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            out_tokens.append(tok)
        jax.block_until_ready(tok)
        t_dec = time.time() - t0
        seqs = jnp.concatenate(out_tokens, axis=1)
    assert np.isfinite(np.asarray(logits)).all(), "NaN logits in decode"
    print(f"[serve] {cfg.name}: prefill({args.batch}x{args.prompt_len}) {t_pf*1e3:.0f} ms; "
          f"{args.tokens-1} decode steps {t_dec*1e3:.0f} ms "
          f"({t_dec/max(args.tokens-1,1)*1e3:.1f} ms/tok)")
    print(f"[serve] sample tokens: {np.asarray(seqs[0,:8])}")
    return seqs


def serve_dvs(args) -> int:
    """Continuous-batching multi-sensor simulation over a `SessionPool`.

    ``--streams`` sensors (default 2x the pool) each produce ``--frames``
    event frames; sensor i comes online at tick i, so the pool sees
    arrivals, departures, and slot refills mid-flight.  Exit code is the
    health gate CI runs: non-zero on non-finite logits or any pool-vs-
    single-session logit mismatch.
    """
    from repro.api import get_net
    from repro.data.pipeline import DVSEventPipeline
    from repro.serving import ContinuousBatcher, StreamRequest

    if args.frames <= 0:
        # nothing to stream — report an idle pool instead of crashing on
        # unbound logits (the pre-pool serve loop's --frames 0 bug)
        print(f"[serve-dvs] --frames {args.frames}: no frames to serve; "
              f"pool of {args.pool} stays idle")
        return 0

    n_streams = args.streams or 2 * args.pool
    if args.program:
        # fleet path: serve a shipped ``.cutie`` artifact — no CutieGraph,
        # no quantization; the pool runs what the device would load
        from repro import artifact

        deployed = artifact.load(args.program)
        g = deployed.graph  # ProgramInfo — serving metadata only
        print(f"[serve-dvs] program loaded from {args.program}: {g.name}, "
              f"{deployed.nbytes} packed weight bytes")
        pipe = DVSEventPipeline(
            n_streams, steps=args.frames, hw=g.input_hw[0], seed=args.seed
        )
        frames, labels = pipe.next_batch()
    else:
        prog = get_net(args.net)
        g = prog.graph
        params = prog.init(jax.random.PRNGKey(args.seed))
        pipe = DVSEventPipeline(
            n_streams, steps=args.frames, hw=g.input_hw[0], seed=args.seed
        )
        frames, labels = pipe.next_batch()
        deployed = prog.quantize(params, calib=frames)

    pool = deployed.serve(
        args.pool, backend=args.backend,
        sharding="auto" if args.shard else None,
    )
    tracer = _make_tracer(args)
    batcher = ContinuousBatcher(pool, tracer=tracer)
    for i in range(n_streams):
        batcher.submit(StreamRequest(
            stream_id=f"sensor-{i}", frames=frames[i],
            label=int(labels[i]), arrival=i,
        ))

    t0 = time.time()
    results = batcher.run()
    jax.block_until_ready(pool.state.buf)
    wall = time.time() - t0
    stats = batcher.stats()
    _write_obs(args, tracer, {batcher.track: deployed}, batcher.metrics,
               tag="serve-dvs")

    finite = all(np.isfinite(r.logits).all() for r in results)
    acc = stats["accuracy"]
    fps = stats["frames_processed"] / wall if wall > 0 else float("nan")
    print(f"[serve-dvs] {g.name} ({args.backend}): {n_streams} sensors x "
          f"{args.frames} frames through a {args.pool}-slot pool "
          f"(shard={pool.sharding is not None})")
    print(f"[serve-dvs] {stats['frames_processed']} frames in "
          f"{stats['ticks']} ticks, {wall:.2f} s -> {fps:.0f} frames/s host, "
          f"mean occupancy {stats['mean_occupancy']:.2f}, "
          f"step retraces {pool.trace_count}")
    chance = f"untrained weights — chance is {1.0 / g.n_classes:.2f}"
    print(f"[serve-dvs] streaming accuracy {acc:.2f} "
          f"({chance if acc < 0.9 else 'vs ground-truth labels'}); "
          f"logits finite: {finite}")

    # the serving contract: each pooled stream == a lone StreamSession
    mismatches = _verify_pool_vs_sessions(
        deployed, results, frames, args.backend, check=min(args.check_streams, n_streams)
    )
    rep = deployed.silicon_report(v=0.5)
    sensor_fps = rep.inf_per_s * g.passes_per_inference
    print(f"[serve-dvs] CUTIE @0.5V would run each sensor at "
          f"{sensor_fps:.0f} frames/s, {rep.energy_uj:.2f} uJ/classification "
          f"({args.pool} sensors -> {args.pool * sensor_fps:.0f} frames/s "
          f"aggregate)")
    if not finite:
        print("[serve-dvs] FAIL: non-finite logits", file=sys.stderr)
        return 1
    if mismatches:
        for m in mismatches:
            print(f"[serve-dvs] FAIL: {m}", file=sys.stderr)
        return 1
    if len(results) != n_streams:
        print(f"[serve-dvs] FAIL: {len(results)}/{n_streams} streams completed",
              file=sys.stderr)
        return 1
    return 0


def serve_fleet_scenario(args) -> int:
    """Multi-tenant fleet simulation over a `repro.serving.FleetRouter`.

    ``--fleet-nets`` registry nets (>= 3 distinct TCN nets by default) are
    registered as fleet tenants; each gets ``--streams`` sensors whose
    arrivals interleave across nets (sensor s of net i arrives at tick
    i + s * n_nets), so every bucket sees admissions, departures, pool
    autoscaling, and FIFO spill mid-flight.  The CI ``fleet-smoke`` gate:
    exit non-zero on any pooled-vs-lone-session logit mismatch, non-finite
    logits, incomplete streams, or any bucket pool tracing more than once
    (the zero-retrace bucket-ladder contract).  ``--out`` writes the full
    fleet stats report (per-net p50/p99 per pool size, scale events,
    trace audit) as JSON for artifact upload.

    ``--gate`` runs the fleet activity-gated (`repro.serving.gating`) on a
    bursty ``--duty-cycle`` trace — the CI ``gate-smoke`` gate: each gated
    stream must reproduce a lone session fed exactly the frames
    `ActivityGate.plan` selects (bit-exact), the processed/skipped split
    must match the plan, and the fleet must show a strictly positive
    energy saving whenever the trace leaves frames quiet.
    """
    import json

    from repro.api import get_net
    from repro.data.pipeline import DVSEventPipeline, KWSSpectrogramPipeline
    from repro.serving import (
        ActivityGate,
        FleetRouter,
        StreamRequest,
        energy_summary,
    )

    net_names = [n.strip() for n in args.fleet_nets.split(",") if n.strip()]
    if len(net_names) < 2:
        print(f"[serve-fleet] need >= 2 nets, got {net_names}", file=sys.stderr)
        return 2
    n_streams = args.streams or 4
    gate = None
    if args.gate:
        gate = ActivityGate(
            wake_threshold=args.wake_threshold,
            park_threshold=args.park_threshold,
            park_after=args.park_after,
        )
    duty = args.duty_cycle if args.duty_cycle is not None else (
        0.4 if args.gate else 1.0
    )
    tracer = _make_tracer(args)
    router = FleetRouter(
        backend=args.backend,
        max_pool_size=args.pool,
        queue_limit=args.queue_limit,
        shrink_after=args.shrink_after,
        ingest=args.ingest,
        sharding="auto" if args.shard else None,
        gate=gate,
        tracer=tracer,
    )
    deps, clips = {}, {}
    for idx, name in enumerate(net_names):
        prog = get_net(name)
        g = prog.graph
        if not g.is_temporal:
            print(f"[serve-fleet] {name} is not temporal; pick TCN nets",
                  file=sys.stderr)
            return 2
        pipe_cls = DVSEventPipeline if g.input_ch == 2 else KWSSpectrogramPipeline
        pipe = pipe_cls(
            n_streams, steps=args.frames, hw=g.input_hw[0],
            n_classes=g.n_classes, seed=args.seed + idx, duty_cycle=duty,
        )
        frames, labels = pipe.next_batch()
        deps[name] = prog.quantize(
            prog.init(jax.random.PRNGKey(args.seed + idx)), calib=frames
        )
        router.register(name, deps[name])
        for s in range(n_streams):
            sid = f"{name}/sensor-{s}"
            clips[sid] = np.asarray(frames[s])
            router.submit(StreamRequest(
                stream_id=sid, frames=clips[sid], label=int(labels[s]),
                arrival=idx + s * len(net_names), net=name,
            ))

    t0 = time.time()
    results = router.run()
    wall = time.time() - t0
    stats = router.stats()
    agg = stats["aggregate"]

    threaded = any(s["ingest_threaded"] for s in stats["nets"].values())
    gating = (f", gated duty~{duty:.2f}" if gate is not None else "")
    print(f"[serve-fleet] {len(net_names)} nets x {n_streams} sensors x "
          f"{args.frames} frames ({args.backend}, ladder cap {args.pool}, "
          f"ingest={'thread' if threaded else 'sync'}{gating})")
    print(f"[serve-fleet] {agg['frames_processed']} frames, "
          f"{agg['completed']} streams in {agg['ticks']} ticks, {wall:.2f} s; "
          f"fleet p50 {agg['latency_ms_p50']:.1f} ms / "
          f"p99 {agg['latency_ms_p99']:.1f} ms per tick")
    failures = []
    for name in net_names:
        s = stats["nets"][name]
        scale = "".join(
            f" {e['from_size']}->{e['to_size']}" for e in s["scale_events"]
        ) or " (none)"
        print(f"[serve-fleet]   {name}: completed {s['completed']}, "
              f"traced {s['pools_traced']}, scale{scale}, "
              f"p50 {s['latency_ms_p50']:.1f} ms")
        # zero-retrace contract: every pool a bucket ever ran traced once
        bad = {sz: tc for sz, tc in s["pools_traced"].items() if tc > 1}
        if bad:
            failures.append(f"{name}: retraced pools {bad}")
        if not any(tc == 1 for tc in s["pools_traced"].values()):
            failures.append(f"{name}: no pool ever traced (bucket never stepped)")

    # per-stream bit-exactness vs lone StreamSessions.  Gated: the lone
    # session is fed exactly the frames ActivityGate.plan selects — the
    # differential contract gated serving must honour.
    finite = all(
        np.isfinite(r.logits).all() for r in results if r.logits is not None
    )
    checked = mismatched = 0
    for r in results:
        clip = clips[r.stream_id]
        if gate is None:
            processed = list(range(clip.shape[0]))
        else:
            plan = gate.plan([ActivityGate.activity(f) for f in clip])
            processed = [t for t, p in enumerate(plan) if p]
            if r.frames_processed != len(processed):
                mismatched += 1
                failures.append(
                    f"{r.stream_id}: processed {r.frames_processed} frames, "
                    f"gate plan says {len(processed)}")
                continue
        checked += 1
        if not processed:
            if r.logits is not None:
                mismatched += 1
                failures.append(
                    f"{r.stream_id}: all-quiet stream has logits")
            continue
        session = deps[r.net].stream(batch=1, backend=args.backend)
        for t in processed:
            ref = session.step(clip[t][None])
        if r.logits is None or not (np.asarray(ref)[0] == r.logits).all():
            mismatched += 1
            failures.append(f"{r.stream_id}: pooled logits != lone session")
    print(f"[serve-fleet] bit-exactness: {checked} streams replayed"
          f"{' (gated frame plan)' if gate is not None else ''}, "
          f"{mismatched} mismatches; logits finite: {finite}")
    if not finite:
        failures.append("non-finite logits")
    if len(results) != len(net_names) * n_streams:
        failures.append(
            f"{len(results)}/{len(net_names) * n_streams} streams completed")

    energy = {}
    if gate is not None:
        for name in net_names:
            sg = stats["nets"][name]["gating"]
            nres = [r for r in results if r.net == name]
            energy[name] = energy_summary(
                deps[name],
                frames_processed=sg["frames_processed"],
                frames_total=sg["frames_processed"] + sg["frames_skipped"],
                completed=sum(1 for r in nres if r.logits is not None),
            )
            e = energy[name]
            print(f"[serve-fleet]   {name}: {e['frames_skipped']} of "
                  f"{e['frames_total']} frames skipped -> "
                  f"{e['energy_uj_saved']:.2f} uJ saved, "
                  f"{e['energy_uj_per_classification']:.2f} uJ/classification "
                  f"(ungated {e['energy_uj_per_classification_ungated']:.2f})")
            if duty < 1.0 and not e["energy_uj_saved"] > 0.0:
                failures.append(
                    f"{name}: non-positive gated energy saving "
                    f"({e['energy_uj_saved']:.3f} uJ at duty {duty:.2f})")

    # the retrace audit and gated savings land in the metrics registry
    # too, so a --metrics-out snapshot carries the zero-retrace and
    # energy story next to the occupancy/latency series
    m_trace = router.metrics.gauge(
        "cutie_trace_count", "Jit traces per (net, pool rung); contract: <= 1")
    for name in net_names:
        for sz, tc in stats["nets"][name]["pools_traced"].items():
            m_trace.labels(net=name, pool_size=str(sz)).set(tc)
    if energy:
        m_saved = router.metrics.gauge(
            "cutie_gate_energy_saved_uj", "uJ the activity gate saved")
        for name, e in energy.items():
            m_saved.labels(net=name).set(e["energy_uj_saved"])
    _write_obs(args, tracer, deps, router.metrics, tag="serve-fleet")

    if args.out:
        report = {"scenario": {
            "nets": net_names, "streams_per_net": n_streams,
            "frames": args.frames, "backend": args.backend,
            "ladder_cap": args.pool, "wall_s": wall,
            "gate": dataclasses.asdict(gate) if gate is not None else None,
            "duty_cycle": duty,
        }, "stats": stats, "energy": energy or None, "failures": failures}
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, default=float)
        print(f"[serve-fleet] report -> {args.out}")
    router.close()
    for msg in failures:
        print(f"[serve-fleet] FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


def _make_tracer(args):
    """A `repro.obs.Tracer` when ``--trace`` was given, else None (the
    serving layer then runs on NULL_TRACER — zero overhead)."""
    if not getattr(args, "trace", None):
        return None
    from repro.obs import Tracer

    return Tracer(clock=args.trace_clock)


def _write_obs(args, tracer, programs, metrics, tag: str) -> None:
    """Write the ``--trace`` Perfetto JSON (serving spans + one sim
    layer-timeline track per served program) and the ``--metrics-out``
    Prometheus snapshot, when requested."""
    if tracer is not None:
        from repro.obs import save_chrome

        save_chrome(
            args.trace, tracer, sim_programs=programs,
            meta={"scenario": tag, "backend": args.backend},
        )
        print(f"[{tag}] trace -> {args.trace} ({len(tracer)} events, "
              f"{tracer.dropped} dropped; load in ui.perfetto.dev)")
    if getattr(args, "metrics_out", None):
        with open(args.metrics_out, "w") as f:
            f.write(metrics.render())
        print(f"[{tag}] metrics -> {args.metrics_out}")


def _verify_pool_vs_sessions(deployed, results, frames, backend, check: int):
    """Replay the first ``check`` streams through independent batch-1
    `StreamSession`s; pooled final logits must match bit-for-bit."""
    mismatches = []
    by_id = {r.stream_id: r for r in results}
    for i in range(check):
        sid = f"sensor-{i}"
        if sid not in by_id:
            mismatches.append(f"{sid}: no result")
            continue
        session = deployed.stream(batch=1, backend=backend)
        for t in range(frames.shape[1]):
            ref_logits = session.step(frames[i:i + 1, t])
        got = by_id[sid].logits
        want = np.asarray(ref_logits)[0]
        if not (got == want).all():
            mismatches.append(
                f"{sid}: pool logits != single-session logits "
                f"(max|diff|={np.abs(got - want).max():.3e})"
            )
    print(f"[serve-dvs] pool vs single-session: {check} streams replayed, "
          f"{len(mismatches)} mismatches")
    return mismatches


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default="none",
                    choices=["none", "ternary", "ternary_packed"])
    ap.add_argument("--dvs", action="store_true")
    from repro.api import BACKENDS
    ap.add_argument("--backend", default="fused", choices=list(BACKENDS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--frames", type=int, default=8,
                    help="dvs: event frames per sensor stream")
    ap.add_argument("--net", default="dvs_cnn_tcn",
                    help="dvs: registry net to serve (e.g. dvs_cnn_tcn_smoke)")
    ap.add_argument("--program", default=None, metavar="FILE.cutie",
                    help="dvs: serve a compiled .cutie artifact "
                         "(repro.artifact) instead of quantizing --net")
    ap.add_argument("--pool", type=int, default=4,
                    help="dvs: SessionPool slots (fixed jitted batch width)")
    ap.add_argument("--streams", type=int, default=0,
                    help="dvs: total sensor streams to serve (0 = 2x pool); "
                         "with --fleet: streams PER NET (0 = 4), arrivals "
                         "staggered across the --fleet-nets buckets (see "
                         "--queue-limit/--shrink-after/--ingest for the "
                         "fleet admission and autoscale knobs)")
    ap.add_argument("--fleet", action="store_true",
                    help="dvs: multi-tenant FleetRouter scenario over "
                         "--fleet-nets instead of a single SessionPool "
                         "(--pool becomes the bucket-ladder cap)")
    ap.add_argument("--fleet-nets",
                    default="dvs_cnn_tcn_smoke,dvs_cnn_tcn_micro,dvs_cnn_tcn_nano",
                    help="fleet: comma-separated registry nets to register "
                         "as tenants (>= 2, temporal only)")
    ap.add_argument("--queue-limit", type=int, default=64,
                    help="fleet: bounded admission FIFO per bucket; "
                         "overflow raises FleetQueueFull")
    ap.add_argument("--shrink-after", type=int, default=3,
                    help="fleet: calm ticks before a bucket shrinks down "
                         "the ladder (grow is immediate)")
    ap.add_argument("--ingest", default="auto",
                    choices=["auto", "thread", "sync", "off"],
                    help="fleet: host-side frame ingestion — feeder thread "
                         "with double buffers (auto/thread), synchronous "
                         "assembly (sync), or no prefetch at all (off)")
    ap.add_argument("--gate", action="store_true",
                    help="fleet: activity-gate the streams (park quiet "
                         "sensors out of their pool slot, wake on events; "
                         "adds the gated-vs-ungated bit-exactness and "
                         "energy-saving gates)")
    ap.add_argument("--duty-cycle", type=float, default=None,
                    help="fleet: fraction of frames carrying events in the "
                         "synthetic traces (default 1.0, or 0.4 with "
                         "--gate — a bursty trace the gate can park on)")
    ap.add_argument("--wake-threshold", type=int, default=16,
                    help="gate: event count that wakes a parked stream")
    ap.add_argument("--park-threshold", type=int, default=4,
                    help="gate: event count below which a frame is quiet")
    ap.add_argument("--park-after", type=int, default=2,
                    help="gate: consecutive quiet frames before parking")
    ap.add_argument("--out", default=None, metavar="FILE.json",
                    help="fleet: write the full stats report as JSON")
    ap.add_argument("--trace", default=None, metavar="FILE.json",
                    help="record a repro.obs trace of the run and write it "
                         "as Chrome/Perfetto trace JSON (tick/step/feeder "
                         "spans, park/wake/scale instants, sim layer "
                         "timelines; inspect with python -m repro.obs)")
    ap.add_argument("--trace-clock", default="wall",
                    choices=["wall", "tick"],
                    help="trace timestamps: wall ns (default) or the "
                         "deterministic per-event sequence")
    ap.add_argument("--metrics-out", default=None, metavar="FILE.prom",
                    help="write the serving metrics registry as a "
                         "Prometheus text snapshot")
    ap.add_argument("--check-streams", type=int, default=2,
                    help="dvs: streams replayed through single sessions for "
                         "the bit-exactness gate")
    ap.add_argument("--shard", action="store_true",
                    help="dvs: shard the pool axis across local devices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.fleet:
        return serve_fleet_scenario(args)
    if args.dvs:
        return serve_dvs(args)
    return serve_lm(args)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    rc = main()
    sys.exit(rc if isinstance(rc, int) else 0)
