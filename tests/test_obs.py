"""repro.obs: tracing, metrics, and export contracts.

The contracts under test:
  * **zero overhead when disabled** — instrumented paths hold `NULL_TRACER`
    unconditionally; it must record nothing and allocate nothing per call
    (one shared no-op span object);
  * **observation never alters serving** — a traced `ContinuousBatcher`
    run produces logits byte-identical to an untraced run, and the jitted
    step still compiles exactly once;
  * **deterministic tick clock** — two runs of the same gated-fleet
    scenario on the ref and fused backends emit the *same* event sequence
    under ``clock="tick"`` (the schedule, not the backend, is the trace);
  * **bounded memory** — the ring buffer drops oldest events on overflow
    and the scheduler's ``latency_trace`` is a bounded `SampleWindow`;
  * **structural validity** — exported Chrome JSON round-trips through
    ``json.loads``, spans nest properly per lane, and `validate_nesting`
    flags an artificially overlapped span.
"""
import builtins
import gc
import json
import os
import threading
import time
import weakref

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.api.program import CutieProgram
from repro.obs import (
    NULL_TRACER,
    MetricsRegistry,
    SampleWindow,
    Tracer,
    layer_timeline,
    load,
    phase_breakdown,
    save_chrome,
    to_chrome,
    trace_diff,
    trace_summary,
    validate_nesting,
)
from repro.obs.__main__ import main as obs_main
from repro.serving import (
    ActivityGate,
    ContinuousBatcher,
    FleetRouter,
    StreamRequest,
)
from repro.obs.tracer import SCHEDSTAT
from repro.serving.scheduler import LATENCY_WINDOW

GATE = ActivityGate(wake_threshold=8, park_threshold=3, park_after=2)


def tiny_graph(name="tiny_obs", tcn_steps=4):
    return api.CutieGraph(
        name=name, input_hw=(4, 4), input_ch=2, n_classes=3,
        tcn_steps=tcn_steps,
        layers=(api.conv2d(2, 4), api.global_pool(),
                api.tcn(4, 4, dilation=1), api.tcn(4, 4, dilation=2),
                api.last_step(), api.fc(4, 3)),
    )


_DEPLOYED = None


def get_deployed():
    global _DEPLOYED
    if _DEPLOYED is None:
        graph = tiny_graph()
        prog = CutieProgram(graph)
        calib = (jax.random.uniform(jax.random.PRNGKey(1),
                                    (2, 6, *graph.input_hw, graph.input_ch))
                 < 0.3).astype(jnp.float32)
        _DEPLOYED = prog.quantize(prog.init(jax.random.PRNGKey(0)),
                                  calib=calib)
    return _DEPLOYED


@pytest.fixture(scope="module")
def deployed():
    return get_deployed()


def event_clips(n_streams, frames, seed=7):
    shape = (n_streams, frames, 4, 4, 2)
    return np.asarray(
        (jax.random.uniform(jax.random.PRNGKey(seed), shape) < 0.3)
        .astype(jnp.float32))


def bursty_clips(n_streams, frames):
    """Alternating quiet / burst frames so the gate parks and wakes."""
    clips = np.zeros((n_streams, frames, 4, 4, 2), np.float32)
    for s in range(n_streams):
        for t in range(frames):
            if (t // 2 + s) % 2 == 0:
                clips[s, t].reshape(-1)[: GATE.wake_threshold + 2] = 1.0
    return clips


# ---------------------------------------------------------------------------
# tracer primitives


def test_null_tracer_records_nothing():
    span = NULL_TRACER.span("tick", track="a", tick=3)
    with span:
        NULL_TRACER.instant("wake", track="a")
        NULL_TRACER.counter("occupancy", 0.5)
    assert NULL_TRACER.events() == []
    assert not NULL_TRACER  # falsy: `tracer or NULL_TRACER` chains work
    assert not NULL_TRACER.enabled
    # the shared-singleton contract: no per-call span allocation
    assert NULL_TRACER.span("x") is NULL_TRACER.span("y")


def test_span_records_on_exit_with_tick_clock():
    tr = Tracer(clock="tick")
    with tr.span("outer", track="lane", tick=0):
        with tr.span("inner", track="lane"):
            pass
    inner, outer = tr.events()
    assert inner.name == "inner" and outer.name == "outer"
    # tick clock: deterministic sequence numbers 0..3
    assert (outer.ts, inner.ts) == (0, 1)
    assert inner.dur == 1 and outer.dur == 3
    assert outer.args == {"tick": 0}
    assert outer.track == "lane"


def test_instant_and_counter_forms():
    tr = Tracer(clock="tick")
    tr.instant("park", track="a", stream="s0")
    tr.counter("occupancy", 0.75, track="a")
    tr.counter("stalls", {"bank": 3, "ndb": 1})
    park, occ, stalls = tr.events()
    assert park.phase == "i" and park.args == {"stream": "s0"}
    assert occ.phase == "C" and occ.args == {"occupancy": 0.75}
    assert stalls.args == {"bank": 3, "ndb": 1}


def test_ring_wraparound_keeps_newest():
    tr = Tracer(capacity=3, clock="tick")
    for i in range(10):
        tr.instant(f"e{i}")
    assert [e.name for e in tr.events()] == ["e7", "e8", "e9"]
    assert tr.dropped == 7
    assert len(tr) == 3
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_rejects_bad_config():
    with pytest.raises(ValueError):
        Tracer(capacity=0)
    with pytest.raises(ValueError):
        Tracer(clock="sundial")


def test_thread_tagging_and_export_lanes():
    tr = Tracer(clock="tick")
    tr.instant("from-main")

    def worker():
        tr.instant("from-worker")

    t = threading.Thread(target=worker, name="cutie-feeder_0")
    t.start()
    t.join()
    names = set(tr.thread_names.values())
    assert names == {"main", "cutie-feeder_0"}
    # untracked events land on per-thread lanes in the export
    doc = to_chrome(tr)
    lanes = trace_summary(doc)["lanes"]
    assert set(lanes) == {"main", "cutie-feeder_0"}


# ---------------------------------------------------------------------------
# metrics registry


def test_metrics_render_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("cutie_frames_total", "Frames").labels(net="a").inc(2)
    reg.gauge("cutie_occupancy", "Occupancy").labels(net="a").set(0.75)
    h = reg.histogram("cutie_tick_seconds", "Tick wall", buckets=(0.01, 0.1))
    h.labels(net="a").observe(0.005)
    h.labels(net="a").observe(0.05)
    h.labels(net="a").observe(5.0)  # beyond the last bucket: +Inf only
    text = reg.render()
    assert "# TYPE cutie_frames_total counter" in text
    assert 'cutie_frames_total{net="a"} 2' in text
    assert 'cutie_occupancy{net="a"} 0.75' in text
    assert 'cutie_tick_seconds_bucket{net="a",le="0.01"} 1' in text
    assert 'cutie_tick_seconds_bucket{net="a",le="0.1"} 2' in text
    assert 'cutie_tick_seconds_bucket{net="a",le="+Inf"} 3' in text
    assert 'cutie_tick_seconds_count{net="a"} 3' in text
    assert text.endswith("\n")


def test_metrics_family_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    a = reg.counter("cutie_x_total")
    assert reg.counter("cutie_x_total") is a
    with pytest.raises(ValueError):
        reg.gauge("cutie_x_total")
    with pytest.raises(ValueError):
        a.labels().inc(-1)  # counters only go up


def test_metrics_snapshot():
    reg = MetricsRegistry()
    reg.counter("cutie_y_total").labels(net="b").inc()
    snap = reg.snapshot()
    assert snap["cutie_y_total"]["series"] == {"net=b": 1.0}


def test_sample_window_bounded_and_observing():
    seen = []
    win = SampleWindow(capacity=4, observe=seen.append)
    for i in range(10):
        win.append(i)
    assert list(win) == [6, 7, 8, 9]  # newest kept, like the ring buffer
    assert seen == list(range(10))  # every sample still reached the hook
    win.clear()
    assert list(win) == []


# ---------------------------------------------------------------------------
# export: chrome JSON, nesting, phase attribution


def _synthetic_tracer():
    tr = Tracer(clock="tick")
    with tr.span("tick", track="net_a", tick=0):
        with tr.span("admit", track="net_a"):
            pass
        with tr.span("assemble", track="net_a"):
            pass
        with tr.span("step", track="net_a"):
            pass
    tr.instant("park", track="net_a", stream="s0")
    tr.counter("occupancy", 0.5, track="net_a")
    return tr


def test_chrome_roundtrip_and_validation(tmp_path):
    path = tmp_path / "trace.json"
    save_chrome(str(path), _synthetic_tracer())
    doc = json.loads(path.read_text())  # plain-json loadable
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["clock"] == "tick"
    loaded = load(str(path))
    assert validate_nesting(loaded) == []
    s = trace_summary(loaded)
    assert s["ok"]
    assert s["spans"] == {"admit": 1, "assemble": 1, "step": 1, "tick": 1}
    assert s["instants"] == {"park": 1}
    assert s["lanes"] == {"net_a": 0}


def test_load_rejects_non_trace(tmp_path):
    path = tmp_path / "not_a_trace.json"
    path.write_text("{}")
    with pytest.raises(ValueError):
        load(str(path))


def test_validate_nesting_flags_overlap():
    lane = {"pid": 1, "tid": 0, "ph": "X", "cat": "serving"}
    doc = {"traceEvents": [
        {**lane, "name": "tick", "ts": 0.0, "dur": 10.0},
        {**lane, "name": "step", "ts": 5.0, "dur": 10.0},  # straddles tick end
    ]}
    problems = validate_nesting(doc)
    assert len(problems) == 1 and "step" in problems[0]
    assert not trace_summary(doc)["ok"]


def test_phase_breakdown_fractions():
    lane = {"pid": 1, "tid": 0, "ph": "X"}
    doc = {"traceEvents": [
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 0,
         "args": {"name": "net_a"}},
        {**lane, "name": "tick", "ts": 0.0, "dur": 10.0},
        {**lane, "name": "step", "ts": 1.0, "dur": 6.0},
        {**lane, "name": "admit", "ts": 8.0, "dur": 2.0},
    ]}
    row = phase_breakdown(doc)["net_a"]
    assert row["ticks"] == 1 and row["tick_total_us"] == 10.0
    assert row["phases"]["step"]["fraction"] == pytest.approx(0.6)
    assert row["phases"]["admit"]["fraction"] == pytest.approx(0.2)
    assert row["phases"]["other"]["fraction"] == pytest.approx(0.2)
    # fractions (incl. the residue) account for all tick time
    total = sum(p["fraction"] for p in row["phases"].values())
    assert total == pytest.approx(1.0)


def test_trace_diff_shapes():
    a = to_chrome(_synthetic_tracer())
    b = to_chrome(_synthetic_tracer())
    assert trace_diff(a, b)["identical_shape"]
    tr = _synthetic_tracer()
    tr.instant("wake", track="net_a")
    d = trace_diff(a, to_chrome(tr))
    assert not d["identical_shape"]
    assert d["instant_count_delta"] == {"wake": {"a": 0, "b": 1}}


def test_layer_timeline_tracks(deployed):
    events = layer_timeline(deployed, name="tiny")
    spans = [e for e in events if e["ph"] == "X"]
    assert len(spans) == len(deployed.plan.layers)
    assert all(e["dur"] >= 1 for e in spans)
    # layers tile back to back on the virtual clock
    for prev, cur in zip(spans, spans[1:]):
        assert cur["ts"] == pytest.approx(prev["ts"] + prev["dur"])
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert counters == {"sim:tiny/stall_cycles", "sim:tiny/dyn_ops",
                        "sim:tiny/util"}


# ---------------------------------------------------------------------------
# serving integration


def _drive_pool(deployed, clips, tracer=None, pool_size=3):
    pool = deployed.serve(pool_size, backend="fused")
    batcher = ContinuousBatcher(pool, tracer=tracer)
    for i in range(clips.shape[0]):
        batcher.submit(StreamRequest(stream_id=f"s{i}", frames=clips[i],
                                     arrival=i))
    results = batcher.run()
    finals = {r.stream_id: np.asarray(r.logits) for r in results}
    return batcher, pool, finals


def test_traced_run_logits_byte_identical(deployed):
    clips = event_clips(6, 5)
    _, _, plain = _drive_pool(deployed, clips, tracer=None)
    tracer = Tracer()
    batcher, pool, traced = _drive_pool(deployed, clips, tracer=tracer)
    assert set(plain) == set(traced)
    for sid in plain:
        assert (plain[sid] == traced[sid]).all()
    assert pool.trace_count == 1  # tracing never touches the jit cache
    spans = {e.name for e in tracer.events() if e.phase == "X"}
    assert {"tick", "admit", "assemble", "step", "pool.step", "demux",
            "retire", "pool.admit", "pool.evict"} <= spans
    # and the untraced run really recorded nothing (NULL_TRACER inside)
    assert batcher.track in {e.track for e in tracer.events() if e.track}


def test_untraced_batcher_uses_null_tracer(deployed):
    pool = deployed.serve(2, backend="fused")
    batcher = ContinuousBatcher(pool)
    assert batcher.tracer is NULL_TRACER
    assert pool.tracer is NULL_TRACER


def test_latency_trace_is_bounded(deployed):
    pool = deployed.serve(2, backend="fused")
    batcher = ContinuousBatcher(pool)
    assert isinstance(batcher.latency_trace, SampleWindow)
    assert batcher.latency_trace.maxlen == LATENCY_WINDOW
    for i in range(LATENCY_WINDOW + 100):
        batcher.latency_trace.append((2, 1e-3))
    assert len(batcher.latency_trace) == LATENCY_WINDOW
    stats = batcher.stats()
    assert stats["latency_ms_p50"] == pytest.approx(1.0)
    assert stats["latency_ms_p99"] == pytest.approx(1.0)
    # every append also reached the all-time histogram
    fam = batcher.metrics.get("cutie_tick_seconds")
    assert fam is not None
    series = fam.labels(net=batcher.track, pool_size="2")
    assert series.count == LATENCY_WINDOW + 100


def _gated_fleet_trace(deployed, backend):
    """One gated 2-bucket fleet scenario under the deterministic clock."""
    tracer = Tracer(clock="tick")
    router = FleetRouter(backend=backend, max_pool_size=2, ingest="sync",
                         gate=GATE, tracer=tracer)
    router.register("net_a", deployed)
    router.register("net_b", deployed)
    clips = bursty_clips(4, 8)
    for i in range(4):
        router.submit(StreamRequest(
            stream_id=f"s{i}", frames=clips[i], arrival=i,
            net="net_a" if i % 2 == 0 else "net_b"))
    results = router.run()
    router.close()
    finals = {r.stream_id: None if r.logits is None else np.asarray(r.logits)
              for r in results}
    return tracer, finals


def test_tick_clock_trace_identical_across_backends(deployed):
    """The schedule IS the trace: ref and fused emit the same sequence."""
    tr_ref, fin_ref = _gated_fleet_trace(deployed, "ref")
    tr_fused, fin_fused = _gated_fleet_trace(deployed, "fused")
    sig_ref = [(e.phase, e.name, e.track) for e in tr_ref.events()]
    sig_fused = [(e.phase, e.name, e.track) for e in tr_fused.events()]
    assert sig_ref == sig_fused
    # tick-clock timestamps are sequence numbers — identical too
    assert [e.ts for e in tr_ref.events()] == [e.ts for e in tr_fused.events()]
    # and the runs themselves agree (same logits both backends)
    assert set(fin_ref) == set(fin_fused)
    for sid, ref in fin_ref.items():
        fused = fin_fused[sid]
        if ref is None:
            assert fused is None
        else:
            assert (ref == fused).all()


def test_fleet_trace_lanes_and_instants(deployed):
    tracer, _ = _gated_fleet_trace(deployed, "fused")
    doc = to_chrome(tracer)
    s = trace_summary(doc)
    assert s["ok"], s["nesting_problems"]
    assert {"net_a", "net_b"} <= set(s["lanes"])
    assert s["instants"].get("park", 0) > 0
    assert s["instants"].get("wake", 0) > 0
    pb = s["phase_breakdown"]
    assert pb["net_a"]["ticks"] > 0 and pb["net_b"]["ticks"] > 0
    for lane in ("net_a", "net_b"):
        assert pb[lane]["phases"]["step"]["us"] > 0


def _parents(events):
    """{span index: name of the innermost span around it on its track}."""
    spans = [e for e in events if e.phase == "X"]
    out = {}
    for i, e in enumerate(spans):
        around = [o for o in spans if o is not e and o.track == e.track
                  and o.ts <= e.ts and e.ts + e.dur <= o.ts + o.dur]
        out[i] = min(around, key=lambda o: o.dur).name if around else None
    return spans, out


def test_tick_phase_spans_nest_and_count(deployed):
    """demux / retire under tick, pool.admit under admit, pool.evict under
    retire; demux's ``streams`` is what the tick stepped, retire's
    ``departed`` what it finished."""
    tracer = Tracer(clock="tick")
    pool = deployed.serve(2, backend="fused")
    batcher = ContinuousBatcher(pool, tracer=tracer)
    clips = event_clips(3, 4)
    for i, n in enumerate((2, 3, 4)):
        batcher.submit(StreamRequest(f"s{i}", clips[i][:n], arrival=i))
    stepped, departed = [], []
    while batcher.pending:
        done = len(batcher.results)
        out = batcher.tick()
        if out:
            stepped.append(len(out))
            departed.append(len(batcher.results) - done)
    assert validate_nesting(to_chrome(tracer)) == []
    spans, parent = _parents(tracer.events())
    want = {"demux": "tick", "retire": "tick", "pool.admit": "admit",
            "pool.evict": "retire", "admit": "tick", "step": "tick",
            "pool.step": "step"}
    for i, e in enumerate(spans):
        if e.name in want:
            assert parent[i] == want[e.name], (e.name, parent[i])
    assert [e.args["streams"] for e in spans if e.name == "demux"] == stepped
    assert [e.args["departed"] for e in spans if e.name == "retire"] == departed
    assert sum(e.name == "pool.admit" for e in spans) == 3
    assert sum(e.name == "pool.evict" for e in spans) == 3
    assert all(isinstance(e.args["slot"], int) for e in spans
               if e.name in ("pool.admit", "pool.evict"))


def test_one_sched_sample_per_non_idle_tick(deployed):
    tracer = Tracer()
    pool = deployed.serve(2, backend="fused")
    batcher = ContinuousBatcher(pool, tracer=tracer)
    clips = event_clips(2, 3)
    batcher.submit(StreamRequest("early", clips[0], arrival=0))
    batcher.submit(StreamRequest("late", clips[1], arrival=6))  # idle gap
    busy = sum(bool(batcher.tick()) for _ in range(12))
    assert batcher.tick_index == 12 and 0 < busy < 12
    samples = [e for e in tracer.events() if e.phase == "C" and e.name == "sched"]
    assert len(samples) == busy
    has_runq = os.path.exists(SCHEDSTAT)
    for e in samples:
        assert e.track == batcher.track
        assert e.args["gc_ms"] >= 0
        assert ("runq_ms" in e.args) == has_runq
        assert e.args.get("runq_ms", 0) >= 0
    # the removed per-tick counters stay removed
    assert {e.name for e in tracer.events() if e.phase == "C"} == {"sched"}


def test_sched_sample_without_schedstat_leaves_runq_out(monkeypatch):
    import repro.obs.tracer as tracer_mod

    monkeypatch.setattr(tracer_mod, "SCHEDSTAT", "/proc/thread-self/no-such-file")
    tr = Tracer(clock="tick")
    tr.sched_end(tr.sched_begin(), track="a")
    (e,) = tr.events()
    assert (e.phase, e.name, e.track) == ("C", "sched", "a")
    assert set(e.args) == {"gc_ms"}
    tr.close()


def test_untraced_batcher_hooks_no_gc_and_reads_no_proc(deployed, monkeypatch):
    clips = event_clips(3, 4)
    _drive_pool(deployed, clips)  # warm every program the run dispatches
    gc.collect()  # finalize tracers of earlier tests: their hooks go now
    hooks = list(gc.callbacks)
    opened = []
    real_open, real_os_open = builtins.open, os.open

    def spy_open(file, *a, **k):
        opened.append(str(file))
        return real_open(file, *a, **k)

    def spy_os_open(path, *a, **k):
        opened.append(str(path))
        return real_os_open(path, *a, **k)

    monkeypatch.setattr(builtins, "open", spy_open)
    monkeypatch.setattr(os, "open", spy_os_open)
    _drive_pool(deployed, clips)
    monkeypatch.undo()
    assert gc.callbacks == hooks
    assert not [p for p in opened if p.startswith("/proc")]


def test_tracer_gc_hook_removed_on_close_and_collection():
    tr = Tracer()
    hook = tr._gc
    assert hook in gc.callbacks
    tr.close()
    tr.close()  # idempotent
    assert hook not in gc.callbacks
    tr = Tracer()
    hook, ref = tr._gc, weakref.ref(tr)
    assert hook in gc.callbacks
    del tr
    gc.collect()
    assert ref() is None and hook not in gc.callbacks


def test_forced_collection_inside_a_tick_shows_in_gc_ms(deployed, monkeypatch):
    tracer = Tracer()
    pool = deployed.serve(2, backend="fused")
    batcher = ContinuousBatcher(pool, tracer=tracer)
    clips = event_clips(2, 4)
    for i in range(2):
        batcher.submit(StreamRequest(f"s{i}", clips[i]))
    batcher.tick()  # warm
    assemble = batcher._assemble

    def assemble_after_a_collection():
        garbage = [[i] for i in range(20000)]  # a pass with work to do
        del garbage
        gc.collect()
        return assemble()

    monkeypatch.setattr(batcher, "_assemble", assemble_after_a_collection)
    batcher.tick()
    last = [e for e in tracer.events() if e.name == "sched"][-1]
    assert last.args["gc_ms"] > 0
    tracer.close()


def test_null_tracer_samples_nothing():
    begin = NULL_TRACER.sched_begin()
    assert begin is None
    assert NULL_TRACER.sched_end(begin, track="a") is None
    with NULL_TRACER.span("retire") as span:
        span.arg("departed", 1)
    assert NULL_TRACER.events() == []


def test_wall_clock_anchor_places_spans():
    """``epoch_ns`` + a span's ts is the wall time the span began, within
    1 ms, before and after `clear`."""
    tr = Tracer()
    for _ in range(2):
        wall = time.time_ns()
        with tr.span("x", track="a"):
            pass
        doc = to_chrome(tr)
        (x,) = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert abs(doc["otherData"]["epoch_ns"] + x["ts"] * 1000 - wall) < 1e6
        time.sleep(0.01)
        tr.clear()
    assert "epoch_ns" not in to_chrome(Tracer(clock="tick"))["otherData"]


def test_mean_occupancy_is_the_mean_over_ticks(deployed):
    """The running mean equals the per-tick occupancies the trace gives
    (demux ``streams`` over the pool size; 0 on idle ticks)."""
    tracer = Tracer(clock="tick")
    pool = deployed.serve(3, backend="fused")
    batcher = ContinuousBatcher(pool, tracer=tracer)
    clips = event_clips(4, 3)
    for i, arrival in enumerate((0, 0, 5, 6)):
        batcher.submit(StreamRequest(f"s{i}", clips[i], arrival=arrival))
    batcher.run()
    occ = []
    for e in sorted(tracer.events(), key=lambda e: e.ts):  # by start
        if e.name == "tick":
            occ.append(0.0)
        elif e.name == "demux":
            occ[-1] = e.args["streams"] / 3
    assert len(occ) == batcher.tick_index and 0 in occ
    assert batcher.stats()["mean_occupancy"] == pytest.approx(np.mean(occ))
    assert not hasattr(batcher, "occupancy_trace")


# ---------------------------------------------------------------------------
# CLI


def test_cli_summarize_ok_and_fail(tmp_path, capsys):
    good = tmp_path / "good.json"
    save_chrome(str(good), _synthetic_tracer())
    assert obs_main(["summarize", str(good)]) == 0
    assert "ok: spans balanced" in capsys.readouterr().out

    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"traceEvents": []}))
    assert obs_main(["summarize", str(empty)]) == 1
    assert "empty trace" in capsys.readouterr().err


def test_cli_diff(tmp_path, capsys):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    save_chrome(str(a), _synthetic_tracer())
    tr = _synthetic_tracer()
    tr.instant("wake", track="net_a")
    save_chrome(str(b), tr)
    assert obs_main(["diff", str(a), str(a)]) == 0
    capsys.readouterr()
    assert obs_main(["diff", str(a), str(b)]) == 0  # report-only by default
    assert obs_main(["diff", str(a), str(b), "--strict"]) == 1
