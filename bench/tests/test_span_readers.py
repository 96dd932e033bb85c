"""The readers of the serving tick's phases and of its scheduling counter,
on a synthetic window of the program's tracer events."""
import types

import pytest
from repro.obs.tracer import Event

from harness import spec

BENCH = spec.Bench()
NEW = ("demux_ms.dvs", "retire_ms.dvs", "slot_ms.dvs", "gc_ms.dvs")
MS = 1_000_000  # ns


def span(name, start_ms, dur_ms, **args):
    return Event("X", name, int(start_ms * MS), int(dur_ms * MS), 0, "dvs", args or None)


def sched(at_ms, **args):
    return Event("C", "sched", int(at_ms * MS), 0, 0, "dvs", args)


def window_events():
    """Two ticks; the first admits and evicts one stream each."""
    return [
        span("pool.admit", 0.2, 0.6, slot=3), span("admit", 0, 1),
        span("assemble", 1, 0.5), span("pool.step", 1.6, 0.8), span("step", 1.5, 1),
        span("demux", 2.5, 6, streams=8), span("pool.evict", 8.6, 0.4, slot=5),
        span("retire", 8.5, 1, departed=1), sched(9.9, gc_ms=0.05, runq_ms=0.02),
        span("tick", 0, 10, tick=0),
        span("admit", 10, 0.5), span("assemble", 10.5, 0.5), span("pool.step", 11.1, 0.8),
        span("step", 11, 1), span("demux", 12, 5, streams=8),
        span("retire", 17, 0.5, departed=0), sched(18.9, gc_ms=0.0, runq_ms=0.04),
        span("tick", 10, 9, tick=1),
    ]


def read(name, events):
    run = types.SimpleNamespace(window=types.SimpleNamespace(spans=events))
    return BENCH.reader(name).read(run)


def test_each_reader_on_a_synthetic_window():
    ev = window_events()
    assert read("demux_ms.dvs", ev) == pytest.approx((6 + 5) / 2)
    assert read("retire_ms.dvs", ev) == pytest.approx((1 + 0.5) / 2)
    assert read("slot_ms.dvs", ev) == pytest.approx((0.6 + 0.4) / 2)  # 0 on tick 1
    assert read("gc_ms.dvs", ev) == pytest.approx(0.025)


def test_tick_self_time_keeps_its_meaning():
    """``tick_self_ms.dvs`` subtracts only admit/assemble/step: the new
    spans inside the tick leave its reading as it was without them."""
    ev = window_events()
    parent = [e for e in ev if e.name in ("tick", "admit", "assemble", "step", "pool.step")]
    assert read("tick_self_ms.dvs", ev) == read("tick_self_ms.dvs", parent)
    assert read("tick_self_ms.dvs", ev) == pytest.approx(((10 - 2.5) + (9 - 2)) / 2)


@pytest.mark.parametrize("name", NEW)
def test_absent_spans_or_counter_read_none(name):
    """An untraced run, and a program without the spans and the counter
    (the occupancy counters of an older program are not read)."""
    older = [e for e in window_events() if e.name in ("tick", "admit", "assemble", "step")]
    older.append(Event("C", "occupancy", 0, 0, 0, "dvs", {"occupancy": 1.0}))
    assert read(name, None) is None
    assert read(name, []) is None
    assert read(name, older) is None


def test_gc_reads_samples_without_a_run_queue_field():
    """A host without schedstat samples ``gc_ms`` alone."""
    ev = [e for e in window_events() if e.name != "sched"]
    ev += [sched(9.9, gc_ms=0.5), sched(18.9, gc_ms=0.1)]
    assert read("gc_ms.dvs", ev) == pytest.approx(0.3)


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_declared_for_the_dvs_cells(name):
    (m,) = [m for m in BENCH.doc["per_layer"] if m["name"] == name]
    assert m["workloads"] == ["dvs_pool8", "dvs_pool64"]
    assert m["moves"] == "frames_per_s" and m["unit"] == "ms" and m["better"] == "lower"
    assert BENCH.reader(name).__file__.endswith(f"{name}.py")


def test_a_traced_test_size_run_reads_every_new_metric(tmp_path):
    """The whole traced run of a test-size DVS cell on the CPU: the
    program's spans and counter reach the window, and each new metric
    reads a value.  (A test size measures overheads, not the chip.)"""
    import support

    r = support.run(support.make_bench(tmp_path), "dvs_small", seconds=1.0, trace=1)
    assert r["correct"], r["checks"]
    got = r["metrics"]
    assert all(got[name]["value"] >= 0 for name in NEW)
    assert got["demux_ms.dvs"]["value"] < got["tick_self_ms.dvs"]["value"]
