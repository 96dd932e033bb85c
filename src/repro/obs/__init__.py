"""repro.obs — zero-overhead-when-disabled observability for the stack.

Three modules:

  * `repro.obs.tracer` — bounded ring-buffer event recorder (`Tracer`;
    `NULL_TRACER` is the always-available disabled instance the
    instrumented hot paths hold when tracing is off).
  * `repro.obs.metrics` — label-keyed counter/gauge/histogram registry
    with a Prometheus text snapshot (`MetricsRegistry`), plus
    `SampleWindow`, the bounded latency-trace replacement.
  * `repro.obs.export` — Chrome/Perfetto trace JSON rendering,
    sim-derived `layer_timeline` hardware tracks, summaries and diffs.

CLI: ``python -m repro.obs {summarize,export,diff}`` (see `__main__`).
Wiring: ``--trace PATH`` on `repro.launch.serve` / `repro.launch.train`.

The serving tick (`ContinuousBatcher.tick`), on the batcher's track:

    tick                      one scheduling round (arg ``tick``)
      gate.park, gate.scan    activity-gate bookkeeping (gated batchers)
      admit                   FIFO slot refill
        pool.admit            `SessionPool.admit`: clear/scatter the slot (``slot``)
      assemble                host batch assembly
      step                    dispatch of the jitted step (``streams``)
        pool.step             the jitted call (``pool_size``)
      demux                   per-stream split of the logits (``streams``)
      retire                  cursors, evictions, results with their host
                              copies (``departed``)
        pool.evict            `SessionPool.evict`: gather the slot (``slot``)
    sched (counter)           one per non-idle tick, at its end: ``gc_ms``,
                              ``runq_ms`` of the ticking thread

On the wall clock an export's ``otherData["epoch_ns"]`` is the wall time
of ts 0: add it to a span's ``ts`` (us x 1000) to place the span on a
`jax.profiler` trace of the same process.
"""
from repro.obs.export import (
    layer_timeline,
    load,
    phase_breakdown,
    save_chrome,
    to_chrome,
    trace_diff,
    trace_summary,
    validate_nesting,
)
from repro.obs.metrics import MetricsRegistry, SampleWindow
from repro.obs.tracer import NULL_TRACER, Event, NullTracer, Tracer

__all__ = [
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "Event",
    "MetricsRegistry",
    "SampleWindow",
    "to_chrome",
    "save_chrome",
    "load",
    "layer_timeline",
    "phase_breakdown",
    "trace_summary",
    "trace_diff",
    "validate_nesting",
]
