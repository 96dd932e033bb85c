"""The profiler's device trace, reduced to what the metrics read.

A traced run records the measured window with `jax.profiler.trace`, with
host tracing off (on the host it slowed the one-shot cell's window
five-fold).  From the trace file this keeps, per TPU device, the events of
its ``XLA Ops`` line: one per operation that ran, with its name, start and
duration, in nanoseconds from the profile's start.  The window is placed
on that clock by its wall-clock start; the host's side comes from the
benchmark's own records of each round and the program's `repro.obs` spans,
shifted onto the same clock.

* busy time: the union of a device's operation intervals inside the window;
* idle share: 1 - busy / window, averaged over the devices;
* kernel time: the summed durations of the operations whose HLO name
  matches a metric's pattern;
* idle gaps: the stretches inside the window where a device ran nothing,
  summed by what the host was doing at each gap's middle.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SESSION_PLANE, SESSION_START = "Task Environment", "profile_start_time"


@dataclasses.dataclass
class DeviceTrace:
    lo_ns: float  # window start, trace clock
    hi_ns: float  # window end
    ops: Dict[int, List[Event]]  # device id -> its operations
    host: List[Event]  # what the host did, on the trace clock

    @property
    def window_s(self) -> float:
        return (self.hi_ns - self.lo_ns) * 1e-9


def op_name(event_name: str) -> str:
    """``%ternary_conv2d_pallas.8 = s8[...] custom-call(...)`` ->
    ``ternary_conv2d_pallas.8``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_kind(event_name: str) -> str:
    """The operation's name without its numeric suffix."""
    return re.sub(r"\.\d+$", "", op_name(event_name))


def load(log_dir: str, window_epoch_ns: int, window_s: float) -> DeviceTrace:
    """The newest ``*.xplane.pb`` under ``log_dir``, with the window that
    began at wall-clock ``window_epoch_ns`` and lasted ``window_s``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane trace under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ops: Dict[int, List[Event]] = {}
    start = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = [(e.name, e.start_ns, e.duration_ns)
                                            for e in line.events]
        elif plane.name == SESSION_PLANE:
            start = int(dict(plane.stats)[SESSION_START])
    if start is None:  # no session clock: the window is the devices' extent
        evs = [e for evs in ops.values() for e in evs]
        lo = min((s for _, s, _ in evs), default=0.0)
        hi = max((s + d for _, s, d in evs), default=lo)
        return DeviceTrace(lo_ns=lo, hi_ns=hi, ops=ops, host=[])
    lo = window_epoch_ns - start
    return DeviceTrace(lo_ns=lo, hi_ns=lo + window_s * 1e9, ops=ops, host=[])


def add_host(trace: DeviceTrace, events: Iterable[Event], t0_ns: int) -> None:
    """Put host events (whose clock reads ``t0_ns`` at the window's start)
    on the trace's clock, for labelling idle gaps."""
    shift = trace.lo_ns - t0_ns
    trace.host += [(name, s + shift, d) for name, s, d in events]


def merged(events: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of the events' intervals, clipped to ``[lo, hi]``, sorted."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s < hi and s + d > lo)
    out: List[Tuple[float, float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_s(trace: DeviceTrace, device: int) -> float:
    return sum(e - s for s, e in merged(trace.ops[device], trace.lo_ns, trace.hi_ns)) * 1e-9


def mean_busy_s(trace: DeviceTrace) -> float:
    """Busy seconds in the window, averaged over the traced devices."""
    if not trace.ops:
        return 0.0
    return sum(busy_s(trace, d) for d in trace.ops) / len(trace.ops)


def idle_share(trace: DeviceTrace) -> Optional[float]:
    """1 - busy / window, averaged over the devices (None: no devices)."""
    if not trace.ops or trace.window_s <= 0:
        return None
    return 1.0 - mean_busy_s(trace) / trace.window_s


def matching(trace: DeviceTrace, pattern: str) -> Dict[int, List[Event]]:
    """Per device, the operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return {d: [e for e in evs if rx.search(op_name(e[0]))] for d, evs in trace.ops.items()}


def top_ops(trace: DeviceTrace, n: int = 10) -> List[List]:
    """The operation kinds that took most device time, seconds per device."""
    total: Dict[str, float] = defaultdict(float)
    for evs in trace.ops.values():
        for name, _, dur in evs:
            total[op_kind(name)] += dur * 1e-9
    k = max(len(trace.ops), 1)
    return [[name, t / k] for name, t in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def _labels(host: List[Event], times: List[float]) -> List[str]:
    """For each of the sorted ``times``: the host events open then,
    outermost first, joined by ``>``."""
    events = sorted((s, s + d, name) for name, s, d in host)
    open_: List[Tuple[float, float, str]] = []  # heap by end
    out, i = [], 0
    for t in times:
        while i < len(events) and events[i][0] <= t:
            heapq.heappush(open_, (events[i][1], events[i][0], events[i][2]))
            i += 1
        while open_ and open_[0][0] <= t:
            heapq.heappop(open_)
        out.append(" > ".join(name for _, _, name in sorted(open_, key=lambda e: e[1]))
                   or "host outside any span")
    return out


def idle_gaps(trace: DeviceTrace, n: int = 10) -> List[List]:
    """Idle seconds per device, summed by what the host was doing at the
    middle of each gap, largest first."""
    total: Dict[str, float] = defaultdict(float)
    for evs in trace.ops.values():
        gaps, t = [], trace.lo_ns
        for s, e in merged(evs, trace.lo_ns, trace.hi_ns) + [(trace.hi_ns, trace.hi_ns)]:
            if s > t:
                gaps.append(((t + s) / 2, s - t))
            t = max(t, e)
        for label, (_, dur) in zip(_labels(trace.host, [m for m, _ in gaps]), gaps):
            total[label] += dur * 1e-9
    k = max(len(trace.ops), 1)
    return [[label, v / k] for label, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]
