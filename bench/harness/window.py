"""What one measured window did: the record a traffic driver returns, for
the metric readers and the comparison with the reference."""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Window:
    seconds: float
    attempted: int  # classifications that came due in the window
    completed: int  # classifications whose logits reached the host
    unmatched: int  # due classifications with no logits, plus logits for no input
    latencies_s: np.ndarray  # one per completed classification
    keys: np.ndarray  # [completed, 2] (library item, position)
    logits: np.ndarray  # [completed, n_classes] as fetched to the host
    fetch_s: np.ndarray  # host time moving results to the host, per round
    rounds: int  # ticks or batches in the window
    spans: Optional[list] = None  # the program's own tracer events, traced runs
    marks: Optional[list] = None  # the client's own (name, start_ns, dur_ns) per round
    t0_ns: int = 0  # perf_counter_ns at the window's start: the clock of the marks
    t0_epoch_ns: int = 0  # time.time_ns() at the same moment
    spans_t0_ns: int = 0  # the same moment on the program tracer's clock


def ns(t: float) -> int:
    return int(t * 1e9)
