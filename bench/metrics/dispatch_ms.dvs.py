"""Mean host time per tick of the serving mechanism's step, in
milliseconds: the program's ``step`` span around
`SessionPool.step_prepared` (host enqueue plus the batch upload; the
device runs asynchronously and is not waited for)."""
import numpy as np


def read(run):
    spans = run.window.spans
    if not spans:
        return None
    steps = [e.dur for e in spans if e.phase == "X" and e.name == "step"]
    return float(np.mean(steps)) * 1e-6 if steps else None
