"""Mean host time per tick of the serving policy's retirement of the
tick's streams, in milliseconds: the program's ``retire`` span
(`ContinuousBatcher.tick`: cursors advanced, finished streams evicted,
their results built with the host copy of their logits)."""
import numpy as np


def read(run):
    durs = [e.dur for e in run.window.spans or () if e.phase == "X" and e.name == "retire"]
    return float(np.mean(durs)) * 1e-6 if durs else None
