"""Mean host time per tick of the serving policy's per-stream split of
the step's logits, in milliseconds: the program's ``demux`` span
(`ContinuousBatcher.tick`, one ``logits[slot]`` slice per stream)."""
import numpy as np


def read(run):
    durs = [e.dur for e in run.window.spans or () if e.phase == "X" and e.name == "demux"]
    return float(np.mean(durs)) * 1e-6 if durs else None
