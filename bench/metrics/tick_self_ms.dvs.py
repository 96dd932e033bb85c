"""Mean self time per tick of the serving policy, in milliseconds: the
program's ``tick`` span (`ContinuousBatcher.tick`) minus its direct
children ``admit``, ``assemble``, ``step`` (and ``gate.*`` when gating).
Today this is mostly the per-stream slicing of the logits and evictions."""
import numpy as np

CHILDREN = ("admit", "assemble", "step", "gate.park", "gate.scan")


def read(run):
    spans = run.window.spans
    if not spans:
        return None
    ticks = [e for e in spans if e.phase == "X" and e.name == "tick"]
    kids = sorted((e.ts, e.ts + e.dur) for e in spans
                  if e.phase == "X" and e.name in CHILDREN)
    if not ticks:
        return None
    starts = np.array([s for s, _ in kids])
    selfs = []
    for t in ticks:
        lo = np.searchsorted(starts, t.ts)
        hi = np.searchsorted(starts, t.ts + t.dur, side="right")
        inner = sum(e - s for s, e in kids[lo:hi] if e <= t.ts + t.dur)
        selfs.append(t.dur - inner)
    return float(np.mean(selfs)) * 1e-6
