"""Mean time per tick the garbage collector's passes took, in
milliseconds: ``gc_ms`` of the program's ``sched`` counter samples, one
per non-idle tick (`Tracer.sched_end`)."""
import numpy as np


def read(run):
    vals = [e.args["gc_ms"] for e in run.window.spans or ()
            if e.phase == "C" and e.name == "sched" and "gc_ms" in e.args]
    return float(np.mean(vals)) if vals else None
