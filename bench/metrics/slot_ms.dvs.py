"""Mean host time per tick of the serving mechanism's slot surgery, in
milliseconds: the program's ``pool.admit`` and ``pool.evict`` spans
(`SessionPool.admit` / `.evict`) summed over the window, over its ticks,
so a tick with neither counts 0.  `pool_closed_loop` admits and evicts only
inside ticks."""
SLOT_SPANS = ("pool.admit", "pool.evict")


def read(run):
    spans = run.window.spans or ()
    ticks = sum(1 for e in spans if e.phase == "X" and e.name == "tick")
    durs = [e.dur for e in spans if e.phase == "X" and e.name in SLOT_SPANS]
    return sum(durs) / ticks * 1e-6 if ticks and durs else None
