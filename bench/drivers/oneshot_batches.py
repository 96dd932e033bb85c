"""One-shot classification of batches through the program's jitted
``forward(backend="fused")``.

Batches of ``batch`` inputs come from a library of ``library`` seeded
batches on the host, are uploaded per call, with ``in_flight`` calls
outstanding.  A classification is one input: timed from the call that
took its batch to the moment the batch's logits are on the host, and
keyed by (batch, row) for the plain reference.  Rows of a batch that come
back without logits are ``unmatched``.
"""
from __future__ import annotations

import functools
import time
from collections import deque

import numpy as np

from harness.window import Window, ns


class Driver:
    def __init__(self, program, cfg: dict, traffic: dict, rng: np.random.Generator,
                 make_inputs, tracer=None):
        import jax

        self.library = make_inputs((traffic["library"], traffic["batch"]))
        self.in_flight = traffic["in_flight"]
        self.rows = traffic["batch"]
        self.forward = jax.jit(functools.partial(program.forward, backend="fused"))

    def warm_up(self) -> None:
        for b in range(min(2, len(self.library))):
            np.asarray(self.forward(self.library[b]))

    def measure(self, seconds: float) -> Window:
        pending: deque = deque()
        lat, fetch, keys, logits, marks = [], [], [], [], []
        count = [0]

        def submit():
            b = count[0] % len(self.library)
            count[0] += 1
            t = time.perf_counter()
            y = self.forward(self.library[b])
            marks.append(("bench.call", ns(t), ns(time.perf_counter() - t)))
            pending.append((t, b, y))

        t0_epoch_ns = time.time_ns()
        t_w0 = t_end = time.perf_counter()
        for _ in range(self.in_flight):
            submit()
        while pending:
            t_sub, b, y = pending.popleft()
            tf = time.perf_counter()
            host = np.asarray(y)
            t_end = time.perf_counter()
            marks.append(("bench.fetch", ns(tf), ns(t_end - tf)))
            lat += [t_end - t_sub] * len(host)
            fetch.append(t_end - tf)
            keys.append(np.stack([np.full(len(host), b), np.arange(len(host))], 1))
            logits.append(host)
            if t_end - t_w0 < seconds:
                submit()
        attempted = count[0] * self.library.shape[1]
        completed = sum(len(k) for k in keys)
        return Window(
            seconds=t_end - t_w0, attempted=attempted, completed=completed,
            unmatched=abs(attempted - completed), latencies_s=np.asarray(lat),
            keys=np.concatenate(keys), logits=np.concatenate(logits).astype(np.float32),
            fetch_s=np.asarray(fetch), rounds=len(fetch), marks=marks,
            t0_ns=ns(t_w0), t0_epoch_ns=t0_epoch_ns,
        )

    def devices(self):
        import jax

        return {jax.devices()[0]}

    def release(self) -> None:
        self.forward = None


def lower(program, cfg: dict, traffic: dict, chips: int, topo):
    """The cell's timed device program, lowered for the described chip
    ``topo`` (``bench/rehearse.py``): the jitted fused forward at the
    cell's batch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    fwd = jax.jit(functools.partial(program.forward, backend="fused"))
    return fwd.lower(jax.ShapeDtypeStruct(
        (traffic["batch"], *cfg["input_hw"], cfg["input_ch"]), jnp.float32,
        sharding=SingleDeviceSharding(topo.devices[0])))
