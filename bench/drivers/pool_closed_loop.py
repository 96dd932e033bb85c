"""Sensor streams through the program's `ContinuousBatcher` over
``serve(pool, backend="fused", sharding=...)``, closed loop.

Streams of ``stream_frames`` frames come from a library of ``library``
seeded clips, reused under fresh stream ids; ``pool`` slots, every slot
always holding a stream and the queue never empty.  A classification is
one frame: timed from the start of the tick that took its frame to the
moment its logits are on the host, and keyed by (clip, position in the
clip) for the plain reference.  A stream that departs in the window owes
one classification per frame it was handed: any it never returned, and
any returned beyond its frames, are ``unmatched``.
"""
from __future__ import annotations

import time

import numpy as np

from harness.window import Window, ns


class Driver:
    def __init__(self, program, cfg: dict, traffic: dict, rng: np.random.Generator,
                 make_inputs, tracer=None):
        from repro.serving import ContinuousBatcher

        self.frames = traffic["stream_frames"]
        self.n_classes = cfg["n_classes"]
        self.library = make_inputs((traffic["library"], self.frames))
        self.order = rng.permutation(traffic["library"])
        self.p = self.rows = traffic["pool"]
        self.pool = program.serve(self.p, backend="fused", sharding=traffic["sharding"])
        self.batcher = ContinuousBatcher(self.pool, tracer=tracer)
        self.tracer = tracer
        self.clip_of: dict = {}
        self.due: dict = {}  # frames handed in, per stream
        self.seen: dict = {}  # logits returned, per stream
        self.streams = 0
        # the first wave is cut to staggered lengths, so slots depart and
        # refill on different ticks instead of all at once
        for i in range(self.p):
            self._submit(self.frames - (i * self.frames) // self.p)
        self._top_up()

    def _submit(self, n_frames: int) -> None:
        from repro.serving import StreamRequest

        sid = f"s{self.streams}"
        clip = int(self.order[self.streams % len(self.order)])
        self.streams += 1
        self.clip_of[sid] = clip
        self.due[sid] = n_frames
        self.seen[sid] = 0
        self.batcher.submit(StreamRequest(stream_id=sid, frames=self.library[clip][:n_frames]))

    def _top_up(self) -> None:
        while self.batcher.queue_depth < self.p:
            self._submit(self.frames)

    def _tick(self):
        """One tick and the fetch of its logits.  Returns the tick's start,
        the fetch's start and end, the keys and logits of the frames
        classified, the logits returned beyond a stream's frames, and the
        frames owed by the streams that departed."""
        import jax

        done = len(self.batcher.results)
        t0 = time.perf_counter()
        out = self.batcher.tick()
        tf = time.perf_counter()
        host = jax.device_get(out)
        t1 = time.perf_counter()
        keys, logits, extra = [], [], 0
        for sid, y in host.items():
            if self.seen[sid] >= self.due[sid]:
                extra += 1
                continue
            keys.append((self.clip_of[sid], self.seen[sid]))
            logits.append(y)
            self.seen[sid] += 1
        owed = sum(self.due[r.stream_id] - self.seen[r.stream_id]
                   for r in self.batcher.results[done:])
        self._top_up()
        return t0, tf, t1, keys, logits, extra, owed

    def warm_up(self) -> None:
        """Tick until every stream of the first wave has departed: every
        slot has then been admitted, stepped, evicted and refilled, and
        every program of the window has compiled."""
        while len(self.batcher.results) < self.p:
            self._tick()

    def measure(self, seconds: float) -> Window:
        lat, fetch, keys, logits, marks = [], [], [], [], []
        extra = owed = 0
        spans_t0 = 0
        if self.tracer is not None:
            self.tracer.clear()
            self.tracer.instant("window")
            spans_t0 = self.tracer.events()[-1].ts
        t0_epoch_ns = time.time_ns()
        t_w0 = t_end = time.perf_counter()
        while time.perf_counter() - t_w0 < seconds:
            t0, tf, t_end, k, y, x, o = self._tick()
            lat += [t_end - t0] * len(k)
            fetch.append(t_end - tf)
            marks += [("bench.tick", ns(t0), ns(tf - t0)),
                      ("bench.fetch", ns(tf), ns(t_end - tf))]
            keys += k
            logits += y
            extra += x
            owed += o
        return Window(
            seconds=t_end - t_w0, attempted=len(keys) + owed, completed=len(keys),
            unmatched=owed + extra, latencies_s=np.asarray(lat),
            keys=np.asarray(keys, np.int64).reshape(-1, 2),
            logits=np.asarray(logits, np.float32).reshape(len(keys), self.n_classes),
            fetch_s=np.asarray(fetch), rounds=len(fetch),
            spans=self.tracer.events() if self.tracer is not None else None,
            marks=marks, t0_ns=ns(t_w0), t0_epoch_ns=t0_epoch_ns, spans_t0_ns=spans_t0,
        )

    def devices(self):
        return set(self.pool.state.buf.sharding.device_set)

    def release(self) -> None:
        self.batcher = self.pool = None


def lower(program, cfg: dict, traffic: dict, chips: int, topo):
    """The cell's timed device program, lowered for the described chips
    ``topo`` (``bench/rehearse.py``): the pool step at the cell's pool size,
    sharded over the cell's chips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding
    from repro.serving import SessionPool

    p = traffic["pool"]
    if chips > 1:
        sharding = NamedSharding(Mesh(np.array(topo.devices[:chips]), ("pool",)),
                                 PartitionSpec("pool"))
    else:
        sharding = SingleDeviceSharding(topo.devices[0])
    pool = SessionPool(program, p, backend="fused", sharding=sharding if chips > 1 else None)

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    return pool._step.lower(
        jax.tree_util.tree_map(spec, pool.state),
        jax.ShapeDtypeStruct((p, *pool.frame_shape), jnp.float32, sharding=sharding),
        jax.ShapeDtypeStruct((p,), jnp.bool_, sharding=sharding))
