"""`SessionPool` — continuous batching of many sensor streams on one jit.

The paper's autonomous mode runs ONE always-on DVS sensor at 8000 inf/s;
the north-star serving system multiplexes MANY.  CUTIE's efficiency comes
from completely unrolled, always-full compute units — the software analogue
is a **fixed-shape** jitted step over a `pool_size`-wide batch whose slots
are kept full by admission/eviction of streams mid-flight:

    pool = deployed.serve(pool_size=8, backend="fused")
    pool.admit("sensor-a"); pool.admit("sensor-b")
    out = pool.step({"sensor-a": frame_a, "sensor-b": frame_b})
    state = pool.evict("sensor-a")          # slot free, refill next tick
    pool.admit("sensor-c")                  # NO retrace: shapes unchanged

Key properties (all tested in tests/test_serving.py):

  * **One trace.**  The step function traces once per pool; admit / evict /
    partial ticks are runtime data (the `active` mask and the frame batch),
    never static arguments.
  * **Bit-exact per stream.**  Each slot's logits equal an independent
    `StreamSession` fed the same frames, on every backend — batching and
    slot masking are invisible to the numerics.
  * **Migratable sessions.**  `evict` returns the stream's `StreamState`
    pytree; `admit(sid, state=...)` scatters it back in — into this pool,
    another pool, or a standalone `StreamSession`.
  * **Optional batch-axis sharding.**  `sharding="auto"` lays the pool axis
    across local devices via `jax.sharding.NamedSharding` (single-device
    hosts: no-op; a pool size the device count does not divide is an
    error, never a silent single-device run).

Empty slots still compute (a zero frame through the CNN) — exactly like the
silicon, which clocks every OCU whether or not the pixel is useful; the
occupancy metric reports how much of the batch was real work.

The pool is duck-typed over its program: anything exposing
``spatial_forward(frames, backend)`` / ``temporal_forward(windows,
backend)`` and a ``.graph`` metadata object with ``name`` / ``is_temporal``
/ ``input_hw`` / ``input_ch`` / ``tcn_steps`` / ``feature_channels`` serves
here.  In practice that is an `api.program.DeployedProgram` (graph-backed)
or an `artifact.LoadedProgram` (a ``.cutie`` artifact, whose ``.graph`` is
a `ProgramInfo` header — fleet serving straight from the shipped binary,
no Python graph object anywhere; tested in tests/test_artifact_loader.py).
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tcn import StreamState
from repro.obs.tracer import NULL_TRACER
from repro.serving.masking import (
    PoolState,
    clear_slot,
    gather_slot,
    masked_push,
    ordered_windows,
    scatter_slot,
)


class PoolFullError(RuntimeError):
    """Raised by `admit` when every slot is occupied (callers queue — see
    `repro.serving.scheduler.ContinuousBatcher`)."""


def resolve_sharding(
    sharding: Union[str, bool, int, None, jax.sharding.Sharding], pool_size: int
) -> Optional[jax.sharding.Sharding]:
    """Turn the user-facing `sharding` argument into a concrete Sharding (or
    None).  "auto"/True shard over all local devices (a no-op on a
    single-device host, a hard error when the pool does not divide across
    several); an int requests exactly that many devices (hard error when
    impossible); a Sharding passes through."""
    if sharding is None or sharding is False:
        return None
    if isinstance(sharding, jax.sharding.Sharding):
        return sharding
    devices = jax.local_devices()
    if sharding == "auto" or sharding is True:
        n = len(devices)
        if n <= 1:
            return None
        if pool_size % n:
            raise ValueError(
                f'sharding="auto": pool_size {pool_size} does not divide '
                f"across this host's {n} devices; use a multiple of {n}, an "
                "int device count, or sharding=None for one device"
            )
    elif isinstance(sharding, int):
        n = sharding
        if n > len(devices):
            raise ValueError(f"requested {n} devices, host has {len(devices)}")
        if pool_size % n:
            raise ValueError(f"pool_size {pool_size} not divisible by {n} devices")
    else:
        raise ValueError(f"unknown sharding spec {sharding!r}")
    mesh = jax.sharding.Mesh(np.array(devices[:n]), ("pool",))
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("pool"))


class SessionPool:
    """Fixed-shape multi-stream serving state over one `DeployedProgram`.

    The pool owns a slot-masked `PoolState` (`[P, T, C]` ring + per-slot
    cursors) and a single jitted step: CNN frontend on the full `[P, H, W,
    C]` frame batch -> masked ring push -> TCN head on the `[P, T, C]`
    ordered windows.  Slot bookkeeping (which stream sits where) is plain
    host-side Python — it never enters the traced computation.
    """

    def __init__(
        self,
        deployed,
        pool_size: int,
        backend: str = "fused",
        jit: bool = True,
        sharding: Union[str, bool, int, None, jax.sharding.Sharding] = None,
        tracer=None,
    ):
        from repro.api.program import check_backend

        check_backend(backend)
        if not deployed.graph.is_temporal:
            raise ValueError(f"{deployed.graph.name} has no TCN memory to pool")
        if pool_size < 1:
            raise ValueError(f"pool_size must be >= 1, got {pool_size}")
        self.deployed = deployed
        self.pool_size = pool_size
        self.backend = backend
        g = deployed.graph
        self.frame_shape: Tuple[int, ...] = (*g.input_hw, g.input_ch)
        self.state = PoolState.create(pool_size, g.tcn_steps, g.feature_channels)
        self._slots: List[Optional[str]] = [None] * pool_size
        self._slot_of: Dict[str, int] = {}
        self._trace_count = 0
        # observability: NULL_TRACER when tracing is off (no-op span, no
        # branch in the hot path); the tracer only ever wraps the jitted
        # call from the OUTSIDE — nothing observes inside the trace
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.track = getattr(deployed.graph, "name", "pool")
        self.sharding = resolve_sharding(sharding, pool_size)
        if self.sharding is not None:
            self.state = self._put(self.state)

        def _step(state: PoolState, frames: jax.Array, active: jax.Array):
            self._trace_count += 1  # python side effect: counts traces only
            feats = deployed.spatial_forward(frames, backend)
            new = masked_push(state, feats, active)
            logits = deployed.temporal_forward(ordered_windows(new), backend)
            return logits, new

        step = _step
        if isinstance(self.sharding, jax.sharding.NamedSharding):
            # every slot is independent, so each device runs the whole step
            # on its own slice of the pool; a Pallas kernel cannot be
            # partitioned by the compiler, only mapped like this (and its
            # outputs carry no varying-axis annotation, hence check_vma off)
            spec = self.sharding.spec
            step = jax.shard_map(
                _step, mesh=self.sharding.mesh, in_specs=(spec, spec, spec),
                out_specs=(spec, spec), check_vma=False,
            )
        self._step = jax.jit(step) if jit else step

    # -- sharding helper ---------------------------------------------------

    def _put(self, tree):
        if self.sharding is None:
            return tree
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self.sharding), tree
        )

    # -- admission control -------------------------------------------------

    def admit(self, stream_id: str, state: Optional[StreamState] = None) -> int:
        """Claim a free slot for ``stream_id`` and return its index.

        With ``state`` given, the stream resumes exactly where it left off
        (scatter of an evicted/exported `StreamState`); without it the slot
        is zeroed — a fresh ring, `window_warm` False.  Raises
        `PoolFullError` when no slot is free and ValueError on a duplicate
        id — admission never silently displaces a live stream.
        """
        if stream_id in self._slot_of:
            raise ValueError(f"stream {stream_id!r} already admitted")
        try:
            slot = self._slots.index(None)
        except ValueError:
            raise PoolFullError(
                f"all {self.pool_size} slots busy; evict before admitting"
            ) from None
        with self.tracer.span("pool.admit", track=self.track, slot=slot):
            if state is None:
                self.state = clear_slot(self.state, slot)
            else:
                self.state = scatter_slot(self.state, slot, state)
            if self.sharding is not None:
                self.state = self._put(self.state)
        self._slots[slot] = stream_id
        self._slot_of[stream_id] = slot
        return slot

    def evict(self, stream_id: str) -> StreamState:
        """Release the stream's slot and hand back its `StreamState` pytree
        (resume later via ``admit(sid, state=...)`` or
        ``StreamSession.load_state``).  The slot is refillable immediately —
        the next `admit` overwrites it without any retrace."""
        slot = self._slot_of.pop(self._require(stream_id))
        self._slots[slot] = None
        with self.tracer.span("pool.evict", track=self.track, slot=slot):
            return gather_slot(self.state, slot)

    def reset(self, stream_id: str) -> None:
        """Per-slot reset: zero this stream's ring and age in place, leaving
        every other slot untouched (`StreamSession.reset` for one lane)."""
        self.state = clear_slot(self.state, self._slot_of[self._require(stream_id)])
        if self.sharding is not None:
            self.state = self._put(self.state)

    def _require(self, stream_id: str) -> str:
        if stream_id not in self._slot_of:
            raise KeyError(
                f"unknown stream {stream_id!r}; active: {sorted(self._slot_of)}"
            )
        return stream_id

    # -- the hot path ------------------------------------------------------

    def prepare(
        self,
        frames: Mapping[str, jax.Array],
        out_batch: Optional[np.ndarray] = None,
        out_active: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side batch assembly: slot-scatter ``frames`` into a
        `[P, *frame_shape]` float32 batch and a `[P]` bool active mask.

        This is the ingestion half of a tick — pure numpy, no device work —
        split out so a fleet feeder thread can run it for the *next* tick
        while the device executes the current one (`repro.serving.fleet
        .FrameFeeder`).  ``out_batch``/``out_active`` reuse caller-owned
        buffers (the feeder's pinned double buffers) instead of allocating.
        """
        for sid in frames:
            self._require(sid)
        if out_batch is None:
            out_batch = np.zeros((self.pool_size, *self.frame_shape), np.float32)
        else:
            out_batch.fill(0.0)
        if out_active is None:
            out_active = np.zeros((self.pool_size,), bool)
        else:
            out_active.fill(False)
        for sid, f in frames.items():
            f = np.asarray(f, np.float32)
            if f.shape == (1, *self.frame_shape):
                f = f[0]
            if f.shape != self.frame_shape:
                raise ValueError(
                    f"stream {sid!r}: frame shape {f.shape} != {self.frame_shape}"
                )
            out_batch[self._slot_of[sid]] = f
            out_active[self._slot_of[sid]] = True
        return out_batch, out_active

    def step_prepared(self, batch: np.ndarray, active: np.ndarray) -> jax.Array:
        """The device half of a tick: run the jitted step on an assembled
        `(batch, active)` pair (see `prepare`) and return the full `[P,
        n_classes]` logits — callers map slots back to stream ids.  The
        host buffers are copied onto the device at dispatch, so a feeder
        may refill them as soon as this returns (double buffering)."""
        with self.tracer.span("pool.step", track=self.track,
                              pool_size=self.pool_size):
            logits, self.state = self._step(
                self.state,
                self._put(jnp.asarray(batch)),
                self._put(jnp.asarray(active)),
            )
        return logits

    def step(self, frames: Mapping[str, jax.Array]) -> Dict[str, jax.Array]:
        """One pool tick.  ``frames`` maps stream id -> `[H, W, C]` frame
        (a leading length-1 batch axis is accepted and squeezed); streams
        that skip this tick keep their ring frozen via the slot mask.
        Returns per-stream logits for exactly the streams that stepped.
        """
        logits = self.step_prepared(*self.prepare(frames))
        return {sid: logits[self._slot_of[sid]] for sid in frames}

    def bind_tracer(self, tracer, track: Optional[str] = None) -> None:
        """Attach a tracer (the batcher wires its own through so pool.step
        spans land on the same export lane as the tick spans)."""
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if track is not None:
            self.track = track

    # -- introspection -----------------------------------------------------

    def slot_of(self, stream_id: str) -> int:
        """The pool slot this stream occupies (KeyError if not admitted)."""
        return self._slot_of[self._require(stream_id)]

    def steps_seen(self, stream_id: str) -> int:
        """Frames this stream has absorbed since (re)admission — the
        per-slot analogue of `StreamSession.steps_seen`."""
        return int(self.state.steps[self._slot_of[self._require(stream_id)]])

    def window_warm(self, stream_id: str) -> bool:
        """True once this stream's full tcn_steps window is real frames."""
        return self.steps_seen(stream_id) >= self.deployed.graph.tcn_steps

    @property
    def active_streams(self) -> Tuple[str, ...]:
        return tuple(s for s in self._slots if s is not None)

    @property
    def free_slots(self) -> int:
        return self.pool_size - len(self._slot_of)

    @property
    def occupancy(self) -> float:
        """Live-stream fraction of the batch, 0..1 — the "how full are the
        compute units" serving metric."""
        return len(self._slot_of) / self.pool_size

    @property
    def trace_count(self) -> int:
        """How many times the step fn has (re)traced — 1 for the pool's
        whole lifetime is the continuous-batching contract.  (Tick/frame/
        occupancy accounting lives in `ContinuousBatcher.stats`, the one
        place that knows scheduling time.)"""
        return self._trace_count

    def __contains__(self, stream_id: str) -> bool:
        return stream_id in self._slot_of

    def __len__(self) -> int:
        return len(self._slot_of)

    def __repr__(self) -> str:
        return (
            f"SessionPool(size={self.pool_size}, backend={self.backend!r}, "
            f"active={len(self._slot_of)}, occupancy={self.occupancy:.2f})"
        )
