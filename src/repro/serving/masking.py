"""Slot-masked TCN ring state — the pure algebra under `SessionPool`.

The silicon keeps its OCU array full on every cycle; the serving analogue is
a **fixed-shape** batched ring state `[P, T, C]` where P is the pool size.
Streams come and go mid-flight, so unlike `TCNStream` (one scalar cursor
shared by the whole batch) every slot carries its own write cursor and its
own monotonic step counter: a stream admitted into slot 3 while slot 0 is
19 frames deep must start its ring at cursor 0 without disturbing anyone.

Everything here is functionally pure and shape-stable, so the pool's step
traces **once** per (pool_size, backend) and admission/eviction/masking are
runtime data — that is the no-retrace property continuous batching needs.
The step takes one `[P]` lane code as a traced argument, never a static
one: bit 0 (`STEP`) pushes the lane's frame, bit 1 (`FRESH`) zeroes the
lane's ring and counters first (`clear_lanes`).  A plain bool mask is the
code with bit 1 unset.  So a cold admission or a reset is data of the next
step, not a device operation of its own, and a departing stream whose state
nobody reads is released without touching the device.

Slot surgery (`gather_slot` / `scatter_slot`) stays host-side and eager: it
converts between the pooled state and the single-stream `StreamState`
pytree that `StreamSession` exposes, which is what makes sessions
migratable: evict a stream from one pool and admit its state into another
(or into a standalone session) with bit-identical logits from then on
(tested in tests/test_serving.py).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.tcn import StreamState, TCNStream

# bits of the step's per-lane code
STEP = 1  # push this tick's frame into the lane
FRESH = 2  # zero the lane's ring and counters before the push


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PoolState:
    """Ring memory for P independent streams: per-slot cursor and age.

    buf    : [P, T, C]  ring contents (slot-major, time, feature channels)
    cursor : [P] int32  next write position per slot (wraps mod T)
    steps  : [P] int32  frames absorbed per slot since (re)admission
    """

    buf: jax.Array
    cursor: jax.Array
    steps: jax.Array

    @staticmethod
    def create(
        pool_size: int, n_steps: int, channels: int, dtype=jnp.float32
    ) -> "PoolState":
        """All-empty pool: zero rings, every cursor at 0, no frames seen."""
        return PoolState(
            buf=jnp.zeros((pool_size, n_steps, channels), dtype),
            cursor=jnp.zeros((pool_size,), jnp.int32),
            steps=jnp.zeros((pool_size,), jnp.int32),
        )

    @property
    def pool_size(self) -> int:
        return self.buf.shape[0]

    @property
    def n_steps(self) -> int:
        return self.buf.shape[1]


def split_lanes(lanes: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Decode the step's `[P]` lane code (int or bool) into its ``(step,
    fresh)`` bool masks: bit 0 (`STEP`) and bit 1 (`FRESH`)."""
    code = lanes.astype(jnp.int32)
    return (code & STEP) != 0, (code & FRESH) != 0


def clear_lanes(state: PoolState, fresh: jax.Array) -> PoolState:
    """Zero the ring and counters of every ``fresh`` lane ([P] bool) and
    leave the rest as they are — a batch of per-slot resets, inside the
    step.  A fresh lane then reads as a just-created pool slot."""
    return PoolState(
        buf=jnp.where(fresh.reshape(-1, 1, 1), 0, state.buf),
        cursor=jnp.where(fresh, 0, state.cursor),
        steps=jnp.where(fresh, 0, state.steps),
    )


def masked_push(state: PoolState, feats: jax.Array, active: jax.Array) -> PoolState:
    """Write ``feats[p]`` at ``cursor[p]`` for every active slot; freeze the
    rest.  feats: [P, C]; active: [P] bool.  Inactive slots keep buf, cursor
    and steps unchanged, so a stream that skips a tick (or an empty slot)
    loses nothing — the compute for its lane still runs (the pool batch is
    always full, like the silicon's compute units) but its state is masked.
    """
    pushed = jax.vmap(
        lambda b, v, c: lax.dynamic_update_index_in_dim(b, v, c, axis=0)
    )(state.buf, feats.astype(state.buf.dtype), state.cursor)
    keep = active.reshape(-1, 1, 1)
    return PoolState(
        buf=jnp.where(keep, pushed, state.buf),
        cursor=jnp.where(active, (state.cursor + 1) % state.n_steps, state.cursor),
        steps=jnp.where(active, state.steps + 1, state.steps),
    )


def ordered_windows(state: PoolState) -> jax.Array:
    """[P, T, C] time-ordered (oldest-first) view per slot — what the TCN
    head consumes.  Per-slot roll by the per-slot cursor; identical values
    to `TCNStream.ordered()` for each stream in isolation."""
    return jax.vmap(lambda b, c: jnp.roll(b, -c, axis=0))(state.buf, state.cursor)


# ---------------------------------------------------------------------------
# Slot surgery — pooled state <-> single-stream state (host-side, eager)
# ---------------------------------------------------------------------------


def gather_slot(state: PoolState, slot: int) -> StreamState:
    """Extract slot ``slot`` as a standalone (batch-free) StreamState."""
    return StreamState(
        ring=TCNStream(buf=state.buf[slot], cursor=state.cursor[slot]),
        steps_seen=state.steps[slot],
    )


def scatter_slot(state: PoolState, slot: int, stream: StreamState) -> PoolState:
    """Place a StreamState into slot ``slot`` (batch-free states only)."""
    if stream.ring.buf.ndim != 2:
        raise ValueError(
            "only batch-free StreamStates scatter into a pool slot; got ring "
            f"buf shape {stream.ring.buf.shape}"
        )
    if stream.ring.buf.shape != state.buf.shape[1:]:
        raise ValueError(
            f"ring shape {stream.ring.buf.shape} does not fit pool slots "
            f"{state.buf.shape[1:]}"
        )
    return PoolState(
        buf=state.buf.at[slot].set(stream.ring.buf.astype(state.buf.dtype)),
        cursor=state.cursor.at[slot].set(stream.ring.cursor.astype(jnp.int32)),
        steps=state.steps.at[slot].set(stream.steps_seen.astype(jnp.int32)),
    )
