"""`repro.serving` — many sensor streams on one fixed-shape jitted batch.

The serving story for the paper's autonomous mode: a `SessionPool`
multiplexes independent DVS streams onto one jitted `stream_step` with
slot-masked ring state and per-slot cursors (continuous batching — no
retrace on admit/evict), `ContinuousBatcher` drives arrivals and
departures over it, and `FleetRouter` scales that to many tenants running
*different* nets concurrently — bucketed pools per net, bounded admission
FIFOs, ladder-based autoscaling, and async host-side frame ingestion.
Entry points: `DeployedProgram.serve(pool_size, backend)` for one net,
`DeployedProgram.serve_fleet()` / `repro.serving.serve_fleet({...})` for
many.

`ActivityGate` adds TinyVers-style duty cycling on top: quiet streams
park out of their pool slot with ring state retained, wake bit-identically
on an event burst, and `energy_summary` prices the skipped frames in uJ on
the same sim counters `silicon_report` uses.

Layering: `masking` (pure state algebra) <- `pool` (mechanism) <-
`gating` (host-side policy) <- `scheduler` (single-net policy) <-
`fleet` (multi-net policy).
`repro.api` stays importable without this package; this package imports
`repro.api.program` only inside `SessionPool` for the backend check.
"""

from repro.serving.masking import (
    PoolState,
    clear_lanes,
    gather_slot,
    masked_push,
    ordered_windows,
    scatter_slot,
)
from repro.serving.fleet import (
    FleetQueueFull,
    FleetRouter,
    FrameFeeder,
    NetBucket,
    ScaleEvent,
    bucket_ladder,
    serve_fleet,
)
from repro.serving.gating import (
    ActivityGate,
    GateState,
    energy_summary,
    frame_energy_uj,
)
from repro.serving.pool import PoolFullError, SessionPool
from repro.serving.scheduler import ContinuousBatcher, StreamRequest, StreamResult

__all__ = [
    "ActivityGate",
    "GateState",
    "energy_summary",
    "frame_energy_uj",
    "FleetQueueFull",
    "FleetRouter",
    "FrameFeeder",
    "NetBucket",
    "ScaleEvent",
    "bucket_ladder",
    "serve_fleet",
    "PoolState",
    "clear_lanes",
    "gather_slot",
    "masked_push",
    "ordered_windows",
    "scatter_slot",
    "PoolFullError",
    "SessionPool",
    "ContinuousBatcher",
    "StreamRequest",
    "StreamResult",
]
