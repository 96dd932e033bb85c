"""Plan-driven kernel autotuning: `TileAssign` geometry -> block shapes.

The fixed ``block_cout=128`` the wrappers used through PR 6 had nothing to
do with the schedule the silicon model prices.  This module closes that gap:
the SAME `repro.sim.plan.ExecutionPlan` that the bitsim executes and
`sim.counters` prices also picks the fused kernel's output-channel block.

Selection rule (`block_for_layer`):

  * **plan-derived** — when the layer's `TileAssign`s have ONE uniform
    output-channel width and the kernel fits the OCU window engine
    (kh, kw <= 3, the line-buffer's native form), the kernel block IS the
    tile width: one grid cell per OCU tile pass, so kernel launches and
    priced tile passes line up one-to-one.  For the paper nets this yields
    96 — the OCU count — on every 96-channel layer.
  * **fallback** — when the plan cannot describe the layer as uniform
    single-window passes (a 5x5 stem needs multiple window passes per
    tile; ragged C_out yields mixed tile widths), or when the tile width is
    a block the TPU cannot tile, the block is 128 where 128 divides C_out
    and one single C_out-wide block otherwise (ops.py never has to pad).
    `cifar10_tnn_wide` — 192 channels in 96-wide tile passes, and the 5x5
    stem `sim.reconcile` reports ``analytic_schedulable=False`` for — is
    the designed counterexample exercising this path.

Every block is one Pallas TPU accepts: a last block dimension must be a
multiple of the 128-lane tile or the whole array dimension (`tpu_legal`).

The conv kernel's batch block (`batch_block`) is the other half of its grid:
how many images one grid cell computes.  It is a pure function of the
launch's shapes, not of the plan — the largest divisor of the batch whose
rows (images x h x w) stay within `ROW_TARGET` and whose cell fits
`VMEM_BUDGET`.  A batch of one, or a map already past the target, gets one
image per cell.

Everything here is a pure function of its inputs: same plan and shapes,
same blocks — determinism is pinned in tests/test_autotune.py.
"""
from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING, Dict, List

if TYPE_CHECKING:  # avoid a hard kernels -> sim import at module load
    from repro.sim.plan import ExecutionPlan, LayerPlan

# The TPU vector lane count: a Pallas block's last dimension must be a
# multiple of it, or span the whole array dimension.
LANES = 128

# Rows (images x h x w) one conv grid cell aims for: enough to amortise the
# cell's fixed cost (weight decode, accumulator zeroing, grid step) over a
# long matmul M, few enough that a 32x32 map still splits a batch into
# several cells (2, 8 and 32 images a cell on 32x32, 16x16 and 8x8 maps;
# PERF.md section 6 records the choice).
ROW_TARGET = 2048

# What one conv grid cell's blocks may hold in VMEM, below the 16 MiB that
# v5e scopes for a kernel by default: the rest is for the decoded weights
# and the epilogue's temporaries.
VMEM_BUDGET = 10 * 2**20

# The OCU window engine holds kh x kw <= 3 x 3 natively; anything larger
# takes multiple window passes per tile and leaves the plan-derived regime.
_NATIVE_WINDOW = 3


@dataclasses.dataclass(frozen=True)
class KernelBlock:
    """One layer's autotuned kernel block.  ``source`` records provenance:
    ``"plan"`` (the `TileAssign` cout width, launches == tile passes) or
    ``"fallback"`` (the plan can't schedule the layer as uniform
    single-window passes, or its tile width is no TPU block)."""

    block_cout: int
    source: str  # "plan" | "fallback"


def tpu_legal(block_cout: int, c_out: int) -> bool:
    """True when Pallas on TPU can tile ``c_out`` channels in blocks of
    ``block_cout`` without padding: the whole width, or a dividing
    multiple of the lane count."""
    return block_cout == c_out or (block_cout % LANES == 0 and c_out % block_cout == 0)


def _fallback_block(c_out: int) -> int:
    # a lane-wide block where it divides, else one C_out-wide block (ops.py
    # pads nothing either way)
    return LANES if c_out % LANES == 0 else c_out


def block_for_layer(lp: "LayerPlan") -> KernelBlock:
    """The kernel block for one conv2d/tcn `LayerPlan` — see module
    docstring for the plan-vs-fallback rule."""
    if lp.kind not in ("conv2d", "tcn"):
        raise ValueError(
            f"layer {lp.index} ({lp.kind}) has no conv kernel block; only "
            "conv2d/tcn layers dispatch through ternary_conv2d"
        )
    widths = lp.cout_tile_widths
    if (len(widths) == 1 and lp.kh <= _NATIVE_WINDOW and lp.kw <= _NATIVE_WINDOW
            and tpu_legal(widths[0], lp.c_out)):
        return KernelBlock(block_cout=widths[0], source="plan")
    return KernelBlock(block_cout=_fallback_block(lp.c_out), source="fallback")


def kernel_block_plan(plan: "ExecutionPlan") -> Dict[str, List[KernelBlock]]:
    """Per-layer blocks for every conv-kernel consumer of ``plan``, keyed
    the way the deploy tables are: ``{"conv": [...], "tcn": [...]}`` in
    layer order — the blocks `sim.PlanExecutor` launches with, which it
    derives per layer (`block_for_layer`)."""
    blocks: Dict[str, List[KernelBlock]] = {"conv": [], "tcn": []}
    for lp in plan.layers:
        if lp.kind == "conv2d":
            blocks["conv"].append(block_for_layer(lp))
        elif lp.kind == "tcn":
            blocks["tcn"].append(block_for_layer(lp))
    return blocks


def _vmem_bytes(shape, itemsize: int) -> int:
    """Bytes an array of ``shape`` takes in VMEM, its last two dimensions
    padded to whole (sublane, lane) tiles: 8 rows of 32 bits, 16 of 16 bits
    or 32 of 8 bits, by 128 lanes."""
    *lead, rows, lanes = shape
    sub = 8 * (4 // itemsize)
    return (math.prod(lead) * itemsize * -(-rows // sub) * sub
            * -(-lanes // LANES) * LANES)


def cell_vmem_bytes(bb: int, h: int, w: int, c_in: int, block_cout: int, *,
                    kh: int = 3, kw: int = 3, in_bytes: int = 1,
                    out_bytes: int = 1, res_bytes: int = 1) -> int:
    """One conv grid cell of ``bb`` images: its double-buffered input,
    output and residual blocks (the output taken at the unpooled size; no
    residual where ``res_bytes`` is 0), the f32 accumulator and one tap
    window widened to f32."""
    hp, wp = h + kh - 1, w + kw - 1
    out = _vmem_bytes((bb, h, w, block_cout), out_bytes)
    res = _vmem_bytes((bb, h, w, block_cout), res_bytes) if res_bytes else 0
    return (2 * (_vmem_bytes((bb, hp, wp, c_in), in_bytes) + out + res)
            + _vmem_bytes((bb * h * w, block_cout), 4)
            + _vmem_bytes((bb, h, w, c_in), 4))


def batch_block(b: int, h: int, w: int, c_in: int, block_cout: int,
                **sizes) -> int:
    """Images per conv grid cell: the largest divisor ``bb`` of the batch
    ``b`` with ``bb * h * w <= ROW_TARGET`` and the cell's blocks within
    `VMEM_BUDGET` (`cell_vmem_bytes`, which takes ``sizes``: window and
    item sizes); 1 where no larger divisor fits."""
    best = 1
    for bb in range(2, b + 1):
        if b % bb:
            continue
        if (bb * h * w > ROW_TARGET
                or cell_vmem_bytes(bb, h, w, c_in, block_cout, **sizes) > VMEM_BUDGET):
            break
        best = bb
    return best
