"""Packed-ternary matmul — CUTIE's dataflow, TPU-native, packed operands.

The CUTIE silicon keeps the output stationary (one OCU per output channel,
accumulator never leaves the unit) and the weights stationary (per-OCU weight
buffers).  The TPU translation of those two properties:

  * **output-stationary**: the (bm, bn) f32 accumulator tile lives in a VMEM
    scratch buffer across the whole K-reduction; it is written to HBM exactly
    once, on the last K step.
  * **minimal weight movement**: weights are stored *2-bit packed* in HBM
    ([K/4, N] uint8) and decoded to add/subtract-select operands only inside
    VMEM, right before the MXU dot.  Each packed byte crosses HBM->VMEM
    exactly once per output tile — an 8x traffic reduction vs bf16 weights,
    which is the part of the paper's "minimize data movement" insight that
    actually transfers to a bandwidth-limited TPU (weight-streaming decode is
    the canonical case).

The in-register decode is `core.ternary.select_masks`' bit algebra (plus =
b1, minus = NOR(b1, b0), operand = plus - minus): the MAC against a
{-1,0,+1} select operand is the OCU adder tree's pass/negate/drop — no
multiplier ever sees a decoded magnitude.

``ternary_matmul_native`` runs the identical decode + dot as straight XLA
ops (single K reduction, no tile loop) — the CPU-native packed path
`ops.ternary_matmul` dispatches when no Pallas machinery is requested.
Bit-identical to the Pallas path on ternary/dyadic data (integer-valued
partial sums are exact in f32 under any accumulation order).

Grid: (M/bm, N/bn, K/bk), K innermost so the accumulator revisits are
contiguous.  Block shapes default to MXU-aligned multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SHIFTS = (0, 2, 4, 6)


def select_tile(wp: jax.Array, dtype) -> jax.Array:
    """[..., K/4, N] uint8 -> [..., K, N] add/subtract-select operands in
    ``dtype``, values {-1, 0, +1} via the plus/minus single-bit selects.

    The expansion is sublane-structured: byte row r expands to rows
    4r..4r+3, matching pack_ternary along the K axis (axis -2).  The bytes
    are widened to int32 before any bit operation: Mosaic on TPU cannot
    shift 8-bit lanes, nor interleave 8-bit rows.  Shared by both kernels'
    bodies and their native XLA twins, so every impl decodes identically.
    """
    wp = wp.astype(jnp.int32)
    *lead, k4, n = wp.shape
    parts = []
    for s in _SHIFTS:
        code = (wp >> s) & 3
        plus = code >> 1
        minus = ((code | plus) & 1) ^ 1
        parts.append(plus - minus)
    w = jnp.stack(parts, axis=-2)  # (..., k4, 4, n)
    return w.reshape(*lead, k4 * 4, n).astype(dtype)


def _tmm_kernel(x_ref, wp_ref, scale_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = select_tile(wp_ref[...], x.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret", "out_dtype"),
)
def ternary_matmul_pallas(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
    out_dtype=None,
):
    """y[M, N] = x[M, K] @ select_decode(w_packed)[K, N] * scale[N].

    ``w_packed``: [K/4, N] uint8 (pack_ternary along K).  ``scale``: [N] or
    [1, N] per-output-channel alpha.  M, K, N must already be padded to the
    block sizes (ops.py handles padding); a direct caller with non-dividing
    blocks gets a `ValueError`, not a silent bad grid.  The kernel
    compiles for TPU; only ``interpret=True`` runs it elsewhere.
    """
    m, k = x.shape
    k4, n = w_packed.shape
    if k != 4 * k4:
        raise ValueError(
            f"K={k} does not match packed K/4={k4}: pad x to the 4-trit "
            "pack quantum (kernels.ops.ternary_matmul pads)"
        )
    if block_k % 4 or k % block_k:
        raise ValueError(
            f"block_k={block_k} must be a multiple of 4 dividing K={k} "
            "(kernels.ops.ternary_matmul clamps and pads)"
        )
    if m % block_m or n % block_n:
        raise ValueError(
            f"block_m={block_m}/block_n={block_n} must divide M={m}/N={n} "
            "(kernels.ops.ternary_matmul pads and slices)"
        )
    scale = scale.reshape(1, n)
    out_dtype = out_dtype or x.dtype
    n_k = k // block_k

    return pl.pallas_call(
        functools.partial(_tmm_kernel, n_k=n_k),
        grid=(m // block_m, n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k // 4, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, w_packed, scale)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def ternary_matmul_native(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    *,
    out_dtype=None,
):
    """The Pallas kernel's math as one straight XLA dot: select-decode the
    packed words, dot, scale.  No M/N/K tiling (XLA tiles the dot itself),
    so the only geometry requirement is the pack quantum."""
    m, k = x.shape
    k4, n = w_packed.shape
    if k != 4 * k4:
        raise ValueError(
            f"K={k} does not match packed K/4={k4}: pad x to the 4-trit "
            "pack quantum (kernels.ops.ternary_matmul pads)"
        )
    w = select_tile(w_packed, x.dtype)
    y = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y = y * scale.reshape(1, n).astype(jnp.float32)
    return y.astype(out_dtype or x.dtype)
