"""Ternary 3x3 conv2d compute path — the CUTIE OCU array, packed operands.

CUTIE's datapath: a line buffer holds a 3-row window of the (SAME-padded)
input feature map; every cycle, all 96 OCUs consume the full 3x3xC_in window
of one output pixel.  The translation here keeps the *whole padded image* of
one sample resident (CUTIE's maximum 64x64x96 map is ~0.8 MB in bf16 —
comfortably VMEM-sized; that is exactly why the silicon could afford
all-on-chip feature maps, and the same dimensioning argument holds here),
and expresses the window reuse as 9 shifted [H*W, C_in] x [C_in, bn]
matmuls accumulated output-stationary.

Weights arrive 2-bit packed along C_in: [KH, KW, C_in/4, C_out] uint8 — the
quantizer's deploy-table bytes, consumed **verbatim**.  The in-register
decode is `core.ternary.select_masks`' algebra: per 2-bit code, ``plus`` is
bit 1 and ``minus`` is NOR of both bits — two single-bit selects, and the
MAC operand is ``plus - minus`` in {-1,0,+1}.  No multiplier ever sees a
decoded magnitude: the dot against a {-1,0,+1} operand is the adder tree's
pass/negate/drop select, which is the "no multipliers" CUTIE trick in the
form an MXU/SIMD unit can execute.  Per output tile the weight traffic is
KH*KW*C_in*bn/4 bytes, once.

The fused epilogue optionally applies CUTIE's activation ternarization
(sign/threshold) and the layer's 2x2 max-pool, which the silicon folds into
the OCU pipeline after the adder tree (ThFU + pooling unit) — so a whole TNN
layer, pooling included, is a single launch whose output is the int8
ternary activation map.  The wide float accumulator never leaves the kernel:
inter-layer traffic is exactly the silicon's 2-bit activation memory model.

Two implementations share the decode + tap walk + epilogue semantics:

  * ``ternary_conv2d_pallas`` — the Pallas kernel, compiled for TPU
    (``interpret=True`` runs the Pallas interpreter on any host).
  * ``ternary_conv2d_native`` — the SAME per-tap matmuls lowered as straight
    XLA ops, batched over samples.  On CPU hosts this skips the Pallas
    interpreter's per-grid-cell emulation entirely; `ops.ternary_conv2d`
    auto-dispatches it there.  With ternary/dyadic data both paths are
    bit-identical (integer-valued partial sums are exact in f32 under any
    accumulation order).

TCN layers arrive here already *mapped* (core.tcn.dilated1d_to_2d): the same
kernel executes dilated 1-D convolutions with zero marshalling, exactly the
paper's scheduling contribution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ternary_matmul import select_tile


def _epilogue(y, scale, thr, *, h: int, w: int, bn: int,
              fuse_ternary: bool, fuse_pool: int):
    """Scale -> optional ThFU ternarize -> optional epilogue max-pool, on a
    (pixels, bn) accumulator (pixels row-major over (h, w)).  Shared by the
    Pallas kernel body and the native path — one semantics definition."""
    y = y * scale.astype(jnp.float32)
    if fuse_ternary:
        # ThFU: per-OCU comparator constants — a (1, bn) threshold row
        # broadcast over the pixels (scalar thresholds arrive pre-splatted)
        y = jnp.where(jnp.abs(y) > thr.astype(jnp.float32), jnp.sign(y), 0.0)
    if fuse_pool > 1:
        # (h*w, bn) is row-major (h, w, bn): group both spatial axes by the
        # pool window and reduce — the silicon's pooling unit, in-epilogue.
        p = fuse_pool
        y = y.reshape(h // p, p, w // p, p, bn).max(axis=(1, 3))
        return y.reshape(h // p, w // p, bn)
    return y.reshape(h, w, bn)


def _tconv_kernel(
    x_ref, wp_ref, scale_ref, thr_ref, o_ref, acc_ref, *, h: int, w: int,
    kh: int, kw: int, fuse_ternary: bool, fuse_pool: int,
):
    """One (sample, output-channel-tile) grid cell: full-image conv."""
    c_in = x_ref.shape[-1]
    bn = o_ref.shape[-1]
    # [KH, KW, C4, bn] uint8 -> [KH, KW, C_in, bn]: the packed C_in axis is
    # axis -2, exactly the matmul kernel's K axis
    wt = select_tile(wp_ref[...], jnp.float32)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    # 9 shifted matmuls == the line-buffer window walk, output-stationary.
    # The window is widened to f32 before the (h, w, c) -> (h*w, c) merge:
    # Mosaic cannot merge int8 rows at every width (TCN D=2 has w=2).
    for dy in range(kh):
        for dx in range(kw):
            xs = x_ref[0, dy : dy + h, dx : dx + w, :].astype(jnp.float32)
            acc_ref[...] += jax.lax.dot_general(
                xs.reshape(h * w, c_in),
                wt[dy, dx],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    y = _epilogue(
        acc_ref[...], scale_ref[...], thr_ref[...], h=h, w=w, bn=bn,
        fuse_ternary=fuse_ternary, fuse_pool=fuse_pool,
    )
    o_ref[...] = y[None].astype(o_ref.dtype)


def _check_geometry(c_in, c4, h, w, fuse_pool):
    if c_in != 4 * c4:
        raise ValueError(
            f"C_in={c_in} does not match packed C_in/4={c4}: activations "
            "must be channel-padded to the 4-trit pack quantum "
            "(kernels.ops.ternary_conv2d pads)"
        )
    if fuse_pool > 1 and (h % fuse_pool or w % fuse_pool):
        raise ValueError(
            f"fuse_pool={fuse_pool} does not divide the {h}x{w} feature map"
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_cout", "interpret", "fuse_ternary", "fuse_pool", "out_dtype"
    ),
)
def ternary_conv2d_pallas(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    threshold: jax.Array,
    *,
    block_cout: int = 128,
    fuse_ternary: bool = False,
    fuse_pool: int = 0,
    interpret: bool = False,
    out_dtype=None,
):
    """SAME ternary conv.  x: [B, H, W, C_in] (unpadded), w_packed:
    [KH, KW, C_in/4, C_out] uint8, scale: [C_out], threshold: [C_out] —
    the ThFU's per-OCU comparator constants (ops.py splats a scalar; only
    read when ``fuse_ternary``).  C_out must be a multiple of
    ``block_cout`` — autotuned blocks arrive plan-checked, and ops.py pads
    ragged C_out up to the block; a direct caller with a non-dividing block
    gets a `ValueError`, not a silent bad grid.  ``fuse_pool`` > 1 appends
    a window/stride ``fuse_pool`` max-pool to the epilogue (after the
    optional ternarization), shrinking the output to [B, H/p, W/p, C_out].
    The kernel compiles for TPU; only ``interpret=True`` runs it elsewhere."""
    b, h, w, c_in = x.shape
    kh, kw, c4, c_out = w_packed.shape
    _check_geometry(c_in, c4, h, w, fuse_pool)
    if not 0 < block_cout <= c_out or c_out % block_cout:
        raise ValueError(
            f"block_cout={block_cout} cannot tile C_out={c_out}: it must "
            "divide C_out (kernels.ops.ternary_conv2d pads ragged C_out to "
            "a block multiple; kernels.autotune only emits dividing blocks)"
        )
    out_dtype = out_dtype or x.dtype
    ph, pw = kh // 2, kw // 2
    xp = jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw), (0, 0)))
    scale = scale.reshape(1, c_out)
    thr = threshold.reshape(1, c_out)
    oh, ow = (h // fuse_pool, w // fuse_pool) if fuse_pool > 1 else (h, w)

    kern = functools.partial(
        _tconv_kernel, h=h, w=w, kh=kh, kw=kw,
        fuse_ternary=fuse_ternary, fuse_pool=fuse_pool,
    )
    return pl.pallas_call(
        kern,
        grid=(b, c_out // block_cout),
        in_specs=[
            pl.BlockSpec((1, h + kh - 1, w + kw - 1, c_in), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((kh, kw, c4, block_cout), lambda i, j: (0, 0, 0, j)),
            pl.BlockSpec((1, block_cout), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_cout), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, oh, ow, block_cout), lambda i, j: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, oh, ow, c_out), out_dtype),
        scratch_shapes=[pltpu.VMEM((h * w, block_cout), jnp.float32)],
        interpret=interpret,
    )(xp, w_packed, scale, thr)


@functools.partial(
    jax.jit,
    static_argnames=("fuse_ternary", "fuse_pool", "out_dtype"),
)
def ternary_conv2d_native(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    threshold: jax.Array,
    *,
    fuse_ternary: bool = False,
    fuse_pool: int = 0,
    out_dtype=None,
):
    """The Pallas kernel's exact tap walk as straight XLA ops — same select
    decode, same 9 shifted matmuls in the same order, same `_epilogue` —
    with the batch folded into the matmul M dimension (one [B*H*W, C_in] x
    [C_in, C_out] dot per tap instead of one grid cell per sample).  This is
    the CPU-native packed path `ops.ternary_conv2d` dispatches when no
    Pallas machinery is requested; there is no block tiling because XLA
    tiles the dots itself."""
    b, h, w, c_in = x.shape
    kh, kw, c4, c_out = w_packed.shape
    _check_geometry(c_in, c4, h, w, fuse_pool)
    out_dtype = out_dtype or x.dtype
    ph, pw = kh // 2, kw // 2
    xp = jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw), (0, 0)))
    wt = select_tile(w_packed, jnp.float32)

    acc = jnp.zeros((b * h * w, c_out), jnp.float32)
    for dy in range(kh):
        for dx in range(kw):
            xs = xp[:, dy : dy + h, dx : dx + w, :].reshape(b * h * w, c_in)
            acc += jax.lax.dot_general(
                xs.astype(jnp.float32),
                wt[dy, dx],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    # batch rides as extra leading pixel rows: run the shared epilogue with
    # h' = b*h (row-major layout makes the pool grouping identical per
    # sample as long as fuse_pool divides h, which _check_geometry ensured)
    y = _epilogue(
        acc, scale.reshape(1, c_out), jnp.reshape(threshold, (1, c_out)),
        h=b * h, w=w, bn=c_out, fuse_ternary=fuse_ternary,
        fuse_pool=fuse_pool,
    )
    oh, ow = (h // fuse_pool, w // fuse_pool) if fuse_pool > 1 else (h, w)
    return y.reshape(b, oh, ow, c_out).astype(out_dtype)
