"""Ternary quantization core — the paper's compute paradigm.

TCN-CUTIE computes with weights AND activations in {-1, 0, +1}.  This module
provides:

  * ``ternary_quantize_weights`` — TWN-style threshold quantizer (Li & Liu,
    2016), the standard training recipe for CUTIE-class networks: per-channel
    threshold ``delta = nu * mean(|w|)`` and scale ``alpha = mean(|w| : |w|>delta)``.
  * ``ternary_quantize_acts`` — symmetric activation ternarizer with a
    configurable threshold (CUTIE applies it after conv+BN, folded offline).
  * Straight-through estimators (STE) for QAT: the forward pass sees the
    quantized value, the backward pass passes gradients through clipped.
  * 2-bit packing/unpacking.  On the TPU the transferable win of ternary is
    *memory traffic*: a ternary weight is 2 bits, so an [K, N] weight matrix
    moves HBM->VMEM at bf16/8 of the cost.  ``pack_ternary``/``unpack_ternary``
    implement the codec used by the Pallas kernels (kernels/ternary_matmul.py).
  * ``select_masks``/``select_decode`` — the same codec read the way the
    OCU adder tree reads it: two single-bit select masks (plus/minus) per
    trit, so a MAC is add/subtract-select instead of a multiply.  The
    compute kernels decode their packed operands through this algebra.

Encoding: t in {-1,0,+1}  ->  (t+1) in {0,1,2}, 2 bits each, 4 values/byte,
value ``i`` in bits ``2i..2i+1`` (little-endian within the byte).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

TERNARY_NU_DEFAULT = 0.7  # TWN threshold factor (0.7 * E|w|)


# ---------------------------------------------------------------------------
# Quantizers
# ---------------------------------------------------------------------------

def ternary_quantize_weights(
    w: jax.Array,
    *,
    nu: float = TERNARY_NU_DEFAULT,
    axis=None,
) -> Tuple[jax.Array, jax.Array]:
    """TWN quantizer.  Returns ``(t, alpha)`` with ``t`` in {-1,0,1} (int8)
    and ``alpha`` the positive per-group scale so that ``w ~= alpha * t``.

    ``axis``: axes to *reduce* over when computing the threshold/scale
    (None = whole tensor).  For a [K, N] matmul weight use ``axis=0`` to get a
    per-output-channel scale, matching CUTIE's per-OCU scaling.
    """
    absw = jnp.abs(w)
    delta = nu * jnp.mean(absw, axis=axis, keepdims=axis is not None)
    mask = absw > delta
    t = jnp.where(mask, jnp.sign(w), 0.0)
    # alpha = mean |w| over the surviving entries (avoid div by zero)
    num = jnp.sum(jnp.where(mask, absw, 0.0), axis=axis, keepdims=axis is not None)
    den = jnp.maximum(jnp.sum(mask, axis=axis, keepdims=axis is not None), 1)
    alpha = num / den
    return t.astype(jnp.int8), alpha.astype(w.dtype)


def ternary_quantize_acts(x: jax.Array, *, threshold: float = 0.5) -> jax.Array:
    """CUTIE activation ternarizer: sign(x) where |x| > threshold else 0.

    In the silicon the threshold comparison is folded with batch-norm into two
    per-channel comparators; here we keep the canonical float form.
    Returns the same dtype as ``x`` with values in {-1, 0, +1}.
    """
    return jnp.where(jnp.abs(x) > threshold, jnp.sign(x), 0.0).astype(x.dtype)


# ---------------------------------------------------------------------------
# Straight-through estimators (QAT)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def ste_ternary_weights(w: jax.Array, nu: float, axis=None) -> jax.Array:
    """Forward: alpha * ternary(w).  Backward: identity on w (clipped).

    ``axis`` selects the threshold/scale grouping exactly as in
    :func:`ternary_quantize_weights`, so QAT (this function) and deployment
    (api/quantize.py) share one quantization grid — axis=(0,1,2) on a conv
    weight gives the per-OCU scale the silicon applies."""
    t, alpha = ternary_quantize_weights(w, nu=nu, axis=axis)
    return alpha * t.astype(w.dtype)


def _stw_fwd(w, nu, axis):
    return ste_ternary_weights(w, nu, axis), (w,)


def _stw_bwd(nu, axis, res, g):
    (w,) = res
    # pass-through inside [-1, 1]*max|w| band; zero outside (standard clip-STE)
    bound = jnp.maximum(jnp.max(jnp.abs(w)), 1e-6)
    return (jnp.where(jnp.abs(w) <= bound, g, 0.0),)


ste_ternary_weights.defvjp(_stw_fwd, _stw_bwd)


@jax.custom_vjp
def ste_ternary_acts(x: jax.Array, threshold) -> jax.Array:
    """Forward: ternarize with ``threshold``.  Backward: hard-tanh STE on
    ``x`` AND a surrogate gradient on ``threshold`` itself, so a per-layer
    threshold passed in as a *traced* scalar is trainable (the ROADMAP's
    learned-thresholds item; cf. xTern's learned quantization bounds).
    A plain Python float threshold behaves exactly as before — its
    cotangent is simply discarded by ``jax.grad`` over the params."""
    return ternary_quantize_acts(x, threshold=threshold)


def _sta_fwd(x, threshold):
    return ste_ternary_acts(x, threshold), (x, threshold)


def _sta_bwd(res, g):
    x, threshold = res
    # hard-tanh style STE window: gradient flows where |x| <= 2*threshold + 1
    dx = jnp.where(jnp.abs(x) <= (2.0 * threshold + 1.0), g, 0.0)
    # d out / d t is exactly -sign(x) * delta(|x| - t); surrogate the delta
    # with a unit-width rect window around t and sum to the threshold shape:
    # everything for a scalar, the leading (non-channel) axes for a
    # per-channel [C] threshold vector — each normalized by sqrt of its own
    # element count so scalar and vector training see the same grad scale.
    near = (jnp.abs(jnp.abs(x) - threshold) <= 0.5).astype(g.dtype)
    contrib = g * jnp.sign(x) * near
    t = jnp.asarray(threshold)
    if t.ndim == 0:
        dt = -jnp.sum(contrib) / jnp.sqrt(jnp.asarray(g.size, g.dtype))
    else:
        dt = -jnp.sum(contrib, axis=tuple(range(contrib.ndim - t.ndim)))
        dt = dt.reshape(t.shape) / jnp.sqrt(jnp.asarray(g.size // t.size, g.dtype))
    return dx, jnp.asarray(dt, dtype=t.dtype)


ste_ternary_acts.defvjp(_sta_fwd, _sta_bwd)


def clamp_threshold(t, lo: float = 0.05, hi: float = 2.0):
    """Keep a learned activation threshold in its meaningful band: below
    ``lo`` the ternarizer degenerates to sign(), far above ``hi`` every
    activation dies.  QAT (``CutieProgram.forward_qat``) and deployment
    folding (``CutieProgram.quantize``) apply the SAME clamp so the trained
    value and the packed deploy-table value round-trip exactly."""
    return jnp.clip(t, lo, hi)


# ---------------------------------------------------------------------------
# 2-bit packing codec
# ---------------------------------------------------------------------------

def _xp(a):
    """The codec's array module: numpy for a numpy array (host-side
    constants, e.g. `sim.WeightMemory` images, run no device program), jax
    for everything else."""
    return np if isinstance(a, np.ndarray) else jnp


def pack_ternary(t: jax.Array, axis: int = -1) -> jax.Array:
    """Pack a {-1,0,1} int array into uint8, 4 values per byte along ``axis``.

    The packed axis length must be a multiple of 4 (pad upstream with zeros —
    zero is a valid ternary value and contributes nothing to dot products).
    """
    xp = _xp(t)
    t = xp.asarray(t)
    axis = axis % t.ndim
    if t.shape[axis] % 4 != 0:
        raise ValueError(f"pack axis length {t.shape[axis]} not a multiple of 4")
    u = (t.astype(xp.int8) + 1).astype(xp.uint8)  # {0,1,2}
    u = xp.moveaxis(u, axis, -1)
    u = u.reshape(*u.shape[:-1], u.shape[-1] // 4, 4)
    shifts = xp.array([0, 2, 4, 6], dtype=xp.uint8)
    packed = xp.sum(u << shifts, axis=-1).astype(xp.uint8)
    return xp.moveaxis(packed, -1, axis)


def unpack_ternary(p: jax.Array, axis: int = -1, *, dtype=jnp.int8) -> jax.Array:
    """Inverse of :func:`pack_ternary`; returns values in {-1,0,1}."""
    xp = _xp(p)
    p = xp.asarray(p)
    axis = axis % p.ndim
    p = xp.moveaxis(p, axis, -1)
    shifts = xp.array([0, 2, 4, 6], dtype=xp.uint8)
    u = (p[..., None] >> shifts) & xp.uint8(3)  # [..., K//4, 4]
    u = u.reshape(*u.shape[:-2], u.shape[-2] * 4)
    t = u.astype(xp.int8) - 1
    return xp.moveaxis(t.astype(dtype), -1, axis)


def select_masks(p: jax.Array, axis: int = -1) -> Tuple[jax.Array, jax.Array]:
    """Decode packed trits to ``(plus, minus)`` **select masks** — the CUTIE
    OCU's add/subtract-select decode, at the codec level.

    For each 2-bit code ``b1b0`` (00 -> -1, 01 -> 0, 10 -> +1):

        plus  = b1                  (the +1 code is exactly "bit 1 set")
        minus = NOR(b1, b0)         (the -1 code is exactly "no bit set")

    Two single-bit selects straight off the packed byte — no subtraction,
    no decoded magnitude.  A MAC against the masks is ``x·plus - x·minus``:
    pass-through, negate, or drop, which is how the silicon's OCU adder
    tree consumes its weight SCM words (and why it needs no multipliers).
    Returns two uint8 0/1 arrays shaped like :func:`unpack_ternary` output;
    ``plus - minus`` reproduces the trits (see :func:`select_decode`).
    The code 11 never occurs in :func:`pack_ternary` output; the select
    decode maps it to +1 (b1 set) — out of contract either way.
    """
    p = jnp.asarray(p)
    axis = axis % p.ndim
    p = jnp.moveaxis(p, axis, -1)
    shifts = jnp.array([0, 2, 4, 6], dtype=jnp.uint8)
    code = (p[..., None] >> shifts) & jnp.uint8(3)  # [..., K//4, 4]
    code = code.reshape(*code.shape[:-2], code.shape[-2] * 4)
    plus = (code >> 1) & jnp.uint8(1)
    minus = ((code | (code >> 1)) & jnp.uint8(1)) ^ jnp.uint8(1)
    return (jnp.moveaxis(plus, -1, axis), jnp.moveaxis(minus, -1, axis))


def select_decode(p: jax.Array, axis: int = -1, *, dtype=jnp.int8) -> jax.Array:
    """``plus - minus`` over :func:`select_masks` — bit-identical to
    :func:`unpack_ternary` on valid packed words, but built from the two
    single-bit selects the add/subtract datapath uses (no ``code - 1``
    arithmetic decode).  This is the form the packed kernels consume."""
    plus, minus = select_masks(p, axis)
    return (plus.astype(jnp.int8) - minus.astype(jnp.int8)).astype(dtype)


def packed_nbytes(shape, axis: int = -1) -> int:
    """Bytes of the packed representation of a ternary tensor of ``shape``."""
    shape = list(shape)
    axis = axis % len(shape)
    shape[axis] = -(-shape[axis] // 4)  # ceil div
    n = 1
    for s in shape:
        n *= s
    return n


def sparsity(t: jax.Array) -> jax.Array:
    """Fraction of exact zeros — CUTIE translates this into toggling savings;
    we report it and exploit it in gradient compression (optim/compress.py)."""
    return jnp.mean((t == 0).astype(jnp.float32))
