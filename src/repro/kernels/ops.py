"""Public jit'd wrappers over the packed compute kernels.

Handle: arbitrary leading batch dims, padding to block multiples, implementation
dispatch, and a quantize+pack convenience.  Three implementations of one
semantics (see ternary_conv2d.py / ternary_matmul.py):

  * ``impl="native"`` — the packed select-decode datapath as straight XLA
    ops.  The default on CPU hosts: identical math to the Pallas kernel
    without paying the interpreter's per-grid-cell emulation.
  * ``impl="pallas"``  — the Pallas kernel (compiled on TPU, interpreter on
    CPU).  The default on TPU hosts.
  * ``impl="interpret"`` — the Pallas interpreter forced, any host: how the
    kernel itself is tested on a CPU.

The deploy program's ``backend="fused"`` calls `ternary_conv2d` with no
``impl``, so the host picks.  ``block_cout=None`` (default) lets the
caller's plan decide: `sim.PlanExecutor` threads each layer's autotuned
block (`kernels.autotune`, from the `ExecutionPlan`'s `TileAssign`
geometry) — the fixed 128 only remains as the fallback for plan-less
direct calls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# The quantize->pad->pack path lives in repro.api.quantize (single
# implementation repo-wide); re-exported here for kernel-facing callers.
from repro.api.quantize import (  # noqa: F401
    quantize_pack_conv_weights,
    quantize_pack_matmul_weights,
)
from repro.kernels.ternary_matmul import (
    ternary_matmul_native,
    ternary_matmul_pallas,
)
from repro.kernels.ternary_conv2d import (
    ternary_conv2d_native,
    ternary_conv2d_pallas,
    ternary_conv2d_residual_pallas,
)

IMPLS = ("native", "pallas", "interpret")


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _resolve_impl(impl: str | None) -> str:
    """One resolution rule for both wrappers: an explicit ``impl`` wins;
    None -> native on CPU, compiled Pallas on TPU."""
    if impl is not None:
        if impl not in IMPLS:
            raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")
        return impl
    return "native" if _on_cpu() else "pallas"


def _interpret_flag(impl: str) -> bool:
    """The Pallas call's interpret flag once ``impl`` resolved to a Pallas
    form: forced for impl="interpret", otherwise interpret iff the host has
    no Mosaic compiler (CPU)."""
    return impl == "interpret" or _on_cpu()


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "impl"),
)
def ternary_matmul(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    impl: str | None = None,
):
    """y[..., N] = x[..., K] @ select_decode(w_packed)[K, N] * scale[N]."""
    impl = _resolve_impl(impl)
    *lead, k = x.shape
    k4, n = w_packed.shape
    if 4 * k4 < k:
        raise ValueError(
            f"packed weight carries K={4 * k4} < input K={k}: the pack "
            "quantum only ever pads, never truncates"
        )
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    if 4 * k4 != k:
        x2 = jnp.pad(x2, ((0, 0), (0, 4 * k4 - k)))
    if impl == "native":
        y = ternary_matmul_native(x2, w_packed, scale.reshape(-1), out_dtype=x.dtype)
        return y.reshape(*lead, n)
    # pad M to block_m, K to block_k, N to block_n for the Pallas grid
    x2 = _pad_to(x2, 0, block_m)
    bk = min(block_k, 4 * k4)
    bk -= bk % 4
    x2 = _pad_to(x2, 1, bk)
    wp = _pad_to(w_packed, 0, bk // 4)
    wp = _pad_to(wp, 1, block_n)
    sc = _pad_to(scale.reshape(-1), 0, block_n)
    bm = min(block_m, x2.shape[0])
    y = ternary_matmul_pallas(
        x2, wp, sc, block_m=bm, block_n=min(block_n, wp.shape[1]),
        block_k=bk, interpret=_interpret_flag(impl),
        out_dtype=x.dtype,
    )
    return y[:m, :n].reshape(*lead, n)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_cout", "fuse_ternary", "fuse_pool", "impl", "out_dtype",
    ),
)
def ternary_conv2d(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    *,
    block_cout: int | None = None,
    fuse_ternary: bool = False,
    threshold=0.5,
    fuse_pool: int = 0,
    residual=None,
    impl: str | None = None,
    out_dtype=None,
):
    """SAME ternary conv over [B, H, W, C_in].  With ``fuse_ternary`` (and
    optionally ``fuse_pool``/``out_dtype=jnp.int8``) the whole CUTIE layer —
    conv, threshold unit, pooling — is one kernel launch emitting 2-bit-class
    ternary activations.  ``threshold`` is the ThFU comparator constant:
    a scalar (splatted across OCUs) or a per-channel [C_out] vector — the
    per-OCU comparator bank programmed at network load time.

    ``block_cout``: the Pallas output-channel block.  ``None`` means "no
    plan spoke": 128, clamped to C_out (plan-driven callers pass each
    layer's `kernels.autotune` block).  Ragged C_out is padded up to the
    block and sliced back out, fused epilogue included.

    ``residual``: a shortcut [B, H, W, C_out] at the conv's output size,
    added to the scaled accumulator before the threshold (and any pool).
    On the Pallas path it launches `ternary_conv2d_residual_pallas`;
    without it the plain kernel launches with its four operands."""
    impl = _resolve_impl(impl)
    kh, kw, c4, c_out = w_packed.shape
    c_in = x.shape[-1]
    if 4 * c4 != c_in:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 4 * c4 - c_in)))
    thr = jnp.asarray(threshold, jnp.float32)
    if thr.ndim == 0:
        thr = jnp.full((c_out,), thr)
    elif thr.shape != (c_out,):
        raise ValueError(f"threshold shape {thr.shape} != ({c_out},)")
    if impl == "native":
        return ternary_conv2d_native(
            x, w_packed, scale.reshape(-1), thr, residual,
            fuse_ternary=fuse_ternary, fuse_pool=fuse_pool,
            out_dtype=out_dtype or x.dtype,
        )
    bc = min(block_cout or 128, c_out)
    wp = _pad_to(w_packed, 3, bc)
    sc = _pad_to(scale.reshape(-1), 0, bc)
    th = _pad_to(thr, 0, bc)
    launch = dict(block_cout=bc, fuse_ternary=fuse_ternary, fuse_pool=fuse_pool,
               interpret=_interpret_flag(impl),
               out_dtype=out_dtype or x.dtype)
    if residual is None:
        y = ternary_conv2d_pallas(x, wp, sc, th, **launch)
    else:
        y = ternary_conv2d_residual_pallas(
            x, wp, sc, th, _pad_to(residual, 3, bc), **launch)
    return y[..., :c_out]
