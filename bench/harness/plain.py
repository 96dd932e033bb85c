"""Plain building blocks of the references: straightforward `jax.numpy`,
no kernels, no packing, no batching tricks.

``dtype`` is the arithmetic type of the whole reference: float32 (at
``HIGHEST`` matmul precision, as the configurations state) or bfloat16
for the control, which accumulates, scales and compares in bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def precision(dtype):
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def conv(x, t, dtype, stride: int = 1):
    """2-D convolution of NHWC ``x`` with HWIO trits ``t``, zero-padded as
    SAME at stride 1, keeping every ``stride``-th output row and column
    from the first."""
    kh, kw = t.shape[:2]
    pad = (((kh - 1) // 2, kh // 2), ((kw - 1) // 2, kw // 2))
    return jax.lax.conv_general_dilated(
        x.astype(dtype), t.astype(dtype), (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision(dtype), preferred_element_type=dtype)


def dot(x, t, dtype):
    return jnp.matmul(x.astype(dtype), t.astype(dtype), precision=precision(dtype),
                      preferred_element_type=dtype)


def ternarize(y, threshold):
    """+1 above ``threshold``, -1 below ``-threshold``, else 0."""
    return jnp.where(jnp.abs(y) > threshold, jnp.sign(y), 0).astype(y.dtype)


def max_pool(x, p: int):
    """Non-overlapping ``p`` x ``p`` max pool of NHWC ``x``."""
    n, h, w, c = x.shape
    return x.reshape(n, h // p, p, w // p, p, c).max(axis=(2, 4))


def conv_stack(x, weights, cfg, dtype):
    """The configuration's 2-D layers up to the first non-conv, non-pool
    layer: conv (at its kernel size and stride), per-channel scale,
    ternarize, and the pool that follows."""
    ci = 0
    thr = jnp.asarray(cfg["act_threshold"], dtype)
    for layer in cfg["layers"]:
        if layer["kind"] == "conv2d":
            w = weights["conv"][ci]
            ci += 1
            y = conv(x, w["t"], dtype, layer.get("stride", 1))
            x = ternarize(y * w["scale"].astype(dtype), thr)
        elif layer["kind"] == "pool":
            x = max_pool(x, layer["window"])
        else:
            break
    return x
