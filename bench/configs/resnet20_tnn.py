"""Plain reference of the ternary ResNet-20 (He et al., arXiv:1512.03385
§4.2, ternarised as in TWN and TTQ).

A SAME 3x3 stem, then three stages of three basic blocks.  Each conv is
scaled per channel and ternarised at the threshold; the second conv of a
block adds the block's input ``a_k`` to its scaled output before the
threshold:

    y_i = scale_i * conv(a_{i-1}, T_i) + S(a_k),    a_i = ternarize(y_i)

``S`` is the identity where the shapes match and otherwise option A: every
2nd row and column from the top-left, and zero channels appended up to the
block's width.  A strided conv keeps every 2nd output row and column from
the first.  A global average over the last map feeds the fc, whose scaled
outputs are the logits.

``reference`` returns the logits of every image of the library,
``[batches, batch, n_classes]``, computed in blocks of images.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from harness import plain

BLOCK = 256  # images per reference call


def option_a(a, shape):
    """``S``: ``a`` [N, H', W', C'] at a block output of ``shape`` [N, H, W,
    C]: every (H'/H)-th row and column from the first, zero channels
    appended after the C' it has."""
    s = a.shape[1] // shape[1]
    a = a[:, ::s, ::s, :]
    return jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, shape[-1] - a.shape[-1])))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _forward(weights, images, cfg_json, dtype):
    cfg = json.loads(cfg_json)
    thr = jnp.asarray(cfg["act_threshold"], dtype)
    x, ci, outputs = images.astype(dtype), 0, {}
    for i, layer in enumerate(cfg["layers"]):
        if layer["kind"] == "conv2d":
            w = weights["conv"][ci]
            ci += 1
            y = plain.conv(x, w["t"], dtype, layer.get("stride", 1))
            y = y * w["scale"].astype(dtype)
            if "shortcut" in layer:
                y = y + option_a(outputs[layer["shortcut"]], y.shape)
            x = outputs[i] = plain.ternarize(y, thr)
        elif layer["kind"] == "global_pool":
            x = x.mean(axis=(1, 2))
    fc = weights["fc"][0]
    return plain.dot(x, fc["t"], dtype) * fc["scale"].astype(dtype)


def reference(weights, library: np.ndarray, cfg: dict, dtype=jnp.float32) -> np.ndarray:
    """Logits of every image: ``[batches, batch, classes]``."""
    key = json.dumps(cfg, sort_keys=True)
    n, b = library.shape[:2]
    images = library.reshape(n * b, *library.shape[2:])
    logits = np.concatenate([
        np.asarray(_forward(weights, jnp.asarray(images[i: i + BLOCK]), key, dtype),
                   np.float32)
        for i in range(0, n * b, BLOCK)
    ])
    return logits.reshape(n, b, -1)
