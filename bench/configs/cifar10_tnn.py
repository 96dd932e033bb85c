"""Plain reference of the 9-layer CIFAR-10 TNN (TCN-CUTIE §7).

Eight SAME 3x3 ternary convs, each scaled per channel and ternarised at
the threshold, with a 2x2 max pool after the 2nd, 5th and 8th; the 4x4x96
map is flattened row-major (height, width, channel) into the fc, whose
scaled outputs are the logits.

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


@functools.partial(jax.jit, static_argnums=(2, 3))
def _forward(weights, images, cfg_json, dtype):
    cfg = json.loads(cfg_json)
    x = plain.conv_stack(images, weights, cfg, dtype)
    x = x.reshape(x.shape[0], -1)
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
