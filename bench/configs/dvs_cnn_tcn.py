"""Plain reference of the DVS CNN + TCN net (TCN-CUTIE §7, ref. [6]).

Per sensor frame: five SAME 3x3 ternary convs, each scaled per channel,
ternarised at the threshold and max-pooled 2x2, then a global average
into one feature vector.  The ring memory holds the newest ``tcn_steps``
feature vectors of the stream, oldest first, zeros where the stream is
younger.  Over that window four causal dilated TCN layers (zero history
before the window's first step), each scaled and ternarised; the newest
step goes through the fc, whose logits are the frame's classification:

    y[n] = sum_j x[n - (taps - 1 - j) * D] . w[j]

``reference`` returns the logits of every frame of every clip in the
library, ``[clips, frames, n_classes]``, computed in blocks of frames.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from harness import plain

BLOCK = 128  # frames (or windows) per reference call


@functools.partial(jax.jit, static_argnums=(2, 3))
def _features(weights, frames, cfg_json, dtype):
    cfg = json.loads(cfg_json)
    x = plain.conv_stack(frames, weights, cfg, dtype)
    return x.mean(axis=(1, 2))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(weights, windows, cfg_json, dtype):
    cfg = json.loads(cfg_json)
    thr = jnp.asarray(cfg["act_threshold"], dtype)
    x = windows.astype(dtype)
    steps = x.shape[1]
    ti = 0
    for layer in cfg["layers"]:
        if layer["kind"] == "tcn":
            w = weights["tcn"][ti]
            ti += 1
            d, taps = layer["dilation"], layer["taps"]
            xp = jnp.pad(x, ((0, 0), ((taps - 1) * d, 0), (0, 0)))
            acc = sum(plain.dot(xp[:, j * d: j * d + steps], w["t"][j], dtype)
                      for j in range(taps))
            x = plain.ternarize(acc * w["scale"].astype(dtype), thr)
        elif layer["kind"] == "last_step":
            x = x[:, -1]
        elif layer["kind"] == "fc":
            fc = weights["fc"][0]
            x = plain.dot(x, fc["t"], dtype) * fc["scale"].astype(dtype)
    return x


def reference(weights, library: np.ndarray, cfg: dict, dtype=jnp.float32) -> np.ndarray:
    """Logits of every frame of every clip: ``[clips, frames, classes]``."""
    key = json.dumps(cfg, sort_keys=True)
    n, t = library.shape[:2]
    frames = library.reshape(n * t, *library.shape[2:])
    feats = np.concatenate([
        np.asarray(_features(weights, jnp.asarray(frames[i: i + BLOCK]), key, dtype),
                   np.float32)
        for i in range(0, n * t, BLOCK)
    ]).reshape(n, t, -1)
    s = cfg["tcn_steps"]
    padded = np.concatenate([np.zeros((n, s - 1, feats.shape[-1]), np.float32), feats], 1)
    idx = np.arange(t)[:, None] + np.arange(s)[None]  # window of frame f: f .. f+s-1
    windows = padded[:, idx].reshape(n * t, s, -1)
    logits = np.concatenate([
        np.asarray(_head(weights, jnp.asarray(windows[i: i + BLOCK]), key, dtype),
                   np.float32)
        for i in range(0, n * t, BLOCK)
    ])
    return logits.reshape(n, t, -1)
