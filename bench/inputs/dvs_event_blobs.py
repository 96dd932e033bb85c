"""Seeded DVS event clips, generated on the host as the traffic sends them.

Copied from the program's synthetic pipeline (``repro.data.pipeline``
``DVSEventPipeline``) and vectorised, so that a later change to the
program's data code cannot move the yardstick.  Events are 0 or 1, exact
in any float type.
"""
from __future__ import annotations

import numpy as np


def generate(rng: np.random.Generator, lead, cfg: dict, *, radius: float, speed: float,
             jitter: int, noise: float) -> np.ndarray:
    """``[clips, frames, h, w, 2]`` float32 for ``lead = (clips, frames)``:
    a blob per clip moving in a class-specific direction, its pixels split
    at random into on (channel 0) and off (channel 1) events, plus
    background noise events at rate ``noise`` on channel 0."""
    n_clips, frames = lead
    h, w = cfg["input_hw"]
    labels = rng.integers(0, cfg["n_classes"], size=n_clips)
    ang = 2 * np.pi * labels / cfg["n_classes"]
    cx = w // 2 + rng.integers(-jitter, jitter, size=n_clips)
    cy = h // 2 + rng.integers(-jitter, jitter, size=n_clips)
    t = np.arange(frames)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    out = np.zeros((n_clips, frames, h, w, 2), np.float32)
    for i in range(n_clips):
        px = (cx[i] + np.cos(ang[i]) * t * speed).astype(np.float32)
        py = (cy[i] + np.sin(ang[i]) * t * speed).astype(np.float32)
        d2 = (xx[None] - px[:, None, None]) ** 2 + (yy[None] - py[:, None, None]) ** 2
        blob = d2 < radius * radius
        on = blob & (rng.random((frames, h, w), np.float32) < 0.5)
        bg = rng.random((frames, h, w), np.float32) < noise
        out[i, ..., 0] = on | bg
        out[i, ..., 1] = blob & ~on
    return out
