"""Seeded ternary images, generated on the host as the traffic sends them.

Copied from the program's synthetic pipeline (``repro.data.pipeline``
``CifarLikePipeline``) and vectorised, so that a later change to the
program's data code cannot move the yardstick.  Pixels are trits, exact
in any float type.
"""
from __future__ import annotations

import numpy as np


def generate(rng: np.random.Generator, lead, cfg: dict, *, noise: float,
             cut: float) -> np.ndarray:
    """``[*lead, h, w, c]`` float32 trits: a class prototype plus Gaussian
    noise, ternarised at ``cut``."""
    shape = (*cfg["input_hw"], cfg["input_ch"])
    n = int(np.prod(lead))
    protos = rng.standard_normal((cfg["n_classes"], *shape), np.float32)
    labels = rng.integers(0, cfg["n_classes"], size=n)
    x = protos[labels] + noise * rng.standard_normal((n, *shape), np.float32)
    return (np.sign(x) * (np.abs(x) > cut)).astype(np.float32).reshape(*lead, *shape)
