"""Seeded ternary weights, made on the device by the benchmark.

The benchmark, not the program, makes the weights: seeded trits and
per-channel scales for every weight-carrying layer of the configuration's
layer table.  The plain reference takes them as they are; the program gets
them through its deploy tables (`harness.program.deploy`).  The sizes and
densities come from the configuration file's ``layers`` and ``assumed``.
"""
from __future__ import annotations

import json
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness import counts


def _trits(key, shape, density):
    u = jax.random.uniform(key, shape)
    return jnp.where(u < density / 2, -1, jnp.where(u < density, 1, 0)).astype(jnp.int8)


def _make(key, cfg_json: str) -> Dict:
    cfg = json.loads(cfg_json)
    assumed = cfg["assumed"]
    dw = assumed["weight_density"]
    lo, hi = assumed["scale_spread"]
    layers = counts.layer_walk(cfg)
    keys = jax.random.split(key, 2 * len(layers))
    out: Dict[str, List] = {"conv": [], "tcn": [], "fc": []}
    for i, l in enumerate(layers):
        kt, ks = keys[2 * i], keys[2 * i + 1]
        if l["kind"] == "conv2d":
            shape = (l["kh"], l["kw"], l["c_in"], l["c_out"])
            fan_in = l["kh"] * l["kw"] * l["c_in"]
        elif l["kind"] == "tcn":
            shape = (l["taps"], l["c_in"], l["c_out"])
            fan_in = l["taps"] * l["c_in"]
        else:
            shape = (l["c_in"], l["c_out"])
            fan_in = l["c_in"]
        dens = assumed["input_density"]
        d_in = dens[i] if i < len(dens) else dens[-1]
        gain = jax.random.uniform(ks, (l["c_out"],), jnp.float32, lo, hi)
        scale = gain / np.float32(np.sqrt(fan_in * dw * d_in))
        out[{"conv2d": "conv", "tcn": "tcn", "fc": "fc"}[l["kind"]]].append(
            {"t": _trits(kt, shape, dw), "scale": scale.astype(jnp.float32)})
    return out


_MAKE = jax.jit(_make, static_argnums=1)


def make(seed32: int, cfg: dict) -> Dict:
    """Every layer's trits (int8) and per-channel scales (float32), from
    the seed, in one jitted call on the default device."""
    w = _MAKE(jax.random.PRNGKey(seed32), json.dumps(cfg, sort_keys=True))
    return jax.block_until_ready(w)
