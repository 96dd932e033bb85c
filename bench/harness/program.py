"""The one place the benchmark touches the system under test.

It deploys the benchmark's seeded trits and scales as the program's packed
tables and checks the program's graph against the configuration's own
layer table.  Nothing here computes a result the benchmark compares: that
is the plain reference's job.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

# The program folds each layer's scale as scale / (bn_sd + 1e-6).  A BN
# standard deviation of 1 - 1e-6 makes that divisor exactly 1.0 in float32,
# so the program's per-channel scale is the benchmark's, bit for bit.
BN_SD_FOLDED = np.float32(1.0) - np.float32(1e-6)

# The keys of each kind of layer that the program's graph must match, with
# the value a layer table may leave out (None: it has to state it).
_LAYER_KEYS = {
    "conv2d": {"c_in": None, "c_out": None, "kernel": None, "stride": 1},
    "pool": {"window": None},
    "tcn": {"c_in": None, "c_out": None, "taps": None, "dilation": None},
    "fc": {"c_in": None, "c_out": None},
}


class GraphMismatch(ValueError):
    """The program's graph is not the configuration's published layers."""


def check_graph(graph, cfg: dict) -> None:
    """The program's registry graph must match the configuration's layer
    table, layer by layer, and its input, classes, ring and threshold."""
    want = {
        "input_hw": tuple(cfg["input_hw"]), "input_ch": cfg["input_ch"],
        "n_classes": cfg["n_classes"], "act_threshold": cfg["act_threshold"],
    }
    if "tcn_steps" in cfg:
        want["tcn_steps"] = cfg["tcn_steps"]
    for key, value in want.items():
        got = getattr(graph, key)
        if got != value:
            raise GraphMismatch(f"{graph.name}: {key} is {got}, configuration says {value}")
    if len(graph.layers) != len(cfg["layers"]):
        raise GraphMismatch(f"{graph.name}: {len(graph.layers)} layers, "
                            f"configuration lists {len(cfg['layers'])}")
    for i, (spec, row) in enumerate(zip(graph.layers, cfg["layers"])):
        if spec.kind != row["kind"]:
            raise GraphMismatch(f"{graph.name} layer {i}: {spec.kind} != {row['kind']}")
        for key, default in _LAYER_KEYS.get(row["kind"], {}).items():
            got = getattr(spec, key, default)
            value = row[key] if default is None else row.get(key, default)
            value = tuple(value) if key == "kernel" else value
            if got != value:
                raise GraphMismatch(f"{graph.name} layer {i} ({spec.kind}): "
                                    f"{key} is {got}, configuration says {value}")


def deploy(cfg: dict, weights: Dict):
    """The program's `DeployedProgram` over the benchmark's weights: trits
    packed by the program's own packing and §4 TCN projection, the scales
    passed through unchanged, thresholds from the configuration."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.api.program import DeployedProgram
    from repro.core.tcn import project_weights_to_2d
    from repro.core.ternary import pack_ternary

    graph = api.get_graph(cfg["registry_net"])
    check_graph(graph, cfg)
    thr = float(cfg["act_threshold"])
    pools = graph.conv_pool_plan()
    tcn_specs = [l for l in graph.layers if l.kind == "tcn"]

    def packed(t):
        pad = (-t.shape[2]) % 4
        return pack_ternary(jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))), axis=2)

    def folded(scale):
        return jnp.full(scale.shape, BN_SD_FOLDED, jnp.float32)

    @jax.jit
    def tables(w):
        out = {"conv": [], "tcn": [], "fc": {}}
        for e in w["conv"]:
            out["conv"].append({"packed": packed(e["t"]), "scale": e["scale"],
                                "bn_sd": folded(e["scale"])})
        for e, spec in zip(w["tcn"], tcn_specs):
            k2d = project_weights_to_2d(e["t"], kh=spec.kernel[0], kw=spec.kernel[1])
            out["tcn"].append({"packed": packed(k2d), "scale": e["scale"],
                               "bn_sd": folded(e["scale"])})
        (fc,) = w["fc"]
        out["fc"] = {"t": fc["t"], "scale": fc["scale"]}
        return out

    t = jax.block_until_ready(tables(weights))
    for i, e in enumerate(t["conv"]):
        e.update(threshold=thr, pool=pools[i])
    for e, spec in zip(t["tcn"], tcn_specs):
        e.update(threshold=thr, dilation=spec.dilation)
    return DeployedProgram(graph, t)
