"""Operations and bytes that a configuration's layers require.

Operations and bytes come from the layer table in the configuration's JSON
file, never from the program.  Required work counts the TCN
incrementally: per frame each TCN layer computes one new time step
(``taps * c_in * c_out`` MACs), whatever the program recomputes.  One MAC
is two operations.  Activations and trits are one byte each and packed
weights four trits per byte, so the byte counts are the least that a
kernel fed in the program's types could move.
"""
from __future__ import annotations

from typing import Dict, List

from harness import trace as trace_mod


def _ceil4(n: int) -> int:
    return -(-n // 4)


def layer_walk(cfg: dict) -> List[Dict]:
    """The weight-carrying layers with their geometry, in order: each
    conv2d with its input size, its output stride (``stride``, default 1:
    the output keeps every stride-th row and column) and the window of a
    pool that follows it, each tcn and the fc."""
    h, w = cfg["input_hw"]
    layers = cfg["layers"]
    out: List[Dict] = []
    for i, l in enumerate(layers):
        kind = l["kind"]
        if kind == "conv2d":
            nxt = layers[i + 1] if i + 1 < len(layers) else {}
            pool = nxt.get("window", 0) if nxt.get("kind") == "pool" else 0
            kh, kw = l["kernel"]
            s = l.get("stride", 1)
            ch, cw = -(-h // s), -(-w // s)
            oh, ow = (ch // pool, cw // pool) if pool else (ch, cw)
            out.append(dict(kind="conv2d", h=h, w=w, kh=kh, kw=kw, c_in=l["c_in"],
                            c_out=l["c_out"], stride=s, conv_h=ch, conv_w=cw,
                            pool=pool, out_h=oh, out_w=ow))
            h, w = ch, cw
        elif kind == "pool":
            h, w = h // l["window"], w // l["window"]
        elif kind == "tcn":
            out.append(dict(kind="tcn", taps=l["taps"], c_in=l["c_in"],
                            c_out=l["c_out"], dilation=l["dilation"]))
        elif kind == "fc":
            out.append(dict(kind="fc", c_in=l["c_in"], c_out=l["c_out"]))
    return out


def macs(layer: Dict) -> int:
    """Required MACs of one layer for one classification: a conv's kept
    output pixels only."""
    if layer["kind"] == "conv2d":
        return (layer["conv_h"] * layer["conv_w"] * layer["kh"] * layer["kw"]
                * layer["c_in"] * layer["c_out"])
    if layer["kind"] == "tcn":
        return layer["taps"] * layer["c_in"] * layer["c_out"]
    return layer["c_in"] * layer["c_out"]


def required_ops(cfg: dict) -> int:
    """Required operations per classification (2 per MAC)."""
    return 2 * sum(macs(l) for l in layer_walk(cfg))


def weight_bytes(layer: Dict) -> int:
    """Packed trit bytes of one layer's weights."""
    if layer["kind"] == "conv2d":
        return layer["kh"] * layer["kw"] * _ceil4(layer["c_in"]) * layer["c_out"]
    if layer["kind"] == "tcn":
        return layer["taps"] * _ceil4(layer["c_in"]) * layer["c_out"]
    return _ceil4(layer["c_in"]) * layer["c_out"]


def row_bytes(layer: Dict) -> int:
    """Activation bytes per row (one frame or image) in and out."""
    if layer["kind"] == "conv2d":
        return (layer["h"] * layer["w"] * layer["c_in"]
                + layer["out_h"] * layer["out_w"] * layer["c_out"])
    if layer["kind"] == "tcn":
        return layer["taps"] * layer["c_in"] + layer["c_out"]
    return layer["c_in"] + 4 * layer["c_out"]


def kernel_layers(cfg: dict) -> List[Dict]:
    """The layers that run as one conv kernel launch each: every conv2d
    and every tcn (mapped onto the 2-D conv), in launch order."""
    return [l for l in layer_walk(cfg) if l["kind"] in ("conv2d", "tcn")]


def least_time_s(layer: Dict, rows: int, peaks: dict) -> float:
    """The least time one launch over ``rows`` rows could take: the larger
    of its operations at the int8 peak and its bytes at HBM bandwidth."""
    ops = 2 * macs(layer) * rows
    nbytes = weight_bytes(layer) + rows * row_bytes(layer)
    return max(ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def rows_per_launch(rows: int, chips: int) -> int:
    """Rows (frames or images) one kernel launch computes on one chip: the
    ``rows`` of one step of the timed program (a pool's slots, a batch),
    split over the chips."""
    if rows % chips:
        raise ValueError(f"{rows} rows do not split over {chips} chips")
    return rows // chips


def roofline_share(run, pattern: str) -> "float | None":
    """Sum of least times over sum of device times of the kernel launches
    that ``pattern`` matches, in percent.  Launches are counted per device
    and mapped onto the configuration's kernel layers, one step being one
    launch per layer.  None where the trace holds no such launch."""
    if run.trace is None:
        return None
    layers = kernel_layers(run.config)
    rows = rows_per_launch(run.rows, run.chips)
    least_per_step = sum(least_time_s(l, rows, run.peaks) for l in layers)
    device_s = least_s = 0.0
    for evs in trace_mod.matching(run.trace, pattern).values():
        device_s += sum(d for _, _, d in evs) * 1e-9
        least_s += len(evs) / len(layers) * least_per_step
    if device_s <= 0:
        return None
    return 100.0 * least_s / device_s


def step_mfu(run) -> float:
    """Required operations per classification times classifications per
    second, over the chips' int8 peak, in percent."""
    rate = run.window.completed / run.window.seconds
    return 100.0 * run.config["required_ops_per_classification"] * rate / (
        run.chips * run.peaks["int8_ops_per_s"])
