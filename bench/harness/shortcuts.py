"""The residual configurations' conv launches, split by whether they take
a shortcut.

A layer table's conv2d row names its shortcut's source with a
``shortcut`` key.  The program launches those convs under their own
kernel name, so each half is read by its own metric, mapped onto its own
layers: the plain launches onto the convs without a shortcut, the
residual launches onto those with one.  A residual launch also reads its
shortcut, one int8 trit per output element, which its least bytes count.
"""
from __future__ import annotations

from typing import Dict, List

from harness import counts
from harness import trace as trace_mod


def conv_layers(cfg: dict, residual: bool) -> List[Dict]:
    """The conv2d layers (`counts.layer_walk` rows) with a shortcut, or
    those without one, in launch order."""
    rows = [l for l in cfg["layers"] if l["kind"] == "conv2d"]
    convs = [l for l in counts.layer_walk(cfg) if l["kind"] == "conv2d"]
    return [c for c, row in zip(convs, rows) if ("shortcut" in row) == residual]


def least_time_s(layer: Dict, rows: int, peaks: dict, residual: bool) -> float:
    """`counts.least_time_s`, with the shortcut's read for a residual
    launch: the larger of its operations at the int8 peak and its bytes,
    shortcut included, at HBM bandwidth."""
    if not residual:
        return counts.least_time_s(layer, rows, peaks)
    ops = 2 * counts.macs(layer) * rows
    shortcut = layer["out_h"] * layer["out_w"] * layer["c_out"]
    nbytes = counts.weight_bytes(layer) + rows * (counts.row_bytes(layer) + shortcut)
    return max(ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def roofline_share(run, pattern: str, residual: bool) -> "float | None":
    """Sum of least times over sum of device times of the launches that
    ``pattern`` matches, mapped onto the convs with (``residual``) or
    without a shortcut, one step being one launch per such conv, in
    percent.  None where the trace holds no such launch."""
    if run.trace is None:
        return None
    layers = conv_layers(run.config, residual)
    if not layers:
        return None
    rows = counts.rows_per_launch(run.rows, run.chips)
    least_per_step = sum(least_time_s(l, rows, run.peaks, residual) for l in layers)
    device_s = least_s = 0.0
    for evs in trace_mod.matching(run.trace, pattern).values():
        device_s += sum(d for _, _, d in evs) * 1e-9
        least_s += len(evs) / len(layers) * least_per_step
    if device_s <= 0:
        return None
    return 100.0 * least_s / device_s
