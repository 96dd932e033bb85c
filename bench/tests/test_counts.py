"""Operations and bytes from the configurations' layer tables."""
import json

import pytest

from harness import counts
from harness.spec import BENCH_DIR


def config(name):
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, ops", [("dvs_cnn_tcn", 126_740_736),
                                       ("cifar10_tnn", 334_460_928)])
def test_required_ops_per_classification(name, ops):
    cfg = config(name)
    assert counts.required_ops(cfg) == ops == cfg["required_ops_per_classification"]


def test_dvs_layers_and_launches():
    walk = counts.layer_walk(config("dvs_cnn_tcn"))
    assert [(l["kind"], l.get("h")) for l in walk] == [
        ("conv2d", 64), ("conv2d", 32), ("conv2d", 16), ("conv2d", 8), ("conv2d", 4),
        ("tcn", None), ("tcn", None), ("tcn", None), ("tcn", None), ("fc", None)]
    assert all(l["pool"] == 2 for l in walk if l["kind"] == "conv2d")
    assert len(counts.kernel_layers(config("dvs_cnn_tcn"))) == 9
    assert len(counts.kernel_layers(config("cifar10_tnn"))) == 8


def test_bytes_of_one_layer():
    stem = counts.layer_walk(config("dvs_cnn_tcn"))[0]
    assert counts.weight_bytes(stem) == 9 * 1 * 64  # 2 channels pack into 1 byte
    assert counts.row_bytes(stem) == 64 * 64 * 2 + 32 * 32 * 64
    tcn = counts.layer_walk(config("dvs_cnn_tcn"))[5]
    assert counts.macs(tcn) == 3 * 96 * 96


def test_least_time_takes_the_larger_bound():
    peaks = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    layer = {"kind": "fc", "c_in": 4, "c_out": 4}
    # 128 ops, and 4 weight bytes + 4 rows x (4 in + 16 out) bytes: memory bound
    assert counts.least_time_s(layer, 4, peaks) == pytest.approx(84 / 1e9)
    fast_memory = {"int8_ops_per_s": 1.0, "hbm_bytes_per_s": 1e12}
    assert counts.least_time_s(layer, 4, fast_memory) == pytest.approx(128.0)


@pytest.mark.parametrize("step_rows, chips, rows", [(32, 4, 8), (8, 1, 8), (256, 1, 256)])
def test_rows_per_launch(step_rows, chips, rows):
    assert counts.rows_per_launch(step_rows, chips) == rows
    with pytest.raises(ValueError):
        counts.rows_per_launch(step_rows + 1, 4)


def test_a_strided_conv_counts_its_kept_pixels_only():
    """A stride-2 3x3 stem and a 1x1 mixer: the stem's output is a quarter
    of its input, and the mixer works on it."""
    cfg = {"input_hw": [16, 16], "layers": [
        {"kind": "conv2d", "c_in": 1, "c_out": 8, "kernel": [3, 3], "stride": 2},
        {"kind": "conv2d", "c_in": 8, "c_out": 8, "kernel": [1, 1]},
        {"kind": "pool", "window": 2},
        {"kind": "fc", "c_in": 128, "c_out": 4}]}
    stem, mixer, fc = counts.layer_walk(cfg)
    assert (stem["conv_h"], stem["out_h"], stem["pool"]) == (8, 8, 0)
    assert (mixer["h"], mixer["out_h"], mixer["pool"]) == (8, 4, 2)
    assert counts.macs(stem) == 8 * 8 * 9 * 1 * 8
    assert counts.macs(mixer) == 8 * 8 * 1 * 8 * 8
    assert counts.required_ops(cfg) == 2 * (4608 + 4096 + 512)
