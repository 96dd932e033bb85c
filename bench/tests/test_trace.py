"""The trace reduction on a small synthetic trace."""
import types

import pytest

from harness import counts
from harness import trace as tr

CONV = "%ternary_conv2d_pallas.{} = s8[8,32,32,64] custom-call(f32[8,66,66,4] %pad.1)"
SLICE = "%slice.3 = f32[12] slice(f32[8,12] %p)"


def synthetic():
    # window 0..1000 ns; device 0 busy 100-300 (two overlapping ops) and
    # 600-700; device 1 busy 0-500, with an op that spills past the window
    ops = {
        0: [(CONV.format(1), 100, 150), (SLICE, 200, 100), (CONV.format(2), 600, 100)],
        1: [(CONV.format(1), 0, 500), (SLICE, 900, 300)],
    }
    host = [("bench.tick", 0, 500), ("PjitFunction(_step)", 50, 100),
            ("bench.fetch", 500, 500)]
    return tr.DeviceTrace(lo_ns=0, hi_ns=1000, ops=ops, host=host)


def test_busy_is_the_union_clipped_to_the_window():
    t = synthetic()
    assert tr.merged(t.ops[0], 0, 1000) == [(100, 300), (600, 700)]
    assert tr.busy_s(t, 0) == pytest.approx(300e-9)
    assert tr.busy_s(t, 1) == pytest.approx(600e-9)
    assert tr.mean_busy_s(t) == pytest.approx(450e-9)
    assert tr.idle_share(t) == pytest.approx(1 - 450 / 1000)


def test_kernel_matching_by_hlo_name():
    t = synthetic()
    hits = tr.matching(t, r"^ternary_conv2d_pallas(\.\d+)?$")
    assert [len(hits[d]) for d in (0, 1)] == [2, 1]
    assert tr.op_name(CONV.format(7)) == "ternary_conv2d_pallas.7"
    assert tr.op_kind(CONV.format(7)) == "ternary_conv2d_pallas"
    assert not tr.matching(t, r"^no_such_kernel$")[0]


def test_top_ops_and_idle_gaps():
    t = synthetic()
    top = dict(tr.top_ops(t))
    assert top["ternary_conv2d_pallas"] == pytest.approx((250 + 500) * 1e-9 / 2)
    gaps = dict(tr.idle_gaps(t))
    # device 0 idles 0-100 (middle in tick > step), 300-600 (middle in
    # tick) and 700-1000 (fetch); device 1 idles 500-900 (fetch)
    assert gaps["bench.tick > PjitFunction(_step)"] == pytest.approx(100e-9 / 2)
    assert gaps["bench.tick"] == pytest.approx(300e-9 / 2)
    assert gaps["bench.fetch"] == pytest.approx((300 + 400) * 1e-9 / 2)
    assert sum(gaps.values()) * 2 == pytest.approx(2000e-9 - 900e-9)


def test_roofline_share_from_the_layer_table():
    cfg = {"input_hw": [4, 4], "layers": [{"kind": "conv2d", "c_in": 4, "c_out": 4,
                                            "kernel": [3, 3]}]}
    peaks = {"int8_ops_per_s": 1e9, "hbm_bytes_per_s": 1e12}
    least = counts.least_time_s(counts.kernel_layers(cfg)[0], 2, peaks)
    t = tr.DeviceTrace(0, 1e9, {0: [(CONV.format(1), 0, least * 1e9 * 4),
                                    (CONV.format(2), 0, least * 1e9 * 4)]}, [])
    run = types.SimpleNamespace(trace=t, config=cfg, traffic={"batch": 2}, rows=2, chips=1, peaks=peaks)
    assert counts.roofline_share(run, r"^ternary_conv2d_pallas") == pytest.approx(25.0)
    assert counts.roofline_share(run, r"^absent$") is None
    run.trace = None
    assert counts.roofline_share(run, r"^ternary_conv2d_pallas") is None


def test_a_recorded_tpu_trace(tmp_path):
    """One dvs_pool8 tick recorded on a TPU v5e: the conv kernel's nine
    launches (five convs, four TCN layers) are found and the device is busy
    for part of the window."""
    import shutil

    from harness.spec import BENCH_DIR

    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    shutil.copy(BENCH_DIR / "tests" / "data" / "pool8_tick.xplane.pb",
                run_dir / "host.xplane.pb")
    start = 1792191070448497837  # the recording's profile_start_time
    whole = tr.load(str(tmp_path), start, 1e9)
    (dev,) = whole.ops
    first = min(s for _, s, _ in whole.ops[dev])
    last = max(s + d for _, s, d in whole.ops[dev])
    t = tr.load(str(tmp_path), start + int(first), (last - first) * 1e-9)
    assert t.lo_ns == first
    assert len(tr.matching(t, r"^ternary_conv2d_pallas(\.\d+)?$")[dev]) == 9
    assert 0 < tr.busy_s(t, dev) <= t.window_s
    assert 0 < tr.idle_share(t) < 1
    assert tr.top_ops(t)[0][0] == "ternary_conv2d_pallas"
