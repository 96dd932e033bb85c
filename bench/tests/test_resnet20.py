"""The ternary ResNet-20 configuration: its layer table against the
program, its required work, its plain reference against the program at
test size, faults planted in the shortcut, and the two readers that split
its conv launches."""
import json
import types

import jax.numpy as jnp
import pytest

import support
from harness import counts, program, shortcuts
from harness import trace as tr
from harness.spec import BENCH_DIR, load_module

CFG = json.loads((BENCH_DIR / "configs" / "resnet20_tnn.json").read_text())
SMOKE = json.loads((BENCH_DIR / "tests" / "data" / "resnet20_tnn_smoke.json").read_text())


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The test-size benchmark plus a ResNet-20 cell at test size and one at
    the published widths, both under the small image traffic."""
    root = tmp_path_factory.mktemp("bench")
    support.make_bench(root)
    smoke = dict(SMOKE, limits=CFG["limits"])
    (root / "bench" / "tests" / "data" / "resnet20_tnn_smoke.json").write_text(
        json.dumps(smoke))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"] += [
        {"name": "resnet20_tnn", "source": CFG["source"],
         "file": "bench/configs/resnet20_tnn.json", "reduced": [], "why": "test"},
        {"name": "resnet20_tnn_smoke", "source": SMOKE["source"],
         "file": "bench/tests/data/resnet20_tnn_smoke.json", "reduced": [], "why": "test"}]
    doc["workloads"] += [
        {"name": "resnet_small", "config": "resnet20_tnn_smoke", "traffic": "images_small",
         "chips": 1, "why": "test"},
        {"name": "resnet_full", "config": "resnet20_tnn", "traffic": "images_small",
         "chips": 1, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    from harness import spec

    return spec.Bench(root)


def test_configuration_matches_the_registry_graph():
    from repro import api

    program.check_graph(api.get_graph(CFG["registry_net"]), CFG)
    program.check_graph(api.get_graph(SMOKE["registry_net"]), SMOKE)


def test_required_ops_per_classification():
    assert counts.required_ops(CFG) == 81_102_080 == CFG["required_ops_per_classification"]
    assert counts.required_ops(SMOKE) == SMOKE["required_ops_per_classification"]


def test_the_layer_table_names_the_shortcuts_of_the_graph():
    from repro import api

    graph = api.get_graph(CFG["registry_net"])
    assert [row.get("shortcut") for row in CFG["layers"]] == [l.shortcut for l in graph.layers]
    residual = shortcuts.conv_layers(CFG, residual=True)
    assert len(residual) == 9 and len(shortcuts.conv_layers(CFG, residual=False)) == 10
    assert 2 * sum(counts.macs(l) for l in residual) / CFG["required_ops_per_classification"] \
        == pytest.approx(0.5236, abs=1e-4)


@pytest.mark.parametrize("cell", ["resnet_small", "resnet_full"])
def test_the_reference_agrees_with_the_program(bench, cell):
    r = support.run(bench, cell)
    assert r["correct"], r["checks"]
    assert r["checks"]["logit_err"]["value"] == 0.0
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["window"]["compiles"] == 0


def test_the_control_fails_the_limit(bench):
    r = support.run(bench, "resnet_small", control=True)
    assert r["control"]["logit_err"] > CFG["limits"]["logit_err"]


def _dropped(a, shape):
    return jnp.zeros(shape, a.dtype)


def _prepended(a, shape):
    s = a.shape[1] // shape[1]
    a = a[:, ::s, ::s, :]
    return jnp.pad(a, ((0, 0),) * 3 + ((shape[-1] - a.shape[-1], 0),))


@pytest.mark.parametrize("fault", ["dropped", "after_threshold", "zeros_prepended"])
def test_a_planted_shortcut_fault_is_not_correct(bench, monkeypatch, fault):
    """The shortcut left out, added to the ternary output instead of before
    the threshold, or padded with its zero channels in front."""
    import repro.api.program as prog

    if fault == "dropped":
        monkeypatch.setattr(prog, "shortcut_map", _dropped)
    elif fault == "zeros_prepended":
        monkeypatch.setattr(prog, "shortcut_map", _prepended)
    else:
        dispatch = prog._dispatch_conv

        def after(x, packed, eff, backend, *, residual=None, **kw):
            t = dispatch(x, packed, eff, backend, **kw)
            if residual is None:
                return t
            return jnp.clip(t + residual, -1, 1).astype(t.dtype)

        monkeypatch.setattr(prog, "_dispatch_conv", after)
    r = support.run(bench, "resnet_small")
    assert not r["correct"]
    assert r["checks"]["logit_err"]["value"] > 0


RES = "%ternary_conv2d_residual_pallas.{} = s8[256,32,32,16] custom-call(s8[256,34,34,16] %p)"
PLAIN = "%ternary_conv2d_pallas.{} = s8[256,32,32,16] custom-call(f32[256,34,34,4] %p)"
PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def _run(ops):
    return types.SimpleNamespace(
        trace=tr.DeviceTrace(lo_ns=0, hi_ns=1e9, ops=ops, host=[]), config=CFG,
        rows=256, chips=1, peaks=PEAKS)


def _step(scale_plain: float, scale_res: float):
    """One step's launches, each lasting its layer's least time times a
    factor: 10 plain and 9 residual."""
    evs, t = [], 0.0
    for residual, scale, name in ((False, scale_plain, PLAIN), (True, scale_res, RES)):
        for i, l in enumerate(shortcuts.conv_layers(CFG, residual)):
            d = shortcuts.least_time_s(l, 256, PEAKS, residual) * 1e9 * scale
            evs.append((name.format(i + 1), t, d))
            t += d
    return evs


def test_the_readers_split_the_launches():
    residual = load_module(BENCH_DIR / "metrics" / "residual_conv2d_roofline.resnet20.py")
    plain = load_module(BENCH_DIR / "metrics" / "ternary_conv2d_roofline.resnet20.py")
    run = _run({0: _step(5.0, 4.0) + _step(5.0, 4.0)})
    assert residual.read(run) == pytest.approx(25.0)
    assert plain.read(run) == pytest.approx(20.0)
    only_plain = _run({0: [e for e in _step(5.0, 4.0) if "residual" not in e[0]]})
    assert residual.read(only_plain) is None
    assert plain.read(only_plain) == pytest.approx(20.0)
    assert residual.read(types.SimpleNamespace(trace=None)) is None


def test_a_residual_launch_counts_its_shortcut_read():
    slow_memory = {"int8_ops_per_s": 1e30, "hbm_bytes_per_s": 1.0}
    layer = shortcuts.conv_layers(CFG, residual=True)[0]
    extra = (shortcuts.least_time_s(layer, 4, slow_memory, True)
             - counts.least_time_s(layer, 4, slow_memory))
    assert extra == 4 * 32 * 32 * 16
