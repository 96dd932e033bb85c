"""Everything a cell needs is a file found by the names in BENCHMARK.json,
and the file keeps to the benchmark's contract."""
import json
import re

import pytest

from harness import spec

BENCH = spec.Bench()
DOC = BENCH.doc
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in DOC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    w = BENCH.cell(cell)
    cfg = BENCH.config(w["config"])
    assert cfg["name"] == w["config"]
    assert callable(BENCH.config_module(w["config"]).reference)
    traffic = BENCH.traffic(w["traffic"])
    driver = BENCH.driver(traffic["driver"])
    assert callable(driver.Driver) and callable(driver.lower)
    assert callable(BENCH.inputs(traffic["inputs"]["kind"]).generate)
    for group in ("end_to_end", "per_layer"):
        for m in BENCH.metrics_for(cell, group):
            assert callable(BENCH.reader(m["name"]).read)


def test_every_cell_reports_setup_another_e2e_and_a_per_layer_metric():
    for cell in CELLS:
        e2e = {m["name"] for m in BENCH.metrics_for(cell, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = BENCH.metrics_for(cell, "per_layer")
        assert per_layer and all(m["moves"] in e2e for m in per_layer)


def test_names_units_and_keys():
    assert DOC["command"] == ["python3", "bench/run.py"]
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in DOC[g]]
    names += CELLS + [c["name"] for c in DOC["configs"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in DOC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.25
    for m in DOC["per_layer"]:
        assert m["source"] in ("host_clock", "device_trace", "program_span", "program_counter")
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert len(json.dumps(DOC)) < 64 * 1024


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        BENCH.cell("no_such_cell")
    with pytest.raises(spec.SpecError):
        BENCH.traffic("no_such_traffic")
    with pytest.raises(spec.SpecError):
        BENCH.reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        BENCH.driver("no_such_driver")
    with pytest.raises(spec.SpecError):
        BENCH.inputs("no_such_generator")


def test_a_split_metric_falls_back_to_its_shared_reader():
    """``step_mfu.cifar`` and ``step_mfu.dvs`` read through ``step_mfu.py``."""
    assert BENCH.reader("step_mfu.cifar").__file__.endswith("step_mfu.py")
    assert BENCH.reader("tick_self_ms.dvs").__file__.endswith("tick_self_ms.dvs.py")


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell, a mix with a driver and an input generator of its own, and
    a metric, added as files plus entries, with no edit to an existing
    file, are found and read."""
    import shutil

    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench")
    doc = json.loads(json.dumps(DOC))
    (tmp_path / "bench" / "traffic" / "events_pool16.json").write_text(json.dumps(
        dict(BENCH.traffic("events_pool8"), pool=16, driver="open_loop",
             inputs={"kind": "spectrograms", "bins": 40})))
    (tmp_path / "bench" / "drivers" / "open_loop.py").write_text(
        "class Driver:\n    pass\n\ndef lower(*a):\n    return None\n")
    (tmp_path / "bench" / "inputs" / "spectrograms.py").write_text(
        "def generate(rng, lead, cfg, *, bins):\n    return (lead, bins)\n")
    (tmp_path / "bench" / "metrics" / "ticks.dvs.py").write_text(
        "def read(run):\n    return run.window.rounds\n")
    doc["workloads"].append({"name": "dvs_pool16", "config": "dvs_cnn_tcn",
                             "traffic": "events_pool16", "chips": 1, "why": "w"})
    doc["per_layer"].append({"name": "ticks.dvs", "unit": "ticks", "better": "higher",
                             "source": "host_clock", "layer": "serving policy",
                             "moves": "frames_per_s", "workloads": ["dvs_pool16"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    b = spec.Bench(tmp_path)
    traffic = b.traffic(b.cell("dvs_pool16")["traffic"])
    assert traffic["pool"] == 16
    assert b.driver(traffic["driver"]).lower() is None
    assert b.inputs(traffic["inputs"]["kind"]).generate(None, (2, 3), {}, bins=40) == ((2, 3), 40)
    assert "ticks.dvs" in [m["name"] for m in b.metrics_for("dvs_pool16", "per_layer")]
    assert b.reader("ticks.dvs").read(type("R", (), {"window": type("W", (), {"rounds": 3})})) == 3
