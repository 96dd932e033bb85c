"""The benchmark's side of the program boundary: its deploy tables and the
check of the program's graph against the configuration's layer table."""
import copy
import json

import numpy as np
import pytest

from harness import program, weights
from harness.spec import BENCH_DIR

SMOKE = json.loads((BENCH_DIR / "tests" / "data" / "dvs_cnn_tcn_smoke.json").read_text())


def test_folded_bn_divides_by_exactly_one():
    assert np.float32(program.BN_SD_FOLDED) + np.float32(1e-6) == np.float32(1.0)


@pytest.mark.parametrize("name", ["dvs_cnn_tcn", "cifar10_tnn"])
def test_configuration_matches_the_registry_graph(name):
    from repro import api

    cfg = json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())
    program.check_graph(api.get_graph(cfg["registry_net"]), cfg)


@pytest.mark.parametrize("edit", ["channels", "dilation", "kind", "ring", "stride"])
def test_a_mismatched_layer_table_is_refused(edit):
    from repro import api

    cfg = copy.deepcopy(SMOKE)
    if edit == "channels":
        cfg["layers"][0]["c_out"] += 1
    elif edit == "dilation":
        next(l for l in cfg["layers"] if l["kind"] == "tcn")["dilation"] = 3
    elif edit == "stride":
        cfg["layers"][0]["stride"] = 2
    elif edit == "kind":
        cfg["layers"][1]["kind"] = "global_pool"
    else:
        cfg["tcn_steps"] += 1
    with pytest.raises(program.GraphMismatch):
        program.check_graph(api.get_graph(cfg["registry_net"]), cfg)


def test_weights_are_seeded_trits_and_reach_the_program_unchanged():
    from repro.core.ternary import unpack_ternary

    a, b = weights.make(5, SMOKE), weights.make(5, SMOKE)
    c = weights.make(6, SMOKE)
    t0 = np.asarray(a["conv"][0]["t"])
    assert np.array_equal(t0, np.asarray(b["conv"][0]["t"]))
    assert not np.array_equal(t0, np.asarray(c["conv"][0]["t"]))
    assert set(np.unique(t0)) <= {-1, 0, 1}
    prog = program.deploy(SMOKE, a)
    packed = prog.tables["conv"][0]["packed"]
    back = np.asarray(unpack_ternary(packed, axis=2))[:, :, : t0.shape[2]]
    assert np.array_equal(back, t0)
    assert np.array_equal(np.asarray(prog.tables["conv"][0]["scale"]),
                          np.asarray(a["conv"][0]["scale"]))
