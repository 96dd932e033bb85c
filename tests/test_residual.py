"""Residual shortcuts: the kernel epilogue, the graph rules, ResNet-20 on
every interpreter, the ``.cutie`` round trip and the second live map's
pricing.

A conv2d with ``shortcut = k`` computes

    y_i = scale_i * conv(a_{i-1}, T_i) + S(a_k),    a_i = ternarize(y_i)

with ``S`` the identity or option A (every 2nd row and column, zero
channels appended).  Each test here fails when the shortcut is dropped,
added after the threshold, or padded on the wrong side.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, artifact
from repro.api.program import CutieProgram, DeployedProgram, shortcut_map
from repro.artifact.format import canonical_json
from repro.core.ternary import pack_ternary
from repro.kernels import ternary_conv2d
from repro.kernels.ref import ternary_conv2d_ref
from repro.sim import SimParams
from repro.sim.counters import count_plan
from repro.sim.memory import FeatureMemory, fmap_bytes
from repro.sim.plan import lower

import resnet_plain


def _trits(rng, shape):
    return jnp.asarray(rng.randint(-1, 2, shape).astype(np.int8))


# ---------------------------------------------------------------------------
# The kernel epilogue
# ---------------------------------------------------------------------------

# (saved map a_k [B, H', W', C'], conv output [B, H, W, C_out], block_cout,
#  per-channel threshold)
EPILOGUE_CASES = {
    "identity": ((2, 8, 8, 16), (2, 8, 8, 16), None, False),
    "option_a": ((2, 8, 8, 8), (2, 4, 4, 16), None, False),
    "block_below_cout": ((2, 8, 8, 8), (2, 4, 4, 16), 8, False),
    "per_channel_threshold": ((2, 8, 8, 16), (2, 8, 8, 16), 8, True),
}


@pytest.mark.parametrize("impl", ["native", "interpret"])
@pytest.mark.parametrize("case", list(EPILOGUE_CASES))
def test_residual_epilogue_matches_ref(case, impl):
    """conv + scale + shortcut, then the threshold: both kernel impls equal
    the oracle bit for bit, and the shortcut changes the answer."""
    a_shape, out_shape, block, per_channel = EPILOGUE_CASES[case]
    rng = np.random.RandomState(31)
    b, h, w, c_out = out_shape
    x = _trits(rng, (b, h, w, 12))
    wp = pack_ternary(_trits(rng, (3, 3, 12, c_out)), axis=2)
    scale = jnp.asarray(rng.uniform(0.1, 0.25, c_out).astype(np.float32))
    thr = (jnp.asarray(rng.uniform(0.3, 0.7, c_out).astype(np.float32))
           if per_channel else 0.5)
    res = shortcut_map(_trits(rng, a_shape), out_shape)
    assert res.shape == out_shape and res.dtype == jnp.int8
    kw = dict(fuse_ternary=True, threshold=thr, out_dtype=jnp.int8)
    got = ternary_conv2d(x, wp, scale, impl=impl, block_cout=block,
                         residual=res, **kw)
    want = ternary_conv2d_ref(x, wp, scale, residual=res, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    plain = ternary_conv2d_ref(x, wp, scale, **kw)
    assert (np.asarray(want) != np.asarray(plain)).mean() > 0.05


def test_option_a_keeps_top_left_and_appends_zero_channels():
    a = jnp.arange(2 * 4 * 4 * 3, dtype=jnp.float32).reshape(2, 4, 4, 3)
    s = np.asarray(shortcut_map(a, (2, 2, 2, 5)))
    np.testing.assert_array_equal(s[..., :3], np.asarray(a)[:, ::2, ::2, :])
    assert not s[..., 3:].any()
    np.testing.assert_array_equal(np.asarray(shortcut_map(a, a.shape)), np.asarray(a))


# ---------------------------------------------------------------------------
# The graph rules
# ---------------------------------------------------------------------------

def _graph(*layers, hw=(8, 8)):
    return api.CutieGraph(name="g", layers=tuple(layers), input_hw=hw,
                          input_ch=4, n_classes=3)


REFUSED = {
    "source_not_a_conv": (api.conv2d(4, 8), api.pool(), api.conv2d(8, 8, shortcut=1)),
    "source_not_earlier": (api.conv2d(4, 8), api.conv2d(8, 8, shortcut=1)),
    "strided_consumer": (api.conv2d(4, 8), api.conv2d(8, 8, stride=2, shortcut=0)),
    "pool_after_consumer": (api.conv2d(4, 8), api.conv2d(8, 8, shortcut=0), api.pool()),
    "pool_after_source": (api.conv2d(4, 8), api.pool(), api.conv2d(8, 8),
                          api.conv2d(8, 8, shortcut=0)),
    "source_wider": (api.conv2d(4, 8), api.conv2d(8, 4), api.conv2d(4, 4, shortcut=0)),
    "channels_short": (api.conv2d(4, 4), api.conv2d(4, 8), api.conv2d(8, 8, shortcut=0)),
    "map_four_times": (api.conv2d(4, 4), api.conv2d(4, 8, stride=2),
                       api.conv2d(8, 8, stride=2), api.conv2d(8, 8, shortcut=0)),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_validate_refuses_bad_shortcut(case):
    width = next(l.c_out for l in reversed(REFUSED[case]) if l.kind == "conv2d")
    layers = REFUSED[case] + (api.global_pool(), api.fc(width, 3))
    with pytest.raises(ValueError, match="shortcut"):
        _graph(*layers).validate()


def test_resnet20_graph_shape():
    g = api.get_graph("resnet20_tnn")
    convs = [l for l in g.layers if l.kind == "conv2d"]
    assert len(g.layers) == 21 and len(convs) == 19
    assert g.shortcut_sources == (0, 2, 4, 6, 8, 10, 12, 14, 16)
    assert [l.shortcut for l in g.layers if l.shortcut is not None] == \
        [0, 2, 4, 6, 8, 10, 12, 14, 16]
    assert [i for i, l in enumerate(g.layers) if l.stride == 2] == [7, 13]
    macs = sum(lp.macs for lp in lower(g).layers)
    assert 2 * macs == 81_102_080
    residual = sum(lp.macs for lp in lower(g).layers if lp.shortcut is not None)
    assert residual == 21_233_664


# ---------------------------------------------------------------------------
# ResNet-20 through every interpreter
# ---------------------------------------------------------------------------

def _seeded_program(name="resnet20_tnn_smoke", seed=5):
    """Seeded trits and per-channel scales as a `DeployedProgram` whose
    effective scales are those scales exactly (BN sd folded to 1)."""
    g = api.get_graph(name)
    weights = resnet_plain.seeded_weights(g, seed)
    one = np.float32(1.0) - np.float32(1e-6)
    tables = {"conv": [], "tcn": [], "fc": {}}
    pools = g.conv_pool_plan()
    for i, (t, s) in enumerate(weights["conv"]):
        pad = (-t.shape[2]) % 4
        tables["conv"].append({
            "packed": pack_ternary(jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))), axis=2),
            "scale": s, "bn_sd": jnp.full(s.shape, one), "threshold": g.act_threshold,
            "pool": pools[i]})
    t, s = weights["fc"]
    tables["fc"] = {"t": t, "scale": s}
    return DeployedProgram(g, tables), weights


def test_resnet20_smoke_bit_exact_on_every_interpreter():
    """fused == ref == bitsim == the plain reference, bit for bit, with the
    shortcut layers' activations neither dead nor saturated."""
    dep, weights = _seeded_program()
    g = dep.graph
    x = _trits(np.random.RandomState(9), (6, *g.input_hw, g.input_ch)).astype(jnp.float32)
    want, maps = resnet_plain.forward(g, weights, x)
    for be in ("fused", "ref", "bitsim"):
        np.testing.assert_array_equal(np.asarray(dep.forward(x, backend=be)),
                                      np.asarray(want), err_msg=be)
    for i in g.shortcut_sources[1:] + (len(g.layers) - 3,):
        density = float((maps[i] != 0).mean())
        assert 0.05 < density < 0.95, (i, density)


def test_qat_forward_honours_the_shortcut():
    """On the per-channel grid, the QAT forward and the calibrated deploy
    agree to float round-off, shortcuts included."""
    graph = dataclasses.replace(api.get_graph("resnet20_tnn_smoke"), qat_per_channel=True)
    prog = CutieProgram(graph)
    p = prog.init(jax.random.PRNGKey(3))
    x = jnp.sign(jax.random.normal(jax.random.PRNGKey(4), (4, *graph.input_hw, 3)))
    qat = prog.forward_qat(p, x)
    dep = prog.quantize(p, calib=x).forward(x, backend="ref")
    np.testing.assert_allclose(np.asarray(qat), np.asarray(dep), rtol=1e-4, atol=1e-4)
    no_shortcut = dataclasses.replace(graph, layers=tuple(
        dataclasses.replace(l, shortcut=None) for l in graph.layers))
    other = CutieProgram(no_shortcut).forward_qat(p, x)
    assert not np.allclose(np.asarray(qat), np.asarray(other), atol=1e-4)


# ---------------------------------------------------------------------------
# The artifact
# ---------------------------------------------------------------------------

def test_artifact_round_trips_the_shortcut():
    dep, _ = _seeded_program()
    data = dep.to_artifact_bytes()
    loaded = artifact.loads(data)
    assert [lp.shortcut for lp in loaded.plan.layers] == \
        [lp.shortcut for lp in lower(dep.graph).layers]
    assert loaded.to_bytes() == data
    x = _trits(np.random.RandomState(2), (2, *dep.graph.input_hw, 3)).astype(jnp.float32)
    for be in ("fused", "bitsim"):
        np.testing.assert_array_equal(np.asarray(loaded.forward(x, backend=be)),
                                      np.asarray(dep.forward(x, backend="ref")))


def test_v2_payload_still_loads():
    """A v2 artifact (no ``shortcut`` key in PLAN) loads on the v3 reader
    with every shortcut none."""
    prog = api.get_net("cifar10_tnn_smoke")
    dep = prog.quantize(prog.init(jax.random.PRNGKey(0)))
    new = dep.to_artifact_bytes()
    lines = []
    for ln in artifact.disassemble(new).splitlines():
        if ln.strip().startswith("version"):
            lines.append("version 2")
        elif ln.strip().startswith("json") and '"shortcut"' in ln:
            pad, body = ln.split("json ", 1)
            obj = json.loads(body)
            for lp in obj.get("layers", ()):
                lp.pop("shortcut")
            lines.append(pad + "json " + canonical_json(obj).decode())
        else:
            lines.append(ln)
    v2 = artifact.reassemble("\n".join(lines))
    assert v2 != new
    loaded = artifact.loads(v2)
    assert all(lp.shortcut is None for lp in loaded.plan.layers)
    x = jnp.sign(jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3)))
    np.testing.assert_array_equal(np.asarray(loaded.forward(x, backend="bitsim")),
                                  np.asarray(dep.forward(x, backend="ref")))


# ---------------------------------------------------------------------------
# The second live map in the feature memory
# ---------------------------------------------------------------------------

def test_feature_memory_prices_the_saved_map():
    """Each consumer reads its shortcut once per output pixel and holds the
    saved map beside its input: a bank that fits the input alone but not
    both stalls exactly the consumers."""
    plan = lower(api.get_graph("resnet20_tnn_smoke"))
    fmem = FeatureMemory(max_cin=96)
    resident = fmem.resident_bytes(plan)
    consumers = {lp.index for lp in plan.layers if lp.shortcut is not None}
    assert set(resident) == consumers
    by_index = {lp.index: lp for lp in plan.layers}
    for i in consumers:
        lp, src = by_index[i], by_index[by_index[i].shortcut]
        oh, ow = fmem.out_hw(src)
        assert resident[i] == fmap_bytes(oh, ow, src.c_out)
        plain = dataclasses.replace(lp, shortcut=None)
        assert (fmem.layer_traffic(lp)["reads"]
                == fmem.layer_traffic(plain)["reads"] + lp.out_pixels)
    stem = by_index[0]
    params = SimParams(fmap_bank_bytes=fmap_bytes(stem.h, stem.w, stem.c_out))
    stalled = {c.index for c in count_plan(plan, params=params) if c.stall_cycles}
    want = {i for i in consumers if fmap_bytes(by_index[i].h, by_index[i].w,
                                               by_index[i].c_in) + resident[i]
            > params.fmap_bank_bytes}
    assert want and stalled == want
    chain = dataclasses.replace(plan, layers=tuple(
        dataclasses.replace(lp, shortcut=None) for lp in plan.layers))
    assert not any(c.stall_cycles for c in count_plan(chain, params=params))
