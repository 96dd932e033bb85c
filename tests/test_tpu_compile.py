"""Compile the main path's Pallas kernels for a described TPU v5e, no chip.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described but not attached.  These tests compile the packed kernels at
the registry nets' published widths (`interpret=False`, fused epilogue, int8
out) and the sharded serving step on four chips, and check that the Pallas
kernel is what the compiled program runs (`tpu_custom_call`).  Nothing runs,
so they say nothing about results or times — `chip_smoke.py` does that on
the chip.

The topology is described inside a module fixture (skipping where it cannot
be), never at import: one process holds the TPU library at a time.  The
persistent compilation cache is off around the compiles, since a compile for
a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.ternary_conv2d import (
    ternary_conv2d_pallas,
    ternary_conv2d_residual_pallas,
)
from repro.kernels.ternary_matmul import ternary_matmul_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_conv(one_chip, x_shape, w_shape, pool, block_cout, x_dtype):
    c_out = w_shape[-1]
    lowered = ternary_conv2d_pallas.lower(
        _spec(x_shape, x_dtype, one_chip), _spec(w_shape, jnp.uint8, one_chip),
        _spec((c_out,), jnp.float32, one_chip), _spec((c_out,), jnp.float32, one_chip),
        block_cout=block_cout, fuse_ternary=True, fuse_pool=pool,
        interpret=False, out_dtype=jnp.int8,
    )
    return lowered.compile().as_text()


# (x [B, H, W, C_in padded to the pack quantum], packed w, fused pool,
#  block_cout, input dtype) — every shape one of the registry nets runs
CONV_CASES = {
    "cifar_stem": ((2, 32, 32, 4), (3, 3, 1, 96), 0, 96, jnp.float32),
    "cifar_96_pooled": ((2, 32, 32, 96), (3, 3, 24, 96), 2, 96, jnp.int8),
    "dvs_stem_64x64": ((2, 64, 64, 4), (3, 3, 1, 64), 2, 64, jnp.float32),
    "dvs_4x4": ((2, 4, 4, 96), (3, 3, 24, 96), 2, 96, jnp.int8),
    # TCN layers after the dilation-D wrap of the 24-step ring: [ceil(24/D)+1, D]
    "tcn_d1": ((2, 25, 1, 96), (3, 3, 24, 96), 0, 96, jnp.int8),
    "tcn_d2": ((2, 13, 2, 96), (3, 3, 24, 96), 0, 96, jnp.int8),
    "tcn_d4": ((2, 7, 4, 96), (3, 3, 24, 96), 0, 96, jnp.int8),
    "tcn_d8": ((2, 4, 8, 96), (3, 3, 24, 96), 0, 96, jnp.int8),
    # at the cells' batches, so the grid cells hold the image blocks
    # `kernels.autotune.batch_block` picks there: 2, 8 and 32 images of 256,
    # and 16, 32, 32, 64 TCN windows of 64
    "cifar_96_pooled_b256": ((256, 32, 32, 96), (3, 3, 24, 96), 2, 96, jnp.int8),
    "cifar_16x16_b256": ((256, 16, 16, 96), (3, 3, 24, 96), 0, 96, jnp.int8),
    "cifar_16x16_pooled_b256": ((256, 16, 16, 96), (3, 3, 24, 96), 2, 96, jnp.int8),
    "cifar_8x8_b256": ((256, 8, 8, 96), (3, 3, 24, 96), 0, 96, jnp.int8),
    "cifar_8x8_pooled_b256": ((256, 8, 8, 96), (3, 3, 24, 96), 2, 96, jnp.int8),
    "tcn_d1_b64": ((64, 25, 1, 96), (3, 3, 24, 96), 0, 96, jnp.int8),
    "tcn_d2_b64": ((64, 13, 2, 96), (3, 3, 24, 96), 0, 96, jnp.int8),
    "tcn_d4_b64": ((64, 7, 4, 96), (3, 3, 24, 96), 0, 96, jnp.int8),
    "tcn_d8_b64": ((64, 4, 8, 96), (3, 3, 24, 96), 0, 96, jnp.int8),
}


@pytest.mark.parametrize("case", list(CONV_CASES))
def test_conv_kernel_compiles_for_v5e(one_chip, case):
    x_shape, w_shape, pool, block, x_dtype = CONV_CASES[case]
    hlo = _compile_conv(one_chip, x_shape, w_shape, pool, block, x_dtype)
    assert "tpu_custom_call" in hlo


# resnet20_tnn's shortcut convs: (batch, H = W, C) of each stage, at two
# images and at resnet20_b256's batch (2, 8 and 32 images per grid cell)
RESIDUAL_CASES = {
    "32x32x16": (2, 32, 16), "16x16x32": (2, 16, 32), "8x8x64": (2, 8, 64),
    "32x32x16_b256": (256, 32, 16), "16x16x32_b256": (256, 16, 32),
    "8x8x64_b256": (256, 8, 64),
}


@pytest.mark.parametrize("case", list(RESIDUAL_CASES))
def test_residual_conv_kernel_compiles_for_v5e(one_chip, case):
    """The residual epilogue (int8 shortcut operand, blocked like the
    output) at each ResNet-20 stage, under its own launch name."""
    b, hw, c = RESIDUAL_CASES[case]
    lowered = ternary_conv2d_residual_pallas.lower(
        _spec((b, hw, hw, c), jnp.int8, one_chip),
        _spec((3, 3, c // 4, c), jnp.uint8, one_chip),
        _spec((c,), jnp.float32, one_chip), _spec((c,), jnp.float32, one_chip),
        _spec((b, hw, hw, c), jnp.int8, one_chip),
        block_cout=c, fuse_ternary=True, interpret=False, out_dtype=jnp.int8,
    )
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "%ternary_conv2d_residual_pallas" in hlo


def _operands(hlo_line: str) -> int:
    return hlo_line.split("custom-call(", 1)[1].split(")", 1)[0].count("%")


def test_resnet20_step_launches_plain_and_residual_kernels(one_chip, monkeypatch):
    """resnet20_tnn's fused forward: its 10 plain convs launch the plain
    kernel under its own name with its four operands, its 9 shortcut convs
    the residual kernel with the shortcut as a fifth."""
    import re

    import repro.kernels.ops as ops
    from repro import api

    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    prog = api.get_net("resnet20_tnn")
    dep = prog.quantize(prog.init(jax.random.PRNGKey(0)))
    fwd = jax.jit(lambda x: dep.forward(x, backend="fused"))
    hlo = fwd.lower(_spec((3, 32, 32, 3), jnp.float32, one_chip)).compile().as_text()
    calls = [l for l in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    plain = [l for l in calls if re.search(r"%ternary_conv2d_pallas(\.\d+)? = ", l)]
    residual = [l for l in calls
                if re.search(r"%ternary_conv2d_residual_pallas(\.\d+)? = ", l)]
    assert (len(plain), len(residual), len(calls)) == (10, 9, 19)
    assert {_operands(l) for l in plain} == {4}
    assert {_operands(l) for l in residual} == {5}


def test_wide_192_layer_compiles_with_autotuned_block(one_chip):
    """cifar10_tnn_wide's 192-channel layer with the block `kernels.autotune`
    picks for it — a block the TPU must accept over the 192-wide array."""
    from repro import api
    from repro.kernels.autotune import block_for_layer
    from repro.sim.plan import lower

    plan = lower(api.get_graph("cifar10_tnn_wide"))
    lp = [lp for lp in plan.layers if lp.kind == "conv2d"][1]
    assert lp.c_out == 192
    block = block_for_layer(lp).block_cout
    hlo = _compile_conv(one_chip, (2, lp.h, lp.w, lp.c_pad),
                        (lp.kh, lp.kw, lp.c_pad // 4, lp.c_out), lp.pool, block,
                        jnp.int8)
    assert "tpu_custom_call" in hlo


def test_matmul_kernel_compiles_for_v5e(one_chip):
    lowered = ternary_matmul_pallas.lower(
        _spec((256, 1024), jnp.float32, one_chip),
        _spec((256, 256), jnp.uint8, one_chip),
        _spec((256,), jnp.float32, one_chip),
        block_m=128, block_n=128, block_k=512, interpret=False,
    )
    assert "tpu_custom_call" in lowered.compile().as_text()


def _sharded_pool_step_hlo(topo, monkeypatch, lane_dtype):
    """The dvs_cnn_tcn pool step at 8 slots over the 4 chips of ``topo``,
    compiled with a ``lane_dtype`` lane argument; returns the HLO text."""
    import repro.kernels.ops as ops
    from repro import api
    from repro.serving import SessionPool

    # steer dispatch to the compiled Pallas path the chip would take, and
    # leave the pool state where it is: nothing can live on a described chip
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    monkeypatch.setattr(jax, "device_put", lambda a, s: a)
    prog = api.get_net("dvs_cnn_tcn")
    dep = prog.quantize(prog.init(jax.random.PRNGKey(0)))
    sharding = NamedSharding(Mesh(np.array(topo.devices), ("pool",)), P("pool"))
    pool = SessionPool(dep, 8, backend="fused", sharding=sharding)

    def spec(a):
        return _spec(a.shape, a.dtype, sharding)

    state = jax.tree_util.tree_map(spec, pool.state)
    frames = _spec((8, *pool.frame_shape), jnp.float32, sharding)
    lanes = _spec((8,), lane_dtype, sharding)
    return pool._step.lower(state, frames, lanes).compile().as_text()


def test_sharded_pool_step_compiles_for_four_chips(topo, monkeypatch):
    """The dvs_cnn_tcn pool step with its pool axis over 4 chips: a Pallas
    call cannot be partitioned by the compiler, so the pool must map its
    step over the devices (`shard_map`) — one kernel per layer, no
    collective anywhere."""
    hlo = _sharded_pool_step_hlo(topo, monkeypatch, jnp.bool_)
    assert "tpu_custom_call" in hlo
    for collective in ("all-gather", "all-reduce", "all-to-all"):
        assert collective not in hlo


def test_sharded_pool_step_with_lane_code_has_no_collective(topo, monkeypatch):
    """The same step as the pool runs it: an int8 lane code whose FRESH bit
    zeroes lanes before the push.  Each chip zeroes its own lanes — still
    no collective."""
    hlo = _sharded_pool_step_hlo(topo, monkeypatch, jnp.int8)
    assert "tpu_custom_call" in hlo
    for collective in ("all-gather", "all-reduce", "all-to-all"):
        assert collective not in hlo
