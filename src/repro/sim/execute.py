"""Plan execution — THE deploy interpreter.

Every deploy program (`api.program.DeployedProgram` and the artifact's
`LoadedProgram`) runs by walking its `ExecutionPlan` here, against the
trit-packed `WeightMemory` images: one walk for every backend, so a layer
mechanism (channel pad, stride, pool, TCN wrap, shortcut) lives in one
place.  The backend only decides how each conv/TCN layer computes:

  * ``fused``  — one `api.program._dispatch_conv` launch with the
    conv+scale+shortcut+threshold(+pool) epilogue, int8 trits out;
  * ``ref``    — the `kernels/ref.py` oracle for the conv, then
    ternarize and pool in this walk (float trits between layers);
  * ``bitsim`` — the plan's tile passes over the packed images
    (unpacked per `TileAssign` slice — tile boundaries are byte-aligned
    because ``max_cin`` is a multiple of the 4-trit pack quantum), partial
    sums accumulated across C_in tiles the way the OCU adder tree does,
    then ternarize and pool, int8 trits out — the silicon's 2-bit
    feature-memory model.

Bit-exactness contract (tests/test_sim.py, tests/test_api.py): with
ternary/dyadic activations — true for every registry net past the input
layer — all partial sums are integer- or dyadic-valued and therefore exact
in float32 under any accumulation order; every backend reads the same
per-OCU effective scale (folded once in `WeightMemory`) and compares
against the same scalar-or-per-channel threshold.  A single-C_in-tile layer
is literally the same XLA convolution the ``ref`` oracle runs, so even a
non-ternary *input* layer (real images) matches bit-for-bit as long as it
fits one tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.api import program
from repro.api.program import _pad_channels, _pool, check_backend
from repro.core.tcn import unwrap_time_axis, wrap_time_axis
from repro.core.ternary import unpack_ternary
from repro.sim.memory import LayerImage, WeightMemory
from repro.sim.plan import ExecutionPlan, LayerPlan


def _ternarize(y: jax.Array, threshold) -> jax.Array:
    thr = jnp.asarray(threshold, jnp.float32)
    return jnp.where(jnp.abs(y) > thr, jnp.sign(y), 0.0)


class PlanExecutor:
    """Executes one `ExecutionPlan` against its `WeightMemory` images on
    one ``backend`` (``"fused"``, ``"ref"`` or ``"bitsim"``).  Pure jnp —
    jits, vmaps, and serves through `StreamSession`/`SessionPool`.

    ``api.program._dispatch_conv`` and ``api.program.shortcut_map`` are
    looked up on that module at call time."""

    def __init__(self, plan: ExecutionPlan, memory: WeightMemory,
                 backend: str = "bitsim"):
        check_backend(backend)
        self.plan = plan
        self.memory = memory
        self.backend = backend
        self._blocks = {}  # layer index -> autotuned KernelBlock

    def _block_cout(self, lp: LayerPlan):
        """This layer's plan-driven kernel block (`kernels.autotune` over
        the SAME `LayerPlan` the counters price)."""
        kb = self._blocks.get(lp.index)
        if kb is None:
            from repro.kernels.autotune import block_for_layer

            kb = self._blocks[lp.index] = block_for_layer(lp)
        return kb.block_cout

    # -- tiled conv (the OCU array walk) -----------------------------------

    def _tiled_conv(self, x: jax.Array, lp: LayerPlan, img: LayerImage) -> jax.Array:
        """SAME conv over [B, H, W, C_pad] as the plan's (cout, cin) tile
        passes; partial sums accumulate across C_in tiles per output tile."""
        xf = x.astype(jnp.float32)
        packed = jnp.asarray(img.packed)
        cout_groups = []
        seen = []
        for t in lp.tiles:
            if (t.cout_lo, t.cout_hi) not in seen:
                seen.append((t.cout_lo, t.cout_hi))
        for co_lo, co_hi in seen:
            acc = None
            for t in lp.tiles:
                if (t.cout_lo, t.cout_hi) != (co_lo, co_hi):
                    continue
                wp = packed[:, :, t.cin_lo // 4 : t.cin_hi // 4, co_lo:co_hi]
                wt = unpack_ternary(wp, axis=2).astype(jnp.float32)
                part = lax.conv_general_dilated(
                    xf[..., t.cin_lo : t.cin_hi],
                    wt,
                    window_strides=(1, 1),
                    padding="SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )
                acc = part if acc is None else acc + part
            cout_groups.append(acc)
        y = cout_groups[0] if len(cout_groups) == 1 else jnp.concatenate(cout_groups, -1)
        return y * jnp.asarray(img.eff_scale).reshape(1, 1, 1, -1)

    def _conv(self, x: jax.Array, lp: LayerPlan, res=None, pool: int = 0):
        """The layer's SAME conv on ``x`` (channel-padded to the image):
        int8 trits on ``fused`` (threshold and ``pool`` in the epilogue),
        the scaled float accumulator with ``res`` added otherwise."""
        img = self.memory.image_for(lp)
        x = _pad_channels(x, lp.c_pad)
        if self.backend == "bitsim":
            y = self._tiled_conv(x, lp, img)
            return y if res is None else y + res.astype(jnp.float32)
        return program._dispatch_conv(
            x, jnp.asarray(img.packed), jnp.asarray(img.eff_scale), self.backend,
            threshold=img.threshold, pool=pool,
            block_cout=self._block_cout(lp), residual=res,
        )

    def _trits(self, y: jax.Array, lp: LayerPlan) -> jax.Array:
        """The threshold unit on an unfused backend's accumulator."""
        t = _ternarize(y, self.memory.image_for(lp).threshold)
        return t.astype(jnp.int8) if self.backend == "bitsim" else t

    def _conv_layer(self, x: jax.Array, lp: LayerPlan, res=None) -> jax.Array:
        """One conv layer; ``res`` is its shortcut map at its output size
        (`api.program.shortcut_map`), added before the threshold.  Stride is
        the post-threshold subsample (a strided conv never absorbs a pool)."""
        if self.backend == "fused":
            t = self._conv(x, lp, res, pool=lp.pool)
        else:
            t = self._trits(self._conv(x, lp, res), lp)
            if lp.pool:
                t = _pool(t, lp.pool)
        if lp.stride > 1:
            t = t[:, :: lp.stride, :: lp.stride, :]
        return t

    def _tcn_layer(self, x: jax.Array, lp: LayerPlan) -> jax.Array:
        """One §4-mapped TCN layer over [B, T, C]: wrap -> SAME conv with
        the rest of the causal (kh-1) pad on top -> unwrap -> threshold."""
        z = wrap_time_axis(x, self.memory.image_for(lp).dilation)
        kh = lp.kh
        zp = jnp.pad(z, ((0, 0), ((kh - 1) - (kh - 1) // 2, 0), (0, 0), (0, 0)))
        y = unwrap_time_axis(self._conv(zp, lp)[:, : z.shape[1]], x.shape[1])
        return y if self.backend == "fused" else self._trits(y, lp)

    def _fc(self, x: jax.Array, lp: LayerPlan) -> jax.Array:
        """The OPU: integer trit dot FIRST, per-class scale AFTER.  With
        ternary/dyadic inputs the dot is integer-valued and exact in float32
        under any summation order, so the logits are identical across batch
        sizes and eager/jit — the serving-pool contract that slot p of a
        P-wide batch reproduces a lone batch-1 session bit for bit."""
        img = self.memory.image_for(lp)
        t = unpack_ternary(np.asarray(img.packed), axis=0)[: lp.c_in]  # a constant
        if not jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(jnp.float32)
        return (x @ t.astype(x.dtype)) * jnp.asarray(img.eff_scale)

    # -- program-level forwards -------------------------------------------

    def spatial_forward(self, x: jax.Array) -> jax.Array:
        """Frontend (or whole spatial net): [B, H, W, C] -> features/logits.
        A shortcut source's output stays live until the layer that adds it."""
        sources, saved = self.plan.shortcut_sources, {}
        for lp in self.plan.spatial_layers:
            if lp.kind == "conv2d":
                res = None
                if lp.shortcut is not None:
                    res = program.shortcut_map(saved.pop(lp.shortcut),
                                               (*x.shape[:3], lp.c_out))
                x = self._conv_layer(x, lp, res)
                if lp.index in sources:
                    saved[lp.index] = x
            elif lp.kind == "pool":
                x = _pool(x, lp.pool)
            elif lp.kind == "global_pool":
                x = x.mean(axis=(1, 2))
            elif lp.kind == "flatten":
                x = x.reshape(x.shape[0], -1)
            elif lp.kind == "fc":
                x = self._fc(x, lp)
        return x

    def temporal_forward(self, feats: jax.Array) -> jax.Array:
        """TCN head + classifier over the ordered window [B, T, C]."""
        x = feats
        for lp in self.plan.temporal_layers:
            if lp.kind == "tcn":
                x = self._tcn_layer(x, lp)
            elif lp.kind == "last_step":
                x = x[:, -1, :]
            elif lp.kind == "fc":
                x = self._fc(x, lp)
        return x
