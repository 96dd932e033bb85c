"""Plan execution — the ``backend="bitsim"`` interpreter, and the
graph-free plan walk every other backend shares.

Walks the `ExecutionPlan` tile-by-tile, reading every weight from the
trit-packed `WeightMemory` images (unpacked per `TileAssign` slice — tile
boundaries are byte-aligned because ``max_cin`` is a multiple of the 4-trit
pack quantum) and accumulating partial sums across C_in tiles the way the
OCU adder tree does.

A non-default ``backend`` ("ref"/"fused"/"pallas"/"interpret") replaces the
tiled-conv walk with one `api.program._dispatch_conv` launch per layer —
the SAME kernels the `DeployedProgram` interpreter dispatches, driven from
the plan + weight images alone.  This is what lets an artifact-loaded
program (`repro.artifact.LoadedProgram`) execute on every backend with no
`CutieGraph` in sight: the plan IS the program.

Bit-exactness contract (tested against ``ref`` and ``fused`` in
tests/test_sim.py): with ternary/dyadic activations — true for every
registry net past the input layer — all partial sums are integer- or
dyadic-valued and therefore exact in float32 under any accumulation order;
the per-OCU effective scale is the *same float32 constant* the deploy
interpreter folds (`WeightMemory._eff_scale`), and the threshold unit
compares against the same scalar-or-per-channel vector the fused kernel
epilogue receives.  A single-C_in-tile layer is literally the same XLA
convolution the ``ref`` oracle runs, so even a non-ternary *input* layer
(real images) matches bit-for-bit as long as it fits one tile.

Inter-layer activations are int8 trits — the silicon's 2-bit feature-memory
model, same as the fused backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.tcn import unwrap_time_axis, wrap_time_axis
from repro.core.ternary import unpack_ternary
from repro.sim.memory import LayerImage, WeightMemory
from repro.sim.plan import ExecutionPlan, LayerPlan


def _pad_channels(x: jax.Array, c: int) -> jax.Array:
    if x.shape[-1] < c:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, c - x.shape[-1]),))
    return x


def _ternarize(y: jax.Array, threshold) -> jax.Array:
    thr = jnp.asarray(threshold, jnp.float32)
    return jnp.where(jnp.abs(y) > thr, jnp.sign(y), 0.0)


def _max_pool(x: jax.Array, window: int) -> jax.Array:
    if jnp.issubdtype(x.dtype, jnp.floating):
        init = -jnp.inf
    else:
        init = jnp.iinfo(x.dtype).min
    return lax.reduce_window(
        x, init, lax.max, (1, window, window, 1), (1, window, window, 1), "VALID"
    )


class PlanExecutor:
    """Executes one `ExecutionPlan` against its `WeightMemory` images.

    Mirrors `DeployedProgram.spatial_forward`/`temporal_forward` semantics
    exactly (the deploy interpreter is the contract); the difference is that
    convolutions run as the plan's scheduled tile passes over the packed
    images instead of one monolithic kernel call.  Pure jnp — jits, vmaps,
    and serves through `StreamSession`/`SessionPool` unchanged.

    ``backend="bitsim"`` (default) is the tiled walk; any other deploy
    backend routes each conv through `api.program._dispatch_conv` with this
    layer's image — fused keeps its single-launch conv+scale+threshold
    (+pool) epilogue and int8 activations, the others return the scaled
    float accumulator and ternarize here, exactly the `DeployedProgram`
    dataflow."""

    def __init__(self, plan: ExecutionPlan, memory: WeightMemory,
                 backend: str = "bitsim"):
        from repro.api.program import check_backend

        check_backend(backend)
        self.plan = plan
        self.memory = memory
        self.backend = backend
        self._blocks = {}  # layer index -> autotuned KernelBlock

    def _block_cout(self, lp: LayerPlan):
        """This layer's plan-driven kernel block (`kernels.autotune` over
        the SAME `LayerPlan` the counters price) — what makes an
        artifact-loaded program run the autotuned packed path with no graph
        objects anywhere."""
        kb = self._blocks.get(lp.index)
        if kb is None:
            from repro.kernels.autotune import block_for_layer

            kb = self._blocks[lp.index] = block_for_layer(lp)
        return kb.block_cout

    # -- constructors ------------------------------------------------------

    @staticmethod
    def for_deployed(deployed, hw=None) -> "PlanExecutor":
        """Lower ``deployed.graph`` and bind its packed tables."""
        from repro.sim.plan import lower

        plan = lower(deployed.graph, hw)
        memory = WeightMemory.from_tables(
            plan, deployed.tables, deployed.graph.act_threshold
        )
        return PlanExecutor(plan, memory)

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

    def _conv_layer(self, x: jax.Array, lp: LayerPlan, res=None) -> jax.Array:
        """One conv layer; ``res`` is its shortcut map at its output size
        (`api.program.shortcut_map`), added before the threshold."""
        from repro.api.program import _dispatch_conv

        img = self.memory.image_for(lp)
        x = _pad_channels(x, lp.c_pad)
        if self.backend == "bitsim":
            y = self._tiled_conv(x, lp, img)
            if res is not None:
                y = y + res.astype(jnp.float32)
        elif self.backend == "fused":
            t = _dispatch_conv(
                x, jnp.asarray(img.packed), jnp.asarray(img.eff_scale),
                "fused", threshold=img.threshold, pool=lp.pool,
                block_cout=self._block_cout(lp), residual=res,
            )
            if lp.stride > 1:
                t = t[:, :: lp.stride, :: lp.stride, :]
            return t
        else:
            y = _dispatch_conv(
                x, jnp.asarray(img.packed), jnp.asarray(img.eff_scale),
                self.backend, block_cout=self._block_cout(lp), residual=res,
            )
        t = _ternarize(y, img.threshold)
        if lp.stride > 1:
            # post-ternarize subsample == strided conv (never pool-fused)
            t = t[:, :: lp.stride, :: lp.stride, :]
        if lp.pool:
            t = _max_pool(t, lp.pool)
        # the deploy interpreter keeps float trits between layers on the
        # unfused backends; bitsim models the 2-bit feature memory as int8
        return t.astype(jnp.int8) if self.backend == "bitsim" else t

    def _tcn_layer(self, x: jax.Array, lp: LayerPlan) -> jax.Array:
        """One §4-mapped TCN layer over [B, T, C]: wrap -> causal-padded
        tiled SAME conv -> unwrap -> threshold, the deploy schedule."""
        from repro.api.program import _dispatch_conv

        img = self.memory.image_for(lp)
        kh = lp.kh
        if self.backend != "bitsim":
            z = wrap_time_axis(x, img.dilation)
            zp = jnp.pad(z, ((0, 0), ((kh - 1) - (kh - 1) // 2, 0), (0, 0), (0, 0)))
            zp = _pad_channels(zp, lp.c_pad)
            if self.backend == "fused":
                y2 = _dispatch_conv(
                    zp, jnp.asarray(img.packed), jnp.asarray(img.eff_scale),
                    "fused", threshold=img.threshold,
                    block_cout=self._block_cout(lp),
                )[:, : z.shape[1]]
                return unwrap_time_axis(y2, x.shape[1])
            y2 = _dispatch_conv(
                zp, jnp.asarray(img.packed), jnp.asarray(img.eff_scale),
                self.backend, block_cout=self._block_cout(lp),
            )[:, : z.shape[1]]
            y = unwrap_time_axis(y2, x.shape[1])
            return _ternarize(y, img.threshold)
        z = wrap_time_axis(x.astype(jnp.float32), img.dilation)
        zp = jnp.pad(z, ((0, 0), ((kh - 1) - (kh - 1) // 2, 0), (0, 0), (0, 0)))
        zp = _pad_channels(zp, lp.c_pad)
        y2 = self._tiled_conv(zp, lp, img)[:, : z.shape[1]]
        y = unwrap_time_axis(y2, x.shape[1])
        return _ternarize(y, img.threshold).astype(jnp.int8)

    def _fc(self, x: jax.Array, lp: LayerPlan) -> jax.Array:
        """The OPU: integer trit dot FIRST, per-class scale AFTER — the
        accumulate-then-scale order that keeps logits bit-identical across
        batch shapes (`DeployedProgram._fc`'s serving contract)."""
        img = self.memory.image_for(lp)
        t = unpack_ternary(jnp.asarray(img.packed), axis=0)[: lp.c_in]
        if not jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(jnp.float32)
        return (x @ t.astype(x.dtype)) * jnp.asarray(img.eff_scale)

    # -- program-level forwards -------------------------------------------

    def spatial_forward(self, x: jax.Array) -> jax.Array:
        """Frontend (or whole spatial net): [B, H, W, C] -> features/logits.
        A shortcut source's output stays live until the layer that adds it."""
        from repro.api.program import shortcut_map

        sources, saved = self.plan.shortcut_sources, {}
        for lp in self.plan.spatial_layers:
            if lp.kind == "conv2d":
                res = None
                if lp.shortcut is not None:
                    res = shortcut_map(saved.pop(lp.shortcut),
                                       (*x.shape[:3], lp.c_out))
                x = self._conv_layer(x, lp, res)
                if lp.index in sources:
                    saved[lp.index] = x
            elif lp.kind == "pool":
                x = _max_pool(x, lp.pool)
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
