"""`CutieProgram` — one network definition, every execution mode.

Compile a declarative `CutieGraph` into an object with the full lifecycle
the paper's silicon implements:

    prog     = get_net("cifar10_tnn")          # repro.api.registry
    params   = prog.init(jax.random.PRNGKey(0))
    logits   = prog.forward_qat(params, x)      # STE fake-quant training path
    deployed = prog.quantize(params, calib=x)   # packed 2-bit weights
    logits   = deployed.forward(x)              # backend="fused" | "ref" | "bitsim"
    session  = deployed.stream(batch=4)         # TCN ring memory (temporal)
    pool     = deployed.serve(pool_size=8)      # multi-sensor continuous batching
    report   = deployed.silicon_report(v=0.5)   # cycles/energy vs Table 1

The QAT path walks the graph with STE fake-quant + per-channel batch-norm
scaling.  The deploy path lowers the graph once to a `sim.ExecutionPlan`,
binds the packed 2-bit weights as `sim.WeightMemory` images with the BN
statistics folded into the per-OCU scale (``calib``) or a fan-in
normalization fallback, and walks the plan with `sim.PlanExecutor` — the
one deploy interpreter, shared with programs loaded from a ``.cutie``
artifact (`PlanProgram`).  With ``calib`` given AND the graph's
``qat_per_channel=True`` (so both paths share one quantization grid),
forward_qat and deployed.forward agree to float round-off on the
calibration distribution; on the default per-layer QAT grid the grids
differ slightly and agreement is approximate — both tested in
tests/test_api.py.

Backends (the executor's per-layer choice):
    fused   conv+scale+shortcut+ternarize(+max-pool) in one packed kernel
            launch per layer (native select-decode on CPU, Pallas on TPU),
            int8 ternary activations between layers — the silicon's 2-bit
            inter-layer memory model and the default of every entry point
    ref     pure-jnp oracles from kernels/ref.py, then ternarize and pool
    bitsim  the plan's OCU/C_in tile passes over the trit-packed images —
            the cycle-counted microarchitecture simulator's functional half

All three produce bit-identical logits whenever every inter-layer tensor is
ternary or a dyadic rational of ternary values (true for all registry nets:
their global_pool windows are power-of-two sized), since they then
accumulate exactly in float32 regardless of summation order.  Tested in
tests/test_api.py and tests/test_fused_backend.py; a net whose global_pool
mean divides by a non-power-of-two could differ in the last ulp at a
threshold crossing.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import quantize as q
from repro.api.graph import CutieGraph
from repro.core import cutie_arch as arch
from repro.core.tcn import (
    StreamState,
    TCNStream,
    conv2d_undilated,
    project_weights_to_2d,
    unwrap_time_axis,
    wrap_time_axis,
)
from repro.core.ternary import clamp_threshold, ste_ternary_acts, ste_ternary_weights
from repro.kernels.ops import ternary_conv2d
from repro.kernels.ref import ternary_conv2d_ref

BACKENDS = ("fused", "ref", "bitsim")
SILICON_SOURCES = ("analytic", "sim")
_BN_EPS = 1e-6


def check_backend(backend: str) -> None:
    """THE backend validation — every entry point routes through here."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")


def _pool(x: jax.Array, window: int) -> jax.Array:
    # concrete-scalar init so JAX still recognizes the monoid max reducer
    # (a traced init breaks the reduce_window_max grad path); int inputs
    # (fused-backend trits) can't hold -inf, use the dtype floor instead.
    if jnp.issubdtype(x.dtype, jnp.floating):
        init = -jnp.inf
    else:
        init = np.array(jnp.iinfo(x.dtype).min, x.dtype)
    return jax.lax.reduce_window(
        x, init, jax.lax.max,
        (1, window, window, 1), (1, window, window, 1), "VALID",
    )


def _bn_sd(y: jax.Array) -> jax.Array:
    """Per-output-channel std — the scale-only BN the silicon folds into its
    two threshold comparators per OCU."""
    return jnp.std(y.astype(jnp.float32), axis=tuple(range(y.ndim - 1)))


def effective_scale(entry: Dict, fan_in: int) -> np.ndarray:
    """THE per-OCU effective-scale fold: calibration BN std folded into the
    TWN alpha, or a 1/sqrt(fan-in) normalization without calibration.
    `repro.sim.memory.WeightMemory` folds each layer through it once, on
    the host in IEEE float32, and every backend reads the constant."""
    scale = np.asarray(entry["scale"], np.float32)
    if "bn_sd" in entry:
        return scale / (np.asarray(entry["bn_sd"], np.float32) + np.float32(_BN_EPS))
    return scale / np.sqrt(np.float32(fan_in))


def _dispatch_conv(x, packed, eff_scale, backend: str, *,
                   threshold=0.5, pool: int = 0,
                   block_cout: Optional[int] = None, residual=None):
    """One SAME ternary conv, as `sim.PlanExecutor` runs it on ``backend``
    "fused" or "ref".  ``x`` must already be channel-padded to
    4 * packed.shape[2].  ``residual`` is the layer's shortcut map at its
    output size (`shortcut_map`), added to the scaled accumulator before
    the threshold.

    "fused" runs the whole CUTIE layer — conv, per-OCU scale, shortcut
    add, threshold unit (``threshold``: scalar or per-channel [C_out]),
    optional ``pool``-window max-pool — in a single packed launch (native
    select-decode datapath on CPU, the Pallas kernel on TPU, with the
    layer's plan-driven ``block_cout``) and emits int8 ternary activations.
    "ref" is the `kernels/ref.py` oracle: the scaled float accumulator,
    threshold and pool left to the caller."""
    if backend == "ref":
        return ternary_conv2d_ref(x, packed, eff_scale, residual=residual)
    return ternary_conv2d(
        x, packed, eff_scale, fuse_ternary=True, threshold=threshold,
        fuse_pool=pool, out_dtype=jnp.int8, block_cout=block_cout,
        residual=residual,
    )


def shortcut_map(a: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    """``S(a_k)``: a saved map [B, H', W', C'] brought to a conv output of
    ``shape`` [B, H, W, C] — the identity where they match, else option A:
    every (H'/H)-th row and column from the top-left, as ``stride`` keeps
    them, and zero channels appended up to C.  `CutieGraph.validate` has
    refused every other relation."""
    s = a.shape[1] // shape[1]
    if s > 1:
        a = a[:, ::s, ::s, :]
    return _pad_channels(a, shape[-1])


def _pad_channels(x: jax.Array, c: int) -> jax.Array:
    if x.shape[-1] < c:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, c - x.shape[-1]),))
    return x


def _ring_window(feats: jax.Array, tcn_steps: int) -> jax.Array:
    """[B, T, C] -> the [B, tcn_steps, C] window the ring memory would hold:
    the newest tcn_steps entries, left-padded with zero history."""
    b, t = feats.shape[:2]
    if t > tcn_steps:
        return feats[:, -tcn_steps:]
    if t < tcn_steps:
        pad = jnp.zeros((b, tcn_steps - t, feats.shape[-1]), feats.dtype)
        return jnp.concatenate([pad, feats], axis=1)
    return feats


class CutieProgram:
    """A compiled (validated) graph: init + QAT forward + quantization."""

    def __init__(self, graph: CutieGraph):
        self.graph = graph.validate()

    # -- parameters --------------------------------------------------------

    def init(self, key: jax.Array, learn_thresholds=False) -> Dict:
        """Kaiming-style float params, grouped by kind:
        {"conv": [{"w"}...], "tcn": [{"w"}...], "fc": {"w"}} (keys only for
        kinds the graph contains — layout shared with the legacy model).

        ``learn_thresholds=True`` adds a ``"thresh"`` group — one trainable
        scalar activation threshold per conv/tcn layer, initialized at the
        graph's ``act_threshold``.  The QAT forward reads them (clamped via
        `core.ternary.clamp_threshold`) instead of the static threshold and
        the STE threshold gradient makes them trainable; ``quantize()``
        folds the trained values into the packed deploy tables
        (`api.quantize.resolve_deploy_thresholds`).

        ``learn_thresholds="per_channel"`` makes each layer's threshold a
        [c_out] *vector* — one comparator constant per OCU, which the fused
        kernel epilogue (and bitsim) consume as a per-channel threshold
        operand at deploy time."""
        g = self.graph
        convs = [l for l in g.layers if l.kind == "conv2d"]
        tcns = [l for l in g.layers if l.kind == "tcn"]
        fcs = [l for l in g.layers if l.kind == "fc"]
        # key schedule kept bit-compatible with the legacy init for the two
        # paper networks (<=8 conv, <=7 tcn layers)
        if len(convs) <= 8 and len(tcns) <= 7:
            ks = jax.random.split(key, 16)
            k_conv = lambda i: ks[i]
            k_tcn = lambda i: ks[8 + i]
            k_fc = ks[-1]
        else:
            ks = jax.random.split(key, len(convs) + len(tcns) + 1)
            k_conv = lambda i: ks[i]
            k_tcn = lambda i: ks[len(convs) + i]
            k_fc = ks[-1]
        p: Dict = {}
        if convs:
            p["conv"] = [
                {"w": jax.random.normal(k_conv(i), (*l.kernel, l.c_in, l.c_out))
                      * (2.0 / (l.kernel[0] * l.kernel[1] * l.c_in)) ** 0.5}
                for i, l in enumerate(convs)
            ]
        if tcns:
            p["tcn"] = [
                {"w": jax.random.normal(k_tcn(i), (l.taps, l.c_in, l.c_out))
                      * (2.0 / (l.taps * l.c_in)) ** 0.5}
                for i, l in enumerate(tcns)
            ]
        if fcs:
            (l,) = fcs
            p["fc"] = {"w": jax.random.normal(k_fc, (l.c_in, l.c_out)) * 0.05}
        if learn_thresholds not in (False, True, "per_channel"):
            raise ValueError(
                f"learn_thresholds={learn_thresholds!r}; expected False, True "
                "or 'per_channel'"
            )
        if learn_thresholds:
            # one DISTINCT buffer per layer (a shared one breaks donation);
            # "per_channel" widens each to a per-OCU [c_out] vector
            per_ch = learn_thresholds == "per_channel"
            t0 = lambda l: jnp.full(
                (l.c_out,) if per_ch else (), self.graph.act_threshold, jnp.float32
            )
            p["thresh"] = {}
            if convs:
                p["thresh"]["conv"] = [t0(l) for l in convs]
            if tcns:
                p["thresh"]["tcn"] = [t0(l) for l in tcns]
        return p

    # -- QAT interpreter ---------------------------------------------------

    def _qat_threshold(self, params: Dict, kind: str, idx: int):
        """The activation threshold layer ``idx`` of ``kind`` trains with:
        the clamped learned scalar when params carry one, else the graph's
        static ``act_threshold``."""
        th = params.get("thresh")
        if th is None or kind not in th:
            return self.graph.act_threshold
        return clamp_threshold(th[kind][idx])

    def spatial_forward_qat(
        self, params: Dict, x: jax.Array, _record: Optional[List] = None,
        nu: Optional[float] = None,
    ) -> jax.Array:
        """The 2-D frontend on [B, H, W, C_in] — per frame for temporal
        graphs, the whole net (including fc) for spatial ones.  ``nu``
        overrides the graph's TWN threshold factor (static per trace — the
        train loop's nu schedules are piecewise-constant for this reason)."""
        g = self.graph
        nu = g.weight_nu if nu is None else nu
        ci = 0
        sources, saved = g.shortcut_sources, {}  # saved: maps kept live
        for i, l in enumerate(g.spatial_layers):
            if l.kind == "conv2d":
                axis = (0, 1, 2) if g.qat_per_channel else None
                wq = ste_ternary_weights(params["conv"][ci]["w"], nu, axis)
                y = jax.lax.conv_general_dilated(
                    x, wq, (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )
                sd = _bn_sd(y)
                if _record is not None:
                    _record.append(sd)
                y = y / (sd + _BN_EPS)
                if l.shortcut is not None:
                    y = y + shortcut_map(saved.pop(l.shortcut), y.shape)
                x = ste_ternary_acts(y, self._qat_threshold(params, "conv", ci))
                if l.stride > 1:
                    # stride = post-ternarize subsample (top-left phase);
                    # ternarization is elementwise, so this is bit-identical
                    # to a strided conv and every backend shares one kernel
                    x = x[:, :: l.stride, :: l.stride, :]
                if i in sources:
                    saved[i] = x
                ci += 1
            elif l.kind == "pool":
                x = _pool(x, l.window)
            elif l.kind == "global_pool":
                x = x.mean(axis=(1, 2))
            elif l.kind == "flatten":
                x = x.reshape(x.shape[0], -1)
            elif l.kind == "fc":
                x = x @ ste_ternary_weights(params["fc"]["w"], nu,
                                            0 if g.qat_per_channel else None)
        return x

    def temporal_forward_qat(
        self, params: Dict, feats: jax.Array, _record: Optional[List] = None,
        nu: Optional[float] = None,
    ) -> jax.Array:
        """TCN head + classifier over the ordered window [B, T, C].  Every
        dilated layer runs through the §4 wrap -> undilated-2-D-conv ->
        unwrap mapping — the exact schedule the silicon executes."""
        g = self.graph
        nu = g.weight_nu if nu is None else nu
        x = feats
        ti = 0
        for l in g.temporal_layers:
            if l.kind == "tcn":
                axis = (0, 1) if g.qat_per_channel else None
                wq = ste_ternary_weights(params["tcn"][ti]["w"], nu, axis)
                z = wrap_time_axis(x, l.dilation)
                y2 = conv2d_undilated(z, project_weights_to_2d(wq, kh=l.kernel[0], kw=l.kernel[1]))
                y = unwrap_time_axis(y2, x.shape[1])
                sd = _bn_sd(y)
                if _record is not None:
                    _record.append(sd)
                x = ste_ternary_acts(
                    y / (sd + _BN_EPS), self._qat_threshold(params, "tcn", ti)
                )
                ti += 1
            elif l.kind == "last_step":
                x = x[:, -1, :]
            elif l.kind == "fc":
                x = x @ ste_ternary_weights(params["fc"]["w"], nu,
                                            0 if g.qat_per_channel else None)
        return x

    def forward_qat(
        self, params: Dict, x: jax.Array, nu: Optional[float] = None
    ) -> jax.Array:
        """Spatial graphs: [B, H, W, C] -> logits.  Temporal graphs:
        frames [B, T, H, W, C] -> logits over exactly what the ring memory
        would hold: the last tcn_steps frames, zero-padded on the left when
        the clip is shorter."""
        g = self.graph
        if not g.is_temporal:
            return self.spatial_forward_qat(params, x, nu=nu)
        feats = jax.vmap(
            lambda f: self.spatial_forward_qat(params, f, nu=nu), in_axes=1, out_axes=1
        )(x)
        return self.temporal_forward_qat(params, _ring_window(feats, g.tcn_steps), nu=nu)

    # -- quantization ------------------------------------------------------

    def quantize(
        self, params: Dict, calib: Optional[jax.Array] = None,
        nu: Optional[float] = None,
    ) -> "DeployedProgram":
        """QAT params -> packed 2-bit deploy tables (one quantize->pad->pack
        path for every layer kind: repro.api.quantize).

        ``calib``: an example input batch.  When given, the QAT forward runs
        once recording each layer's BN std, which deployment folds into the
        per-OCU scale — the silicon's offline BN/threshold folding.  Without
        it, a 1/sqrt(fan-in) normalization keeps accumulations in range.

        ``nu`` overrides the graph's TWN threshold factor — pass the final
        value of a scheduled-nu training run so packing quantizes on the
        grid the params were trained for (repro.train passes this).

        Learned per-layer thresholds (``init(learn_thresholds=True)``) are
        clamped and folded into each table entry's ``"threshold"`` — the
        fused backend's static epilogue constant.
        """
        g = self.graph
        nu = g.weight_nu if nu is None else nu
        tables: Dict = {"conv": [], "tcn": [], "fc": {}}
        # Per-layer epilogue metadata rides with the packed weights so the
        # deploy tables are self-describing for the fused backend; the
        # threshold is the learned per-layer value when the params carry one
        # (ROADMAP quantization item), else the graph's static one.
        thresholds = q.resolve_deploy_thresholds(g, params)
        pool_plan = g.conv_pool_plan()
        for li, lp in enumerate(params.get("conv", [])):
            packed, scale = q.quantize_pack_conv_weights(lp["w"], nu=nu)
            tables["conv"].append({
                "packed": packed, "scale": scale,
                "threshold": thresholds["conv"][li], "pool": pool_plan[li],
            })
        tcn_specs = [l for l in g.layers if l.kind == "tcn"]
        for ti, (lp, l) in enumerate(zip(params.get("tcn", []), tcn_specs)):
            packed, scale = q.quantize_pack_tcn_weights(
                lp["w"], nu=nu, kh=l.kernel[0], kw=l.kernel[1]
            )
            tables["tcn"].append({
                "packed": packed, "scale": scale, "dilation": l.dilation,
                "threshold": thresholds["tcn"][ti],
            })
        if "fc" in params:
            t, a = q.ternary_quantize_weights(params["fc"]["w"], nu=nu, axis=0)
            tables["fc"] = {"t": t, "scale": a.reshape(-1)}
        if calib is not None:
            spatial_rec: List = []
            temporal_rec: List = []
            if g.is_temporal:
                # pooled statistics over all frames, then over the window;
                # the same nu as the packed tables — folded scales must
                # match the deployed weight grid
                frames = calib.reshape(-1, *calib.shape[2:])
                feats = self.spatial_forward_qat(
                    params, frames, _record=spatial_rec, nu=nu
                )
                window = feats.reshape(calib.shape[0], calib.shape[1], -1)
                self.temporal_forward_qat(
                    params, _ring_window(window, g.tcn_steps),
                    _record=temporal_rec, nu=nu,
                )
            else:
                self.spatial_forward_qat(params, calib, _record=spatial_rec, nu=nu)
            for entry, sd in zip(tables["conv"], spatial_rec):
                entry["bn_sd"] = sd
            for entry, sd in zip(tables["tcn"], temporal_rec):
                entry["bn_sd"] = sd
        return DeployedProgram(g, tables)

    # -- silicon model -----------------------------------------------------

    def silicon_report(
        self, v: float = 0.5, hw: Optional[arch.CutieHW] = None,
        source: str = "analytic",
    ) -> "SiliconReport":
        """Cycles/energy for this graph at supply ``v`` — see module-level
        `silicon_report` (the Table-1 loop).  ``source="sim"`` prices the
        `repro.sim` execution plan instead of the closed formula."""
        return silicon_report(self.graph, v=v, hw=hw, source=source)


class PlanProgram:
    """The execution surface every deploy program shares: each backend's
    `repro.sim.PlanExecutor` walks ``self.plan`` over ``self.memory``.

    A subclass supplies ``graph`` (a `CutieGraph`, or the artifact's
    graph-free `artifact.ProgramInfo` — serving reads only its metadata),
    ``plan`` (the `sim.ExecutionPlan`) and ``memory`` (the
    `sim.WeightMemory` images)."""

    @functools.cached_property
    def _executors(self) -> Dict:
        return {}

    def _executor(self, backend: str):
        ex = self._executors.get(backend)
        if ex is None:
            from repro.sim.execute import PlanExecutor

            ex = self._executors[backend] = PlanExecutor(
                self.plan, self.memory, backend
            )
        return ex

    @property
    def nbytes(self) -> int:
        """Total packed weight-image bytes (the device's weight SCM load)."""
        return self.memory.nbytes

    def spatial_forward(self, x: jax.Array, backend: str = "fused") -> jax.Array:
        """Frontend (or whole spatial net) on packed weights: [B,H,W,C] ->
        feature vector / logits."""
        return self._executor(backend).spatial_forward(x)

    def temporal_forward(self, feats: jax.Array, backend: str = "fused") -> jax.Array:
        """TCN head over the ordered window [B, T, C] -> logits, via the §4
        mapping + the 2-D conv kernel (SAME pad adjusted to causal)."""
        return self._executor(backend).temporal_forward(feats)

    def forward(self, x: jax.Array, backend: str = "fused") -> jax.Array:
        """Whole-network deploy inference.  Spatial graphs: [B,H,W,C] ->
        logits.  Temporal graphs: frames [B,T,H,W,C] -> logits over the
        ring window (last tcn_steps frames, zero history on the left) —
        bit-identical to streaming the frames through ``stream()`` (tested,
        including clips longer than the ring)."""
        g = self.graph
        if not g.is_temporal:
            return self.spatial_forward(x, backend)
        feats = jax.vmap(
            lambda f: self.spatial_forward(f, backend), in_axes=1, out_axes=1
        )(x)
        return self.temporal_forward(_ring_window(feats, g.tcn_steps), backend)

    # -- streaming (the silicon's autonomous mode) ------------------------

    def stream_step(
        self, stream: TCNStream, frame: jax.Array, backend: str = "fused"
    ) -> Tuple[jax.Array, TCNStream]:
        """Pure-functional step: one sensor frame -> (logits, new stream).
        CNN frontend -> push feature vector into the ring -> TCN head over
        the ordered window; past frames are never recomputed."""
        feat = self.spatial_forward(frame, backend)
        stream = stream.push(feat.astype(stream.buf.dtype))
        window = stream.ordered()
        if window.ndim == 2:
            window = window[None]
        return self.temporal_forward(window, backend), stream

    def stream(
        self, batch: Optional[int] = None, backend: str = "fused", jit: bool = True
    ) -> "StreamSession":
        """Open a stateful streaming session over this program's TCN ring
        (temporal graphs only): ``session.step(frame)`` per sensor frame.

            session = deployed.stream(batch=4)
            for frame in frames:
                logits = session.step(frame)     # one label per frame
        """
        if not self.graph.is_temporal:
            raise ValueError(f"{self.graph.name} has no TCN memory to stream into")
        return StreamSession(self, batch=batch, backend=backend, jit=jit)

    def serve(self, pool_size: int, backend: str = "fused", **kwargs):
        """Multi-sensor serving: a `repro.serving.SessionPool` of
        ``pool_size`` slots over this program — one jitted fixed-batch step,
        streams admitted/evicted mid-flight (continuous batching), optional
        ``sharding`` of the pool axis across local devices.  See
        `repro.serving` for the pool/scheduler API."""
        from repro.serving import SessionPool

        return SessionPool(self, pool_size, backend=backend, **kwargs)

    def serve_fleet(self, name: Optional[str] = None, backend: str = "fused",
                    **kwargs):
        """Fleet serving: a `repro.serving.FleetRouter` with this program
        registered under ``name`` (the graph name by default).  Register
        further nets on the returned router to serve many tenants —
        bucketed pools, bounded admission FIFOs, ladder autoscaling, async
        ingestion.  See `repro.serving.fleet`."""
        from repro.serving import FleetRouter

        router = FleetRouter(backend=backend, **kwargs)
        router.register(name or self.graph.name, self)
        return router


@dataclasses.dataclass
class DeployedProgram(PlanProgram):
    """Packed 2-bit weights of a `CutieGraph`, executed through its plan.

    ``tables`` layout (shared with the legacy ``quantize_for_deploy``):
      conv: [{"packed", "scale", ("bn_sd")} ...]   packed along C_in
      tcn:  [{"packed", "scale", "dilation", ("bn_sd")} ...]  §4-projected 2-D
      fc:   {"t", "scale"}                          dense int8 trits

    ``plan`` and ``memory`` are derived once, on first use (at the latest
    the first trace of a forward): the graph lowered by `sim.lower`, the
    tables bound as `sim.WeightMemory` images with each layer's effective
    scale folded (`effective_scale`)."""

    graph: CutieGraph
    tables: Dict

    @functools.cached_property
    def plan(self):
        from repro.sim.plan import lower

        return lower(self.graph)

    @functools.cached_property
    def memory(self):
        from repro.sim.memory import WeightMemory

        return WeightMemory.from_tables(self.plan, self.tables,
                                        self.graph.act_threshold)

    # -- artifact export (repro.artifact) ----------------------------------

    def to_artifact_bytes(self) -> bytes:
        """Assemble this program into ``.cutie`` container bytes — the
        compiled plan + the packed deploy tables, verbatim (see
        `repro.artifact`).  ``artifact.loads`` gives back a `LoadedProgram`
        that executes/streams/serves bit-identically with no graph."""
        from repro.artifact import assemble

        return assemble(self)

    def save_artifact(self, path) -> int:
        """Write the ``.cutie`` artifact to ``path``; returns byte count."""
        from repro.artifact import save

        return save(self, path)

    # -- silicon model -----------------------------------------------------

    def silicon_report(
        self, v: float = 0.5, hw: Optional[arch.CutieHW] = None,
        source: str = "analytic",
    ) -> "SiliconReport":
        """Cycles/energy for the deployed graph at supply ``v`` — see
        module-level `silicon_report` (the Table-1 loop).  ``source="sim"``
        prices the same `ExecutionPlan` every backend executes, with
        dynamic energy priced on THIS program's packed weight images
        (sparsity-aware) rather than the ideal dense schedule."""
        memory = self.memory if source == "sim" else None
        return silicon_report(self.graph, v=v, hw=hw, source=source,
                              memory=memory)


class StreamSession:
    """Stateful wrapper over the TCN ring memory (24 x C x 2 bit SCM).

    ``step(frame)`` returns the per-frame logits and advances the ring —
    the serving-facing analogue of `DeployedProgram.stream_step`, with the
    step function jitted once per session.

    The whole session state is ONE pytree (`core.tcn.StreamState`: ring +
    monotonic frame counter), so it moves wholesale: `export_state()` hands
    it out, `load_state()` takes it back, and a `repro.serving.SessionPool`
    scatters it into (or gathers it out of) a slot of the pooled `[P, T,
    C]` state — a session can hop between standalone and pooled execution
    with bit-identical logits.
    """

    def __init__(self, deployed: PlanProgram, batch: Optional[int] = None,
                 backend: str = "fused", jit: bool = True):
        check_backend(backend)
        self.deployed = deployed
        self.backend = backend
        self.batch = batch
        g = deployed.graph
        self.state = StreamState.create(g.tcn_steps, g.feature_channels, batch=batch)

        def fn(state: StreamState, frame: jax.Array):
            logits, ring = deployed.stream_step(state.ring, frame, backend)
            return logits, StreamState(ring=ring, steps_seen=state.steps_seen + 1)

        self._step = jax.jit(fn) if jit else fn

    @property
    def steps_seen(self) -> int:
        """Frames absorbed since creation/reset; monotonic across the ring
        cursor's wrap (it lives in the state pytree, inside the jit)."""
        return int(self.state.steps_seen)

    @property
    def window_warm(self) -> bool:
        """True once the full tcn_steps window holds real (non-pad) frames."""
        return self.steps_seen >= self.deployed.graph.tcn_steps

    def step(self, frame: jax.Array) -> jax.Array:
        """Absorb one sensor frame ([H,W,C], or [B,H,W,C] for batched
        sessions) and return the per-frame logits; the ring advances."""
        logits, self.state = self._step(self.state, frame)
        return logits

    def reset(self) -> None:
        """Forget all history: fresh zero ring, frame counter back to 0."""
        g = self.deployed.graph
        self.state = StreamState.create(g.tcn_steps, g.feature_channels, batch=self.batch)

    # -- state as a first-class value -------------------------------------

    def export_state(self) -> StreamState:
        """The session's complete state pytree (share/checkpoint/admit into
        a `SessionPool` via ``pool.admit(sid, state=...)``)."""
        return self.state

    def load_state(self, state: StreamState) -> None:
        """Resume from an exported/evicted state.  Shape-checked against
        this session's ring geometry."""
        expect = self.state.ring.buf.shape
        if state.ring.buf.shape != expect:
            raise ValueError(
                f"state ring shape {state.ring.buf.shape} != session {expect}"
            )
        self.state = state


# ---------------------------------------------------------------------------
# Graph -> analytical silicon model (core.cutie_arch)
# ---------------------------------------------------------------------------

def export_conv_layers(
    graph: CutieGraph,
    repeat_frontend: Optional[int] = None,
    hw: Optional[arch.CutieHW] = None,
) -> List[arch.ConvLayer]:
    """Lower the graph to the layer list of the analytic silicon model.

    Since the `repro.sim` subsystem, this is a thin view over THE one
    lowering path: `sim.lower` compiles the graph into an `ExecutionPlan`
    (where tiling and kernel-size handling live) and
    `ExecutionPlan.to_arch_layers` projects it onto `arch.ConvLayer` rows —
    temporal graphs count ``passes_per_inference`` frontend passes per
    classification, TCN layers appear in their §4 mapped 2-D form
    [ceil(T/D), D].  A non-default ``hw`` (smaller OCU array, wider
    ``max_cin``) re-tiles the schedule accordingly.
    """
    from repro.sim.plan import lower

    return lower(graph, hw).to_arch_layers(repeat_frontend)


@dataclasses.dataclass
class SiliconReport:
    """The closed loop: graph -> cycles/energy -> paper's measured corner.

    ``ideal`` is the uncalibrated schedule — the analytic pixel-per-cycle
    formula (``source="analytic"``) or the `repro.sim` execution plan's
    counted cycles (``source="sim"``); ``calibrated`` projects it onto the
    measured silicon through the published (inf/s, uJ) corner, and
    ``calibration.consistent`` is the model's validity check (cycle and
    energy overheads must agree — they do for both paper networks)."""

    graph_name: str
    v: float
    ideal: arch.NetReport
    calibration: Optional[arch.Calibration]
    calibrated: Optional[arch.NetReport]
    source: str = "analytic"

    @property
    def report(self) -> arch.NetReport:
        return self.calibrated if self.calibrated is not None else self.ideal

    @property
    def energy_uj(self) -> float:
        return self.report.energy_j * 1e6

    @property
    def inf_per_s(self) -> float:
        return self.report.inf_per_s

    @property
    def eff_topsw(self) -> float:
        return self.report.eff_topsw_paper

    @property
    def peak_eff_topsw(self) -> float:
        return self.ideal.peak_layer_eff_topsw_paper

    def summary(self) -> str:
        """Human-readable report block (the launchers print this)."""
        lines = [
            f"[{self.graph_name} @ {self.v:.2f} V, {self.source} schedule]",
            f"  peak efficiency : {self.peak_eff_topsw:8.0f} TOp/s/W",
            f"  energy/inference: {self.energy_uj:8.2f} uJ"
            + ("" if self.calibrated is not None else " (ideal schedule)"),
            f"  inference rate  : {self.inf_per_s:8.0f} inf/s",
            f"  avg efficiency  : {self.eff_topsw:8.1f} TOp/s/W",
        ]
        if self.calibration is not None:
            lines.append(
                f"  calibration     : cycle x{self.calibration.cycle_overhead:.2f}, "
                f"energy x{self.calibration.energy_overhead:.2f}, "
                f"consistent={self.calibration.consistent}"
            )
        return "\n".join(lines)


def silicon_report_from_plan(
    plan, v: float = 0.5, hw: Optional[arch.CutieHW] = None,
    source: str = "analytic", memory=None,
    paper_energy_uj: Optional[float] = None,
    paper_inf_per_s: Optional[float] = None,
) -> SiliconReport:
    """The graph-free Table-1 loop: price a compiled `ExecutionPlan`
    directly — what `LoadedProgram.silicon_report` runs on an artifact,
    where no `CutieGraph` exists.

    ``source="sim"`` counts the plan's schedule (stall counters included);
    a `repro.sim.WeightMemory` in ``memory`` additionally prices dynamic
    energy on the program's measured weight sparsity — the golden model
    runs on the real program, not an ideal.  ``source="analytic"`` projects
    the plan onto the closed formula.  The paper corner (when given)
    calibrates at the 0.5 V measurement point, as the paper does."""
    if source not in SILICON_SOURCES:
        raise ValueError(
            f"unknown silicon source {source!r}; expected one of {SILICON_SOURCES}"
        )
    hw = hw or arch.CutieHW()
    if source == "sim":
        from repro.sim import evaluate_plan

        def _eval(at_v: float) -> arch.NetReport:
            return evaluate_plan(plan, hw, at_v, memory=memory)
    else:
        layers = plan.to_arch_layers()

        def _eval(at_v: float) -> arch.NetReport:
            return arch.evaluate_network(plan.graph_name, layers, hw, at_v)

    ideal = _eval(v)
    cal = calibrated = None
    if paper_energy_uj is not None and paper_inf_per_s is not None:
        cal = arch.calibrate(_eval(0.5), paper_inf_per_s, paper_energy_uj)
        calibrated = arch.apply_calibration(ideal, cal)
    return SiliconReport(
        graph_name=plan.graph_name, v=v, ideal=ideal, calibration=cal,
        calibrated=calibrated, source=source,
    )


def silicon_report(
    graph: CutieGraph, v: float = 0.5, hw: Optional[arch.CutieHW] = None,
    source: str = "analytic", memory=None,
) -> SiliconReport:
    """Evaluate the CUTIE silicon model on this graph and, when the graph
    carries a published corner, calibrate against it (at the paper's 0.5 V
    measurement point, as the paper does).

    ``source`` picks the cycle model: ``"analytic"`` is the closed
    pixel-per-cycle formula over `export_conv_layers`; ``"sim"`` lowers the
    graph to its `repro.sim.ExecutionPlan` and ingests the simulator's
    per-layer cycle counters (`arch.evaluate_network_counts`) — same
    electrical model, auditable schedule, feature-memory stall counters
    included.  The two must reconcile within the gated tolerance
    (`repro.sim.reconcile`, CI ``sim-smoke``).  ``memory`` (a
    `repro.sim.WeightMemory`, sim source only) switches dynamic energy to
    the program's measured weight sparsity — `DeployedProgram
    .silicon_report` passes its own packed images through here."""
    if source not in SILICON_SOURCES:
        raise ValueError(
            f"unknown silicon source {source!r}; expected one of {SILICON_SOURCES}"
        )
    hw = hw or arch.CutieHW()
    from repro.sim.plan import lower

    return silicon_report_from_plan(
        lower(graph, hw), v=v, hw=hw, source=source, memory=memory,
        paper_energy_uj=graph.paper_energy_uj,
        paper_inf_per_s=graph.paper_inf_per_s,
    )
