"""Declarative ternary-network description — the input to `CutieProgram`.

A `CutieGraph` is a flat, ordered tuple of `LayerSpec`s over the layer kinds
the CUTIE datapath executes:

  * ``conv2d``      — SAME ternary convolution (the OCU array's native op;
                      3x3 by default, 1x1 for pointwise layers, an optional
                      output ``stride`` realized as a post-ternarize
                      subsample so every backend shares one conv kernel, and
                      an optional residual ``shortcut`` from an earlier conv)
  * ``pool``        — 2x2 max pool (the silicon's inter-layer pooling unit)
  * ``global_pool`` — spatial global average (DVS frontend -> feature vector)
  * ``flatten``     — [B,H,W,C] -> [B,H*W*C] (CIFAR head)
  * ``tcn``         — dilated causal 1-D conv, executed through the paper's
                      §4 mapping onto the *same* undilated 2-D conv engine
  * ``last_step``   — take the newest time step of a [B,T,C] sequence
  * ``fc``          — ternary-weight classifier matmul

The split between *spatial* layers (everything before the first temporal
kind) and *temporal* layers mirrors the silicon: the 2-D CNN frontend runs
once per sensor frame, pushes one feature vector into the 24-step TCN ring
memory, and the TCN head classifies over the ordered window.  A graph with
no temporal layers (CIFAR) is a plain one-shot classifier.

Layers form a chain — each consumes the previous layer's output — with one
exception, the residual shortcut: a conv2d with ``shortcut = k`` (the index
in ``layers`` of an earlier conv2d) adds layer k's ternary output ``a_k`` to
its scaled accumulator before the threshold,

    y_i = scale_i * conv(a_{i-1}, T_i) + S(a_k),    a_i = ternarize(y_i),

where ``S`` is the identity when the shapes match and otherwise He et al.'s
option A: keep every 2nd row and column from the top-left (as ``stride``
does) and append zero channels up to ``c_out``.  ``validate`` refuses every
other shape relation, a shortcut on a strided conv or on a conv a pool
follows, and a shortcut whose source a pool follows.  Every interpreter —
the QAT and deploy forwards, `repro.sim`'s plan and executor, the ``.cutie``
artifact — keeps ``a_k`` live until its consumer.

The graph is also the single source of truth for the analytical silicon
model: `repro.api.program.export_conv_layers` lowers it to
`core.cutie_arch.ConvLayer`s, so `deployed.silicon_report()` closes the loop
between the JAX model and the paper's Table 1 numbers.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

_TEMPORAL_KINDS = ("tcn", "last_step")
_WEIGHT_KINDS = ("conv2d", "tcn", "fc")


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One CUTIE-mappable layer.  Only the fields relevant to ``kind`` are
    meaningful; use the constructor helpers (`conv2d`, `pool`, ...) below."""

    kind: str
    c_in: int = 0
    c_out: int = 0
    kernel: Tuple[int, int] = (3, 3)
    taps: int = 3        # tcn: 1-D kernel taps (must fit kernel height)
    dilation: int = 1    # tcn: dilation D
    window: int = 2      # pool: window/stride
    stride: int = 1      # conv2d: output stride (post-ternarize subsample)
    shortcut: Optional[int] = None  # conv2d: index of the residual's source conv

    @property
    def has_weights(self) -> bool:
        return self.kind in _WEIGHT_KINDS


def conv2d(
    c_in: int, c_out: int, kernel: Tuple[int, int] = (3, 3), stride: int = 1,
    shortcut: Optional[int] = None,
) -> LayerSpec:
    """SAME ternary 2-D convolution — the OCU array's native op.  ``kernel``
    may be ``(1, 1)`` for a pointwise layer.  ``stride > 1`` subsamples the
    ternarized output (top-left phase) — because ternarization is
    elementwise, subsampling after it is bit-identical to a strided conv,
    so all backends reuse the one SAME-conv kernel.  A strided conv never
    absorbs a following pool (`CutieGraph.conv_pool_plan`).  ``shortcut``
    names an earlier conv2d whose output is added before the threshold
    (module docstring)."""
    return LayerSpec(kind="conv2d", c_in=c_in, c_out=c_out, kernel=kernel,
                     stride=stride, shortcut=shortcut)


def pool(window: int = 2) -> LayerSpec:
    """Max pool, window == stride — the silicon's inter-layer pooling unit
    (a pool directly after a conv2d is sunk into the fused kernel epilogue,
    see `CutieGraph.conv_pool_plan`)."""
    return LayerSpec(kind="pool", window=window)


def global_pool() -> LayerSpec:
    """Spatial global average: [B,H,W,C] -> [B,C] (the DVS frontend's
    feature-vector reduction before the TCN ring)."""
    return LayerSpec(kind="global_pool")


def flatten() -> LayerSpec:
    """[B,H,W,C] -> [B, H*W*C] (the CIFAR head's layout change)."""
    return LayerSpec(kind="flatten")


def tcn(c_in: int, c_out: int, dilation: int, taps: int = 3) -> LayerSpec:
    """Dilated causal 1-D conv, executed through the paper's §4 mapping on
    the same undilated 2-D engine (``taps`` must fit the kernel height)."""
    return LayerSpec(kind="tcn", c_in=c_in, c_out=c_out, dilation=dilation, taps=taps)


def last_step() -> LayerSpec:
    """Take the newest time step of a [B,T,C] sequence (TCN head -> FC)."""
    return LayerSpec(kind="last_step")


def fc(c_in: int, c_out: int) -> LayerSpec:
    """Ternary-weight classifier matmul (the OPU: integer accumulate, then
    per-class scale)."""
    return LayerSpec(kind="fc", c_in=c_in, c_out=c_out)


@dataclasses.dataclass(frozen=True)
class CutieGraph:
    """A full network: layers + input geometry + deployment metadata.

    ``passes_per_inference``: CNN frontend passes per classification — the
    DVS network of [6] feeds 5 frames into the TCN memory per label, and the
    silicon model must count those cycles (the TCN memory is exactly what
    makes the *other* 19 window steps free).

    ``paper_energy_uj`` / ``paper_inf_per_s``: the measured silicon corner
    this network calibrates against (None = no published numbers; the
    silicon report is then ideal-schedule only).
    """

    name: str
    layers: Tuple[LayerSpec, ...]
    input_hw: Tuple[int, int]
    input_ch: int
    n_classes: int
    act_threshold: float = 0.5
    weight_nu: float = 0.7
    # QAT quantization granularity.  False: one TWN threshold/scale per layer
    # (the legacy training recipe).  True: the per-output-channel grid the
    # deployment tables use — forward_qat then matches deployed.forward on
    # the ref backend to float round-off when quantize() is calibrated.
    qat_per_channel: bool = False
    tcn_steps: int = 24
    passes_per_inference: int = 1
    paper_energy_uj: Optional[float] = None
    paper_inf_per_s: Optional[float] = None

    # -- structure ---------------------------------------------------------

    @property
    def is_temporal(self) -> bool:
        return any(l.kind in _TEMPORAL_KINDS for l in self.layers)

    def _split(self) -> int:
        for i, l in enumerate(self.layers):
            if l.kind in _TEMPORAL_KINDS:
                return i
        return len(self.layers)

    @property
    def spatial_layers(self) -> Tuple[LayerSpec, ...]:
        """The 2-D frontend (everything executed per frame)."""
        return self.layers[: self._split()]

    @property
    def temporal_layers(self) -> Tuple[LayerSpec, ...]:
        """TCN head + classifier, operating on the [B, T, C] window."""
        return self.layers[self._split():]

    def conv_pool_plan(self) -> Tuple[int, ...]:
        """Per spatial conv2d, the window of an *immediately following* pool
        layer (0 when the conv feeds anything else) — the fusion plan the
        deploy backends use to sink CUTIE's pooling unit into the conv
        kernel's epilogue.  Length == number of spatial conv2d layers."""
        sp = self.spatial_layers
        plan: List[int] = []
        for i, l in enumerate(sp):
            if l.kind != "conv2d":
                continue
            nxt = sp[i + 1] if i + 1 < len(sp) else None
            fuse = (nxt is not None and nxt.kind == "pool" and l.stride == 1)
            plan.append(nxt.window if fuse else 0)
        return tuple(plan)

    @property
    def feature_channels(self) -> int:
        """Width of the feature vector entering the TCN memory (temporal
        graphs only) — the silicon's ring is tcn_steps x this x 2 bit."""
        for l in self.temporal_layers:
            if l.kind == "tcn":
                return l.c_in
        raise ValueError(f"{self.name}: no tcn layer")

    @property
    def shortcut_sources(self) -> Tuple[int, ...]:
        """Indices of the conv2d layers whose output a later layer's
        shortcut reads — the maps an interpreter must keep live."""
        return tuple(sorted({l.shortcut for l in self.layers
                             if l.shortcut is not None}))

    # -- validation --------------------------------------------------------

    def _check_shortcut(self, i: int, out: Dict[int, Tuple[int, int, int]],
                        shape: Tuple[int, int, int]) -> None:
        """Layer ``i``'s shortcut against the maps already computed
        (``out``: conv index -> its output (h, w, c)) and its own output
        ``shape``: identity or option A, nothing else."""
        l, where = self.layers[i], f"{self.name} layer {i} (conv2d)"
        k = l.shortcut
        nxt = self.layers[i + 1].kind if i + 1 < len(self.layers) else None
        if not 0 <= k < i or self.layers[k].kind != "conv2d":
            raise ValueError(f"{where}: shortcut {k} is not an earlier conv2d")
        if l.stride > 1 or nxt == "pool":
            raise ValueError(f"{where}: a shortcut needs stride 1 and no pool after")
        if self.layers[k + 1].kind == "pool":
            raise ValueError(f"{where}: shortcut source {k} is followed by a pool")
        (hk, wk, ck), (h, w, c) = out[k], shape
        if (hk, wk, ck) != (h, w, c) and not (
                (hk, wk) == (2 * h, 2 * w) and ck <= c):
            raise ValueError(
                f"{where}: shortcut map {hk}x{wk}x{ck} is neither {h}x{w}x{c} "
                f"nor its option-A source {2 * h}x{2 * w}x(<= {c})"
            )

    def validate(self) -> "CutieGraph":
        """Shape-chain the graph; raises ValueError on inconsistency."""
        h, w = self.input_hw
        c = self.input_ch
        seen_temporal = False
        flat: Optional[int] = None  # features after flatten, None otherwise
        conv_out: Dict[int, Tuple[int, int, int]] = {}
        for i, l in enumerate(self.layers):
            where = f"{self.name} layer {i} ({l.kind})"
            if l.kind in _TEMPORAL_KINDS:
                seen_temporal = True
            elif seen_temporal and l.kind != "fc":
                raise ValueError(f"{where}: spatial layer after temporal layers")
            if l.kind == "conv2d":
                if l.c_in != c:
                    raise ValueError(f"{where}: c_in {l.c_in} != incoming {c}")
                if l.stride < 1:
                    raise ValueError(f"{where}: stride {l.stride} < 1")
                if l.stride > 1 and (h % l.stride or w % l.stride):
                    raise ValueError(
                        f"{where}: {h}x{w} not divisible by stride {l.stride}"
                    )
                h, w = h // l.stride, w // l.stride
                c = l.c_out
                if l.shortcut is not None:
                    self._check_shortcut(i, conv_out, (h, w, c))
                conv_out[i] = (h, w, c)
            elif l.kind == "pool":
                if h % l.window or w % l.window:
                    raise ValueError(f"{where}: {h}x{w} not divisible by {l.window}")
                h, w = h // l.window, w // l.window
            elif l.kind == "global_pool":
                h = w = 1
            elif l.kind == "flatten":
                flat = h * w * c
            elif l.kind == "tcn":
                if l.c_in != c:
                    raise ValueError(f"{where}: c_in {l.c_in} != incoming {c}")
                if l.taps > l.kernel[0]:
                    raise ValueError(f"{where}: {l.taps} taps exceed kernel height")
                c = l.c_out
            elif l.kind == "last_step":
                pass
            elif l.kind == "fc":
                expect = flat if flat is not None else c
                if l.c_in != expect:
                    raise ValueError(f"{where}: c_in {l.c_in} != incoming {expect}")
                c = l.c_out
            else:
                raise ValueError(f"{where}: unknown layer kind")
        if c != self.n_classes:
            raise ValueError(
                f"{self.name}: final width {c} != n_classes {self.n_classes}"
            )
        return self
