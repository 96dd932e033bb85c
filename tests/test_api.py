"""The `repro.api` program layer: registry, QAT<->deploy, backends,
streaming, silicon report, and the single quantize->pad->pack path."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.api import quantize as apiq
from repro.api.program import CutieProgram, export_conv_layers
from repro.core import cutie_arch as arch
from repro.core.ternary import unpack_ternary
from repro.kernels import ops as kops


@pytest.fixture(scope="module")
def cifar_prog():
    return api.get_net("cifar10_tnn")


@pytest.fixture(scope="module")
def dvs_prog():
    return api.get_net("dvs_cnn_tcn")


@pytest.fixture(scope="module")
def cifar_batch():
    return jnp.sign(jax.random.normal(jax.random.PRNGKey(11), (4, 32, 32, 3)))


class TestRegistry:
    def test_round_trip(self, cifar_prog):
        assert isinstance(cifar_prog, CutieProgram)
        assert cifar_prog.graph.name == "cifar10_tnn"
        assert {"cifar10_tnn", "dvs_cnn_tcn"} <= set(api.list_nets())

    def test_legacy_aliases(self):
        assert api.get_net("cutie_cifar10").graph.n_classes == 10
        assert api.get_net("cutie_dvs").graph.n_classes == 12

    def test_unknown_net(self):
        with pytest.raises(KeyError):
            api.get_net("resnet50")

    def test_register_custom_net(self):
        g = api.CutieGraph(
            name="tiny", input_hw=(8, 8), input_ch=4, n_classes=4,
            layers=(api.conv2d(4, 8), api.pool(), api.flatten(), api.fc(8 * 16, 4)),
        )
        api.register_net("tiny_test_net", g)
        prog = api.get_net("tiny_test_net")
        p = prog.init(jax.random.PRNGKey(0))
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 4)))
        assert prog.forward_qat(p, x).shape == (2, 4)

    def test_graph_validation_rejects_bad_channels(self):
        g = api.CutieGraph(
            name="bad", input_hw=(8, 8), input_ch=4, n_classes=4,
            layers=(api.conv2d(3, 8), api.flatten(), api.fc(8 * 64, 4)),
        )
        with pytest.raises(ValueError):
            g.validate()


class TestQATDeployAgreement:
    def test_exact_on_ref_backend(self, cifar_batch):
        """With the per-channel QAT grid and BN calibration, the packed-
        weight deploy path reproduces forward_qat to float round-off on the
        calibration batch — one network definition, one numerics."""
        graph = dataclasses.replace(api.get_graph("cifar10_tnn"), qat_per_channel=True)
        prog = CutieProgram(graph)
        p = prog.init(jax.random.PRNGKey(3))
        qat = prog.forward_qat(p, cifar_batch)
        deployed = prog.quantize(p, calib=cifar_batch)
        dep = deployed.forward(cifar_batch, backend="ref")
        np.testing.assert_allclose(np.asarray(qat), np.asarray(dep), rtol=1e-4, atol=1e-4)

    def test_legacy_grid_logits_track_qat(self, cifar_prog, cifar_batch):
        """On the legacy per-layer QAT grid the weight grids differ slightly
        (per-layer vs per-channel thresholds), so agreement is approximate:
        calibrated deployment logits must strongly correlate with QAT."""
        p = cifar_prog.init(jax.random.PRNGKey(4))
        qat = np.asarray(cifar_prog.forward_qat(p, cifar_batch))
        dep = np.asarray(
            cifar_prog.quantize(p, calib=cifar_batch).forward(cifar_batch, backend="ref")
        )
        cos = float((qat * dep).sum() / (np.linalg.norm(qat) * np.linalg.norm(dep)))
        assert cos > 0.5, cos


class TestTallTCNKernels:
    def test_kh5_tcn_deploy_aligns_with_qat(self):
        """5-tap TCN kernels (kernel height 5): the deploy path's causal pad
        must line up with conv2d_undilated's schedule — QAT and ref-backend
        deploy agree exactly on the shared per-channel grid."""
        g = api.CutieGraph(
            name="tall_tcn", input_hw=(4, 4), input_ch=2, n_classes=3,
            tcn_steps=8, qat_per_channel=True,
            layers=(api.conv2d(2, 4), api.global_pool(),
                    api.LayerSpec(kind="tcn", c_in=4, c_out=4, kernel=(5, 3),
                                  taps=5, dilation=2),
                    api.last_step(), api.fc(4, 3)),
        )
        prog = CutieProgram(g)
        p = prog.init(jax.random.PRNGKey(14))
        frames = (jax.random.uniform(jax.random.PRNGKey(15), (2, 8, 4, 4, 2)) < 0.3
                  ).astype(jnp.float32)
        qat = prog.forward_qat(p, frames)
        dep = prog.quantize(p, calib=frames).forward(frames, backend="ref")
        np.testing.assert_allclose(np.asarray(qat), np.asarray(dep), rtol=1e-4, atol=1e-4)


class TestBackends:
    def test_all_backends_agree(self, cifar_prog, cifar_batch):
        p = cifar_prog.init(jax.random.PRNGKey(5))
        deployed = cifar_prog.quantize(p, calib=cifar_batch)
        outs = {b: np.asarray(deployed.forward(cifar_batch, backend=b))
                for b in api.BACKENDS}
        assert set(outs) == {"fused", "ref", "bitsim"}
        np.testing.assert_array_equal(outs["fused"], outs["ref"])
        np.testing.assert_array_equal(outs["bitsim"], outs["ref"])

    def test_unknown_backend_raises(self, cifar_prog, cifar_batch):
        p = cifar_prog.init(jax.random.PRNGKey(5))
        deployed = cifar_prog.quantize(p)
        with pytest.raises(ValueError):
            deployed.forward(cifar_batch, backend="cuda")


class TestStreaming:
    def test_stream_equals_batch_forward(self, dvs_prog):
        """Frame-by-frame streaming through the TCN ring memory must equal
        the batched window forward — the silicon memory is transparent."""
        p = dvs_prog.init(jax.random.PRNGKey(6))
        deployed = dvs_prog.quantize(p)
        frames = (jax.random.uniform(jax.random.PRNGKey(7), (2, 4, 64, 64, 2)) < 0.05
                  ).astype(jnp.float32)
        session = deployed.stream(batch=2)
        for t in range(4):
            logits_stream = session.step(frames[:, t])
        logits_batch = deployed.forward(frames)
        np.testing.assert_allclose(
            np.asarray(logits_stream), np.asarray(logits_batch), rtol=1e-5, atol=1e-5
        )
        assert session.steps_seen == 4

    def test_long_clip_forward_matches_streaming(self):
        """When the clip is longer than the ring, batch forward must use
        exactly the window the ring holds (last tcn_steps frames) — not the
        whole clip."""
        g = api.CutieGraph(
            name="tiny_tcn_long", input_hw=(4, 4), input_ch=2, n_classes=3, tcn_steps=3,
            layers=(api.conv2d(2, 4), api.global_pool(),
                    api.tcn(4, 4, dilation=1), api.last_step(), api.fc(4, 3)),
        )
        prog = CutieProgram(g)
        deployed = prog.quantize(prog.init(jax.random.PRNGKey(1)))
        frames = (jax.random.uniform(jax.random.PRNGKey(2), (1, 7, 4, 4, 2)) < 0.3
                  ).astype(jnp.float32)
        session = deployed.stream(batch=1, backend="ref")
        for t in range(7):
            logits_stream = session.step(frames[:, t])
        logits_batch = deployed.forward(frames, backend="ref")
        np.testing.assert_allclose(
            np.asarray(logits_stream), np.asarray(logits_batch), rtol=1e-5, atol=1e-5
        )

    def test_steps_seen_is_monotonic_past_ring_wrap(self):
        """steps_seen must keep counting after the ring cursor wraps."""
        g = api.CutieGraph(
            name="tiny_tcn", input_hw=(4, 4), input_ch=2, n_classes=3, tcn_steps=3,
            layers=(api.conv2d(2, 4), api.global_pool(),
                    api.tcn(4, 4, dilation=1), api.last_step(), api.fc(4, 3)),
        )
        prog = CutieProgram(g)
        deployed = prog.quantize(prog.init(jax.random.PRNGKey(0)))
        session = deployed.stream(batch=1, backend="ref")
        for t in range(5):  # wraps the 3-slot ring
            session.step(jnp.zeros((1, 4, 4, 2)))
        assert session.steps_seen == 5
        assert session.window_warm
        session.reset()
        assert session.steps_seen == 0 and not session.window_warm

    def test_reset_replays_identically(self):
        """reset() must restore the exact initial state: replaying the same
        clip after a reset reproduces the first pass bit-for-bit, and the
        warm-window flag follows steps_seen across the reset."""
        g = api.CutieGraph(
            name="tiny_tcn_reset", input_hw=(4, 4), input_ch=2, n_classes=3,
            tcn_steps=3,
            layers=(api.conv2d(2, 4), api.global_pool(),
                    api.tcn(4, 4, dilation=1), api.last_step(), api.fc(4, 3)),
        )
        prog = CutieProgram(g)
        deployed = prog.quantize(prog.init(jax.random.PRNGKey(2)))
        frames = (jax.random.uniform(jax.random.PRNGKey(3), (1, 4, 4, 4, 2)) < 0.3
                  ).astype(jnp.float32)
        session = deployed.stream(batch=1, backend="ref")
        first = [np.asarray(session.step(frames[:, t])) for t in range(4)]
        assert session.window_warm
        session.reset()
        assert session.steps_seen == 0 and not session.window_warm
        second = [np.asarray(session.step(frames[:, t])) for t in range(4)]
        for a, b in zip(first, second):
            np.testing.assert_array_equal(a, b)

    def test_export_load_state_round_trip(self):
        """export_state/load_state hand the session's pytree around without
        perturbing the stream (and shape-check foreign states)."""
        g = api.CutieGraph(
            name="tiny_tcn_state", input_hw=(4, 4), input_ch=2, n_classes=3,
            tcn_steps=3,
            layers=(api.conv2d(2, 4), api.global_pool(),
                    api.tcn(4, 4, dilation=1), api.last_step(), api.fc(4, 3)),
        )
        prog = CutieProgram(g)
        deployed = prog.quantize(prog.init(jax.random.PRNGKey(4)))
        frames = (jax.random.uniform(jax.random.PRNGKey(5), (1, 4, 4, 4, 2)) < 0.3
                  ).astype(jnp.float32)
        a = deployed.stream(batch=1, backend="ref")
        b = deployed.stream(batch=1, backend="ref")
        a.step(frames[:, 0]); a.step(frames[:, 1])
        b.load_state(a.export_state())
        assert b.steps_seen == 2
        np.testing.assert_array_equal(
            np.asarray(a.step(frames[:, 2])), np.asarray(b.step(frames[:, 2]))
        )
        wrong = deployed.stream(batch=2, backend="ref")
        with pytest.raises(ValueError, match="ring shape"):
            b.load_state(wrong.export_state())

    def test_stream_on_spatial_net_raises(self, cifar_prog):
        p = cifar_prog.init(jax.random.PRNGKey(6))
        with pytest.raises(ValueError):
            cifar_prog.quantize(p).stream()

    def test_qat_full_pass_shapes(self, dvs_prog):
        p = dvs_prog.init(jax.random.PRNGKey(8))
        frames = jnp.zeros((2, 5, 64, 64, 2))
        assert dvs_prog.forward_qat(p, frames).shape == (2, 12)


class TestSiliconReport:
    def test_cifar_graph_exports_paper_layers(self):
        """The graph lowers to exactly the Table-1 CIFAR layer list."""
        ours = export_conv_layers(api.get_graph("cifar10_tnn"))
        paper = arch.cifar10_9layer_layers()
        assert ours == paper

    def test_dvs_graph_exports_paper_layers(self):
        ours = export_conv_layers(api.get_graph("dvs_cnn_tcn"))
        paper = arch.dvs_cnn_tcn_layers()
        # ours additionally counts the tiny FC head (1 cycle, 2304 Op)
        assert ours[:-1] == paper
        assert ours[-1].is_fc

    def test_cifar_reproduces_paper_corner(self, cifar_prog):
        """deployed.silicon_report(v=0.5) must land on the paper's measured
        2.72 uJ / 3200 inf/s within the Calibration.consistent tolerance."""
        p = cifar_prog.init(jax.random.PRNGKey(9))
        rep = cifar_prog.quantize(p).silicon_report(v=0.5)
        assert rep.calibration is not None and rep.calibration.consistent
        assert abs(rep.energy_uj - arch.PAPER["cifar_energy_uj"]) < 0.01
        assert abs(rep.inf_per_s - arch.PAPER["cifar_inf_per_s"]) < 1.0
        # ideal-schedule numbers stay within the calibration overhead band
        assert rep.ideal.energy_j * 1e6 < arch.PAPER["cifar_energy_uj"]
        assert rep.summary()

    def test_dvs_report_calibrates(self, dvs_prog):
        """DVS calibrates onto the measured corner.  Note: the paper's DVS
        cycle/energy overheads disagree (1.2x vs 4.9x — its inf/s counting
        convention), so unlike CIFAR, `consistent` is not asserted."""
        rep = dvs_prog.silicon_report(v=0.5)
        assert rep.calibration is not None
        assert abs(rep.energy_uj - arch.PAPER["dvs_energy_uj"]) < 0.01
        assert abs(rep.inf_per_s - arch.PAPER["dvs_inf_per_s"] / 5.0) < 1.0

    def test_voltage_scaling(self, cifar_prog):
        lo = cifar_prog.silicon_report(v=0.5)
        hi = cifar_prog.silicon_report(v=0.9)
        assert hi.inf_per_s > lo.inf_per_s
        assert hi.energy_uj > lo.energy_uj


class TestQuantizeDedupe:
    """Exactly one quantize->pad->pack implementation repo-wide."""

    def test_ops_helpers_are_the_api_helpers(self):
        assert kops.quantize_pack_conv_weights is apiq.quantize_pack_conv_weights
        assert kops.quantize_pack_matmul_weights is apiq.quantize_pack_matmul_weights

    def test_deploy_tables_bit_identical_to_kernel_helper(self, cifar_prog):
        """The deploy path and the kernel-facing helper must produce
        bit-identical packed bytes for the same weights."""
        p = cifar_prog.init(jax.random.PRNGKey(10))
        deployed = cifar_prog.quantize(p)
        for lp, entry in zip(p["conv"], deployed.tables["conv"]):
            packed, scale = kops.quantize_pack_conv_weights(lp["w"])
            np.testing.assert_array_equal(np.asarray(entry["packed"]), np.asarray(packed))
            np.testing.assert_allclose(np.asarray(entry["scale"]), np.asarray(scale))

    def test_matmul_vs_conv_pack_share_codec(self):
        """Same trits packed along different axes unpack identically."""
        w = jax.random.normal(jax.random.PRNGKey(12), (12, 8))
        pk, _ = apiq.quantize_pad_pack(w, reduce_axes=0, pack_axis=0)
        pk2, _ = apiq.quantize_pad_pack(w, reduce_axes=0, pack_axis=1)
        np.testing.assert_array_equal(
            np.asarray(unpack_ternary(pk, axis=0)), np.asarray(unpack_ternary(pk2, axis=1))
        )

    def test_tcn_pack_matches_projection(self):
        w = jax.random.normal(jax.random.PRNGKey(13), (3, 8, 8))
        packed, scale = apiq.quantize_pack_tcn_weights(w)
        k2d = unpack_ternary(packed, axis=2)
        assert k2d.shape == (3, 3, 8, 8)
        # only the middle column carries taps (paper §4 projection)
        assert not np.asarray(k2d[:, 0]).any() and not np.asarray(k2d[:, 2]).any()
