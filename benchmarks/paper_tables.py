"""Benchmarks reproducing the paper's tables/figures from the CUTIE model.

  * table1()         — Table 1: CIFAR-10 comparison vs [8]/[9]
                       (energy/inference, throughput, peak efficiency at
                       0.5 V and 0.9 V).
  * fig5()           — energy/inference + inferences/sec vs voltage.
  * fig6()           — peak energy efficiency + peak throughput vs voltage.
  * silicon_sweep()  — registry nets x voltage corners x {analytic, sim}
                       cycle/energy rows; ``--silicon`` writes them to the
                       committed ``BENCH_silicon.json``, whose analytic-vs-
                       sim divergence is gated by
                       ``scripts/check_bench_regression.py --silicon``.

The layer lists come from the `repro.api` registry graphs — the SAME graphs
that drive QAT/deployment — lowered through `export_conv_layers`, so these
tables stay in lockstep with the executable models.  Each row validates
against the paper's reported numbers where the paper is internally
consistent; discrepancies are printed with the calibration factor.

    python benchmarks/paper_tables.py                  # print the tables
    python benchmarks/paper_tables.py --silicon        # write BENCH_silicon.json
    python benchmarks/paper_tables.py --bitsim-check   # CI exactness gate
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT / "src"))

from repro.api import export_conv_layers, get_graph, silicon_report
from repro.core.cutie_arch import (
    PAPER,
    CutieHW,
    apply_calibration,
    calibrate,
    evaluate_network,
    voltage_sweep,
)
from repro.sim import reconcile

HW = CutieHW()

CIFAR_GRAPH = get_graph("cifar10_tnn")
DVS_GRAPH = get_graph("dvs_cnn_tcn")


def table1():
    """Table 1 comparison rows; returns list of (name, value, paper)."""
    r05 = silicon_report(CIFAR_GRAPH, v=0.5, hw=HW)
    r09 = silicon_report(CIFAR_GRAPH, v=0.9, hw=HW)
    cal = r05.calibration
    rows = [
        ("peak_eff_0.5V_TOp/s/W", r05.peak_eff_topsw, PAPER["peak_eff_0v5_topsw"]),
        ("peak_eff_0.9V_TOp/s/W", r09.peak_eff_topsw, PAPER["peak_eff_0v9_topsw"]),
        ("peak_tput_0.5V_TOp/s", r05.ideal.peak_tput_tops_paper, PAPER["peak_tput_0v5_tops"]),
        ("peak_tput_0.9V_TOp/s", r09.ideal.peak_tput_tops_paper, PAPER["peak_tput_0v9_tops"]),
        ("cifar_energy_uJ(calibrated)", r05.energy_uj, PAPER["cifar_energy_uj"]),
        ("cifar_inf_per_s(calibrated)", r05.inf_per_s, PAPER["cifar_inf_per_s"]),
        ("cifar_energy_uJ(ideal)", r05.ideal.energy_j * 1e6, PAPER["cifar_energy_uj"]),
        ("soa_improvement_vs_[8]",
         PAPER["peak_eff_0v5_topsw"] / PAPER["soa_binary_10nm_topsw"], 1.67),
        ("energy_vs_[9]_13.86uJ", PAPER["soa_cifar_energy_uj"][0] / r05.energy_uj, 13.86 / 2.72),
        ("energy_vs_[8]_3.2uJ", PAPER["soa_cifar_energy_uj"][1] / r05.energy_uj, 3.2 / 2.72),
        ("calib_cycle_overhead", cal.cycle_overhead, None),
        ("calib_energy_overhead", cal.energy_overhead, None),
    ]
    return rows


def fig5(steps: int = 9):
    """Voltage sweep rows: (net, V, uJ/inf, inf/s) — calibrated model."""
    out = []
    for graph, label, per_class in ((CIFAR_GRAPH, "cifar10", 1.0), (DVS_GRAPH, "dvs", 5.0)):
        layers = export_conv_layers(graph)
        r05 = evaluate_network(label, layers, HW, 0.5)
        # the paper counts CNN passes as 'inferences' for DVS (the TCN
        # memory amortizes the window); graph.paper_inf_per_s already holds
        # the per-classification target
        cal = calibrate(r05, graph.paper_inf_per_s, graph.paper_energy_uj)
        for r in voltage_sweep(layers, HW, label, steps=steps):
            rc = apply_calibration(r, cal)
            out.append((label, round(r.v, 3), rc.energy_j * 1e6, rc.inf_per_s * per_class))
    return out


def fig6(steps: int = 9):
    """(V, peak TOp/s/W, peak TOp/s) for the CIFAR first-layer burst."""
    out = []
    layers = export_conv_layers(CIFAR_GRAPH)
    for r in voltage_sweep(layers, HW, "cifar10", steps=steps):
        out.append((round(r.v, 3), r.peak_layer_eff_topsw_paper, r.peak_tput_tops_paper))
    return out


def dvs_tcn_soa_comparison():
    """§8 comparisons: energy/op vs the TCN KWS accelerator [10] and the
    energy ratios vs TrueNorth [2] / Loihi [11]."""
    rep = silicon_report(DVS_GRAPH, v=0.5, hw=HW)
    ours_topsw = rep.eff_topsw
    kws_lo, kws_hi = PAPER["soa_tcn_kws_topsw"]
    return [
        ("dvs_avg_eff_TOp/s/W", ours_topsw, None),
        ("vs_kws_accel_low", ours_topsw / kws_lo, "5-15x claimed"),
        ("vs_kws_accel_high", ours_topsw / kws_hi, None),
        ("truenorth_energy_ratio", PAPER["truenorth_energy_ratio"], 3250.0),
        ("loihi_energy_ratio", PAPER["loihi_energy_ratio"], 63.4),
    ]


# ---------------------------------------------------------------------------
# Registry nets x voltage corners x {analytic, sim}  ->  BENCH_silicon.json
# ---------------------------------------------------------------------------

SILICON_NETS = (
    "cifar10_tnn", "dvs_cnn_tcn", "cifar10_tnn_wide",
    "cifar10_tnn_smoke", "dvs_cnn_tcn_smoke", "cifar10_tnn_wide_smoke",
)
SILICON_CORNERS = (0.5, 0.65, 0.8)


def silicon_sweep(nets=SILICON_NETS, corners=SILICON_CORNERS):
    """One row per (net, V, source): cycles and energy under the analytic
    formula and under the `repro.sim` execution plan, plus the 0.5 V
    reconciliation (``divergence_at_0v5``).  Pure arithmetic — the rows are
    bit-reproducible across hosts, so the committed ``BENCH_silicon.json``
    doubles as the regression baseline for the silicon model itself."""
    rows = []
    for net in nets:
        graph = get_graph(net)
        rec = reconcile(graph, hw=HW)
        for v in corners:
            for source in ("analytic", "sim"):
                rep = silicon_report(graph, v=v, hw=HW, source=source)
                rows.append({
                    "net": net,
                    "v": v,
                    "source": source,
                    "cycles": rep.ideal.cycles,
                    "ideal_energy_uj": rep.ideal.energy_j * 1e6,
                    "ideal_inf_per_s": rep.ideal.inf_per_s,
                    "energy_uj": rep.energy_uj,
                    "inf_per_s": rep.inf_per_s,
                    "calibrated": rep.calibrated is not None,
                    "analytic_schedulable": rec["analytic_schedulable"],
                    "divergence_at_0v5": rec["divergence"],
                    # feature-memory serialization the analytic formula can
                    # never see — zero for every registry net on the Kraken
                    # bank geometry (double buffering holds by construction)
                    "stall_cycles": rec["stall_cycles"],
                })
    return rows


def write_silicon_bench(out: Path, nets=SILICON_NETS, corners=SILICON_CORNERS) -> int:
    rows = silicon_sweep(nets, corners)
    payload = {
        "meta": {
            "schema": "BENCH_silicon.v1",
            "nets": list(nets),
            "corners": list(corners),
            "note": (
                "deterministic model output - regenerate with "
                "'python benchmarks/paper_tables.py --silicon' and commit; "
                "gated by scripts/check_bench_regression.py --silicon"
            ),
        },
        "results": rows,
    }
    out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"[silicon] wrote {out} ({len(rows)} rows)")
    return 0


def check_bitsim_exactness(nets=("cifar10_tnn", "dvs_cnn_tcn", "cifar10_tnn_wide")) -> int:
    """CI `sim-smoke` gate: backend="bitsim" must be bit-exact vs "ref" on
    the paper-size registry nets — batch forward everywhere, plus a
    streamed-vs-batch check on the temporal net, plus the artifact round
    trip (assemble -> loads -> bitsim forward with no graph object) landing
    on the same logits.  Returns a nonzero exit code on any mismatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import artifact
    from repro.api import get_net

    failures = 0
    for name in nets:
        prog = get_net(name)
        g = prog.graph
        key = jax.random.PRNGKey(0)
        if g.is_temporal:
            x = (jax.random.uniform(key, (1, 3, *g.input_hw, g.input_ch))
                 < 0.05).astype(jnp.float32)
        else:
            x = jnp.sign(jax.random.normal(key, (1, *g.input_hw, g.input_ch)))
        dep = prog.quantize(prog.init(jax.random.PRNGKey(1)), calib=x)
        got = np.asarray(dep.forward(x, backend="bitsim"))
        want = np.asarray(dep.forward(x, backend="ref"))
        exact = bool((got == want).all())
        print(f"[sim-check] {name}: bitsim==ref {'OK' if exact else 'MISMATCH'}")
        failures += 0 if exact else 1
        if g.is_temporal:
            session = dep.stream(batch=1, backend="bitsim")
            for t in range(x.shape[1]):
                logits = session.step(x[:, t])
            s_exact = bool((np.asarray(logits) == got).all())
            print(f"[sim-check] {name}: stream==batch {'OK' if s_exact else 'MISMATCH'}")
            failures += 0 if s_exact else 1
        data = dep.to_artifact_bytes()
        loaded = artifact.loads(data)
        a_exact = bool(
            (np.asarray(loaded.forward(x, backend="bitsim")) == got).all()
            and artifact.reassemble(artifact.disassemble(data)) == data
        )
        print(f"[sim-check] {name}: artifact==graph {'OK' if a_exact else 'MISMATCH'}")
        failures += 0 if a_exact else 1
    return 1 if failures else 0


def _print_tables() -> None:
    for label, rows in (
        ("Table 1", table1()),
        ("DVS/TCN SoA", dvs_tcn_soa_comparison()),
    ):
        print(f"== {label} ==")
        for name, value, paper in rows:
            ref = "" if paper is None else f"   (paper: {paper})"
            print(f"  {name:32s} {value:12.4g}{ref}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--silicon", action="store_true",
                    help="write the nets x corners x sources sweep JSON")
    ap.add_argument("--bitsim-check", action="store_true",
                    help="bitsim-vs-ref bit-exactness on the paper-size nets")
    ap.add_argument("--out", type=Path, default=REPO_ROOT / "BENCH_silicon.json",
                    help="output path for --silicon")
    args = ap.parse_args(argv)
    if args.bitsim_check:
        return check_bitsim_exactness()
    if args.silicon:
        return write_silicon_bench(args.out)
    _print_tables()
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main())
