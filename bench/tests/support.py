"""A test-size benchmark: the real harness, configurations' references,
drivers and metric readers, over the registry's smoke-size nets."""
from __future__ import annotations

import argparse
import json
import shutil
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"

CELLS = {
    "dvs_small": ("dvs_cnn_tcn_smoke", "events_small", 1),
    "dvs_small_x4": ("dvs_cnn_tcn_smoke", "events_small_x4", 4),
    "cifar_small": ("cifar10_tnn_smoke", "images_small", 1),
    # the configurations at their published widths, under small traffic
    "dvs_full": ("dvs_cnn_tcn", "events_small", 1),
    "cifar_full": ("cifar10_tnn", "images_small", 1),
}
REAL = {"dvs_cnn_tcn_smoke": "dvs_cnn_tcn", "cifar10_tnn_smoke": "cifar10_tnn"}


def make_bench(root: Path):
    """A checkout-like tree at ``root``: the real ``bench`` directory and
    configurations, plus test-size configurations (holding the real
    limits) and small traffic."""
    from harness import spec

    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    configs = [c for c in real["configs"]]
    for name in sorted(REAL):
        cfg = json.loads((DATA / f"{name}.json").read_text())
        cfg["limits"] = json.loads((BENCH / "configs" / f"{REAL[name]}.json").read_text())["limits"]
        (root / "bench" / "tests" / "data" / f"{name}.json").write_text(json.dumps(cfg))
        configs.append({"name": name, "source": cfg["source"],
                        "file": f"bench/tests/data/{name}.json", "reduced": [], "why": "test"})
    for name, pool, sharding in (("events_small", 4, None), ("events_small_x4", 8, 4)):
        traffic = json.loads((BENCH / "traffic" / "events_pool8.json").read_text())
        traffic.update(pool=pool, sharding=sharding, stream_frames=12, library=6)
        (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
    traffic = json.loads((BENCH / "traffic" / "images_b256.json").read_text())
    traffic.update(batch=8, library=3)
    (root / "bench" / "traffic" / "images_small.json").write_text(json.dumps(traffic))
    doc = dict(real, configs=configs, workloads=[
        {"name": n, "config": c, "traffic": t, "chips": k, "why": "test"}
        for n, (c, t, k) in CELLS.items()])
    for group in ("end_to_end", "per_layer"):
        doc[group] = [{k: v for k, v in m.items() if k != "workloads"} for m in real[group]]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return spec.Bench(root)


def cpu_devices(n: int):
    import jax

    return jax.devices()[:n]


def peaks_for_any(kind: str) -> dict:
    return json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def run(bench, cell: str, seed: int = 11, seconds: float = 0.5, trace: int = 0,
        control: bool = False) -> dict:
    """One run of a test-size cell on the CPU devices."""
    from harness import cli

    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds, trace=trace)
    return cli.run_cell(bench, args, time.time(), require_chip=cpu_devices,
                        lookup_peaks=peaks_for_any, control=control)
