"""Discovery by name: everything a cell needs, found from ``BENCHMARK.json``.

* the cell's configuration: the JSON file that ``configs[].file`` names,
  and beside it the Python module its ``module`` key names (the plain
  reference);
* the cell's traffic: ``bench/traffic/<traffic>.json``, a data file that
  names its driver and its input generator;
* the driver: ``bench/drivers/<driver>.py``, a module with a ``Driver``
  class and ``lower`` (the timed program, for ``bench/rehearse.py``).
  ``Driver(program, cfg, traffic, rng, make_inputs, tracer)`` builds the
  cell's inputs and entry; it has ``library`` (the inputs, for the
  reference), ``rows`` (rows one step computes), ``warm_up()``,
  ``measure(seconds) -> Window``, ``devices()`` and ``release()``;
* the input generator: ``bench/inputs/<kind>.py``, a module with
  ``generate(rng, lead, cfg, **params)``;
* each metric: ``bench/metrics/<metric name>.py``, a module with
  ``read(run) -> float | None``; where that file is absent, the file of
  the name before its first dot (``step_mfu.cifar`` ->
  ``step_mfu.py``), for a quantity split by the end-to-end metric it moves.

A later cell, mix or metric is new files plus new entries: nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(LookupError):
    """A name in ``BENCHMARK.json`` that the files do not back."""


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric names hold dots, so not by name)."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench_dir = self.root / "bench"

    def cell(self, name: str) -> Dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r}; known: "
                        f"{[w['name'] for w in self.doc['workloads']]}")

    def _config_file(self, name: str) -> Path:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return self.root / c["file"]
        raise SpecError(f"no config {name!r}")

    def config(self, name: str) -> Dict:
        return json.loads(self._config_file(name).read_text())

    def config_module(self, name: str) -> ModuleType:
        """The configuration's plain reference, the module its file names."""
        return load_module(self._config_file(name).parent / self.config(name)["module"])

    def traffic(self, name: str) -> Dict:
        path = self.bench_dir / "traffic" / f"{name}.json"
        if not path.is_file():
            raise SpecError(f"no traffic file {path}")
        return json.loads(path.read_text())

    def metrics_for(self, cell: str, group: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        those that list it, and those without a ``workloads`` key (for a
        per-layer metric: where the cell reports the metric it moves)."""
        e2e = [m for m in self.doc["end_to_end"]
               if "workloads" not in m or cell in m["workloads"]]
        if group == "end_to_end":
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.doc["per_layer"]
                if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)]

    def driver(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "drivers" / f"{name}.py")

    def inputs(self, kind: str) -> ModuleType:
        return load_module(self.bench_dir / "inputs" / f"{kind}.py")

    def reader(self, metric: str) -> ModuleType:
        path = self.bench_dir / "metrics" / f"{metric}.py"
        if not path.is_file():
            path = path.with_name(metric.split(".", 1)[0] + ".py")
        return load_module(path)
