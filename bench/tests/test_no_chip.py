"""Without a TPU the benchmark exits non-zero and prints no result, and so
does a checkout that holds only BENCHMARK.json and the benchmark's files."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def run(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dvs_pool8", "--seed", "5000000011",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("layout", ["checkout", "benchmark_files_only"])
def test_exits_non_zero_without_a_chip(tmp_path, layout):
    root = ROOT
    if layout == "benchmark_files_only":
        root = tmp_path
        shutil.copy(ROOT / "BENCHMARK.json", root)
        shutil.copytree(ROOT / "bench", root / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run(root)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "no TPU found" in p.stderr or "repro" in p.stderr
