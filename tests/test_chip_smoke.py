"""`chip_smoke.py` and the compile-cache rule, checked without a chip.

The script must refuse to run anywhere but a TPU, and refuse to run without
the repository beside it, printing no result in either case.  Its phases
are rehearsed here on the CPU at smoke size with the two chip-only checks
(platform, Pallas kernel in the HLO) stubbed — the control flow, the pool
contract and the fused-vs-ref comparison are the same code the chip runs.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]


def _run_smoke(cwd: Path):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            continue
    return True


def test_refuses_a_host_without_tpu():
    proc = _run_smoke(REPO)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert _no_result(proc.stdout)


def test_refuses_to_run_without_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert "repro package is not next to this script" in proc.stderr
    assert _no_result(proc.stdout)


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    seen = []
    monkeypatch.setattr(mod, "require_tpu", lambda n: jax.devices())
    monkeypatch.setattr(mod, "assert_kernel",
                        lambda lowered, what: seen.append((what, lowered.as_text())))
    monkeypatch.setattr(mod, "DVS_NET", "dvs_cnn_tcn_smoke")
    monkeypatch.setattr(mod, "CIFAR_NET", "cifar10_tnn_smoke")
    monkeypatch.setattr(mod, "FRAMES", 10)  # still wraps the smoke net's 8-step ring
    mod.kernel_checks = seen
    return mod


def test_phases_pass_at_smoke_size_on_cpu(smoke):
    assert smoke.serve_phase() == []
    assert smoke.forward_phase() == []
    assert [what for what, _ in smoke.kernel_checks] == [
        "dvs_cnn_tcn_smoke fused pool step", "cifar10_tnn_smoke fused forward",
    ]
    assert all(hlo for _, hlo in smoke.kernel_checks)


def test_compile_cache_defers_to_the_environment(monkeypatch):
    from repro.launch.compile_cache import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    from repro.launch.compile_cache import use_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
