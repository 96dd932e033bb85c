"""The chip: find it or fail, count compilations, read memory, and keep
JAX's compilation cache at a fixed path inside the checkout."""
from __future__ import annotations

import os
import sys
import time

from harness.spec import ROOT

CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def use_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set (the program then uses the same), else ``<checkout>/.jax_cache``.
    Every program is cached, however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_tpu(chips: int):
    """The first ``chips`` TPU devices; never a CPU fallback."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no TPU found: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees {len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts the programs JAX builds: every request (``n``), and those the
    persistent cache answered (``hits``); ``n - hits`` were compiled."""

    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, duration: float, **kwargs) -> None:
        if name == self.REQUEST:
            self.n += 1

    def _on_event(self, name: str, **kwargs) -> None:
        if name == self.HIT:
            self.hits += 1


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no statistics)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def process_start_time() -> float:
    """Wall-clock time this process started (Linux ``/proc``), else now."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
