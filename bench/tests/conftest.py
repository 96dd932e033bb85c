"""The benchmark's own tests run on the CPU, at test sizes, without a chip:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
