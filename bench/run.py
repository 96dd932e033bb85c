"""The chip benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU chips of this machine and
prints one JSON result as the last line of standard output.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.  See ``harness/cli.py`` for what a run does.
"""
import sys
from pathlib import Path

_BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

from harness import cli, device  # noqa: E402

if __name__ == "__main__":
    sys.exit(cli.main(sys.argv[1:], device.process_start_time()))
