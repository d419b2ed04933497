"""Benchmark entry point: one cell, one run, one JSON result line.

  python3 perfbench/run.py --workload er1000.pendulum --seed 7 \\
      --seconds 10 --trace 0

Runs on the machine it is started on and refuses (exit 3, no result)
without a TPU or with fewer chips than the cell asks for. See
perfbench/harness.py for what a run does and PERF.md for the cells.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from perfbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
