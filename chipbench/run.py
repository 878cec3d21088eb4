#!/usr/bin/env python3
"""Runs one benchmark cell on the chip and prints its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``. The run warms the cell's
executables (set-up), drives the planning service for ``--seconds`` with the
cell's traffic from ``--seed``, checks the answers against the plain
references, and prints one JSON object as the last line of standard output.
With ``--trace 1`` the window is traced and the line holds the per-layer
metrics. Without a TPU it exits non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.harness import main, use_compile_cache  # noqa: E402

if __name__ == "__main__":
    use_compile_cache()
    sys.exit(main(t_process=T_PROCESS))
