#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs only on TPU chips; see ``bench/mrmrbench/cli.py``.
"""

import time

T0 = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from mrmrbench.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
