"""Benchmark harness entry point — one suite per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run``            (smoke scale, default)
``REPRO_BENCH_SCALE=full python -m benchmarks.run``    (paper-scale inputs)
``python -m benchmarks.run --only fig5_rows,fig8_nodes``

Prints ``name,us_per_call,derived`` CSV rows per point and writes JSON under
``results/bench/``; EXPERIMENTS.md tables are regenerated from those files
by ``benchmarks/report.py``.
"""

from __future__ import annotations

import argparse
import importlib
import time
import traceback

# Suite name -> module with a ``main()``.  Modules import lazily, in run
# order: the fig suites start worker processes that each claim the
# accelerator, so this process must not touch JAX before they finish.
# ``kernels`` runs in this process and therefore comes last.
SUITES = {
    "fig5_rows": "benchmarks.fig5_rows",
    "fig6_cols": "benchmarks.fig6_cols",
    "fig7_selected": "benchmarks.fig7_selected",
    "fig8_nodes": "benchmarks.fig8_nodes",
    "fig9_encodings": "benchmarks.fig9_encodings",
    "kernels": "benchmarks.bench_kernels",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names (default: all)")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(SUITES)
    unknown = sorted(set(names) - set(SUITES))
    if unknown:
        raise SystemExit(f"unknown suites {unknown}; known: {list(SUITES)}")
    names.sort(key=list(SUITES).index)  # in-process suites last
    failed = []
    print("name,us_per_call,derived")
    for name in names:
        t0 = time.time()
        try:
            importlib.import_module(SUITES[name]).main()
            print(f"# suite {name} done in {time.time()-t0:.0f}s", flush=True)
        except Exception:
            failed.append(name)
            print(f"# suite {name} FAILED:\n{traceback.format_exc()}")
    if failed:
        raise SystemExit(f"failed suites: {failed}")


if __name__ == "__main__":
    main()
