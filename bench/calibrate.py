#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload tall.mid --seeds 11 12 13

For each seed: write the cell's dataset, make one fit through the same
front door, sizes and compiled programs as a run's window, and compare it
with the plain reference; then compare the control, the reference
computed with every operation rounded to bfloat16 and put in the
program's place, teacher-forced on the program's picks
(``reference.control_numbers``).  Prints one JSON line per seed and,
last, the widest program reading and the narrowest control reading of
each number.  Runs only on TPU chips, in one process so that set-up is
paid once.
"""

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from mrmrbench import cli, corral, manifest, reference  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import numpy as np

    spec = manifest.load()
    cell = manifest.cell(spec, args.workload)
    config = manifest.config(spec, cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    cli.set_environment()
    devices = cli.require_chips(int(cell["chips"]))
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    worst = {k: 0.0 for k in reference.LIMITS}
    least = {k: float("inf") for k in reference.LIMITS}
    for seed in args.seeds:
        x_path, y_path, _ = corral.ensure_dataset(cli.DATA_DIR, config, seed)
        fit = cli.fitter(config, traffic, devices, x_path, y_path)
        t = time.perf_counter()
        answer, _ = fit()
        fit_s = time.perf_counter() - t
        X = np.load(str(x_path), mmap_mode="r")
        y = np.load(str(y_path), mmap_mode="r")
        tables = reference.count_tables(
            X, y, answer.ids.tolist(), int(config["num_values"]),
            int(config["num_classes"]),
        )
        ref = reference.Scorer(tables, traffic["criterion"])
        control = reference.Scorer(
            tables, traffic["criterion"], reference.bf16_round
        )
        got = reference.compare(answer, ref)
        ctl = reference.control_numbers(control, ref, answer.ids)
        for k in worst:
            worst[k] = max(worst[k], got[k])
            least[k] = min(least[k], ctl[k])
        print(json.dumps(dict(
            seed=seed, fit_s=fit_s, program=got, control=ctl,
            ids=answer.ids.tolist(),
        )), flush=True)
    print(json.dumps(dict(
        workload=args.workload, seeds=len(args.seeds),
        program_widest=worst, control_narrowest=least,
        device=dict(kind=devices[0].device_kind, count=len(devices)),
    )), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
