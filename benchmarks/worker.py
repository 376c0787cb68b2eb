"""Benchmark worker: one mRMR job in a fresh process.

Run as a subprocess so the forced host-device count (the simulated cluster
size — the paper's "number of nodes") is set before jax initialises::

    PYTHONPATH=src REPRO_DEVICES=8 python -m benchmarks.worker \
        --rows 100000 --cols 1000 --select 10 --encoding conventional

Prints exactly one JSON dict on the last stdout line.
"""

import os

_DEVICES = int(os.environ.get("REPRO_DEVICES", "1"))
if _DEVICES > 1:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_DEVICES} "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo_analysis import analyze_hlo
from repro.core.mrmr import make_alternative_fn, make_conventional_fn
from repro.core.scores import MIScore, PearsonMIScore
from repro.data.synthetic import corral_dataset_np
from repro.dist.meshes import make_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, required=True, help="observations M")
    ap.add_argument("--cols", type=int, required=True, help="features N")
    ap.add_argument("--select", type=int, default=10)
    ap.add_argument("--encoding", default="conventional",
                    choices=["conventional", "alternative"])
    ap.add_argument("--score", default="mi", choices=["mi", "pearson"])
    ap.add_argument("--incremental", type=int, default=0,
                    help="0 = paper-faithful recompute, 1 = running-sum")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--analyze", type=int, default=0,
                    help="1 = also lower+compile and parse collective bytes")
    args = ap.parse_args()

    n_dev = len(jax.devices())
    X_np, y_np = corral_dataset_np(args.rows, args.cols, seed=args.seed)

    if args.encoding == "conventional":
        mesh = make_mesh((n_dev,), ("data",)) if n_dev > 1 else None
        # pad rows to the device count (out-of-range value 2 -> zero one-hot)
        pad = (-args.rows) % n_dev
        if pad:
            X_np = np.concatenate([X_np, np.full((pad, args.cols), 2, np.int8)])
            y_np = np.concatenate([y_np, np.full((pad,), 2, np.int8)])
        score = MIScore(num_values=2, num_classes=2)
        fn = make_conventional_fn(
            args.select, score, mesh=mesh, obs_axes=("data",),
            incremental=bool(args.incremental),
        )
        if mesh is not None:
            X = jax.device_put(X_np, NamedSharding(mesh, P("data", None)))
            y = jax.device_put(y_np, NamedSharding(mesh, P("data")))
        else:
            X, y = jnp.asarray(X_np), jnp.asarray(y_np)
    else:
        # alternative encoding stores features as rows: (N, M)
        Xr_np = np.ascontiguousarray(X_np.T)
        mesh = make_mesh((n_dev,), ("model",)) if n_dev > 1 else None
        pad = (-args.cols) % n_dev
        if pad:
            Xr_np = np.concatenate(
                [Xr_np, np.zeros((pad, args.rows), np.int8)]
            )
        if args.score == "mi":
            score = MIScore(num_values=2, num_classes=2)
            Xr_np = Xr_np.astype(np.int8)
        else:
            score = PearsonMIScore()
            Xr_np = Xr_np.astype(np.float32)
        fn = make_alternative_fn(
            args.select, score, args.cols, mesh=mesh, feat_axes=("model",),
            incremental=bool(args.incremental),
        )
        if mesh is not None:
            X = jax.device_put(Xr_np, NamedSharding(mesh, P("model", None)))
            y = jax.device_put(
                y_np.astype(Xr_np.dtype), NamedSharding(mesh, P())
            )
        else:
            X, y = jnp.asarray(Xr_np), jnp.asarray(y_np.astype(Xr_np.dtype))

    rec = dict(vars(args), devices=n_dev)

    if args.analyze:
        lowered = fn.lower(X, y)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t0, 2)
        hc = analyze_hlo(compiled.as_text())
        rec["hlo"] = {
            "flops_per_device": hc["flops"],
            "bytes_per_device": hc["bytes"],
            "collective_operand_bytes": hc["collectives"]["operand_bytes"],
            "collective_wire_bytes": hc["collectives"]["wire_bytes"],
            "by_type": {
                k: v["operand_bytes"]
                for k, v in hc["collectives"]["by_type"].items()
            },
        }

    # warmup (compile + first run)
    t0 = time.perf_counter()
    sel, gains, _rel = fn(X, y)
    sel.block_until_ready()
    rec["warmup_s"] = round(time.perf_counter() - t0, 3)

    times = []
    for _ in range(args.repeats):
        t0 = time.perf_counter()
        sel, gains, _rel = fn(X, y)
        sel.block_until_ready()
        times.append(time.perf_counter() - t0)
    sel_np = np.asarray(sel).tolist()
    rec.update(
        times_s=[round(t, 4) for t in times],
        best_s=round(min(times), 4),
        mean_s=round(float(np.mean(times)), 4),
        selected=sel_np,
        gains=[round(float(g), 4) for g in np.asarray(gains)],
        # dataset ground truth: 8 relevant cols (0..7) + correlated col 8
        relevant_hits=len(set(sel_np) & set(range(9))),
    )
    print(json.dumps(rec))


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
