"""Feature-selection driver — the paper's job as a production CLI.

    PYTHONPATH=src python -m repro.launch.select --rows 100000 --cols 1000 \
        --select 10 --encoding conventional

    # 2-D grid over 8 simulated devices, explicit mesh shape:
    PYTHONPATH=src REPRO_DEVICES=8 python -m repro.launch.select \
        --encoding grid --mesh-obs 4 --mesh-feat 2

    # Out-of-core: stream a memmapped .npy that never fits in device
    # memory, 65536 observations per block:
    PYTHONPATH=src python -m repro.launch.select \
        --input data.npy --target target.npy --block-obs 65536 --prefetch 2

    # Quotient-form mRMR (MIQ) instead of the paper's difference form;
    # any registered criterion runs on any engine, streamed or in-memory:
    PYTHONPATH=src python -m repro.launch.select --criterion miq

    # Class-conditioned objectives: JMI and CMIM fold I(x_k; x_j | y)
    # against the marginal redundancy — same pass count as mid, the
    # redundancy sweep just carries a class axis:
    PYTHONPATH=src python -m repro.launch.select --criterion jmi
    PYTHONPATH=src python -m repro.launch.select --criterion cmim

    # Parquet input (pyarrow): row batches decode block-by-block from the
    # file's row groups; target = last column, dtypes from the schema:
    PYTHONPATH=src python -m repro.launch.select \
        --input data.parquet --select 10 --block-obs 65536

    # Wide regime: stream with feature-sharded statistics over 2 devices
    # (the per-pair statistics state splits across the model axis):
    PYTHONPATH=src REPRO_DEVICES=2 python -m repro.launch.select \
        --input wide.npy --target target.npy --block-obs 4096 --mesh-feat 2

    # Continuous data with exact discrete MI: one streaming quantile-sketch
    # pass cuts 32 equal-frequency bins per feature, then blocks encode to
    # int codes on the fly (device-side, fused with the contingency sums):
    PYTHONPATH=src python -m repro.launch.select \
        --input floats.csv --bins 32 --block-obs 65536

    # Cut the L-pass I/O tax: speculate 8 redundancy candidates per pass
    # (select=32 drops from 31 redundancy passes toward 4-5) and spill
    # parsed/encoded blocks so passes 2..L replay memmapped chunks —
    # selections stay bitwise-identical to the plain streaming engine:
    PYTHONPATH=src python -m repro.launch.select \
        --input data.csv --select 32 --batch-candidates 8 \
        --spill-dir /tmp/spill --readahead 2

Inputs: ``--input data.npz`` (arrays ``X`` rows=observations, ``y``) loads
in-memory; ``--input data.npy`` (+ ``--target target.npy``) memmaps and
streams block-by-block through the ``streaming`` engine; ``--input
data.csv`` streams a CSV (target = last column); ``--input data.parquet``
streams Parquet row batches (pyarrow; target = last column); default is
the paper's CorrAL-style synthetic generator.  The whole distribution strategy goes
through :class:`repro.MRMRSelector`: encoding ``auto`` applies the paper's
§III aspect-ratio rule (streamed sources always run the streaming engine),
explicit encodings shard over whatever devices jax exposes, and ``grid``
places a 2-D (observation × feature) mesh — shape from
``--mesh-obs``/``--mesh-feat`` or auto-factored.  The same mesh flags
apply to streamed inputs: tall sources shard blocks over the observation
axis, wide sources shard blocks and statistics over the feature axis, and
a mesh with both axes streams on the 2-D grid.  ``REPRO_DEVICES=N``
forces N simulated host devices (set before jax initialises).
"""

from __future__ import annotations

import os

_DEVICES = int(os.environ.get("REPRO_DEVICES", "0"))
if _DEVICES > 1:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_DEVICES} "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse
import json
import time

import jax
import numpy as np

from repro.core.criteria import available_criteria, resolve_criterion
from repro.core.scores import MIScore, PearsonMIScore
from repro.core.selector import (
    MRMRSelector,
    available_encodings,
    check_num_select,
)
from repro.data.sources import CSVSource, NpySource
from repro.data.synthetic import corral_dataset_np
from repro.dist.meshes import make_mesh


def _load_input(args):
    """-> (X, y, source): arrays for in-memory fits OR a DataSource."""
    path = args.input
    if path is None:
        X, y = corral_dataset_np(args.rows, args.cols, seed=args.seed)
        return X, y, None
    if path.endswith(".npz"):
        data = np.load(path)
        return data["X"], data["y"], None
    if path.endswith(".npy"):
        if not args.target:
            raise SystemExit("--target <y.npy> is required with a .npy input")
        return None, None, NpySource(path, args.target)
    if path.endswith(".csv"):
        # Binned fits read float columns (the sketch pass discretises);
        # plain MI expects pre-discretised integer categories.
        dtype = np.int32 if args.score == "mi" and not args.bins else np.float32
        return None, None, CSVSource(path, dtype=dtype)
    if path.endswith(".parquet"):
        from repro.data.sources import ParquetSource  # soft pyarrow gate

        try:
            # Block dtype comes from the file's schema (all-integral
            # columns -> int32, else float32); target = last column.
            return None, None, ParquetSource(path)
        except ImportError as e:
            raise SystemExit(str(e)) from None
    raise SystemExit(
        f"unsupported --input {path!r} (.npz, .npy, .csv or .parquet)"
    )


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--input", default=None,
                    help=".npz with X,y | .npy matrix (see --target) | .csv")
    ap.add_argument("--target", default=None,
                    help="target-vector .npy for a .npy --input")
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--cols", type=int, default=1000)
    ap.add_argument("--select", type=int, default=10)
    ap.add_argument("--encoding", default="auto",
                    choices=("auto",) + available_encodings())
    ap.add_argument("--criterion", default="mid",
                    help="greedy objective: mid (paper's difference form), "
                         "miq (quotient), maxrel (relevance only; streamed "
                         "fits then need a single pass of I/O), jmi / cmim "
                         "(class-conditioned redundancy), or any name added "
                         "via register_criterion")
    ap.add_argument("--mesh-obs", type=int, default=0,
                    help="observation-axis mesh extent (grid; 0 = auto)")
    ap.add_argument("--mesh-feat", type=int, default=0,
                    help="feature-axis mesh extent (grid; 0 = auto)")
    ap.add_argument("--score", default="mi", choices=["mi", "pearson"])
    ap.add_argument("--num-values", type=int, default=2)
    ap.add_argument("--num-classes", type=int, default=2)
    ap.add_argument("--incremental", type=int, default=1)
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--block-obs", type=int, default=65536,
                    help="observations per streamed block (DataSource inputs)")
    ap.add_argument("--prefetch", default="auto",
                    help="streamed blocks placed ahead of device "
                         "accumulation (0 = synchronous placer; 'auto' "
                         "= off on CPU, 2 elsewhere)")
    ap.add_argument("--batch-candidates", type=int, default=1,
                    help="redundancy vectors speculated per streamed pass "
                         "(q): cuts select=L from L-1 redundancy passes "
                         "toward ceil((L-1)/q); selections are identical")
    ap.add_argument("--spill-dir", default=None,
                    help="encoded-block spill cache directory: pass 1 "
                         "spills parsed/encoded blocks as .npy chunks, "
                         "passes 2..L replay them memmapped")
    ap.add_argument("--spill-budget-mb", type=int, default=0,
                    help="LRU byte budget for --spill-dir in MiB (0 = "
                         "unbounded)")
    ap.add_argument("--readahead", type=int, default=0,
                    help="raw blocks read ahead across pass boundaries "
                         "(0 = off; supersedes --prefetch)")
    ap.add_argument("--bins", type=int, default=0,
                    help="quantile-discretise continuous features into this "
                         "many equal-frequency bins (one streaming sketch "
                         "pass) and select with exact discrete MI; 0 = off")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--output", default=None,
                    help="write the full MRMRResult (selected, gains, "
                         "relevance, provenance) as JSON to this path")
    args = ap.parse_args(argv)

    # Validate the criterion name here — free-form (any registered name,
    # including user plugins imported via sitecustomize) beats a frozen
    # argparse choices list, but an unknown name should exit with the
    # registry, not escape as a traceback out of fit().
    try:
        resolve_criterion(args.criterion)
    except ValueError:
        raise SystemExit(
            f"--criterion {args.criterion!r} is not registered; "
            f"available: {', '.join(available_criteria())} "
            "(register_criterion adds more)"
        ) from None

    X, y, source = _load_input(args)

    # Fail the bounds check here, before any engine work: the selector
    # raises the same ValueError, but a CLI user should see a one-line
    # message, not a traceback out of fit().
    n_features = source.num_features if source is not None else X.shape[1]
    try:
        check_num_select(args.select, n_features)
    except ValueError as e:
        raise SystemExit(f"--select invalid: {e}") from None

    if args.bins:
        # Auto-resolve: the selector wraps continuous inputs in a
        # BinnedSource and sizes the MI score from the bin config.
        score = None
        if X is not None:
            X = X.astype(np.float32)
    elif args.score == "mi":
        score = MIScore(num_values=args.num_values,
                        num_classes=args.num_classes)
    else:
        score = PearsonMIScore()
        if X is not None:
            X = X.astype(np.float32)

    mesh = None
    if args.mesh_obs or args.mesh_feat:
        n_dev = len(jax.devices())
        obs = args.mesh_obs or max(n_dev // max(args.mesh_feat, 1), 1)
        feat = args.mesh_feat or max(n_dev // obs, 1)
        mesh = make_mesh((obs, feat), ("data", "model"))

    prefetch = args.prefetch if args.prefetch == "auto" else int(args.prefetch)
    t0 = time.time()
    sel = MRMRSelector(
        num_select=args.select, score=score, criterion=args.criterion,
        encoding=args.encoding, mesh=mesh,
        incremental=bool(args.incremental), block=args.block,
        block_obs=args.block_obs, prefetch=prefetch,
        bins=args.bins or None,
        batch_candidates=args.batch_candidates,
        spill_dir=args.spill_dir,
        spill_budget_bytes=args.spill_budget_mb * 2**20 or None,
        readahead=args.readahead,
    )
    sel = sel.fit(source) if source is not None else sel.fit(X, y)
    plan = sel.plan_
    out = {
        "encoding": plan.encoding,
        "criterion": sel.result_.criterion,
        "mesh": dict(zip(plan.mesh_axes, plan.mesh_shape)),
        "devices": len(jax.devices()),
        "incremental": plan.incremental,
        "selected": sel.selected_.tolist(),
        "gains": [round(float(g), 5) for g in sel.gains_],
        "seconds": round(time.time() - t0, 3),
    }
    if plan.encoding == "streaming":
        out["block_obs"] = plan.block_obs  # effective (rounded) size
        out["prefetch"] = plan.prefetch   # resolved ("auto" -> int)
        if plan.batch_candidates > 1:
            out["batch_candidates"] = plan.batch_candidates
        if plan.spill_dir is not None:
            out["spill_dir"] = plan.spill_dir
        if plan.readahead:
            out["readahead"] = plan.readahead
        if sel.result_.io is not None:
            out["io"] = sel.result_.io
    if plan.bins is not None:
        out["bins"] = plan.bins
    if args.output:
        # The same MRMRResult.to_json payload the service's result cache
        # persists — MRMRResult.from_json round-trips it.
        with open(args.output, "w") as f:
            f.write(sel.result_.to_json())
        out["output"] = args.output
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
