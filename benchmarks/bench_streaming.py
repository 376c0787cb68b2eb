"""Streaming vs in-memory fit: throughput + peak-memory estimate (dry-run).

Generates the paper's CorrAL-style dataset straight to a memmapped
``.npy`` (never materialising it on the host), fits once in-memory and
once per ``(--block-obs, --prefetch)`` cell through the streaming engine,
verifies the selections agree, and records wall time, scoring-pass
throughput and the peak *input* bytes resident on device — ``M·N`` for
in-memory vs ``block_obs·N`` + statistics for streaming.  ``--prefetch
0,2`` turns the same table into a synchronous-vs-double-buffered placer
comparison.  A second **wide** dataset (``--wide-rows``/``--wide-cols``,
``m/n <= 0.25`` — the regime where feature-sharded statistics matter)
runs the same grid against the in-memory alternative engine.  A third
**continuous** float dataset (``--cont-rows``/``--cont-cols``/``--bins``)
compares exact MI on sketch-binned codes (``bins=``, in-memory vs
streaming, selections must agree) against the Pearson approximation —
the only pre-binning continuous path — and times the one-off quantile
sketch pass that cuts the bin edges.

The **I/O-tax cells** (``--batch-candidates`` / ``--spill-dir`` /
``--readahead``) measure the three pass-count/pass-cost knobs on the
smallest tall block (the regime where per-pass cost dominates): batched
redundancy (``+qN``), the encoded-block spill cache (``+spill``),
cross-pass read-ahead (``+raN``) and all three combined.  Each cell must
reproduce the plain streaming selections bitwise and records the
engine's ``io`` ledger (passes / blocks / bytes, parse-vs-replay split)
alongside the timing.

``--criterion mid,miq,jmi,cmim`` adds a greedy-objective axis: the FIRST
criterion runs the full (block x prefetch) grid on both datasets; each
further criterion runs one tall cell (largest block, last prefetch depth)
plus its own in-memory baseline.  For the marginal folds (miq) the cell
shows the fold is free (O(N) host math per pick; passes/IO identical to
mid's same-block cell); for the conditional folds (jmi/cmim) it prices
the class axis exactly — ``io.state_bytes`` doubles (d_c x the pair
statistics) while passes and bytes_read stay identical to mid, because
the 3-way count rides the same sweep via the fused-target trick.
Streaming cells must reproduce the in-memory selections OF THE SAME
CRITERION.

    PYTHONPATH=src python benchmarks/bench_streaming.py --rows 200000 \
        --cols 256 --select 10 --block-obs 16384,65536 --prefetch 0,2 \
        --criterion mid,miq,jmi,cmim --out BENCH_streaming.json

The committed ``BENCH_streaming.json`` at the repo root is the baseline
(default sizes above, criteria ``mid,miq,jmi,cmim``) that later PRs
compare their perf trajectory to.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

from repro import MIScore, MRMRSelector, PearsonMIScore
from repro.data.binning import clear_binner_memo, fit_binned
from repro.data.sources import CorralSource, NpySource


def _fit_record(
    mode: str, rows: int, cols: int, select: int, fit_fn,
    peak_input_bytes: int, repeats: int = 1,
) -> dict:
    # min over repeats: the shared CI/container boxes these run on are
    # noisy, and the minimum is the least-contended (most comparable)
    # observation of each cell.
    dt = float("inf")
    for _ in range(repeats):
        t0 = time.time()
        sel = fit_fn()
        dt = min(dt, time.time() - t0)
    # Both engines run L scoring passes (1 relevance + L-1/L redundancy);
    # rows/s is nominal pass throughput over the whole selection.
    rec = dict(
        mode=mode,
        rows=rows,
        cols=cols,
        select=select,
        seconds=round(dt, 3),
        rows_per_s=round(rows * select / dt),
        peak_input_bytes=int(peak_input_bytes),
        repeats=repeats,
        selected=sel.selected_.tolist(),
    )
    # Streamed fits carry the pass/bytes ledger: savings from batching /
    # spilling / read-ahead are asserted from it, not eyeballed.
    if sel.result_ is not None and sel.result_.io is not None:
        rec["io"] = sel.result_.io
    return rec


def _bench_dataset(
    tag: str, rows: int, cols: int, select: int, blocks, prefetches,
    seed: int, tmp: str, repeats: int, criterion: str = "mid",
) -> list:
    """In-memory baseline + the (block_obs × prefetch) streaming grid for
    one dataset; every streaming cell must reproduce the baseline OF THE
    SAME CRITERION."""
    score = MIScore(num_values=2, num_classes=2)
    state_bytes = cols * 2 * 2 * 4  # (N, d_v, d_c) statistics
    x_path = os.path.join(tmp, f"{tag}X.npy")
    y_path = os.path.join(tmp, f"{tag}y.npy")
    if not (os.path.exists(x_path) and os.path.exists(y_path)):
        # tag + seed pin the dataset, so a later criterion's run over the
        # same tag reuses the files instead of regenerating ~rows x cols.
        CorralSource(rows, cols, seed=seed).to_npy(x_path, y_path)
    X, y = NpySource(x_path, y_path).materialize()

    parts = ([] if tag == "tall" else [tag]) + (
        [] if criterion == "mid" else [criterion]
    )
    prefix = "".join(f"{p}_" for p in parts)
    records = [
        _fit_record(
            f"{prefix}in_memory", rows, cols, select,
            lambda: MRMRSelector(num_select=select, score=score,
                                 criterion=criterion).fit(X, y),
            X.nbytes, repeats,
        )
    ]
    base = records[0]["selected"]
    for bo in blocks:
        # Warm the compiled accumulate for this block shape (a select=2 fit
        # traces both the class and feature passes), so the prefetch cells
        # compare placement strategies, not compilation order.
        MRMRSelector(num_select=2, score=score, block_obs=bo).fit(
            NpySource(x_path, y_path)
        )
        for pf in prefetches:
            rec = _fit_record(
                f"{prefix}streaming@{bo}+pf{pf}", rows, cols, select,
                lambda bo=bo, pf=pf: MRMRSelector(
                    num_select=select, score=score, criterion=criterion,
                    block_obs=bo, prefetch=pf,
                ).fit(NpySource(x_path, y_path)),
                bo * cols * X.dtype.itemsize + state_bytes, repeats,
            )
            rec["block_obs"] = bo
            rec["prefetch"] = pf
            if rec["selected"] != base:
                raise SystemExit(
                    f"{rec['mode']} diverged: {rec['selected']} != {base}"
                )
            records.append(rec)
    for r in records:
        r["criterion"] = criterion
    return records


def _bench_io_tax(
    tag: str, rows: int, cols: int, select: int, bo: int, base: list,
    qs, spill_root: str, readahead: int, tmp: str, repeats: int,
) -> list:
    """The L-pass I/O-tax cells on one dataset: batched redundancy,
    encoded-block spill, cross-pass read-ahead, and all three combined.
    Every cell must reproduce the plain streaming selections bitwise."""
    score = MIScore(num_values=2, num_classes=2)
    state_bytes = cols * 2 * 2 * 4
    x_path = os.path.join(tmp, f"{tag}X.npy")
    y_path = os.path.join(tmp, f"{tag}y.npy")
    prefix = "" if tag == "tall" else f"{tag}_"
    dtype_bytes = np.load(x_path, mmap_mode="r").dtype.itemsize

    def cell(mode: str, state_mult: int = 1, **knobs) -> dict:
        rec = _fit_record(
            f"{prefix}{mode}", rows, cols, select,
            lambda: MRMRSelector(
                num_select=select, score=score, block_obs=bo, **knobs
            ).fit(NpySource(x_path, y_path)),
            bo * cols * dtype_bytes + state_bytes * state_mult, repeats,
        )
        rec["block_obs"] = bo
        rec.update(knobs)
        if rec["selected"] != base:
            raise SystemExit(
                f"{rec['mode']} diverged: {rec['selected']} != {base}"
            )
        return rec

    records = []
    for q in qs:
        # Warm the batched (vmapped) accumulate for this (block, q) shape
        # so the cell times passes, not the one-off XLA compile.
        MRMRSelector(num_select=2, score=score, block_obs=bo,
                     batch_candidates=q).fit(NpySource(x_path, y_path))
        records.append(cell(f"streaming@{bo}+q{q}", state_mult=q,
                            batch_candidates=q))
    # Spill cells share one directory so repeats 2..R (and the combined
    # cell) time the replay path; min-over-repeats records the warm state,
    # the io ledger of the last run shows parse vs replay traffic.
    spill = os.path.join(spill_root, tag)
    records.append(cell(f"streaming@{bo}+spill", spill_dir=spill))
    records.append(cell(f"streaming@{bo}+ra{readahead}",
                        readahead=readahead))
    q = max(qs)
    records.append(cell(
        f"streaming@{bo}+q{q}+spill+ra{readahead}", state_mult=q,
        batch_candidates=q, spill_dir=spill, readahead=readahead,
    ))
    for r in records:
        r["criterion"] = "mid"
    return records


def _bench_continuous(
    rows: int, cols: int, select: int, bins: int, blocks, prefetch: int,
    seed: int, tmp: str, repeats: int,
) -> list:
    """Continuous float dataset: exact-MI-on-binned-codes (``bins=``) vs the
    Pearson approximation (the only pre-binning continuous path), plus the
    cost of the one-off sketch pass that cuts the bin edges."""
    x_path = os.path.join(tmp, "contX.npy")
    y_path = os.path.join(tmp, "conty.npy")
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, size=rows).astype(np.int32)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    k = min(8, cols)
    X[:, :k] += y[:, None] * np.linspace(1.5, 0.3, k)[None, :].astype(
        np.float32
    )
    np.save(x_path, X)
    np.save(y_path, y)

    # The sketch pass is the only cost bins= adds on top of the discrete
    # streaming path: one extra read of the source.  Cleared memo each
    # repeat so every timing pays the full pass.
    dt = float("inf")
    for _ in range(repeats):
        clear_binner_memo()
        t0 = time.time()
        fit_binned(NpySource(x_path, y_path), bins, block_obs=max(blocks))
        dt = min(dt, time.time() - t0)
    records = [dict(
        mode="cont_sketch_pass", rows=rows, cols=cols, select=select,
        seconds=round(dt, 3), rows_per_s=round(rows / dt),
        peak_input_bytes=max(blocks) * cols * 4, repeats=repeats,
        selected=[], criterion="mid", bins=bins,
    )]

    records.append(_fit_record(
        "cont_binned_in_memory", rows, cols, select,
        lambda: MRMRSelector(num_select=select, bins=bins).fit(X, y),
        X.nbytes, repeats,
    ))
    base = records[-1]["selected"]
    for bo in blocks:
        rec = _fit_record(
            f"cont_binned_streaming@{bo}+pf{prefetch}", rows, cols, select,
            lambda bo=bo: MRMRSelector(
                num_select=select, bins=bins, block_obs=bo,
                prefetch=prefetch,
            ).fit(NpySource(x_path, y_path)),
            bo * cols * 4, repeats,
        )
        rec["block_obs"] = bo
        rec["prefetch"] = prefetch
        if rec["selected"] != base:
            raise SystemExit(
                f"{rec['mode']} diverged: {rec['selected']} != {base}"
            )
        records.append(rec)
    # bins-off comparator: the Pearson approximation is the only engine
    # path that accepts raw floats.  Different score, so selections may
    # legitimately differ — no divergence check, just the throughput cell.
    bo = max(blocks)
    rec = _fit_record(
        f"cont_pearson_streaming@{bo}+pf{prefetch}", rows, cols, select,
        lambda: MRMRSelector(
            num_select=select, score=PearsonMIScore(), block_obs=bo,
            prefetch=prefetch,
        ).fit(NpySource(x_path, y_path)),
        bo * cols * 4, repeats,
    )
    rec["block_obs"] = bo
    rec["prefetch"] = prefetch
    records.append(rec)
    for r in records:
        r.setdefault("criterion", "mid")
        r["bins"] = bins if "pearson" not in r["mode"] else 0
    return records


def main(argv=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--cols", type=int, default=256)
    ap.add_argument("--select", type=int, default=10)
    ap.add_argument("--block-obs", default="16384,65536",
                    help="comma-separated streaming block sizes (tall case)")
    ap.add_argument("--prefetch", default="0,2",
                    help="comma-separated prefetch depths (0 = synchronous)")
    ap.add_argument("--wide-rows", type=int, default=4096,
                    help="wide-case rows (0 skips the wide case)")
    ap.add_argument("--wide-cols", type=int, default=16384)
    ap.add_argument("--wide-block-obs", default="1024,4096",
                    help="comma-separated streaming block sizes (wide case)")
    ap.add_argument("--cont-rows", type=int, default=100_000,
                    help="continuous-case rows (0 skips the continuous case)")
    ap.add_argument("--cont-cols", type=int, default=64)
    ap.add_argument("--cont-block-obs", default="16384,65536",
                    help="comma-separated streaming block sizes (continuous)")
    ap.add_argument("--bins", type=int, default=16,
                    help="equal-frequency bins for the continuous case")
    ap.add_argument("--batch-candidates", default="4,8",
                    help="comma-separated q values for the batched-"
                         "redundancy cells (empty string skips them)")
    ap.add_argument("--spill-dir", default="",
                    help="encoded-block spill directory for the spill "
                         "cells (default: a per-run temp dir)")
    ap.add_argument("--readahead", type=int, default=2,
                    help="cross-pass read-ahead depth for the read-ahead "
                         "and combined cells")
    ap.add_argument("--criterion", default="mid,miq,jmi,cmim",
                    help="comma-separated greedy objectives; the first runs "
                         "the full grid, the rest one tall cell each "
                         "(largest block, last prefetch) + in-memory "
                         "baseline")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed repeats per cell (min is recorded)")
    ap.add_argument("--out", default=None, help="write records to this JSON")
    args = ap.parse_args(argv)
    if args.repeats < 1:
        ap.error(f"--repeats must be >= 1, got {args.repeats}")

    prefetches = [int(p) for p in args.prefetch.split(",")]
    criteria = args.criterion.split(",")
    tall_blocks = [int(b) for b in args.block_obs.split(",")]
    with tempfile.TemporaryDirectory() as tmp:
        records = _bench_dataset(
            "tall", args.rows, args.cols, args.select,
            tall_blocks, prefetches, args.seed, tmp, args.repeats,
            criterion=criteria[0],
        )
        for crit in criteria[1:]:
            # One cell per extra criterion: the fold is O(N) host math per
            # pick, so its throughput must sit within noise of the first
            # criterion's same-block cell.
            records += _bench_dataset(
                "tall", args.rows, args.cols, args.select,
                [max(tall_blocks)], prefetches[-1:], args.seed, tmp,
                args.repeats, criterion=crit,
            )
        qs = [int(q) for q in args.batch_candidates.split(",") if q]
        if qs:
            # I/O-tax cells ride the smallest tall block — the regime
            # where per-pass cost dominates and the PR 7 baseline showed
            # the 3x falloff the knobs attack.
            tall_base = next(
                r for r in records if r["mode"].startswith("streaming@")
            )["selected"]
            records += _bench_io_tax(
                "tall", args.rows, args.cols, args.select,
                min(tall_blocks), tall_base, qs,
                args.spill_dir or os.path.join(tmp, "spill"),
                args.readahead, tmp, args.repeats,
            )
        if args.wide_rows > 0:
            if args.wide_rows > args.wide_cols * 0.25:
                raise SystemExit(
                    f"--wide-rows {args.wide_rows} / --wide-cols "
                    f"{args.wide_cols} is not wide (m/n must be <= 0.25)"
                )
            wide_blocks = [int(b) for b in args.wide_block_obs.split(",")]
            wide_records = _bench_dataset(
                "wide", args.wide_rows, args.wide_cols, args.select,
                wide_blocks, prefetches,
                args.seed + 1, tmp, args.repeats, criterion=criteria[0],
            )
            records += wide_records
            if qs:
                records += _bench_io_tax(
                    "wide", args.wide_rows, args.wide_cols, args.select,
                    min(wide_blocks), wide_records[0]["selected"], qs,
                    args.spill_dir or os.path.join(tmp, "spill"),
                    args.readahead, tmp, args.repeats,
                )
        if args.cont_rows > 0:
            records += _bench_continuous(
                args.cont_rows, args.cont_cols, args.select, args.bins,
                [int(b) for b in args.cont_block_obs.split(",")],
                prefetches[-1], args.seed + 2, tmp, args.repeats,
            )

    for r in records:
        print(
            f"{r['mode']:<30s} {r['seconds']:8.2f}s "
            f"{r['rows_per_s']:>12,d} rows/s "
            f"peak_input={r['peak_input_bytes'] / 1e6:8.1f} MB"
        )
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
    return records


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
