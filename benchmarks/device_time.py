"""Where a tall streamed fit spends its time: device work per block vs wall.

    PYTHONPATH=src python -m benchmarks.device_time              # full size
    PYTHONPATH=src python -m benchmarks.device_time --rows 131072

Writes CorrAL (``--rows`` x 256 int8) to a ``.npy`` file and fits it
streamed (block_obs 65536, select 10, mid), Pallas counts and jnp counts
(``MIScore(use_pallas=False)``) in alternation, reporting each warm wall.
Then it times on device-resident blocks:

* ``acc_pallas`` / ``acc_jnp``: the accumulate program each fit ran, as
  ``AccumulateLog`` recorded it (the engine's own program and layout);
* ``kernel``: ``contingency_tables_pallas`` on an int32 block, i.e. the
  accumulate without its int8 -> int32 widening and bookkeeping.

Each op is timed two ways.  ``device_us``: ``--iters`` and twice as many
runs inside one jitted loop (one dispatch each), the difference divided
by ``--iters`` — device time per block.  ``dispatch_us``: ``--iters``
separate back-to-back calls (the accumulate chains its state), elapsed /
iters — the per-block cost as the fit pays it, host dispatch included.
HBM bytes per block are counted from the shapes (read + write), not
measured.  The accumulate widens the int8 block to int32 ahead of the
kernel; ``widened_block`` reports, from the compiled program, whether XLA
placed that int32 copy in HBM or in on-chip VMEM (memory space 1), and the
accumulate's HBM bytes count it only in HBM.  ``host_feed`` times one pass
of the fit's input path without the count: read each block from the
memmap, then ``device_put`` it and wait.

Prints one JSON line per measurement; the last line is the summary.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import re
import shutil
import statistics
import time

import numpy as np

REPO = pathlib.Path(__file__).resolve().parents[1]
BLOCK = 65536


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=2**21)
    ap.add_argument("--cols", type=int, default=256)
    ap.add_argument("--select", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--data-dir", default=str(REPO / "results" / "device_time_data")
    )
    return ap


def _emit(**kw) -> dict:
    print(json.dumps(kw), flush=True)
    return kw


def _fit(source, score, select):
    from repro import MRMRSelector
    from repro.core.streaming import AccumulateLog

    t0 = time.perf_counter()
    with AccumulateLog() as log:
        sel = MRMRSelector(
            num_select=select, score=score, block_obs=BLOCK
        ).fit(source)
    return sel, time.perf_counter() - t0, log


def _concrete(abstract, rng):
    """Device arrays in {0, 1} with each abstract argument's layout."""
    import jax

    def one(a):
        v = rng.integers(0, 2, a.shape).astype(a.dtype)
        return jax.device_put(v, a.sharding)

    return jax.tree.map(one, abstract)


def _dispatch_s(step, args, iters, chain) -> list[float]:
    """Seconds per call over ``iters`` back-to-back calls, five times: the
    per-block cost as the fit pays it, dispatch included."""
    import jax

    jax.block_until_ready(step(*args))
    out = []
    for _ in range(5):
        a = args
        t0 = time.perf_counter()
        for _ in range(iters):
            r = step(*a)
            if chain:
                a = (r,) + tuple(a[1:])
        jax.block_until_ready(r)
        out.append((time.perf_counter() - t0) / iters)
    return out


def _device_s(op, block, carry, iters) -> list[float]:
    """Device seconds per ``op(carry, block) -> carry``, five times.

    The op runs ``iters`` and ``2 * iters`` times inside one program
    (one dispatch each); the difference over ``iters`` is its time.  An
    optimization barrier ties the block to the carry, so XLA can neither
    hoist the work out of the loop nor skip any of it."""
    import jax
    from jax import lax

    @jax.jit
    def loop(n, block, carry):
        def body(_, c):
            b, c = lax.optimization_barrier((block, c))
            return op(c, b)

        return lax.fori_loop(0, n, body, carry)

    jax.block_until_ready(loop(1, block, carry))
    out = []
    for _ in range(5):
        ts = []
        for n in (iters, 2 * iters):
            t0 = time.perf_counter()
            jax.block_until_ready(loop(n, block, carry))
            ts.append(time.perf_counter() - t0)
        out.append((ts[1] - ts[0]) / iters)
    return out


def _widened_memory_space(text: str, m: int, f: int) -> str | None:
    """Where the compiled accumulate keeps its int32 copy of the (m, f)
    block: "vmem" (memory space 1), "hbm", or None when the program has
    no such convert (the CPU fuses it away)."""
    hit = re.search(rf"= s32\[{m},{f}\]\{{([^}}]*)\}} convert\(", text)
    if hit is None:
        return None
    return "vmem" if "S(1)" in hit.group(1) else "hbm"


def _host_feed(source, x_sharding, y_sharding) -> dict:
    import jax

    read = put = 0.0
    nbytes = 0
    for X, y in source.iter_blocks(BLOCK):
        t0 = time.perf_counter()
        X, y = np.ascontiguousarray(X), np.ascontiguousarray(y)
        t1 = time.perf_counter()
        jax.block_until_ready(
            (jax.device_put(X, x_sharding), jax.device_put(y, y_sharding))
        )
        t2 = time.perf_counter()
        read, put = read + t1 - t0, put + t2 - t1
        nbytes += X.nbytes + y.nbytes
    return dict(read_s=read, put_s=put, bytes=nbytes)


def main(argv=None) -> dict:
    args = _parser().parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from repro.core.scores import MIScore
    from repro.data.sources import CorralSource, NpySource
    from repro.kernels.contingency import contingency_tables_pallas

    dev = jax.devices()[0]
    data_dir = pathlib.Path(args.data_dir)
    data_dir.mkdir(parents=True, exist_ok=False)
    try:
        x_path, y_path = data_dir / "X.npy", data_dir / "y.npy"
        CorralSource(args.rows, args.cols, seed=args.seed).to_npy(
            str(x_path), str(y_path)
        )
        source = NpySource(str(x_path), str(y_path))
        scores = dict(
            pallas=MIScore(2, 2), jnp=MIScore(2, 2, use_pallas=False)
        )
        logs, walls, blocks = {}, {k: [] for k in scores}, None
        for name, score in scores.items():  # warm-up: compiles
            sel, _, logs[name] = _fit(source, score, args.select)
            blocks = sel.result_.io["blocks_read"]
        for _ in range(args.rounds):
            for name, score in scores.items():
                _, wall, _ = _fit(source, score, args.select)
                walls[name].append(wall)
        _emit(fit_wall_s=walls, blocks_per_fit=blocks)

        rng = np.random.default_rng(args.seed)
        timed = {}  # op -> (dispatch s per call, device s per block)
        for name, log in logs.items():
            entry = log.entries[0]  # relevance pass; mid reuses it
            state, X, *rest = _concrete(entry.args, rng)
            timed[f"acc_{name}"] = (
                _dispatch_s(entry.fn, (state, X, *rest), args.iters, True),
                _device_s(
                    lambda c, b, fn=entry.fn, rest=rest: fn(c, b, *rest),
                    X, state, args.iters,
                ),
            )
        x_abs, y_abs = logs["pallas"].entries[0].args[1:3]
        m, f = x_abs.shape
        X32 = _concrete(jax.ShapeDtypeStruct(x_abs.shape, jnp.int32), rng)
        y32 = jnp.asarray(rng.integers(0, 2, m), jnp.int32)
        interpret = dev.platform != "tpu"  # CPU rehearsals only

        def count(X):
            return contingency_tables_pallas(X, y32, 2, 2, interpret=interpret)

        timed["kernel"] = (
            _dispatch_s(jax.jit(count), (X32,), args.iters, False),
            _device_s(
                lambda c, b: c + count(b), X32,
                jnp.zeros((f, 2, 2), jnp.float32), args.iters,
            ),
        )
        # The accumulate reads the int8 block and widens it to int32 for the
        # kernel (64 MiB per 65536 x 256 block).  That copy costs HBM bytes
        # (written, then read back by the kernel) only where XLA put it there.
        widened = _widened_memory_space(
            logs["pallas"].compiled_texts()[0], m, f
        )
        _emit(widened_block=widened)
        hbm_bytes = dict(kernel=m * f * 4 + m * 4)
        hbm_bytes["acc_pallas"] = m * f + m * 2 + (
            2 * m * f * 4 if widened == "hbm" else 0
        )
        for name, (disp, devs) in timed.items():
            med = statistics.median(devs)
            nb = hbm_bytes.get(name)
            _emit(
                op=name,
                device_us=[t * 1e6 for t in devs], median_device_us=med * 1e6,
                dispatch_us=[t * 1e6 for t in disp],
                median_dispatch_us=statistics.median(disp) * 1e6,
                hbm_bytes=nb, gb_per_s=None if nb is None else nb / med / 1e9,
            )
        feed = _host_feed(source, x_abs.sharding, y_abs.sharding)
        feed_s = feed["read_s"] + feed["put_s"]
        _emit(host_feed=feed, gb_per_s=feed["bytes"] / feed_s / 1e9)

        summary = dict(
            device=dict(platform=dev.platform, kind=dev.device_kind),
            rows=args.rows, cols=args.cols, blocks_per_fit=blocks,
            widened_block=widened,
        )
        for name in scores:
            acc_s = statistics.median(timed[f"acc_{name}"][1]) * blocks
            wall = statistics.median(walls[name])
            summary[name] = dict(
                wall_s=wall, accumulate_s=acc_s, accumulate_share=acc_s / wall
            )
        return _emit(summary=summary)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
