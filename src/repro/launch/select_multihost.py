"""Multi-host feature selection — one process per shard, loopback or real.

Spawn mode (the default) stands up an N-process ``jax.distributed``
cluster on this machine's CPU — a free loopback coordinator port, N child
copies of this script pinned to ``JAX_PLATFORMS=cpu``, gloo collectives;
it refuses any other platform, since N processes on one host would each
claim every accelerator chip — runs the SAME selection in
every process with ``MRMRSelector(hosts=N)``, asserts every host
committed the identical picks/gains, and prints one merged JSON report:

    # 2-process map-reduce over a streamed .npy (each host reads only
    # its shard of the file):
    PYTHONPATH=src python -m repro.launch.select_multihost \\
        --num-processes 2 --input data.npy --target y.npy --select 10

    # Synthetic CorrAL-style data, wide regime, spill + batching:
    PYTHONPATH=src python -m repro.launch.select_multihost \\
        --num-processes 2 --rows 200 --cols 2048 --select 8 \\
        --batch-candidates 4 --spill-dir /tmp/spill

Worker mode (``--process-id`` set, as spawn mode sets it for its
children) joins the coordinator, fits, and prints this host's result —
which is how a REAL cluster runs it: one invocation per machine with
``--coordinator host0:port --num-processes N --process-id i`` (or the
``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID``
environment variables).

Every host returns the identical selection — the per-pass reduce is a
collective psum of exact integer statistics, so there is no designated
master to gather from; spawn mode's cross-host assertion is checking a
guarantee, not electing a winner.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

_MARK = "MHRESULT:"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0's coordinator (spawn mode "
                         "picks a free loopback port when omitted)")
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's id 0..N-1; omitting it runs spawn "
                         "mode, which launches all N workers locally")
    ap.add_argument("--input", default=None,
                    help=".npy matrix (see --target), .csv or .parquet; "
                         "default = synthetic CorrAL-style data")
    ap.add_argument("--target", default=None,
                    help="target-vector .npy for a .npy --input")
    ap.add_argument("--rows", type=int, default=6000)
    ap.add_argument("--cols", type=int, default=24)
    ap.add_argument("--select", type=int, default=4)
    ap.add_argument("--criterion", default="mid")
    ap.add_argument("--score", default="mi", choices=["mi", "pearson"])
    ap.add_argument("--num-values", type=int, default=2)
    ap.add_argument("--num-classes", type=int, default=2)
    ap.add_argument("--block-obs", type=int, default=65536)
    ap.add_argument("--batch-candidates", type=int, default=1)
    ap.add_argument("--spill-dir", default=None)
    ap.add_argument("--readahead", type=int, default=0)
    ap.add_argument("--bins", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def _load_source(args):
    """The worker's DataSource — every host builds the IDENTICAL source
    (same paths, same synthetic seed); the HostShardSpec decides which
    rows/columns of it this host actually reads."""
    import numpy as np

    from repro.data.sources import ArraySource, CSVSource, NpySource

    if args.input is None:
        from repro.data.synthetic import corral_dataset_np

        X, y = corral_dataset_np(args.rows, args.cols, seed=args.seed)
        if args.score == "pearson" or args.bins:
            X = X.astype(np.float32)
        return ArraySource(X, y)
    if args.input.endswith(".npy"):
        if not args.target:
            raise SystemExit("--target <y.npy> is required with a .npy input")
        return NpySource(args.input, args.target)
    if args.input.endswith(".csv"):
        dtype = np.int32 if args.score == "mi" and not args.bins else np.float32
        return CSVSource(args.input, dtype=dtype)
    if args.input.endswith(".parquet"):
        from repro.data.sources import ParquetSource

        return ParquetSource(args.input)
    raise SystemExit(f"unsupported --input {args.input!r}")


def _run_worker(args) -> dict:
    # Join the cluster BEFORE any jax computation: backend init locks the
    # device set, and the gloo knob must land first.
    from repro.dist.multihost import init_multihost

    ctx = init_multihost(
        coordinator=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )

    from repro.core.scores import MIScore, PearsonMIScore
    from repro.core.selector import MRMRSelector

    if args.bins:
        score = None
    elif args.score == "mi":
        score = MIScore(
            num_values=args.num_values, num_classes=args.num_classes
        )
    else:
        score = PearsonMIScore()
    source = _load_source(args)
    t0 = time.time()
    sel = MRMRSelector(
        num_select=args.select,
        score=score,
        criterion=args.criterion,
        block_obs=args.block_obs,
        batch_candidates=args.batch_candidates,
        spill_dir=args.spill_dir,
        readahead=args.readahead,
        bins=args.bins or None,
        hosts="auto",
    ).fit(source)
    return dict(
        process_id=ctx.process_id,
        num_processes=ctx.num_processes,
        selected=sel.selected_.tolist(),
        gains=[float(g) for g in sel.gains_],
        criterion=sel.result_.criterion,
        io=sel.result_.io,
        seconds=round(time.time() - t0, 3),
    )


def _spawn(args, argv) -> dict:
    # The loopback cluster is a CPU cluster (gloo collectives): N children
    # on one machine would otherwise each claim every accelerator chip.
    platforms = os.environ.get("JAX_PLATFORMS") or "cpu"
    if platforms != "cpu":
        raise SystemExit(
            f"spawn mode runs its {args.num_processes} processes on the CPU "
            f"of this machine, but JAX_PLATFORMS={platforms!r}: one process "
            "per accelerator needs worker mode (--coordinator, "
            "--num-processes, --process-id) on each host"
        )
    coordinator = args.coordinator or f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(args.num_processes):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # Children resolve their place from argv, not env — scrub any
        # inherited multihost env so a nested launch can't cross wires.
        for k in ("REPRO_COORDINATOR", "REPRO_NUM_PROCESSES",
                  "REPRO_PROCESS_ID"):
            env.pop(k, None)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.launch.select_multihost",
             *argv, "--coordinator", coordinator, "--process-id", str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        ))
    results = {}
    failed = []
    for pid, p in enumerate(procs):
        out, err = p.communicate(timeout=1800)
        payload = next(
            (l[len(_MARK):] for l in out.splitlines() if l.startswith(_MARK)),
            None,
        )
        if p.returncode != 0 or payload is None:
            failed.append(
                f"--- worker {pid} (rc={p.returncode}) ---\n"
                f"{out[-2000:]}\n{err[-2000:]}"
            )
            continue
        results[pid] = json.loads(payload)
    if failed:
        raise SystemExit("\n".join(failed))
    first = results[0]
    for pid, r in results.items():
        if r["selected"] != first["selected"] or r["gains"] != first["gains"]:
            raise SystemExit(
                f"host {pid} disagrees with host 0:\n"
                f"  host 0: {first['selected']} {first['gains']}\n"
                f"  host {pid}: {r['selected']} {r['gains']}"
            )
    merged = dict(
        num_processes=args.num_processes,
        coordinator=coordinator,
        selected=first["selected"],
        gains=first["gains"],
        criterion=first["criterion"],
        hosts=first["io"].get("hosts"),
        per_host_io={
            pid: {k: r["io"][k] for k in ("passes", "blocks_read",
                                          "bytes_read", "state_bytes")}
            for pid, r in sorted(results.items())
        },
        seconds=max(r["seconds"] for r in results.values()),
    )
    print(json.dumps(merged))
    return merged


def main(argv=None) -> dict:
    argv = list(sys.argv[1:] if argv is None else argv)
    args = _parser().parse_args(argv)
    if args.num_processes < 1:
        raise SystemExit("--num-processes must be >= 1")
    if args.process_id is None and os.environ.get("REPRO_PROCESS_ID"):
        # Real-cluster launchers configure workers purely via env vars.
        args.process_id = int(os.environ["REPRO_PROCESS_ID"])
        args.coordinator = args.coordinator or os.environ.get(
            "REPRO_COORDINATOR"
        )
        args.num_processes = int(os.environ.get(
            "REPRO_NUM_PROCESSES", args.num_processes
        ))
    if args.process_id is not None:
        out = _run_worker(args)
        print(_MARK + json.dumps(out))
        return out
    return _spawn(args, argv)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
