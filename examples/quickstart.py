"""Quickstart: distributed mRMR feature selection in 30 lines.

    PYTHONPATH=src python examples/quickstart.py

Generates the paper's CorrAL-style dataset (Eq. 3: the class is a boolean
function of features 0..7, feature 8 is partially correlated, the rest is
noise), then runs mRMR through the ``MRMRSelector`` front door: once
auto-planned (the paper's §III aspect-ratio rule picks the encoding) and
once per explicit encoding, checking they recover the relevant features.
Also selects with the quotient-form criterion (``criterion="miq"``; from
the CLI: ``python -m repro.launch.select --criterion miq``) and the
class-conditioned pair (``"jmi"``/``"cmim"``) — the greedy objective is
pluggable, orthogonal to the encoding.
"""

import json
import os
import subprocess
import sys
import tempfile

import jax
import numpy as np

from repro import MRMRSelector
from repro.data.sources import ArraySource, CorralSource
from repro.data.synthetic import corral_dataset
from repro.serve import SelectionService

# Dataset sizes: the CorrAL table, the wide streamed table, the continuous
# table, the tall table of the I/O-tax demo and the multi-host table.
ROWS, COLS, L = 20_000, 64, 10
WIDE_ROWS, WIDE_COLS = 512, 2048
FLOAT_ROWS, FLOAT_COLS = 5_000, 32
TALL_ROWS = 50_000
MULTIHOST_ROWS = 6_000


def main() -> dict:
    out = {}
    X, y = corral_dataset(ROWS, COLS, seed=0)
    print(f"dataset: X{X.shape} y{y.shape}  devices: {jax.device_count()}")

    fs = MRMRSelector(num_select=L).fit(X, y)
    out["auto"] = fs.plan_.encoding
    print(f"{'auto':>12s}: planned encoding = {fs.plan_.encoding!r}")

    for encoding in ("conventional", "alternative"):
        fs = MRMRSelector(num_select=L, encoding=encoding).fit(X, y)
        sel = out[encoding] = fs.selected_.tolist()
        hits = sorted(set(sel) & set(range(9)))
        print(f"{encoding:>12s}: selected {sel}")
        print(f"{'':>12s}  relevant recovered: {hits} ({len(hits)}/9)")

    Xt = fs.transform(np.asarray(X))
    print(f"transform: {np.asarray(X).shape} -> {Xt.shape}")

    # Swap the greedy objective without touching anything else: MIQ
    # divides relevance by mean redundancy instead of subtracting it.  The
    # selector's read side reports what ran (result_) plus sklearn-style
    # accessors.
    fs = MRMRSelector(num_select=L, criterion="miq").fit(X, y)
    out["miq"] = fs.selected_.tolist()
    print(f"{'miq':>12s}: selected {out['miq']} "
          f"(criterion={fs.result_.criterion!r}, "
          f"engine={fs.result_.engine!r})")
    print(f"{'':>12s}  support mask sum = {int(fs.get_support().sum())}, "
          f"top-relevance feature = {int(fs.scores_.argmax())}, "
          f"rank of feature 0 = {int(fs.ranking_[0])}")

    # Class-conditioned criteria: JMI and CMIM fold the gap
    # I(x;x_j|y) - I(x;x_j) (mean vs worst-case) — one fused 3-way count
    # per pair feeds both terms, so they cost the same passes as mid.
    for criterion in ("jmi", "cmim"):
        fs = MRMRSelector(num_select=L, criterion=criterion).fit(X, y)
        sel = out[criterion] = fs.selected_.tolist()
        hits = sorted(set(sel) & set(range(9)))
        print(f"{criterion:>12s}: selected {sel} "
              f"(relevant recovered: {len(hits)}/9)")

    # Out-of-core wide regime: a DataSource streams observation-blocks and
    # a wide dataset (obs/feat <= 0.25) plans feature-sharded statistics —
    # the per-pair statistics state splits across devices instead of
    # replicating.  ``prefetch`` double-buffers placement (host reads block
    # i+1 while the device accumulates block i); selections match the
    # in-memory engines.
    wide_src = CorralSource(WIDE_ROWS, WIDE_COLS, seed=0)
    fs = MRMRSelector(num_select=L, block_obs=128, prefetch=2).fit(wide_src)
    plan = fs.plan_
    out["streaming"] = fs.selected_.tolist()
    print(f"{'streaming':>12s}: encoding={plan.encoding!r} "
          f"obs_axes={plan.obs_axes} feat_axes={plan.feat_axes} "
          f"block_obs={plan.block_obs} prefetch={plan.prefetch}")
    print(f"{'':>12s}  selected {out['streaming']}")

    # Continuous features, exact discrete MI: bins= cuts equal-frequency
    # bin edges from ONE streaming quantile-sketch pass, then every block
    # encodes to int codes on the fly (device-side, fused with the
    # contingency sums).  The float dataset below would otherwise be
    # refused by the MI path; with bins= it fits on both the in-memory and
    # streaming engines, and the selections agree at every block size
    # because the sketch (and hence the edges) is a pure function of the
    # row stream.
    rng = np.random.default_rng(0)
    yf = rng.integers(0, 2, size=FLOAT_ROWS)
    Xf = rng.normal(size=(FLOAT_ROWS, FLOAT_COLS))
    Xf[:, :4] += yf[:, None] * np.array([1.6, 1.2, 0.8, 0.5])  # informative

    fs_mem = MRMRSelector(num_select=4, bins=16).fit(Xf, yf)
    fs_str = MRMRSelector(num_select=4, bins=16, block_obs=512).fit(
        ArraySource(Xf, yf)
    )
    out["binned"] = (fs_mem.selected_.tolist(), fs_str.selected_.tolist())
    print(f"{'binned':>12s}: in-memory {out['binned'][0]} == "
          f"streaming {out['binned'][1]} (bins={fs_str.plan_.bins})")

    # Cutting the L-pass I/O tax: a streamed fit costs 1 relevance pass
    # plus num_select-1 redundancy passes over the source.  Three
    # composable knobs attack that, with selections bitwise-identical to
    # the plain engine:
    #   batch_candidates=q  speculates the top-q candidates' redundancy
    #                       vectors per pass -> ~ceil((L-1)/q) redundancy
    #                       passes (select=32 at q=8: 31 passes -> 5);
    #   spill_dir=          spills each parsed/encoded block on pass 1 and
    #                       replays memmapped chunks on passes 2..L (CSV
    #                       parse + bin encode paid once per dataset);
    #   readahead=          streams the next pass's first blocks while the
    #                       device drains the current pass's tail.
    # The result reports the measured ledger (result_.io), so the pass
    # math is observable, not guessed.
    with tempfile.TemporaryDirectory() as spill:
        tall_src = CorralSource(TALL_ROWS, COLS, seed=0)
        plain = MRMRSelector(num_select=L, block_obs=8192).fit(tall_src)
        fast = MRMRSelector(
            num_select=L, block_obs=8192, batch_candidates=8,
            spill_dir=spill, readahead=2,
        ).fit(tall_src)
        assert list(plain.selected_) == list(fast.selected_)
        out["io_passes"] = (plain.result_.io["passes"],
                            fast.result_.io["passes"])
        print(f"{'io tax':>12s}: plain passes={out['io_passes'][0]} "
              f"vs batched+spill+readahead={out['io_passes'][1]} "
              f"(cache: {fast.result_.io['cache']})")

    # Selection-as-a-service: fits run as managed jobs behind a bounded
    # work queue, with a content-addressed result cache (source
    # fingerprint x score x criterion x num_select) and idempotency-key
    # coalescing — the identical resubmission below is a cache hit with
    # zero engine or I/O passes, and a stampede of identical concurrent
    # submits runs once.
    # (CLI: python -m repro.launch.serve_select --repeat 2 --distinct-select 3)
    with SelectionService(workers=2) as svc:
        job = svc.submit(CorralSource(ROWS, COLS, seed=0), num_select=L)
        result = svc.result(job)  # blocks until DONE; raises on FAILED
        again = svc.submit(CorralSource(ROWS, COLS, seed=0), num_select=L)
        info = svc.poll(again)
        out["service"] = [int(v) for v in result.selected]
        out["service_cache_hit"] = info.cache_hit
        print(f"{'service':>12s}: selected {out['service']}")
        print(f"{'':>12s}  resubmission cache_hit={info.cache_hit} "
              f"cache={svc.stats()['cache']}")

    # Multi-host map-reduce: the same streaming fit across N
    # jax.distributed processes, each reading ONLY its shard of the data
    # (§III applied to hosts: tall -> row ranges, wide -> column ranges,
    # both-large -> a 2-D host grid).  The per-pass reduce is an explicit
    # psum of exact integer statistics, so every host commits the
    # identical selection — asserted by the launcher.  In a worker you
    # would call init_multihost() then MRMRSelector(..., hosts="auto");
    # here we drive the spawn-mode launcher, which stands up a loopback
    # 2-process cluster.  (Real cluster: one invocation per machine with
    # --coordinator/--process-id.)  The loopback cluster runs on the CPU,
    # so this process keeps its chip.
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.select_multihost",
         "--num-processes", "2", "--rows", str(MULTIHOST_ROWS),
         "--cols", "24", "--select", "4",
         "--block-obs", str(MULTIHOST_ROWS // 4)],
        capture_output=True, text=True, check=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    mh = out["multihost"] = json.loads(proc.stdout.splitlines()[-1])
    agg = mh["hosts"]["aggregate"]["bytes_read"]
    shares = [round(h["bytes_read"] / agg, 2)
              for h in mh["hosts"]["per_host"]]
    print(f"{'multihost':>12s}: grid={mh['hosts']['grid']} "
          f"selected {mh['selected']}")
    print(f"{'':>12s}  per-host share of bytes read: {shares}")
    return out


if __name__ == "__main__":
    main()
