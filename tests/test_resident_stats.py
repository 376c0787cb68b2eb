"""A fit left to size its default MI score, whose blocks stay on the
device, takes the category counts from the blocks it placed instead of a
stats scan of the source: one read of the source a fit, the same answers
as the fit with the score the scan gives, and the scan's memo left behind.

The CPU backend reports no device memory, so these tests set the resident
budget by hand.  The four-device case runs this file as a script in a
child process with four host devices, as the multi-device suites do.
"""

import contextlib
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

import repro.dist.streaming as dist
from repro import MRMRSelector, PearsonMIScore
from repro.core.selector import score_of_stats
from repro.core.streaming import AccumulateLog
from repro.data.sources import ArraySource, NpySource, clear_stats_memo

SELECT = 5
# rows, columns, block rows: tall (the last block ragged) and wide
LAYOUTS = {
    "tall": (1000, 13, 256),
    "wide": (48, 300, 16),
    "tall.obs4": (1000, 13, 256),
}


def _data(rows, cols, seed=4):
    """Categories 0..2 and classes 0..1, with the largest feature category
    (3) and class (2) only in the last rows: a reduce that misses a block
    or a chip's shard sizes the score too small."""
    rng = np.random.default_rng(seed)
    X = rng.integers(0, 3, size=(rows, cols)).astype(np.int8)
    y = ((X[:, 0] + X[:, 1]) % 2).astype(np.int8)
    flip = rng.random(rows) < 0.1
    y[flip] = 1 - y[flip]
    X[-1, cols // 2] = 3
    y[-2] = 2
    return X, y


class Counting(ArraySource):
    """An ArraySource that records the block size of each ``iter_blocks``."""

    def __init__(self, X, y):
        super().__init__(X, y)
        self.calls = []

    def iter_blocks(self, block_obs):
        self.calls.append(block_obs)
        return super().iter_blocks(block_obs)


@contextlib.contextmanager
def _budget(nbytes):
    """Every fit's resident budget is ``nbytes`` (None: the backend
    reports no memory)."""
    was = dist.resident_budget
    dist.resident_budget = lambda devices: nbytes
    try:
        yield
    finally:
        dist.resident_budget = was


def _same(a, b):
    np.testing.assert_array_equal(a.selected_, b.selected_)
    for got, want in [(a.gains_, b.gains_), (a.scores_, b.scores_)]:
        np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


def check(layout, criterion):
    """The default-score fit of ``layout`` under ``criterion``, resident
    and streamed, against the fit with the scan's explicit score."""
    rows, cols, block = LAYOUTS[layout]
    X, y = _data(rows, cols)
    kw = dict(num_select=SELECT, criterion=criterion, block_obs=block)
    if layout.endswith("obs4"):
        from repro.dist import make_mesh

        kw["mesh"] = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    clear_stats_memo()
    explicit = score_of_stats(ArraySource(X, y).stats())
    assert (explicit.num_values, explicit.num_classes) == (4, 3)
    want = MRMRSelector(score=explicit, **kw).fit(ArraySource(X, y))
    terms = SELECT + (SELECT - 1) * (criterion == "jmi")

    with _budget(1 << 40):
        clear_stats_memo()
        src = Counting(X, y)
        got = MRMRSelector(**kw).fit(src)
    _same(got, want)
    assert got.plan_.score == explicit
    io = got.result_.io
    # one read of the source at the fit's block size, and no scan
    assert src.calls.count(got.plan_.block_obs) == 1, src.calls
    assert io["bytes_read"] == X.nbytes + y.nbytes
    assert io["resident_stats"] == 1
    assert io["resident_passes"] == SELECT
    # a copy a finalize term and a pick, and one for the four extrema
    assert io["host_syncs"] == terms + SELECT + 1
    assert io["host_syncs"] == (
        3 * SELECT if criterion == "jmi" else 2 * SELECT + 1
    )
    assert not dist._RESERVED

    # budgeted below the need: the fit streams and scans as it did
    with _budget(io["resident_need_bytes"] - 1):
        clear_stats_memo()
        src = Counting(X, y)
        streamed = MRMRSelector(**kw).fit(src)
    _same(streamed, want)
    assert streamed.plan_.score == explicit
    sio = streamed.result_.io
    assert sio["resident_stats"] == 0 and sio["resident_passes"] == 0
    assert src.calls.count(streamed.plan_.block_obs) == SELECT + 1, src.calls
    assert sio["host_syncs"] == terms + SELECT


@pytest.mark.parametrize("criterion", ["mid", "jmi"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_default_score_sized_from_resident_blocks(layout, criterion):
    if not layout.endswith("obs4"):
        check(layout, criterion)
        return
    proc = subprocess.run(
        [sys.executable, __file__, layout, criterion],
        capture_output=True, text=True, timeout=600,
        env={
            **os.environ,
            "PYTHONPATH": str(pathlib.Path(__file__).parents[1] / "src"),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        },
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip().endswith("OK"), proc.stdout[-2000:]


@pytest.mark.parametrize("where", ["feature", "target"])
def test_negative_categories_raise_before_any_count(where):
    X, y = _data(*LAYOUTS["tall"][:2])
    if where == "feature":
        X[-1, 0] = -1
    else:
        y[-1] = -1
    with _budget(1 << 40), AccumulateLog() as log:
        clear_stats_memo()
        with pytest.raises(ValueError, match="negative category"):
            MRMRSelector(num_select=SELECT, block_obs=256).fit(
                ArraySource(X, y)
            )
    assert log.entries == []
    assert not dist._RESERVED  # the placed blocks went with the fit


class OpaqueTarget(Counting):
    """A source whose target dtype is not known before a read."""

    target_dtype = None


@pytest.mark.parametrize("target", ["float", "unknown"])
def test_other_targets_take_the_scan(target):
    X, y = _data(*LAYOUTS["tall"][:2])
    if target == "float":
        y = y.astype(np.float32)
        src, explicit = Counting(X, y), PearsonMIScore()
    else:
        src = OpaqueTarget(X, y)
        explicit = score_of_stats(ArraySource(X, y).stats())
    want = MRMRSelector(num_select=SELECT, score=explicit, block_obs=256).fit(
        ArraySource(X, y)
    )
    with _budget(1 << 40):
        clear_stats_memo()
        got = MRMRSelector(num_select=SELECT, block_obs=256).fit(src)
    _same(got, want)
    assert got.plan_.score == explicit
    io = got.result_.io
    assert io["resident_stats"] == 0
    # the front door's scan stops at the first block where the target is
    # float (continuous data); else it reads every block
    assert src.calls.count(256) == 2
    assert io["resident_passes"] == SELECT - 1
    assert io["bytes_read"] == src.X.nbytes + src.y.nbytes


def test_resident_fit_leaves_the_stats_memo(tmp_path):
    X, y = _data(*LAYOUTS["tall"][:2])
    xp, yp = str(tmp_path / "X.npy"), str(tmp_path / "y.npy")
    np.save(xp, X)
    np.save(yp, y)
    reads = []

    class CountingNpy(NpySource):
        def iter_blocks(self, block_obs):
            reads.append(block_obs)
            return super().iter_blocks(block_obs)

    with _budget(1 << 40):
        clear_stats_memo()
        src = CountingNpy(xp, yp)
        sel = MRMRSelector(num_select=SELECT, block_obs=256).fit(src)
    assert sel.result_.io["resident_stats"] == 1
    assert reads == [256]
    st = src.stats()
    assert (st.discrete, st.num_values, st.num_classes) == (True, 4, 3)
    assert CountingNpy(xp, yp).stats() == st
    assert reads == [256]  # neither stats() read anything


if __name__ == "__main__":
    check(*sys.argv[1:3])
    print("OK")
