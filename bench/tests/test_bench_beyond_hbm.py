"""The cell whose dataset sits above the device budget: the reader of
``resident_need_share``, and whole runs of ``tall.beyond_hbm`` on the CPU
at a small ragged size, with the device made to report a budget the
dataset exceeds, so that every pass streams and the last block is
partial."""

import pathlib
import sys
import time

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from mrmrbench import manifest, reference  # noqa: E402

# 4 whole blocks of 4,096 rows and a last one of 616
SMALL = dict(rows=17_000, features=64, block_obs=4096)
NEED = 5 * 4096 * (64 + 8 + 1)  # resident bytes of SMALL, int8 codes


class Run:
    def __init__(self, io):
        self.io, self.trace = io, None


def test_resident_need_share_reads_io():
    read = manifest.reader("resident_need_share")
    got = read(Run({"resident_need_bytes": 300, "resident_budget_bytes": 240}))
    assert got == 125.0
    assert read(Run({"resident_need_bytes": 1, "resident_budget_bytes": 4})) == 25.0


def test_resident_need_share_without_a_counter_reads_nothing():
    read = manifest.reader("resident_need_share")
    assert read(Run({"resident_need_bytes": 300})) is None
    assert read(Run({"resident_budget_bytes": 240})) is None
    assert read(Run({"resident_need_bytes": 300, "resident_budget_bytes": 0})) is None
    assert read(Run({"passes": 10, "resident_passes": 9})) is None
    assert read(Run(None)) is None


def test_resident_need_share_is_a_declared_metric():
    (m,) = [
        m for m in manifest.load()["per_layer"]
        if m["name"] == "resident_need_share"
    ]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "lower", "program_counter", "host→device placement", "fit_s"
    )
    assert "workloads" not in m


@pytest.fixture
def streamed_run(tmp_path, monkeypatch):
    """Drive ``run_cell`` of ``tall.beyond_hbm`` on the CPU at ``SMALL``,
    the device reporting memory whose resident budget is half the dataset's
    need; -> ``run()`` giving ``(result, io of every fit)``."""
    import jax

    from mrmrbench import cli
    from repro.core.streaming import clear_acc_fn_cache

    monkeypatch.setattr(
        "repro.runtime.compile_cache.enable_compile_cache", lambda: None
    )
    monkeypatch.setattr(
        type(jax.devices()[0]), "memory_stats",
        lambda device: dict(bytes_limit=NEED, bytes_in_use=0),
    )
    ios = []
    fitter = cli.fitter

    def recording(*args, **kwargs):
        fit = fitter(*args, **kwargs)

        def recorded(*a, **k):
            answer, io = fit(*a, **k)
            ios.append(io)
            return answer, io

        return recorded

    monkeypatch.setattr(cli, "fitter", recording)
    clear_acc_fn_cache()

    def run():
        spec = manifest.load()
        cell = manifest.cell(spec, "tall.beyond_hbm")
        config = dict(manifest.config(spec, cell["config"]), **SMALL)
        result = cli.run_cell(
            spec, "tall.beyond_hbm", 2**31 + 151, 0.2, False,
            jax.devices()[:1], time.perf_counter(),
            data_dir=tmp_path / "data", trace_dir=tmp_path / "traces",
            config=config,
        )
        return result, ios

    yield run
    clear_acc_fn_cache()


def test_streamed_ragged_run_is_correct(streamed_run):
    result, ios = streamed_run()
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert ios and all(io["resident_passes"] == 0 for io in ios)
    io = ios[-1]
    assert io["passes"] == 5 and io["blocks_read"] == 5 * 5
    assert io["resident_need_bytes"] == NEED
    assert io["resident_budget_bytes"] == NEED // 2
    assert manifest.reader("resident_need_share")(Run(io)) == 200.0


def test_ragged_block_left_out(streamed_run, monkeypatch):
    from repro.dist.streaming import BlockPlacer

    stage = BlockPlacer.stage

    def drop_ragged(self, X_block, target):
        staged_X, staged_target, valid = stage(self, X_block, target)
        if X_block.shape[0] < self.block_obs:
            valid = np.zeros_like(valid)
        return staged_X, staged_target, valid

    monkeypatch.setattr(BlockPlacer, "stage", drop_ragged)
    result, _ = streamed_run()
    assert not result["correct"] and result["failed"] == result["attempted"]
    gap = result["checks"]["relevance_gap"]["value"]
    assert gap > reference.LIMITS["relevance_gap"]
