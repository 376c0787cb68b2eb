"""The reader of ``resident_stats``: whether a fit sized its default score
from the blocks it keeps on the device, nothing where the program has no
such counter, and whole runs of ``tall.mid`` on the CPU at a small size,
with the device made to report memory the dataset fits in, so that every
fit of the window, the warm-up's too, sizes its score that way."""

import pathlib
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from mrmrbench import manifest  # noqa: E402

SMALL = dict(rows=16384, features=64, block_obs=4096)


class Run:
    def __init__(self, io):
        self.io, self.trace = io, None


def test_resident_stats_reads_io():
    read = manifest.reader("resident_stats")
    assert read(Run({"resident_stats": 1, "resident_passes": 10})) == 1.0
    assert read(Run({"resident_stats": 0, "resident_passes": 0})) == 0.0


def test_resident_stats_without_the_counter_reads_nothing():
    read = manifest.reader("resident_stats")
    assert read(Run({"resident_passes": 9, "host_syncs": 20})) is None
    assert read(Run(None)) is None
    assert read(Run({})) is None


def test_resident_stats_is_a_declared_metric():
    (m,) = [
        m for m in manifest.load()["per_layer"] if m["name"] == "resident_stats"
    ]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "count", "higher", "program_counter", "front door / plan", "fit_s"
    )
    assert "workloads" not in m


@pytest.mark.parametrize("workload", ["tall.mid", "tall.jmi"])
def test_resident_run_sizes_its_score_from_its_blocks(
    tmp_path, monkeypatch, workload
):
    import jax

    from mrmrbench import cli
    from repro.core.streaming import clear_acc_fn_cache

    monkeypatch.setattr(
        "repro.runtime.compile_cache.enable_compile_cache", lambda: None
    )
    monkeypatch.setattr(
        type(jax.devices()[0]), "memory_stats",
        lambda device: dict(bytes_limit=1 << 34, bytes_in_use=0),
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
    try:
        spec = manifest.load()
        cell = manifest.cell(spec, workload)
        config = dict(manifest.config(spec, cell["config"]), **SMALL)
        result = cli.run_cell(
            spec, workload, 2**31 + 163, 0.2, False, jax.devices()[:1],
            time.perf_counter(), data_dir=tmp_path / "data",
            trace_dir=tmp_path / "traces", config=config,
        )
    finally:
        clear_acc_fn_cache()
    assert result["correct"] and result["failed"] == 0
    assert len(ios) >= 2  # the warm-up and the window's fits
    select = int(config["num_select"])
    warm, *window = ios
    assert warm["resident_passes"] == 2
    for io in window:
        assert io["resident_stats"] == 1
        assert io["resident_passes"] == select
        assert io["blocks_read"] == SMALL["rows"] // SMALL["block_obs"]
    assert all(io["resident_stats"] == 1 for io in ios)
