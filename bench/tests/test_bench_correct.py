"""The comparison that decides ``correct``: the reference's counts and MI
by hand, the bfloat16 control failing, and whole runs of a cell (the
look for a chip skipped, at a size a test can hold) seeing ``correct``
come out false with the timed path broken underneath."""

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from mrmrbench import corral, manifest, reference  # noqa: E402

SMALL = dict(rows=16384, features=64, block_obs=4096)


def _data(seed=5, rows=16384, features=64):
    Xs, ys = [], []
    for _, X, y in corral.generate(seed, rows, features, 0.05):
        Xs.append(X)
        ys.append(y)
    return np.concatenate(Xs), np.concatenate(ys)


def test_counts_match_direct_counting():
    X, y = _data(rows=3000, features=12)
    t = reference.count_tables(X, y, [8, 2], 2, 2)
    for f in range(12):
        for v in range(2):
            for c in range(2):
                assert t.rel[f, v, c] == np.sum((X[:, f] == v) & (y == c))
                for w in range(2):
                    want = np.sum((X[:, f] == v) & (X[:, 8] == w) & (y == c))
                    assert t.pair[8][f, v, w, c] == want


def test_mutual_info_by_hand():
    # p = [[.4, .1], [.1, .4]]: MI = .8 ln 1.6 + .2 ln .4
    got = reference.mutual_info(np.array([[4, 1], [1, 4]]))
    assert got == pytest.approx(0.8 * np.log(1.6) + 0.2 * np.log(0.4), rel=1e-12)
    assert reference.mutual_info(np.array([[5, 5], [5, 5]])) == 0.0


def test_conditional_mutual_info_by_hand():
    counts = np.zeros((2, 2, 2))
    counts[:, :, 0] = [[4, 1], [1, 4]]  # dependent within class 0
    counts[:, :, 1] = [[5, 0], [5, 0]]  # independent within class 1
    # each class holds half the rows
    want = 0.5 * reference.mutual_info(np.array([[4, 1], [1, 4]]))
    assert reference.conditional_mutual_info(counts) == pytest.approx(want)


@pytest.mark.parametrize("criterion", ["mid", "jmi"])
def test_control_fails_and_program_passes(criterion):
    """The program's fit at a test size passes; the reference computed in
    bfloat16 in its place fails by far."""
    from repro import MRMRSelector

    X, y = _data()
    sel = MRMRSelector(num_select=6, criterion=criterion, block_obs=4096).fit(X, y)
    tables = reference.count_tables(X, y, range(X.shape[1]), 2, 2)
    ref = reference.Scorer(tables, criterion)
    program = reference.Answer(sel.selected_, sel.gains_, sel.scores_)
    assert reference.judge(reference.compare(program, ref))
    control = reference.Scorer(tables, criterion, reference.bf16_round)
    for got in (
        reference.compare(control.greedy(6), ref),
        reference.control_numbers(control, ref, program.ids),
    ):
        assert not reference.judge(got)
        assert min(got.values()) > 3 * max(reference.LIMITS.values())


def test_reference_greedy_is_sound():
    X, y = _data()
    tables = reference.count_tables(X, y, range(X.shape[1]), 2, 2)
    ref = reference.Scorer(tables, "mid")
    own = ref.greedy(6)
    assert reference.compare(own, ref) == dict(select_gap=0.0, relevance_gap=0.0)
    assert set(own.ids[:5].tolist()) <= set(range(9))


def test_answers_that_break_the_rules_fail():
    X, y = _data()
    tables = reference.count_tables(X, y, range(X.shape[1]), 2, 2)
    ref = reference.Scorer(tables, "mid")
    good = ref.greedy(4)
    twice = reference.Answer(np.array([good.ids[0]] * 4), good.gains, good.relevance)
    assert not reference.judge(reference.compare(twice, ref))
    short = reference.Answer(good.ids, good.gains, good.relevance[:-1])
    assert not reference.judge(reference.compare(short, ref))


# -- whole runs with the timed path broken --------------------------------


@pytest.fixture
def cell_run(tmp_path, monkeypatch):
    """Drive ``run_cell`` on the CPU at a small size, fresh programs."""
    import jax

    from mrmrbench import cli
    from repro.core.streaming import clear_acc_fn_cache

    monkeypatch.setattr(
        "repro.runtime.compile_cache.enable_compile_cache", lambda: None
    )
    clear_acc_fn_cache()

    def run(workload="tall.mid"):
        spec = manifest.load()
        cell = manifest.cell(spec, workload)
        config = dict(manifest.config(spec, cell["config"]), **SMALL)
        return cli.run_cell(
            spec, workload, 2**31 + 99, 0.2, False, jax.devices()[:1],
            time.perf_counter(), data_dir=tmp_path / "data",
            trace_dir=tmp_path / "traces", config=config,
        )

    yield run
    clear_acc_fn_cache()


@pytest.mark.parametrize("workload", ["tall.mid", "tall.jmi"])
def test_sound_run_is_correct(cell_run, workload):
    result = cell_run(workload)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"fit_s", "setup_s"}


def test_answer_altered_where_produced(cell_run, monkeypatch):
    import repro.core.streaming as streaming

    greedy = streaming._greedy_select

    def altered(*args, **kwargs):
        rel, selected, gains = greedy(*args, **kwargs)
        selected = selected.copy()
        selected[[0, -1]] = selected[[-1, 0]]  # first pick swapped with last
        return rel, selected, gains

    monkeypatch.setattr(streaming, "_greedy_select", altered)
    result = cell_run()
    assert not result["correct"] and result["failed"] == result["attempted"]


def test_half_the_rows_left_out(cell_run, monkeypatch):
    from repro.dist.streaming import BlockPlacer

    stage = BlockPlacer.stage

    def half(self, X_block, target):
        X_block, target, valid = stage(self, X_block, target)
        valid = valid.copy()
        valid[len(valid) // 2 :] = False
        return X_block, target, valid

    monkeypatch.setattr(BlockPlacer, "stage", half)
    result = cell_run()
    assert not result["correct"]
    assert result["checks"]["relevance_gap"]["value"] > reference.LIMITS["relevance_gap"]


def test_state_returned_unchanged(cell_run, monkeypatch):
    from repro.core.scores import MIScore

    monkeypatch.setattr(MIScore, "accumulate", lambda self, state, *a, **k: state)
    result = cell_run()
    assert not result["correct"]


EXCHANGE = r"""
import json, sys, time, pathlib
sys.path.insert(0, {bench!r}); sys.path.insert(0, {src!r})
import jax
if sys.argv[1] == "broken":
    jax.lax.psum = lambda x, axes, **kw: x
from mrmrbench import cli, manifest
import repro.runtime.compile_cache as cc
cc.enable_compile_cache = lambda: None
spec = manifest.load()
config = dict(manifest.config(spec, "corral_tall_1m"), **{small!r})
tmp = pathlib.Path(sys.argv[2])
r = cli.run_cell(spec, "tall.mid.x4", 7, 0.2, False, jax.devices()[:4],
                 time.perf_counter(), data_dir=tmp / "data",
                 trace_dir=tmp / "traces", config=config)
print(json.dumps(r))
"""


@pytest.mark.parametrize("mode, correct", [("sound", True), ("broken", False)])
def test_exchange_between_chips_left_out(tmp_path, mode, correct):
    """Four CPU devices; with the per-block psum taken out, each chip
    keeps its own quarter of the counts."""
    script = EXCHANGE.format(bench=str(BENCH), src=str(ROOT / "src"), small=SMALL)
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    out = subprocess.run(
        [sys.executable, "-c", script, mode, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is correct
    assert result["device"]["count"] == 4
