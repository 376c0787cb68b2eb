"""The command-line front doors, called in-process.

``python -m repro.launch.select`` and ``python -m repro.launch.serve_select``
on a small CorrAL table written to ``tmp_path`` in each input format.
Every case checks the CLI's selection against ``mrmr_reference`` (or
``MRMRSelector`` with the same settings) on the same arrays.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro import MIScore, MRMRSelector, PearsonMIScore
from repro.core import MRMRResult, mrmr_reference
from repro.data.sources import CorralSource
from repro.data.synthetic import corral_dataset_np
from repro.launch import select, serve_select

ROWS, COLS, L = 700, 12, 5
BLOCK = ["--block-obs", "256"]  # 700 rows: two full blocks and a short one
CRITERIA = ("mid", "miq", "maxrel", "jmi", "cmim", "mifs", "cife", "icap")


@pytest.fixture(scope="module")
def corral():
    X, y = CorralSource(ROWS, COLS, seed=5, flip_prob=0.02).materialize()
    return X.astype(np.int32), y.astype(np.int32)


@pytest.fixture(scope="module")
def npy(corral, tmp_path_factory):
    """-> the argv of a ``.npy`` input with its ``--target``."""
    d = tmp_path_factory.mktemp("npy")
    X, y = corral
    np.save(d / "X.npy", X)
    np.save(d / "y.npy", y)
    return ["--input", str(d / "X.npy"), "--target", str(d / "y.npy")]


def _ref(X, y, num_select=L, score=None, **kw):
    res = mrmr_reference(
        jnp.asarray(X.T), jnp.asarray(y), num_select,
        score or MIScore(2, 2), **kw,
    )
    return np.asarray(res.selected).tolist()


def _select(*argv):
    return select.main(["--select", str(L), *argv])


class TestInputs:
    def test_npy_with_target(self, corral, npy):
        out = _select(*npy, *BLOCK)
        assert out["encoding"] == "streaming"
        assert out["selected"] == _ref(*corral)
        assert out["io"]["passes"] == L

    def test_npz(self, corral, tmp_path):
        X, y = corral
        np.savez(tmp_path / "data.npz", X=X, y=y)
        out = _select("--input", str(tmp_path / "data.npz"))
        assert out["encoding"] == "conventional"
        assert out["selected"] == _ref(X, y)

    def test_csv(self, corral, tmp_path):
        X, y = corral
        path = tmp_path / "data.csv"
        header = ",".join([f"f{j}" for j in range(COLS)] + ["label"])
        np.savetxt(path, np.column_stack([X, y]), fmt="%d", delimiter=",",
                   header=header, comments="")
        out = _select("--input", str(path), *BLOCK)
        assert out["encoding"] == "streaming"
        assert out["selected"] == _ref(X, y)

    def test_parquet(self, corral, tmp_path):
        pa = pytest.importorskip("pyarrow")
        import pyarrow.parquet as pq

        X, y = corral
        cols = {f"f{j}": X[:, j] for j in range(COLS)}
        cols["label"] = y
        path = tmp_path / "data.parquet"
        pq.write_table(pa.table(cols), path, row_group_size=300)
        out = _select("--input", str(path), *BLOCK)
        assert out["encoding"] == "streaming"
        assert out["selected"] == _ref(X, y)

    def test_synthetic_default(self):
        out = _select("--rows", str(ROWS), "--cols", str(COLS), "--seed", "3")
        X, y = corral_dataset_np(ROWS, COLS, seed=3)
        assert out["selected"] == _ref(X, y)
        assert set(out["selected"]) <= set(range(9))


@pytest.mark.parametrize(
    "encoding", ["conventional", "alternative", "grid", "streaming", "auto"]
)
def test_encoding(corral, tmp_path, encoding):
    X, y = corral
    np.savez(tmp_path / "data.npz", X=X, y=y)
    out = _select("--input", str(tmp_path / "data.npz"),
                  "--encoding", encoding, *BLOCK)
    want = "conventional" if encoding == "auto" else encoding
    assert out["encoding"] == want
    assert out["selected"] == _ref(X, y)


@pytest.mark.parametrize("criterion", CRITERIA)
def test_criterion(corral, npy, criterion):
    out = _select(*npy, *BLOCK, "--criterion", criterion)
    assert out["criterion"] == criterion
    assert out["selected"] == _ref(*corral, criterion=criterion)
    # A relevance-only criterion needs one pass of the source, not L.
    assert out["io"]["passes"] == (1 if criterion == "maxrel" else L)


class TestOptions:
    def test_score_pearson(self, corral, tmp_path):
        X, y = corral
        np.savez(tmp_path / "data.npz", X=X, y=y)
        out = _select("--input", str(tmp_path / "data.npz"),
                      "--score", "pearson")
        want = MRMRSelector(num_select=L, score=PearsonMIScore()).fit(
            X.astype(np.float32), y
        )
        assert out["selected"] == want.selected_.tolist()
        assert out["encoding"] == want.plan_.encoding

    def test_bins_on_a_float_input(self, tmp_path):
        rng = np.random.default_rng(0)
        y = rng.integers(0, 2, ROWS).astype(np.int32)
        Xf = rng.normal(size=(ROWS, COLS)).astype(np.float32)
        Xf[:, :3] += y[:, None] * np.array([1.5, 1.0, 0.6], np.float32)
        np.save(tmp_path / "Xf.npy", Xf)
        np.save(tmp_path / "y.npy", y)
        out = _select("--input", str(tmp_path / "Xf.npy"),
                      "--target", str(tmp_path / "y.npy"), "--bins", "8",
                      *BLOCK)
        want = MRMRSelector(num_select=L, bins=8).fit(Xf, y)
        assert out["bins"] == 8
        assert out["selected"] == want.selected_.tolist()
        assert set(out["selected"][:3]) == {0, 1, 2}

    def test_spill_dir(self, corral, npy, tmp_path):
        out = _select(*npy, *BLOCK, "--spill-dir", str(tmp_path / "spill"))
        assert out["selected"] == _ref(*corral)
        assert out["spill_dir"] == str(tmp_path / "spill")
        cache = out["io"]["cache"]
        assert cache["parse_passes"] == 1 and cache["replay_passes"] == L - 1

    def test_readahead(self, corral, npy):
        out = _select(*npy, *BLOCK, "--readahead", "2")
        assert out["readahead"] == 2
        assert out["selected"] == _ref(*corral)

    def test_batch_candidates(self, corral, npy):
        out = _select(*npy, *BLOCK, "--batch-candidates", "3")
        assert out["batch_candidates"] == 3
        assert out["selected"] == _ref(*corral)
        assert out["io"]["passes"] < L

    def test_incremental_0(self, corral, tmp_path):
        X, y = corral
        np.savez(tmp_path / "data.npz", X=X, y=y)
        out = _select("--input", str(tmp_path / "data.npz"),
                      "--incremental", "0")
        assert out["incremental"] is False
        assert out["selected"] == _ref(X, y, incremental=False)

    def test_output_reads_back_as_json(self, corral, npy, tmp_path):
        path = tmp_path / "result.json"
        out = _select(*npy, *BLOCK, "--output", str(path))
        assert out["output"] == str(path)
        payload = json.loads(path.read_text())
        assert payload["selected"] == out["selected"] == _ref(*corral)
        back = MRMRResult.from_json(path.read_text())
        assert np.asarray(back.selected).tolist() == out["selected"]
        np.testing.assert_allclose(np.asarray(back.gains), out["gains"],
                                   atol=1e-5)


class TestServeSelect:
    SOURCE = f"corral:{ROWS}x{COLS}:4"

    def _want(self, num_select):
        X, y = CorralSource(ROWS, COLS, seed=4).materialize()
        return _ref(X.astype(np.int32), y.astype(np.int32), num_select)

    def test_repeat_is_served_from_the_cache(self):
        out = serve_select.main(["--source", self.SOURCE, "--select", str(L),
                                 "--repeat", "2", "--block-obs", "256"])
        first, second = out["jobs"]
        assert first["selected"] == second["selected"] == self._want(L)
        assert not first["cache_hit"] and second["cache_hit"]
        assert out["stats"]["cache"]["hits"] == 1

    def test_distinct_select(self):
        out = serve_select.main(["--source", self.SOURCE, "--select", str(L),
                                 "--repeat", "1", "--distinct-select", "3",
                                 "--block-obs", "256"])
        first, distinct = out["jobs"]
        assert first["selected"] == self._want(L)
        assert distinct["selected"] == self._want(3)
        assert not distinct["cache_hit"]
        assert out["stats"]["cache"]["misses"] == 2
