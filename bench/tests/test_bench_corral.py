"""The benchmark's copy of the CorrAL generator is pinned: a drift in it
would change every cell's data, so its output at a tiny size is held to a
stored checksum.  The program's own generator may change freely."""

import hashlib
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from mrmrbench import corral  # noqa: E402

PINNED = "df93535387b9c88d22f22bf9d5a42882d3429ca06ce51da78b26f0c298114947"


def _digest(seed, rows, features, flip):
    h = hashlib.sha256()
    for lo, X, y in corral.generate(seed, rows, features, flip):
        h.update(np.int64(lo).tobytes())
        h.update(X.tobytes())
        h.update(y.tobytes())
    return h.hexdigest()


def test_generator_output_is_pinned():
    # 10000 rows: one whole chunk and a ragged one; a seed past 2**32.
    assert _digest(2**33 + 5, 10000, 16, 0.05) == PINNED


def test_generator_follows_eq3():
    (_, X, y), = corral.generate(3, 4096, 12, 0.0)
    x = X[:, :8].astype(bool)
    c = ((x[:, 0] & x[:, 1]) | (x[:, 2] & x[:, 3])) & (
        (x[:, 4] & x[:, 5]) | (x[:, 6] & x[:, 7])
    )
    assert np.array_equal(y, c.astype(np.int8))
    agree = np.mean(X[:, 8] == y)
    assert 0.72 < agree < 0.78
    assert set(np.unique(X).tolist()) == {0, 1}


def test_label_flips_follow_flip_prob():
    (_, X, y0), = corral.generate(4, 8192, 9, 0.0)
    (_, _, y1), = corral.generate(4, 8192, 9, 0.05)
    assert 0.035 < np.mean(y0 != y1) < 0.065


def test_too_few_features_is_refused():
    with pytest.raises(ValueError):
        next(corral.generate(0, 10, 8, 0.05))


def test_dataset_written_once_and_newest_kept(tmp_path):
    config = dict(name="c", rows=9000, features=10, flip_prob=0.05)
    x1, y1, wrote = corral.ensure_dataset(tmp_path, config, 1)
    assert wrote and np.load(x1).shape == (9000, 10)
    assert corral.ensure_dataset(tmp_path, config, 1)[2] is False
    x2, _, wrote = corral.ensure_dataset(tmp_path, config, 2)
    assert wrote and not x1.exists() and x2.exists()
    got = np.load(x2)
    want = np.concatenate([X for _, X, _ in corral.generate(2, 9000, 10, 0.05)])
    assert np.array_equal(got, want)
