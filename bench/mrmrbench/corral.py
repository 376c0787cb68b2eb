"""The paper's CorrAL generator (arXiv:1709.02327, §V, Eq. 3), kept with the
benchmark so that a change to the program's own generator cannot change
the yardstick.

Rows come in fixed chunks of ``CHUNK`` rows, each drawn from
``numpy.random.default_rng((seed, chunk_index))``, so a dataset is a pure
function of ``(seed, rows, features, flip_prob)``.  Column layout: 0..7
relevant (the class is ``((x0 & x1) | (x2 & x3)) & ((x4 & x5) | (x6 &
x7))``), 8 agrees with the class on 75 % of rows, the rest are iid fair
bits; ``flip_prob`` of the labels are flipped after column 8 is drawn.
"""

from __future__ import annotations

import pathlib
import shutil

import numpy as np

CHUNK = 8192


def chunk(seed: int, index: int, rows: int, features: int, flip_prob: float):
    """Rows ``[index * CHUNK, index * CHUNK + rows)`` as ``(X int8, y int8)``."""
    rng = np.random.default_rng((int(seed), int(index)))
    blk = rng.integers(0, 2, size=(rows, features), dtype=np.int8)
    x = [blk[:, i].astype(bool) for i in range(8)]
    c = ((x[0] & x[1]) | (x[2] & x[3])) & ((x[4] & x[5]) | (x[6] & x[7]))
    agree = rng.random(rows) < 0.75
    blk[:, 8] = np.where(agree, c, ~c)
    if flip_prob > 0:
        flips = rng.random(rows) < flip_prob
        c = np.where(flips, ~c, c)
    return blk, c.astype(np.int8)


def generate(seed: int, rows: int, features: int, flip_prob: float):
    """Yield ``(row offset, X, y)`` chunks of the whole dataset."""
    if features < 9:
        raise ValueError("CorrAL needs at least 9 features")
    for index in range(-(-rows // CHUNK)):
        lo = index * CHUNK
        n = min(CHUNK, rows - lo)
        yield (lo,) + chunk(seed, index, n, features, flip_prob)


def dataset_paths(data_dir: pathlib.Path, config: dict, seed: int):
    """``(X.npy, y.npy)`` of one configuration's dataset for one seed."""
    d = data_dir / config["name"] / f"seed{int(seed)}"
    return d / "X.npy", d / "y.npy"


def ensure_dataset(data_dir: pathlib.Path, config: dict, seed: int):
    """Write the dataset of ``config`` and ``seed`` unless it is there.

    Returns ``(x_path, y_path, wrote)``.  Only the newest dataset of a
    configuration is kept: writing one deletes the others.  A dataset is
    written under a temporary name and renamed when complete, so an
    interrupted write is never reused.
    """
    x_path, y_path = dataset_paths(data_dir, config, seed)
    final = x_path.parent
    if (final / "complete").exists():
        return x_path, y_path, False
    conf_dir = final.parent
    if conf_dir.exists():
        shutil.rmtree(conf_dir)
    tmp = conf_dir / (final.name + ".partial")
    tmp.mkdir(parents=True)
    rows, features = int(config["rows"]), int(config["features"])
    X = np.lib.format.open_memmap(
        tmp / "X.npy", mode="w+", dtype=np.int8, shape=(rows, features)
    )
    y = np.lib.format.open_memmap(
        tmp / "y.npy", mode="w+", dtype=np.int8, shape=(rows,)
    )
    for lo, xb, yb in generate(seed, rows, features, float(config["flip_prob"])):
        X[lo : lo + xb.shape[0]] = xb
        y[lo : lo + yb.shape[0]] = yb
    X.flush()
    y.flush()
    del X, y
    (tmp / "complete").touch()
    tmp.rename(final)
    return x_path, y_path, True
