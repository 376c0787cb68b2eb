"""Synthetic datasets — the paper's CorrAL-style generator (Eq. 3).

The paper evaluates on binary artificial datasets where the class depends on
8 features:

    c = ((x1 ^ x2) v (x3 ^ x4)) ^ ((x5 ^ x6) v (x7 ^ x8))        (Eq. 3)

with all remaining features irrelevant noise.  We reproduce that generator
(deterministically, chunked so millions of rows stream without a host-memory
spike) and add a partially-correlated column (as in CorrAL) and continuous
variants for the alternative-encoding/Pearson path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

RELEVANT = 8  # features participating in Eq. 3 (placed at indices 0..7)


def _class_from_relevant(xr: Array) -> Array:
    """Eq. 3 of the paper applied to the first 8 boolean columns."""
    x1, x2, x3, x4, x5, x6, x7, x8 = (xr[:, i] for i in range(8))
    return (((x1 & x2) | (x3 & x4)) & ((x5 & x6) | (x7 & x8))).astype(jnp.int32)


def corral_dataset(
    num_rows: int,
    num_cols: int,
    *,
    seed: int = 0,
    flip_prob: float = 0.05,
    correlated_col: bool = True,
    dtype=jnp.int8,
):
    """Paper §V dataset: binary, class from Eq. 3, remaining cols irrelevant.

    Returns (X (num_rows, num_cols) in {0,1}, y (num_rows,) in {0,1}).
    Column layout: 0..7 relevant; 8 (optionally) partially correlated with
    the class (CorrAL-style, 75% agreement); the rest iid noise.
    ``flip_prob`` injects label noise so MI values are non-degenerate.
    """
    if num_cols < RELEVANT + 1:
        raise ValueError(f"need at least {RELEVANT + 1} columns")
    key = jax.random.PRNGKey(seed)
    kx, kc, kf = jax.random.split(key, 3)
    X = jax.random.bernoulli(kx, 0.5, (num_rows, num_cols)).astype(jnp.bool_)
    y = _class_from_relevant(X[:, :RELEVANT])
    if correlated_col:
        agree = jax.random.bernoulli(kc, 0.75, (num_rows,))
        corr_col = jnp.where(agree, y.astype(jnp.bool_), ~y.astype(jnp.bool_))
        X = X.at[:, RELEVANT].set(corr_col)
    if flip_prob > 0:
        flips = jax.random.bernoulli(kf, flip_prob, (num_rows,))
        y = jnp.where(flips, 1 - y, y)
    return X.astype(dtype), y


def corral_dataset_np(
    num_rows: int,
    num_cols: int,
    *,
    seed: int = 0,
    flip_prob: float = 0.05,
    chunk: int = 1_000_000,
) -> tuple[np.ndarray, np.ndarray]:
    """Streaming numpy generator for benchmark-scale datasets (paper uses up
    to 10M rows): builds int8 chunks without a (rows, cols) float allocation."""
    rng = np.random.default_rng(seed)
    X = np.empty((num_rows, num_cols), dtype=np.int8)
    y = np.empty((num_rows,), dtype=np.int8)
    for start in range(0, num_rows, chunk):
        stop = min(start + chunk, num_rows)
        blk = rng.integers(0, 2, size=(stop - start, num_cols), dtype=np.int8)
        x = [blk[:, i].astype(bool) for i in range(8)]
        c = (((x[0] & x[1]) | (x[2] & x[3]))
             & ((x[4] & x[5]) | (x[6] & x[7])))
        agree = rng.random(stop - start) < 0.75
        blk[:, RELEVANT] = np.where(agree, c, ~c)
        if flip_prob > 0:
            flips = rng.random(stop - start) < flip_prob
            c = np.where(flips, ~c, c)
        X[start:stop] = blk
        y[start:stop] = c.astype(np.int8)
    return X, y


def continuous_wide_dataset(
    num_rows: int,
    num_cols: int,
    *,
    seed: int = 0,
    signal_cols: int = 8,
    noise: float = 0.5,
):
    """Continuous S/W-style dataset for the alternative/Pearson path.

    The first ``signal_cols`` columns carry graded linear signal about a
    binary class; later signal columns are partially redundant copies of
    earlier ones, so mRMR's redundancy term is exercised (not just ranking).
    """
    key = jax.random.PRNGKey(seed)
    ky, kx, kn, kr = jax.random.split(key, 4)
    y = jax.random.bernoulli(ky, 0.5, (num_rows,)).astype(jnp.float32)
    X = jax.random.normal(kx, (num_rows, num_cols), jnp.float32)
    strengths = jnp.linspace(1.5, 0.5, signal_cols)
    sig = y[:, None] * strengths[None, :] + noise * jax.random.normal(
        kn, (num_rows, signal_cols)
    )
    X = X.at[:, :signal_cols].set(sig)
    # Redundant shadow of column 0 -> should be down-ranked by mRMR.
    if num_cols > signal_cols:
        X = X.at[:, signal_cols].set(
            X[:, 0] + 0.1 * jax.random.normal(kr, (num_rows,))
        )
    return X, y.astype(jnp.int32)

