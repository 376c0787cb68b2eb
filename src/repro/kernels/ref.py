"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are tested against
(``tests/test_kernels.py`` sweeps shapes/dtypes with ``interpret=True``).
They delegate to the core library where the math already exists, so the
kernel contract and the algorithm stay in lock-step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import contingency as _contingency
from repro.core import scores as _scores

Array = jax.Array


def contingency_tables(X: Array, y: Array, num_values: int, num_classes: int) -> Array:
    """(M, F) int, (M,) int -> (F, V, C) float32 contingency tables.

    Out-of-range entries (padding) contribute zero counts.
    """
    return _contingency.batched_counts(
        X, y, num_values, num_classes, block=max(1, min(64, X.shape[1]))
    )


def conditional_tables(
    X: Array, xj: Array, y: Array, num_values: int, num_classes: int
) -> Array:
    """(M, F), (M,), (M,) -> (F, V, V, C) class-conditioned pair tables.

    ``counts[f, v, w, c]`` counts rows where ``X[:, f] == v``,
    ``xj == w`` and ``y == c``; out-of-range entries contribute zero.
    """
    return _contingency.conditional_counts(
        X, xj, y, num_values, num_values, num_classes,
        block=max(1, min(64, X.shape[1])),
    )


def pearson_corr(X: Array, Y: Array) -> Array:
    """(F, M), (T, M) -> (F, T) Pearson correlation of rows."""
    return _scores.pearson_rows(X, Y)


def mi_scores(counts: Array) -> Array:
    """(F, V, C) counts -> (F,) mutual information in nats."""
    return _scores.mi_from_counts(counts)


def bin_codes(X: Array, edges: Array) -> Array:
    """(B, N) floats x (N, E) sorted edges -> (B, N) int32 bin codes.

    ``searchsorted(side="right")`` per feature column; comparisons in f32
    to match the host encoder and the Pallas kernel bit-for-bit.
    """
    return jax.vmap(
        lambda e, col: jnp.searchsorted(e, col, side="right"),
        in_axes=(0, 1),
        out_axes=1,
    )(edges.astype(jnp.float32), X.astype(jnp.float32)).astype(jnp.int32)


def cor2mi(corr: Array) -> Array:
    """Listing-8 Gaussian MI approximation."""
    return _scores.cor2mi(corr)

