"""jit'd dispatch wrappers for the Pallas kernels.

``use_pallas``:
  * ``"auto"``  — compiled Pallas on TPU, interpreted Pallas is NOT silently
    used on CPU (interpret mode is a correctness harness, ~100x slower than
    jnp); CPU gets the jnp oracle instead.
  * ``True``    — force Pallas (interpret=True off-TPU; used by tests).
  * ``False``   — force the jnp oracle.

This keeps one call site per op across the library while staying runnable
on both the CPU CI container and a real TPU pod.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.binning import bin_codes_pallas
from repro.kernels.contingency import (
    conditional_tables_pallas,
    contingency_tables_pallas,
)
from repro.kernels.mi_score import mi_scores_pallas
from repro.kernels.pearson import pearson_corr_pallas

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _decide(use_pallas) -> tuple[bool, bool]:
    """-> (run_pallas, interpret)."""
    if use_pallas == "auto":
        return (_on_tpu(), False)
    if use_pallas:
        return (True, not _on_tpu())
    return (False, False)


@functools.partial(
    jax.jit, static_argnames=("num_values", "num_classes", "use_pallas")
)
def contingency_tables(
    X: Array, y: Array, num_values: int, num_classes: int, use_pallas="auto"
) -> Array:
    """(M, F), (M,) -> (F, V, C) contingency tables."""
    run, interp = _decide(use_pallas)
    if run:
        return contingency_tables_pallas(
            X, y, num_values, num_classes, interpret=interp
        )
    return ref.contingency_tables(X, y, num_values, num_classes)


@functools.partial(
    jax.jit, static_argnames=("num_values", "num_classes", "use_pallas")
)
def conditional_tables(
    X: Array, xj: Array, y: Array, num_values: int, num_classes: int,
    use_pallas="auto",
) -> Array:
    """(M, F), (M,), (M,) -> (F, V, V, C) class-conditioned pair tables.

    The JMI/CMIM redundancy statistic: marginal pair counts split per
    class, so one call yields both ``I(x_k; x_j)`` (class-summed) and
    ``I(x_k; x_j | y)`` (class-weighted per-slice MI).
    """
    run, interp = _decide(use_pallas)
    if run:
        return conditional_tables_pallas(
            X, xj, y, num_values, num_classes, interpret=interp
        )
    return ref.conditional_tables(X, xj, y, num_values, num_classes)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def pearson_corr(X: Array, Y: Array, use_pallas="auto") -> Array:
    """(F, M), (T, M) -> (F, T) row correlations."""
    run, interp = _decide(use_pallas)
    if run:
        return pearson_corr_pallas(X, Y, interpret=interp)
    return ref.pearson_corr(X, Y)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def mi_scores(counts: Array, use_pallas="auto") -> Array:
    """(F, V, C) counts -> (F,) MI (nats)."""
    run, interp = _decide(use_pallas)
    if run:
        return mi_scores_pallas(counts, interpret=interp)
    return ref.mi_scores(counts)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def bin_codes(X: Array, edges: Array, use_pallas="auto") -> Array:
    """(B, N) floats x (N, E) sorted edges -> (B, N) int32 bin codes."""
    run, interp = _decide(use_pallas)
    if run:
        return bin_codes_pallas(X, edges, interpret=interp)
    return ref.bin_codes(X, edges)


def mi_tables(
    X: Array, y: Array, num_values: int, num_classes: int, use_pallas="auto"
) -> Array:
    """Fused convenience: per-feature MI against ``y`` from raw columns."""
    counts = contingency_tables(X, y, num_values, num_classes, use_pallas)
    return mi_scores(counts, use_pallas)

