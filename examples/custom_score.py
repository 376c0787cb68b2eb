"""Custom feature-score functions (paper §IV.D, Listings 7-8).

The paper's alternative encoding exposes ``getResult(variableArray,
classArray, selectedVariablesArray) -> Double``.  Our JAX equivalent is a
``CustomScore`` whose ``get_result(v, cls, selected, n_selected)`` is traced
and vectorised over the feature shard — the same contract, but compiled.
Custom scores go through the same ``MRMRSelector`` front door as everything
else: the planner routes them to the feature-sharded (map-only) encoding
automatically, and the selector owns the layout transposition.

Two scores are shown:
  1. the paper's own example — Pearson-correlation MI approximation
     (Listing 8: f = -0.5*log(1-rho^2));
  2. a user-defined score the paper never shipped — an ANOVA-F-style
     signal-to-noise ratio, demonstrating that anything expressible in jnp
     drops in.
"""

import jax.numpy as jnp
import numpy as np

from repro import CustomScore, MRMRSelector, PearsonMIScore
from repro.core.scores import cor2mi
from repro.data.synthetic import continuous_wide_dataset

N_OBS, N_FEAT, K = 2_000, 4_096, 8


# --- 1. paper Listing 8, literally -----------------------------------------
def listing8_get_result(v, cls, selected, n_selected):
    """v (M,), cls (M,), selected (L, M); rows >= n_selected are zeros."""

    def pcc(a, b):
        a = a - a.mean()
        b = b - b.mean()
        return (a * b).sum() / jnp.sqrt((a * a).sum() * (b * b).sum() + 1e-12)

    sc = cor2mi(pcc(v, cls))
    live = jnp.arange(selected.shape[0]) < n_selected
    sfs = jnp.where(
        live, cor2mi(jnp.vectorize(pcc, signature="(m),(m)->()")(selected, v)), 0.0
    ).sum()
    coeff = jnp.where(n_selected > 0, 1.0 / jnp.maximum(n_selected, 1), 1.0)
    return sc - coeff * sfs


# --- 2. a user-defined score ------------------------------------------------
def anova_f_get_result(v, cls, selected, n_selected):
    """Relevance = between/within-class variance; redundancy = |rho|."""
    m1 = jnp.where(cls > 0.5, v, 0).sum() / jnp.maximum((cls > 0.5).sum(), 1)
    m0 = jnp.where(cls <= 0.5, v, 0).sum() / jnp.maximum((cls <= 0.5).sum(), 1)
    within = v.var() + 1e-6
    rel = (m1 - m0) ** 2 / within

    def absrho(a):
        a = a - a.mean()
        b = v - v.mean()
        return jnp.abs(
            (a * b).sum() / jnp.sqrt((a * a).sum() * (b * b).sum() + 1e-12)
        )

    live = jnp.arange(selected.shape[0]) < n_selected
    red = jnp.where(
        live, jnp.vectorize(absrho, signature="(m)->()")(selected), 0.0
    ).sum()
    return rel - red / jnp.maximum(n_selected, 1)


def main():
    X, y = continuous_wide_dataset(N_OBS, N_FEAT, seed=0)
    X = jnp.asarray(X)  # conventional orientation (obs × features)

    selected = {}
    for name, score in [
        ("built-in PearsonMI", PearsonMIScore()),
        ("Listing 8 (custom)", CustomScore(get_result=listing8_get_result)),
        ("ANOVA-F (custom)", CustomScore(get_result=anova_f_get_result)),
    ]:
        fs = MRMRSelector(num_select=K, score=score).fit(X, y)
        sel = selected[name] = [int(k) for k in fs.selected_]
        print(f"{name:>20s}: selected {sel} "
              f"(encoding={fs.plan_.encoding})")
        print(f"{'':>20s}  signal cols (0-7) recovered: "
              f"{len(set(sel) & set(range(8)))}/8, "
              f"redundant shadow col 8 picked: {8 in sel}")
    return selected


if __name__ == "__main__":
    main()
