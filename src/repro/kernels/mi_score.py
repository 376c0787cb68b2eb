"""Pallas TPU kernel: batched mutual information from contingency tables.

Reduces stacked (F, V, C) contingency tables to per-feature MI in nats:

    MI_f = sum_{v,c} p log(p / (p_v * p_c)),   p = counts_f / total_f

Memory-bound elementwise-log + reduction; fusing it avoids three extra HBM
round-trips (p, px*py, terms) after the contingency kernel.  Tables are
laid out as (V*C, F) with features on the lanes, and each (v, c) cell is
read as its own lane-dense (1, TF) row, so the marginals are sums of rows
and no in-kernel reshape is needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

_EPS = 1e-12


def _kernel(c_ref, out_ref, *, num_values: int, num_classes: int):
    V, C = num_values, num_classes
    rows = [c_ref[r : r + 1, :] for r in range(V * C)]  # (1, TF) each
    cells = [rows[v * C : (v + 1) * C] for v in range(V)]
    total = jnp.maximum(sum(x for row in cells for x in row), 1.0)  # (1, TF)
    p = [[x / total for x in row] for row in cells]
    px = [sum(row) for row in p]
    py = [sum(p[v][c] for v in range(V)) for c in range(C)]
    mi = jnp.zeros_like(total)
    for v in range(V):
        for c in range(C):
            ratio = p[v][c] / jnp.maximum(px[v] * py[c], _EPS)
            mi += jnp.where(
                p[v][c] > 0, p[v][c] * jnp.log(jnp.maximum(ratio, _EPS)), 0.0
            )
    out_ref[...] = mi


def mi_scores_pallas(
    counts: Array,
    *,
    tile_f: int = 256,
    interpret: bool = False,
) -> Array:
    """(F, V, C) counts -> (F,) MI in nats (float32)."""
    F, V, C = counts.shape
    tile_f = min(tile_f, -(-F // 128) * 128)
    pad_f = (-F) % tile_f
    flat = counts.reshape(F, V * C).astype(jnp.float32).T  # (V*C, F)
    flat = jnp.pad(flat, ((0, 0), (0, pad_f)))
    fp = flat.shape[1]

    out = pl.pallas_call(
        functools.partial(_kernel, num_values=V, num_classes=C),
        grid=(fp // tile_f,),
        in_specs=[pl.BlockSpec((V * C, tile_f), lambda f: (0, f))],
        out_specs=pl.BlockSpec((1, tile_f), lambda f: (0, f)),
        out_shape=jax.ShapeDtypeStruct((1, fp), jnp.float32),
        interpret=interpret,
        name="mi_scores",
    )(flat)

    return out[0, :F]
