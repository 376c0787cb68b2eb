"""Pallas TPU kernel: quantile-bin encoding (batched searchsorted).

Maps a float block ``X (B, N)`` against per-feature sorted edge rows
``edges (N, E)`` to int32 bin codes::

    code[b, n] = #{ k : edges[n, k] <= X[b, n] }

which is exactly ``searchsorted(edges[n], X[:, n], side="right")`` — the
comparison-sum form trades the branchy binary search for ``E`` dense
vectorised compares, the right shape for the VPU (E = bins - 1 is small,
tens not thousands).  Fused ahead of contingency accumulation it keeps
binned streaming on-device: raw float blocks go HBM -> codes -> one-hot
counts without round-tripping int blocks through host memory.

Both operands tile over features on the lane dimension; edge rows are
broadcast across the batch tile.  Padding: batch/feature pads are zeros
(codes for pad lanes are garbage and sliced off), edge pads are +inf so a
padded edge column never increments a real code.  Comparisons are f32 on
both the host (``QuantileBinner.transform``) and device paths, so the two
encodes agree bitwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _kernel(x_ref, e_ref, out_ref, *, num_edges: int):
    x = x_ref[...]            # (TB, TN) f32
    codes = jnp.zeros(x.shape, jnp.int32)
    # E is small and static: unrolled compare-accumulate, one broadcast
    # edge row per step.
    for k in range(num_edges):
        edge_k = e_ref[:, k][None, :]          # (1, TN)
        codes = codes + (x >= edge_k).astype(jnp.int32)
    out_ref[...] = codes


def bin_codes_pallas(
    X: Array,
    edges: Array,
    *,
    tile_b: int = 256,
    tile_n: int = 128,
    interpret: bool = False,
) -> Array:
    """(B, N) floats x (N, E) sorted edges -> (B, N) int32 codes."""
    B, N = X.shape
    Ne, E = edges.shape
    if Ne != N:
        raise ValueError(f"edges rows {Ne} != features {N}")
    tile_b = min(tile_b, B)
    tile_n = min(tile_n, N)
    pad_b = (-B) % tile_b
    pad_n = (-N) % tile_n

    Xf = jnp.pad(X.astype(jnp.float32), ((0, pad_b), (0, pad_n)))
    ef = jnp.pad(
        edges.astype(jnp.float32),
        ((0, pad_n), (0, 0)),
        constant_values=jnp.inf,
    )
    bp, np_ = Xf.shape

    out = pl.pallas_call(
        functools.partial(_kernel, num_edges=E),
        grid=(bp // tile_b, np_ // tile_n),
        in_specs=[
            pl.BlockSpec((tile_b, tile_n), lambda b, n: (b, n)),
            pl.BlockSpec((tile_n, E), lambda b, n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, tile_n), lambda b, n: (b, n)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.int32),
        interpret=interpret,
        name="bin_codes",
    )(Xf, ef)

    return out[:B, :N]
