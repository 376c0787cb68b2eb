"""Pallas TPU kernel: batched contingency tables as one-hot MXU matmuls.

The paper's conventional-encoding hot loop emits one contingency table per
(observation, candidate-feature) pair and sums them (mapper + combiner).  A
GPU port would scatter-add; TPUs have no fast scatter, so we reformulate the
histogram as matmuls over on-the-fly one-hot masks:

    out[c, v, f] = sum_m  [y[m] == c] * [X[m, f] == v]
                 = (B @ A_v)[c, f],
    B = onehot(y_tile)^T, shape (C, TM);  A_v = [X_tile == v], (TM, TF)

so every (TM, TF) input tile becomes V lane-dense (C, TM) x (TM, TF) MXU
contractions, one per category value.  No in-kernel array has V or C as
its minor (lane) dimension: features ride the lanes of the masks and of
the output, and the target arrives as a lane-dense (1, TM) row.  The
output block (C, V·TF) is revisited along the M grid axis
(accumulate-into-output); the masks never leave VMEM.

X is counted as it arrives, in its own integer dtype (the int8 blocks of
a streamed fit, int32 bin codes): each (TM, TF) tile is copied to VMEM as
it lies and widened to int32 there, so no widened or padded copy of X
is made in HBM (where X's device layout is column-major, XLA still
relays it out once, in its own dtype, ahead of the call).  The feature
grid is cdiv(F, TF): the last tile may overhang X, and its out-of-range
columns, whatever they read, land only in output columns >= F, which the
wrapper drops.  Only y is padded, to a whole number of row tiles with an
out-of-range code, so a row tile that overhangs X meets all-zero class
one-hots and counts nothing.

Tiling defaults: TM=1024 rows (rounded to the row packing of X's dtype,
32 for int8, 8 for int32, where M is smaller), TF=256 features (128 when
F <= 128).  VMEM use is the double-buffered X tile (2·TM·TF bytes an
element: 512 KiB in int8, 2 MiB in int32), its int32 widening (1 MiB),
one f32 mask (1 MiB) and the output block (C·V·TF·4, 2 MiB at V=32,
C=64) — inside the v5e scoped-VMEM limit.  ``tests/test_tpu_compile.py``
compiles it for v5e at 65536 int8 rows with V in {2, 3, 16, 32}, C=2, at
width 1000 (a ragged last tile), and as the class-fused conditional
count (C = 2V) at V in {2, 16, 32}; it also checks that the compiled
accumulate hands the kernel the int8 block itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_LANES = 128


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def _kernel(x_ref, y_ref, out_ref, *, num_values: int, num_classes: int):
    """One (TM, TF) tile of X against the matching (1, TM) row of y."""

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.int32)  # (TM, TF), widened in VMEM
    y = y_ref[...]  # (1, TM) int32
    tm, tf = x.shape
    # Transposed class one-hot, classes on sublanes.  Out-of-range
    # (padding) rows one-hot to all-zero columns and count nothing.
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (num_classes, tm), 0)
    b = (y == iota_c).astype(jnp.float32)  # (C, TM)
    for v in range(num_values):
        a = (x == v).astype(jnp.float32)  # (TM, TF)
        out_ref[:, v * tf : (v + 1) * tf] += jnp.dot(
            b, a, preferred_element_type=jnp.float32
        )


def contingency_tables_pallas(
    X: Array,
    y: Array,
    num_values: int,
    num_classes: int,
    *,
    tile_m: int = 1024,
    tile_f: int | None = None,
    interpret: bool = False,
) -> Array:
    """(M, F) int, (M,) int -> (F, V, C) float32 contingency tables.

    X is counted in the integer dtype it arrives in, with no widened or
    padded copy of it.  Padding rows may carry out-of-range values; they
    contribute nothing.  Explicit ``tile_m`` / ``tile_f`` are taken as
    given (interpret-mode tile sweeps); on TPU they must be multiples of
    128.
    """
    M, F = X.shape
    if tile_f is None:
        tile_f = min(2 * _LANES, _round_up(F, _LANES))
    # Rows per sublane tile of X's dtype: 8 at 32 bits, 32 for int8.
    packing = max(8, 32 // X.dtype.itemsize)
    tile_m = min(tile_m, _round_up(max(M, 1), packing))

    # X is not padded: see the module docstring for its overhanging tiles.
    nmb = pl.cdiv(M, tile_m)
    nfb = pl.cdiv(F, tile_f)
    big = jnp.int32(2**31 - 1)  # out of range of any category
    yp = jnp.pad(
        y.astype(jnp.int32), (0, nmb * tile_m - M), constant_values=big
    )[None, :]

    out = pl.pallas_call(
        functools.partial(_kernel, num_values=num_values, num_classes=num_classes),
        grid=(nfb, nmb),
        in_specs=[
            pl.BlockSpec((tile_m, tile_f), lambda f, m: (m, f)),
            pl.BlockSpec((1, tile_m), lambda f, m: (0, m)),
        ],
        out_specs=pl.BlockSpec(
            (num_classes, num_values * tile_f), lambda f, m: (0, f)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (num_classes, nfb * num_values * tile_f), jnp.float32,
            # Varying like its inputs under a type-checked shard_map (the
            # in-memory alternative engine calls this inside one).
            vma=jax.typeof(X).vma | jax.typeof(yp).vma,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="contingency_tables",
    )(X, yp)

    # (C, feature-block, V, TF) -> (F, V, C): a small relayout of the
    # counts, not of the data.
    out = out.reshape(num_classes, nfb, num_values, tile_f)
    return out.transpose(1, 3, 2, 0).reshape(
        nfb * tile_f, num_values, num_classes
    )[:F]


def conditional_tables_pallas(
    X: Array,
    xj: Array,
    y: Array,
    num_values: int,
    num_classes: int,
    *,
    tile_m: int = 1024,
    tile_f: int | None = None,
    interpret: bool = False,
) -> Array:
    """(M, F), (M,), (M,) -> (F, V, V, C) class-conditioned pair tables.

    The class axis is fused into the pair target (``xj * C + y``, guarded
    against out-of-range inputs) so the SAME tiled one-hot-matmul kernel
    above produces the 3-way counts — the target one-hot just widens from
    ``V`` to ``V * C`` sublanes.  ``counts.sum(-1)`` recovers the marginal
    pair table; each ``[..., c]`` slice is the within-class table.
    """
    from repro.core.contingency import fuse_targets  # shared fuse semantics

    fused = fuse_targets(xj, y, num_values, num_classes)
    out = contingency_tables_pallas(
        X, fused, num_values, num_values * num_classes,
        tile_m=tile_m, tile_f=tile_f, interpret=interpret,
    )
    return out.reshape(out.shape[0], num_values, num_values, num_classes)
