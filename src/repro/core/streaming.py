"""Streaming mRMR — the paper's MapReduce fit over out-of-core data.

This is the data regime the paper actually targets: a dataset too large to
hold in device memory, visited as observation-blocks.  Each scoring pass
is one MapReduce job in the paper's conventional encoding — ``map`` =
per-block sufficient statistics (contingency tables for MI, running
moments for Pearson), ``combine`` = the block-level batched einsum,
``reduce`` = the state-carrying sum across blocks (plus the mesh
all-reduce when blocks are sharded).  The greedy loop is host-driven:

    pass 0:        relevance statistics vs the class   -> rel (N,)
    pick l, then:  statistics of ALL features vs the just-selected column
                   (read from the same blocks, no column cache), folded
                   into the criterion's running state

Total I/O is ``L`` passes over the source (1 relevance + L-1 redundancy,
the running-fold formulation — selections identical to the paper's
recompute, as with the in-memory engines) while peak device memory is
``O(block_obs × N)`` for the block plus the statistics state,
independent of ``num_obs``.  The greedy objective is pluggable
(``criterion=`` — ``mid``/``miq``/``maxrel``/``jmi``/``cmim`` or
anything registered via
:func:`repro.core.criteria.register_criterion`); a criterion that
declares ``needs_redundancy = False`` (``maxrel``) collapses the whole
fit to ONE relevance pass of I/O, while one that declares
``needs_conditional_redundancy = True`` (``jmi``/``cmim``) widens each
redundancy pass's target one-hot by the class axis (host-fused codes,
``"feature_cond"`` statistics state) so the SAME sweep yields both
``I(x_k; x_j)`` and ``I(x_k; x_j | y)`` — no extra pass, and zero extra
state bytes for criteria that never ask (asserted via ``io["state_bytes"]``).

At production scale that ``L``-pass tax is the wall-clock story, so the
engine carries three composable knobs that attack pass count and
per-pass cost — selections stay bitwise-identical to the plain engine
under every combination:

* ``batch_candidates=q`` — **batched redundancy.**  When a redundancy
  pass is unavoidable, score the pass's target column *and* the top
  ``q-1`` remaining candidates by the current objective in the same
  sweep (the statistics state grows a ``q``-sized leading axis; targets
  ride as ``(q, B)`` slabs).  The greedy loop then commits picks with
  exact per-pick :class:`~repro.core.criteria.Criterion` folds, drawing
  each needed redundancy vector from the batch when speculation hit and
  paying a fresh pass only on a miss — redundancy vectors are pairwise
  properties of the data, so a speculated vector is never invalidated by
  later picks and stays usable for the rest of the fit.  ``num_select=L``
  drops from ``L-1`` redundancy passes toward ``⌈(L-1)/q⌉``.
* ``spill_dir=`` — **encoded-block spill cache** (:class:`repro.data.
  block_cache.BlockCacheSource`).  Pass 1 writes each block — post CSV
  parse, post quantile-bin encode — to compact ``.npy`` chunks; passes
  2..L replay memmapped chunks, so parse/encode cost is paid once per
  dataset instead of once per pass.  A binned source spills its *int
  codes* (the device-side fused encode is skipped in favour of encoding
  exactly once on the host).
* ``readahead=`` — **cross-pass read-ahead** (:class:`~repro.dist.
  streaming.CrossPassReader`).  Block reads never depend on the
  just-picked column (only the pass-target extraction does, a host
  slice at consume time), so a reader thread streams the head of pass
  ``l+1`` while the device drains the tail of pass ``l``, removing the
  per-pass cold-start bubble.  ``readahead > 0`` supersedes the in-pass
  ``prefetch`` thread: the reader is the producer and staging runs at
  consume time.

Where the device can hold the whole dataset, the ``L``-pass tax falls to
one pass without a knob: a single-host fit of more than one pass keeps
the placed blocks of its relevance pass on the device
(:class:`~repro.dist.streaming.ResidentBlocks`) when they fit
:func:`~repro.dist.streaming.resident_budget`, a share of the free
device memory the backend reports less what running fits have been
promised, and counts every later pass from them with its target cut on
the device — the same accumulate programs on the
same blocks in the same order, so selections stay bitwise.  Where such a
fit is left to size its default MI score and no stats of the source are
memoised, its first pass places the blocks before counting anything, the
category counts reduce from them on the device in place of a stats scan
of the source, and the relevance pass counts from them too: one read of
the source a fit.  The blocks
are freed when the fit returns or raises.  Fits above the budget,
multi-host fits, the fused binned path and backends that report no
memory (the CPU) stream every pass.

Both of the paper's §III regimes stream:

* **tall** — blocks shard over ``obs_axes`` (the paper's conventional
  partitioning); statistics reduce with one ``psum`` per block.
* **wide** — blocks *and the statistics state* shard over ``feat_axes``
  (the alternative/vertical partitioning), so the ``O(N · d_v · d_c)``
  per-pair state that would blow one device spreads across the mesh:
  per-device statistics memory is ``O(N/shards · d_v · d_c)`` (times
  ``q`` under batching).
* **both-large** — a 2-D (obs × feat) grid combines the two.

In every layout the per-block count runs under ``shard_map``
(:func:`_per_shard_step`): each device counts its own slice with the
Pallas kernel on TPU, which XLA could not partition on its own.

``prefetch`` double-buffers placement (:class:`~repro.dist.streaming.
PrefetchPlacer`): the host reads/pads/``device_put``s block ``i+1`` while
the device accumulates block ``i``; ``0`` restores the synchronous path
and ``"auto"`` applies :func:`~repro.dist.streaming.resolve_prefetch`
(off on CPU, where the staging thread measurably loses to async sync
dispatch; on elsewhere).

Every fit reports its I/O on the result: ``MRMRResult.io`` carries
``passes`` / ``blocks_read`` / ``bytes_read`` counters (plus the spill
cache's parse-vs-replay split when ``spill_dir`` is set), so the pass
math above is asserted by tests and benchmarks, not eyeballed.  It also
counts the host round trips: ``host_syncs`` (device-to-host copies, one
per finalize term and one per pick's objective) and ``h2d_bytes`` (host
arrays placed on the device: every block triple, plus the vectors the
greedy loop folds, and the column ids a resident pass cuts its targets
by), ``resident_passes``, the passes counted from device-resident
blocks, and ``resident_stats``, 1 where the default score was sized from
them.  A fit that weighs residency also reports the bytes its placed
blocks would take a device (``resident_need_bytes``) and the budget they
were held to (``resident_budget_bytes``, where the backend reports
memory).  Each layer boundary is a ``mrmr.*`` profiler span
(:mod:`repro.runtime.tracing`).
"""

from __future__ import annotations

import itertools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.criteria import Criterion, resolve_criterion
from repro.core.mrmr import MRMRResult, WarmJitCache, check_conditional_support
from repro.core.scores import MIScore, ScoreFn
from repro.core.selector import (
    check_num_select,
    register_engine,
    score_of_stats,
)
from repro.data.binning import BinnedSource, _as_class_labels
from repro.data.block_cache import BlockCacheSource
from repro.data.sources import (
    DataSource,
    ShardSource,
    SourceStats,
    as_source,
    needs_category_scan,
)
from repro.dist.multihost import HostCollectives, HostShardSpec
from repro.dist.streaming import (
    BlockPlacer,
    CrossPassReader,
    PrefetchPlacer,
    ResidentBlocks,
    resolve_prefetch,
)
from repro.runtime import tracing

_NEG_INF = float("-inf")

# Warm accumulate cache: one jitted accumulate per (score × mesh layout ×
# block shape × candidate-batch width).  A fresh ``jax.jit`` every fit
# would recompile the whole per-block step each time; keeping the wrapper
# keyed by the placed geometry means repeat streamed fits (the selection
# service's steady state) pay zero compile after the first.
_ACC_FN_CACHE = WarmJitCache(capacity=32)


def _cached_acc_fn(
    score: ScoreFn,
    placer: BlockPlacer,
    mesh: Mesh | None,
    num_edges: int | None = None,
    batch: int | None = None,
):
    """The jitted per-block accumulate.

    ``batch=None`` is the classic single-target step.  ``batch=q`` vmaps
    the *same* accumulate over a leading candidate axis — state leaves
    ``(q, N, ...)``, targets ``(q, B)``, the block shared — so each slice
    runs the identical per-target arithmetic as the unbatched step
    (contingency counts are exact integers; selections stay bitwise).
    """
    key = (
        "acc_fn", score, mesh, placer.block_obs, placer.padded_features,
        placer.obs_axes, placer.feat_axes, num_edges, batch,
    )

    def build():
        # Pin the state layout (feature-sharded in the wide regime) through
        # the compiled accumulate, so XLA never gathers the per-pair
        # statistics.
        state0 = score.init_state(
            placer.padded_features, "class" if batch is None else "feature"
        )
        if batch is not None:
            state0 = jax.tree.map(
                lambda leaf: jnp.zeros(
                    (batch,) + jnp.asarray(leaf).shape, jnp.asarray(leaf).dtype
                ),
                state0,
            )
        shardings = placer.state_shardings(state0)
        step = (
            score.accumulate
            if batch is None
            else jax.vmap(score.accumulate, in_axes=(0, None, 0, None))
        )
        if num_edges is not None:
            from repro.kernels import ops  # lazy: avoids core<->kernels cycle

            use_pallas = getattr(score, "use_pallas", "auto")
            count = step

            # Fused binned accumulate: the raw float block encodes to bin
            # codes on device (Pallas/jnp searchsorted) feeding straight
            # into the one-hot contingency sum — no int block round-trips
            # through host memory.  Edges ride as a traced argument, so
            # this compiles once per geometry, not per fitted-edge content.
            def step(state, X_block, target, valid, edges):
                codes = ops.bin_codes(X_block, edges, use_pallas=use_pallas)
                return count(state, codes, target, valid)

        if mesh is not None and score.supports_state_merge:
            step = _per_shard_step(step, placer, shardings, batch, num_edges)
        return jax.jit(step, out_shardings=shardings)

    fn = _ACC_FN_CACHE.get_or_build(key, build)
    return _ACC_LOGS[-1]._wrap(fn) if _ACC_LOGS else fn


# Active ``AccumulateLog``s, innermost last.
_ACC_LOGS: list = []


class LoggedAccumulate(NamedTuple):
    fn: Any  # the jitted accumulate
    args: tuple  # jax.ShapeDtypeStruct pytree of its first call
    output: Any  # the state that call returned


class AccumulateLog:
    """Records the accumulate programs streamed fits run while it is open.

    ``with AccumulateLog() as log: selector.fit(source)`` keeps one entry
    per distinct program and argument layout the fit ran: the jitted
    accumulate, its abstract arguments (shape, dtype, sharding) and the
    first state it returned.  So a check of what ran — ``compiled_texts()``
    for a ``tpu_custom_call``, ``entries[i].output.addressable_shards`` for
    the state layout — sees the engine's own programs, not a rebuild.
    Process-wide while open; costs a few microseconds of host time per
    block, nothing when no log is open.
    """

    def __init__(self):
        self._entries: dict = {}

    @property
    def entries(self) -> list[LoggedAccumulate]:
        """One per distinct program and layout, in the order first run."""
        return list(self._entries.values())

    def compiled_texts(self) -> list[str]:
        """Optimized HLO of every recorded program.  Each is compiled
        again, which the persistent compile cache serves where it is on."""
        return [
            fn.lower(*args).compile().as_text() for fn, args, _ in self.entries
        ]

    def _wrap(self, fn):
        def logged(*args):
            out = fn(*args)
            leaves, tree = jax.tree.flatten(args)
            sig = (id(fn), tree, tuple((a.shape, a.dtype) for a in leaves))
            if sig not in self._entries:
                abstract = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=a.sharding
                    ),
                    args,
                )
                self._entries[sig] = LoggedAccumulate(fn, abstract, out)
            return out

        return logged

    def __enter__(self) -> "AccumulateLog":
        _ACC_LOGS.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _ACC_LOGS.remove(self)


def _per_shard_step(step, placer: BlockPlacer, shardings, batch, num_edges):
    """Run ``step`` on each device's own slice of the block.

    Pallas kernels cannot be partitioned by XLA, so the count runs under
    ``shard_map``: every device counts its row/column slice into a zero
    state, the obs-sharded layouts ``psum`` those counts over the
    observation axes (once per block), and the sum lands on the state in
    its placer layout.  Only scores whose state merges by plain addition
    (``supports_state_merge``) take this path; the rest are pure jnp and
    left to XLA's partitioner."""
    obs, feat = placer.obs_axes or None, placer.feat_axes or None
    in_specs = (
        jax.tree.map(lambda s: s.spec, shardings),
        P(obs, feat),
        P(obs) if batch is None else P(None, obs),
        P(obs),
    ) + (() if num_edges is None else (P(feat, None),))

    def local(state, *block):
        delta = step(jax.tree.map(jnp.zeros_like, state), *block)
        if obs:
            delta = jax.lax.psum(delta, obs)
        return jax.tree.map(jnp.add, state, delta)

    # check_vma=False: interpret-mode Pallas (the CPU test harness) cannot
    # type varying axes; the specs above pin the layout either way.
    return jax.shard_map(
        local, mesh=placer.mesh, in_specs=in_specs, out_specs=in_specs[0],
        check_vma=False,
    )


def _placed_edges(edges: np.ndarray, placer: BlockPlacer):
    """Land fitted bin edges (N, E) padded to the placer's feature extent
    and sharded to match the block columns.  Pad rows are +inf so a padded
    feature's codes stay 0 (its statistics rows are sliced off anyway)."""
    e = np.asarray(edges, np.float32)
    pad = placer.padded_features - e.shape[0]
    if pad:
        e = np.concatenate(
            [e, np.full((pad, e.shape[1]), np.inf, np.float32)]
        )
    if placer.mesh is not None:
        spec = P(placer.feat_axes if placer.feat_axes else None, None)
        return jax.device_put(e, NamedSharding(placer.mesh, spec))
    return jnp.asarray(e)


def acc_fn_cache_stats() -> dict:
    """Hit/miss/eviction counters of the warm accumulate cache."""
    return _ACC_FN_CACHE.stats()


def clear_acc_fn_cache() -> None:
    """Drop every warmed accumulate fn (tests; frees executables)."""
    _ACC_FN_CACHE.clear()


def _extract_target(
    X_blk: np.ndarray,
    y_blk: np.ndarray,
    target_cols,
    binner,
    cond_classes: int | None = None,
):
    """The pass target from one raw host block: the class (``None``), one
    feature column (int -> ``(B,)``) or a batch of candidate columns
    (sequence -> ``(q, B)``).  With a ``binner`` the block is raw float32
    and each target column encodes through the same f32 ``searchsorted``
    the device kernel runs, so host and device codes agree bitwise.

    ``cond_classes`` marks a class-conditioned redundancy pass (JMI/CMIM):
    each extracted column fuses with the class labels into one code
    ``col * cond_classes + label`` — the host-side twin of
    :func:`repro.core.contingency.fuse_targets`, feeding the same
    accumulate with a ``num_values * cond_classes``-wide one-hot."""
    if target_cols is None:
        return _as_class_labels(y_blk) if binner is not None else y_blk
    labels = None
    if cond_classes is not None:
        labels = (
            _as_class_labels(y_blk) if binner is not None else y_blk
        ).astype(np.int64)

    def column(c):
        c = int(c)
        col = (
            binner.encode_column(c, X_blk[:, c])
            if binner is not None
            else X_blk[:, c]
        )
        if labels is None:
            return col
        return (col.astype(np.int64) * cond_classes + labels).astype(np.int32)

    if np.ndim(target_cols) == 0:
        return column(target_cols)
    cols = [column(c) for c in target_cols]
    return np.ascontiguousarray(np.stack(cols))


class _PassIO:
    """Per-fit I/O ledger: every pass/block/byte the engine consumes,
    plus the peak statistics-state footprint (``state_bytes`` — how the
    conditional-criterion memory tax is asserted, not eyeballed), and the
    host round trips: device-to-host copies (``host_syncs``) and bytes of
    host arrays placed on the device (``h2d_bytes``).  ``fit`` is the id
    the fit's spans carry."""

    def __init__(self):
        self.fit = tracing.fit_id()
        self.passes = 0
        self.blocks_read = 0
        self.bytes_read = 0
        self.state_bytes = 0
        self.host_syncs = 0
        self.h2d_bytes = 0
        self.resident_passes = 0
        # 1 where the default score was sized from device-resident blocks
        self.resident_stats = 0
        # Set where the fit weighs keeping its blocks on the device: the
        # bytes they would take a device, and the budget they were held to
        # (None where the backend reports no memory).
        self.resident_need_bytes = None
        self.resident_budget_bytes = None

    def count(self, raw_blocks):
        for X_blk, y_blk in raw_blocks:
            self.blocks_read += 1
            self.bytes_read += X_blk.nbytes + y_blk.nbytes
            yield X_blk, y_blk

    def note_state(self, state):
        size = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
        self.state_bytes = max(self.state_bytes, size)

    def note_placed(self, arrays):
        """Count the bytes of placed ``arrays``; returns them."""
        self.h2d_bytes += sum(a.nbytes for a in arrays)
        return arrays

    def to_device(self, tree):
        """Host arrays -> device arrays, their bytes counted."""
        self.note_placed(jax.tree.leaves(tree))
        return jax.tree.map(jnp.asarray, tree)

    def to_host(self, x) -> np.ndarray:
        """A device array copied to a fresh float32 host array: one sync."""
        self.host_syncs += 1
        return np.array(x, np.float32)

    def as_dict(self) -> dict:
        out = dict(
            passes=self.passes,
            blocks_read=self.blocks_read,
            bytes_read=self.bytes_read,
            state_bytes=self.state_bytes,
            host_syncs=self.host_syncs,
            h2d_bytes=self.h2d_bytes,
            resident_passes=self.resident_passes,
            resident_stats=self.resident_stats,
        )
        for key in ("resident_need_bytes", "resident_budget_bytes"):
            if getattr(self, key) is not None:
                out[key] = getattr(self, key)
        return out


def _score_pass(
    raw_pass,
    source: DataSource,
    score: ScoreFn,
    acc_fn,
    placer: BlockPlacer,
    target_cols,
    prefetch: int,
    io: _PassIO,
    binned: "BinnedSource | None" = None,
    batch: int | None = None,
    conditional: bool = False,
    merge_state=None,
    keep: int | None = None,
    resident: ResidentBlocks | None = None,
):
    """One full map-reduce pass over ``raw_pass`` (an ``(X, y)`` raw host
    block iterator): ``(N,)`` scores of every feature against the class
    (``target_cols=None``) / one column (int), or ``(q, N)`` scores
    against a batch of candidate columns (sequence of length ``q``).

    ``conditional=True`` (JMI/CMIM redundancy passes) fuses the class into
    the target codes and returns ``dict(marginal=..., conditional=...)``
    arrays instead — both terms from the ONE counting sweep.

    ``merge_state`` is the multi-host reduce hook: applied to the fully
    accumulated state *before* finalize (a cross-process psum of exact
    integer counts), so finalisation runs on the merged statistics
    exactly as if one process had counted every block.  ``keep``
    overrides how many leading feature rows survive the padding slice
    (default: the source's full width; a column-sharded host keeps only
    its own columns, dropping appended target columns too).

    ``resident`` keeps the fit's blocks on the device: the first pass
    (the relevance pass) places ``raw_pass`` and keeps every placed block;
    once it has, a pass ignores ``raw_pass`` and counts from the kept
    blocks, its target cut on the device."""
    ids = {"fit": io.fit, "pass": io.passes}
    io.passes += 1
    binner = binned.binner if binned is not None else None
    cond = conditional and target_cols is not None
    kind = (
        "class"
        if target_cols is None
        else ("feature_cond" if cond else "feature")
    )
    from_resident = resident is not None and resident.complete
    with tracing.span(
        tracing.PASS, kind=kind, batch=batch or 1,
        resident=int(from_resident), **ids,
    ):
        if batch is None:
            state = score.init_state(placer.padded_features, kind)
        else:
            state = jax.tree.map(
                lambda leaf: jnp.zeros(
                    (batch,) + jnp.asarray(leaf).shape, jnp.asarray(leaf).dtype
                ),
                score.init_state(placer.padded_features, kind),
            )
        state = placer.place_state(state)
        io.note_state(state)
        cond_classes = score.num_classes if cond else None

        if from_resident:
            io.resident_passes += 1
            cols = None
            if target_cols is not None:
                (cols,) = io.note_placed([placer.place_ids(target_cols)])
            placed = resident.triples(cols, cond_classes, **ids)
        else:
            placed = _placed_blocks(
                raw_pass, placer, target_cols, prefetch, io, ids, binner,
                cond_classes,
            )
            if resident is not None and target_cols is None:
                placed = resident.keep(placed)
        for block, triple in enumerate(placed):
            with tracing.span(tracing.ACCUMULATE, block=block, **ids):
                state = acc_fn(state, *triple)
        if merge_state is not None:
            state = merge_state(state)
        # Drop feature-padding columns on every read.
        n = source.num_features if keep is None else int(keep)
        with tracing.span(tracing.FINALIZE, **ids):
            if cond:
                fin = (
                    score.finalize_conditional
                    if batch is None
                    else jax.vmap(score.finalize_conditional)
                )
                terms = {k: io.to_host(v) for k, v in fin(state).items()}
                if batch is None:
                    return {k: v[:n] for k, v in terms.items()}
                return {k: v[:, :n] for k, v in terms.items()}
            if batch is None:
                return io.to_host(score.finalize(state))[:n]
            return io.to_host(jax.vmap(score.finalize)(state))[:, :n]


def _placed_blocks(
    raw_pass, placer: BlockPlacer, target_cols, prefetch: int, io: _PassIO,
    ids: dict, binner=None, cond_classes: int | None = None,
):
    """The placed ``(X, target, valid)`` triple of each raw host block of
    ``raw_pass``: read, target extracted, staged and placed, each block's
    bytes counted on ``io``; with ``prefetch`` the reads and staging run on
    a :class:`~repro.dist.streaming.PrefetchPlacer` thread."""

    def staged_blocks():
        for block, (X_blk, y_blk) in enumerate(io.count(raw_pass)):
            with tracing.span(tracing.STAGE, block=block, **ids):
                if binner is not None:
                    X_blk = np.asarray(X_blk, np.float32)
                target = _extract_target(
                    X_blk, y_blk, target_cols, binner, cond_classes
                )
                staged = placer.stage(X_blk, target)
            yield staged

    if prefetch > 0:
        placed = PrefetchPlacer(placer, depth=prefetch).stream(
            staged_blocks(), **ids
        )
    else:
        placed = (
            placer.place(staged, block=block, **ids)
            for block, staged in enumerate(staged_blocks())
        )
    return map(io.note_placed, placed)


@jax.jit
def _widen_extrema(extrema, X, y):
    """``(x_max, x_min, y_max, y_min)`` widened by one placed block.  Pad
    rows and columns hold zeros, which the ranges take in anyway."""
    x_max, x_min, y_max, y_min = extrema
    return (
        jnp.maximum(x_max, X.max()), jnp.minimum(x_min, X.min()),
        jnp.maximum(y_max, y.max()), jnp.minimum(y_min, y.min()),
    )


def _scanned_score(source: DataSource, block_obs: int, fit: int) -> ScoreFn:
    """The default score from the source's stats scan, which runs in a
    ``mrmr.plan`` span."""
    with tracing.span(tracing.PLAN, fit=fit):
        return score_of_stats(source.stats(block_obs))


def _score_of_kept_blocks(
    raw_pass, source: DataSource, placer: BlockPlacer, prefetch: int,
    io: _PassIO, resident: ResidentBlocks,
) -> ScoreFn:
    """The default score of a fit that keeps its blocks on the device,
    sized from them in place of a stats scan of the source.

    The first pass reads, stages and places ``raw_pass`` into
    ``resident``, counting nothing.  Then, in a ``mrmr.plan`` span, the
    extremes of the kept features and classes reduce on the device (across
    every chip the blocks are sharded over) and come to the host in one
    sync.  The stats they give are memoised on ``source`` as its own scan
    would leave them, so a later fit of the same data reads none."""
    ids = {"fit": io.fit, "pass": io.passes}
    for _ in resident.keep(
        _placed_blocks(raw_pass, placer, None, prefetch, io, ids)
    ):
        pass
    with tracing.span(tracing.PLAN, fit=io.fit):
        X, y, _ = resident.blocks[0]
        extrema = (
            jnp.zeros((), X.dtype), jnp.zeros((), X.dtype),
            jnp.zeros((), y.dtype), jnp.zeros((), y.dtype),
        )
        for X, y, _ in resident.blocks:
            extrema = _widen_extrema(extrema, X, y)
        io.host_syncs += 1
        x_max, x_min, y_max, y_min = map(int, jax.device_get(extrema))
        st = SourceStats.from_extrema(True, x_max, x_min, y_max, y_min)
    source.remember_stats(st)
    io.resident_stats = 1
    return score_of_stats(st)


def _pass_reader(
    block_src: DataSource,
    block_obs: int,
    io: _PassIO,
    readahead: int,
    max_passes: int,
):
    """-> ``(next_raw, reader)``: ``next_raw()`` gives the next pass's raw
    block iterator, each read in a ``mrmr.read`` span.  With ``readahead``
    the reads run on a :class:`~repro.dist.streaming.CrossPassReader`
    thread (returned, for the caller to close) that reads at most
    ``max_passes`` passes, else where the pass iterates."""
    pass_ids = itertools.count()

    def read_pass():
        return tracing.traced_reads(
            block_src.iter_blocks(block_obs), block_src.num_obs,
            fit=io.fit, **{"pass": next(pass_ids)},
        )

    if readahead <= 0:
        return read_pass, None
    reader = CrossPassReader(read_pass, depth=readahead, max_passes=max_passes)
    return (
        lambda: reader.next_pass(fit=io.fit, **{"pass": io.passes})
    ), reader


def _max_passes(
    crit: Criterion, num_select: int, resident: ResidentBlocks | None = None
) -> int:
    """Upper bound on the passes a fit reads from its source; batching and
    speculation only lower it, and the cross-pass reader stops wherever the
    fit actually ends.  A resident fit reads its first pass alone."""
    if resident is not None or not crit.needs_redundancy:
        return 1
    return num_select


def _resident_blocks(
    placer: BlockPlacer,
    source: DataSource,
    crit: Criterion,
    num_select: int,
    io: _PassIO,
) -> ResidentBlocks | None:
    """Keep this fit's blocks on the device when it makes more than one
    pass and the whole placed dataset fits :func:`~repro.dist.streaming.
    resident_budget` of every device it lands on (its bytes then stay
    promised until the fit ends); else None, and every pass streams.  A
    source whose feature dtype is unknown before a read is counted at 8
    bytes a value.  A fit of more than one pass records on ``io`` the
    bytes a device would hold and, where the backend reports memory, the
    budget they were held to."""
    if num_select < 2 or not crit.needs_redundancy:
        return None
    dtype = source.feature_dtype
    itemsize = 8 if dtype is None else np.dtype(dtype).itemsize
    io.resident_need_bytes = placer.resident_bytes(source.num_obs, itemsize)
    resident, io.resident_budget_bytes = ResidentBlocks.reserve(
        placer, io.resident_need_bytes
    )
    return resident


def _greedy_select(
    run_pass, crit: Criterion, n: int, num_select: int, q: int, io: _PassIO
):
    """The host-driven greedy loop shared by the single- and multi-host
    fits: one relevance pass, then exact per-pick criterion folds with
    ``q``-wide redundancy speculation.  ``run_pass(target_cols, batch=)``
    hides where blocks come from and how per-host statistics merge — by
    the time a vector reaches this loop every participating host holds
    the identical full-width copy, so every host commits the identical
    pick with no designated master.  Each pick is one ``mrmr.pick`` span
    that leaves out the pass it calls: a pick's redundancy is folded at
    the start of the next pick."""
    rel = run_pass(None)
    rel_j = io.to_device(rel)
    cstate = crit.init_state(n)
    mask = np.zeros((n,), bool)
    selected = np.full((num_select,), -1, np.int32)
    gains = np.zeros((num_select,), np.float32)
    # Speculated redundancy vectors by feature id: a vector is a pure
    # pairwise property of the data, so once computed it stays valid
    # for the whole fit (an in-batch pick never invalidates it).
    pending: dict = {}
    red = None  # the last pick's redundancy terms, not yet folded
    for l in range(num_select):
        with tracing.span(tracing.PICK, fit=io.fit, pick=l):
            if red is not None:
                cstate = crit.update(cstate, io.to_device(red), l - 1)
                red = None
            # The criterion fold is the same pure-f32 jnp math the
            # in-memory engines trace on the device, so argmax ties
            # resolve identically to theirs (toward the lowest id).
            g = io.to_host(crit.objective(rel_j, cstate, l))
            g[mask] = _NEG_INF
            k = int(np.argmax(g))
            selected[l], gains[l] = k, g[k]
            mask[k] = True
            if l + 1 >= num_select or not crit.needs_redundancy:
                continue
            if k in pending:
                red = pending.pop(k)  # speculation hit: zero I/O
            elif q > 1:
                # One sweep scores the needed column plus the top
                # q-1 remaining candidates by the CURRENT objective —
                # the same lazy-greedy bet that objectives shift
                # slowly between folds.
                cols = [k]
                for j in np.argsort(-g, kind="stable"):
                    if len(cols) == q:
                        break
                    j = int(j)
                    if mask[j] or j in pending or g[j] == _NEG_INF:
                        continue
                    cols.append(j)
        if red is not None:
            continue
        if q == 1:
            red = run_pass(k)
            continue
        # Short batches pad by repeating the last column so the
        # accumulate keeps one compiled shape per q.
        padded = cols + [cols[-1]] * (q - len(cols))
        reds = run_pass(padded, batch=q)
        for i, c in enumerate(cols):
            pending[c] = (
                {k2: v[i] for k2, v in reds.items()}
                if isinstance(reds, dict)
                else reds[i]
            )
        red = pending.pop(k)
    return rel, selected, gains


def mrmr_streaming(
    source,
    num_select: int,
    score: ScoreFn,
    *,
    block_obs: int = 65536,
    mesh: Mesh | None = None,
    obs_axes=("data",),
    feat_axes=(),
    prefetch="auto",
    criterion: Criterion | str = "mid",
    batch_candidates: int = 1,
    spill_dir: str | None = None,
    spill_budget_bytes: int | None = None,
    readahead: int = 0,
    shards: "HostShardSpec | None" = None,
    collectives: "HostCollectives | None" = None,
) -> MRMRResult:
    """Greedy mRMR over a :class:`~repro.data.sources.DataSource`.

    Args:
      source: a ``DataSource`` (or an ``(X, y)`` pair to wrap).
      num_select: L, number of features to pick.
      score: a streaming-capable ``ScoreFn`` (``supports_streaming``), or
        None for the default: exact MI sized by the data's category counts
        where its dtypes are integral, else Pearson-MI.  Where those counts
        are not memoised and the fit keeps its blocks on the device, they
        come from the placed blocks (:func:`_score_of_kept_blocks`), so the
        source is read once; otherwise from ``source.stats()``.
      block_obs: observations per device block — the peak-memory knob
        (rounded up to the mesh's observation extent).
      mesh / obs_axes / feat_axes: shard each block over the observation
        axes, the feature axes, or both (the 2-D grid).  Feature sharding
        also shards the statistics state, the wide-regime memory wall;
        observation sharding reduces statistics with one all-reduce per
        block, the paper's reducer on the ICI ring.
      prefetch: host blocks to read/pad/place ahead of device
        accumulation (0 = synchronous placement; ``"auto"`` resolves per
        backend, see :func:`~repro.dist.streaming.resolve_prefetch`).
      criterion: greedy objective — a name (``"mid"``/``"miq"``/
        ``"maxrel"``/``"jmi"``/``"cmim"``) or
        :class:`~repro.core.criteria.Criterion`.  The fold runs on the
        same (N,)-sized vectors the in-memory engines fold, so
        selections agree engine-for-engine per criterion.  Conditional
        criteria (``jmi``/``cmim``) require an :class:`~repro.core.
        scores.MIScore` (or any score with a conditional decomposition).
      batch_candidates: redundancy vectors speculated per pass (``q``).
        1 reproduces the classic one-pass-per-pick loop; ``q > 1`` cuts
        redundancy passes toward ``⌈(L-1)/q⌉`` at ``q×`` the statistics
        memory and identical selections.
      spill_dir: directory for the encoded-block spill cache — pass 1
        writes parsed/encoded blocks, passes 2..L replay them memmapped
        (zero parse, zero re-encode); a resident fit writes them and
        replays nothing.  ``spill_budget_bytes`` bounds the directory
        LRU-wise.
      readahead: raw blocks the cross-pass reader streams ahead of the
        consumer, across pass boundaries (0 = off); a resident fit reads
        its first pass alone.  Supersedes ``prefetch`` when positive.
      shards: a :class:`~repro.dist.multihost.HostShardSpec` placing this
        process on the cross-host grid — the fit then reads ONLY this
        host's block/column ranges and merges per-pass statistics with
        explicit collectives (see :func:`_mrmr_streaming_multihost`).
        ``None`` or a single-host spec runs today's one-process path.
      collectives: a pre-built :class:`~repro.dist.multihost.
        HostCollectives` for ``shards`` (built on demand when omitted).
    """
    crit = resolve_criterion(criterion)
    source = as_source(*source) if isinstance(source, tuple) else as_source(source)
    n = source.num_features
    check_num_select(num_select, n)
    prefetch = resolve_prefetch(prefetch)
    q = int(batch_candidates)
    if q < 1:
        raise ValueError(f"batch_candidates must be >= 1, got {q}")
    if readahead < 0:
        raise ValueError(f"readahead must be >= 0, got {readahead}")
    multihost = shards is not None and not shards.is_single_host
    if score is None and (multihost or not needs_category_scan(source)):
        score = _scanned_score(source, block_obs, tracing.fit_id())
    # A score still None is an MIScore to be sized, which streams and
    # supports every criterion.
    if score is not None and not score.supports_streaming:
        raise ValueError(
            f"{type(score).__name__} cannot stream: it has no "
            "sufficient-statistics decomposition (init_state/accumulate/"
            "finalize). Materialise the data and use an in-memory engine."
        )
    # JMI/CMIM need class-conditioned pair statistics; fail before any
    # I/O if the score can't produce them.  Non-conditional criteria keep
    # the exact pre-refactor pass shapes and state bytes.
    if score is not None:
        check_conditional_support(score, crit)
    needs_cond = crit.needs_redundancy and crit.needs_conditional_redundancy

    if multihost:
        return _mrmr_streaming_multihost(
            source,
            num_select,
            score,
            spec=shards,
            coll=collectives,
            block_obs=block_obs,
            mesh=mesh,
            obs_axes=obs_axes,
            feat_axes=feat_axes,
            prefetch=prefetch,
            crit=crit,
            q=q,
            spill_dir=spill_dir,
            spill_budget_bytes=spill_budget_bytes,
            readahead=readahead,
        )

    # A caller-wrapped BlockCacheSource reports its counters on the result
    # the same as an engine-built one.
    given = source
    spill: BlockCacheSource | None = (
        source if isinstance(source, BlockCacheSource) else None
    )
    if spill_dir is not None:
        # The cache sits post parse/encode: wrapping a BinnedSource spills
        # its int codes, so replay passes skip the bin encode too (the
        # device-side fused encode is deliberately bypassed — encoding
        # happens exactly once, on the staging pass).
        spill = BlockCacheSource(
            source, spill_dir, budget_bytes=spill_budget_bytes
        )
        source = spill

    placer = BlockPlacer(block_obs, mesh, obs_axes, feat_axes, num_features=n)

    # A BinnedSource scoring discrete MI streams FUSED: raw float blocks
    # go to the device and are encoded there (Pallas searchsorted on TPU,
    # jnp elsewhere) directly ahead of the contingency sum.  The sketch
    # pass (memoised by fingerprint) happens here, before the first
    # scoring pass.  Any other score falls back to host-side encoding
    # through the wrapper's normal iter_blocks.  (A score left to size is
    # never a binned source's: its dtypes are integral.)
    binned = (
        source
        if isinstance(source, BinnedSource) and isinstance(score, MIScore)
        else None
    )

    # Raw block production: the fused binned path streams the *base*
    # source's float blocks (the device encodes them); everything else —
    # including a spill-cached binned source, whose cache already holds
    # the codes — streams the source itself.
    block_src = binned.base if binned is not None else source
    io = _PassIO()
    resident = (
        None if binned is not None
        else _resident_blocks(placer, source, crit, num_select, io)
    )
    if score is None and resident is None:
        score = _scanned_score(given, block_obs, io.fit)
    next_raw, reader = _pass_reader(
        block_src, placer.block_obs, io, readahead,
        _max_passes(crit, num_select, resident),
    )
    if reader is not None:
        prefetch = 0  # the reader thread is the producer; stage at consume

    try:
        if score is None:
            # The relevance pass places its blocks before it counts, so
            # the score's category counts come from them, not a scan.
            score = _score_of_kept_blocks(
                next_raw(), given, placer, prefetch, io, resident
            )
        acc_fn, acc_fn_q = _acc_fns(score, placer, mesh, q, binned)

        def run_pass(target_cols, batch=None):
            raw = (
                None if resident is not None and resident.complete
                else next_raw()
            )
            return _score_pass(
                raw, source, score, acc_fn if batch is None else acc_fn_q,
                placer, target_cols, prefetch, io, binned, batch,
                conditional=needs_cond and target_cols is not None,
                resident=resident,
            )

        rel, selected, gains = _greedy_select(
            run_pass, crit, n, num_select, q, io
        )
    finally:
        if reader is not None:
            reader.close()
        if resident is not None:
            resident.delete()
    io_report = io.as_dict()
    if spill is not None:
        io_report["cache"] = dict(spill.counters)
    return MRMRResult(
        selected=jnp.asarray(selected),
        gains=jnp.asarray(gains),
        relevance=jnp.asarray(rel),
        criterion=crit.name,
        engine="streaming",
        io=io_report,
    )


def _acc_fns(
    score: ScoreFn, placer: BlockPlacer, mesh: Mesh | None, q: int,
    binned: BinnedSource | None,
):
    """-> ``(acc_fn, acc_fn_q)``: the accumulate of a single-target pass
    and, where ``q > 1``, of a ``q``-wide batched one (else None).  A
    ``binned`` source's accumulates take its fitted edges, placed once, and
    encode the raw float block on the device."""
    if binned is None:
        return (
            _cached_acc_fn(score, placer, mesh),
            _cached_acc_fn(score, placer, mesh, batch=q) if q > 1 else None,
        )
    edges = binned.binner.edges_
    num_edges = edges.shape[1]
    edges_dev = _placed_edges(edges, placer)

    def with_edges(base_fn):
        return lambda state, X_block, target, valid: base_fn(
            state, X_block, target, valid, edges_dev
        )

    return (
        with_edges(_cached_acc_fn(score, placer, mesh, num_edges=num_edges)),
        with_edges(
            _cached_acc_fn(score, placer, mesh, num_edges=num_edges, batch=q)
        ) if q > 1 else None,
    )


def _mrmr_streaming_multihost(
    source,
    num_select: int,
    score: ScoreFn,
    *,
    spec: HostShardSpec,
    coll: "HostCollectives | None",
    block_obs: int,
    mesh: Mesh | None,
    obs_axes,
    feat_axes,
    prefetch: int,
    crit: Criterion,
    q: int,
    spill_dir: str | None,
    spill_budget_bytes: int | None,
    readahead: int,
) -> MRMRResult:
    """The cross-host fit: this process reads ONLY its shard, the per-pass
    reduce is an explicit collective, and every host runs the identical
    greedy loop on identical merged vectors.

    The paper's two partitionings map onto the host grid exactly as they
    map onto the device mesh:

    * **tall** (``grid=(H, 1)``): each host streams its row window at
      full width and accumulates a full-width statistics state; one
      ``psum`` of the exact integer counts reconstructs the global state
      bitwise on every host before finalize — scores (hence picks) are
      identical to one process having read everything.
    * **wide** (``grid=(1, H)``): each host streams every row of its own
      column group; states never merge (each host already saw all rows).
      Finalised per-column scores scatter-``assemble`` into the full
      ``(N,)`` vector (one non-zero addend per column — float adds
      against zeros, exact).  Redundancy targets a host doesn't own ride
      as *appended columns*: a synchronous single-column shard stream
      aligned block-for-block with the main stream, so the augmented
      state is ``local_cols + t`` wide and targets always live at local
      indices ``local_cols..local_cols+t-1``.
    * **2-D grid**: both — ``psum_obs`` collapses the row partitions
      (column groups padded to the widest, zeros are the additive
      identity), then the ``obs_coord == 0`` row of hosts assembles.

    Per-host device placement still applies *within* each process
    (``mesh``/``obs_axes`` shard the local block over local devices), but
    column-partitioned regimes force ``feat_axes=()`` per host: with no
    device feature-sharding the placer's padded width equals the exact
    shard width, which is what makes cross-host state shapes align
    deterministically regardless of local device count.
    """
    n = source.num_features
    if (spec.num_obs, spec.num_features) != (source.num_obs, n):
        raise ValueError(
            f"HostShardSpec geometry {(spec.num_obs, spec.num_features)} "
            f"does not match the source {(source.num_obs, n)}"
        )
    if spec.partitions_obs and not score.supports_state_merge:
        raise ValueError(
            f"{type(score).__name__} statistics cannot merge across row "
            "partitions (supports_state_merge=False): its state is not a "
            "plain sum over blocks.  Use an MI score, or a column-only "
            "host grid (grid=(1, H)) where no state merge is needed."
        )
    if spec.partitions_cols and feat_axes:
        raise ValueError(
            "column-partitioned multi-host fits require feat_axes=() per "
            "host: device feature-sharding would pad the statistics width "
            "past the exact shard width and break cross-host alignment"
        )
    if isinstance(source, BlockCacheSource):
        raise ValueError(
            "pass spill_dir= instead of a pre-wrapped BlockCacheSource: "
            "multi-host fits spill per-host shard streams under a "
            "process-namespaced entry"
        )
    if coll is None:
        coll = HostCollectives(spec)
    needs_cond = crit.needs_redundancy and crit.needs_conditional_redundancy
    (clo, _chi) = spec.col_range
    n_local = spec.local_cols

    # Each host's block stream: ONLY its row/column windows.  Spill (when
    # asked) caches the shard stream under a per-process namespace, so
    # hosts sharing one filesystem can never race each other's chunks.
    shard_src = ShardSource(source, spec.obs_range, spec.col_range)
    stream_src: DataSource = shard_src
    spill: BlockCacheSource | None = None
    if spill_dir is not None:
        spill = BlockCacheSource(
            shard_src,
            spill_dir,
            budget_bytes=spill_budget_bytes,
            namespace=f"h{spec.host_id}",
        )
        stream_src = spill

    # Tall hosts hold every column; column-partitioned hosts size their
    # placer (and state) to the exact shard width (feat_axes=() makes
    # padded_features == num_features, asserted by the placer contract).
    width_rel = n_local if spec.partitions_cols else n
    placer_rel = BlockPlacer(
        block_obs, mesh, obs_axes, feat_axes, num_features=width_rel
    )
    eff_bo = placer_rel.block_obs
    _red_placers: dict = {}

    def red_placer(aug: int) -> BlockPlacer:
        p = _red_placers.get(aug)
        if p is None:
            p = BlockPlacer(
                block_obs, mesh, obs_axes, (), num_features=n_local + aug
            )
            _red_placers[aug] = p
        return p

    def aug_blocks(raw, cols):
        """Append each target column's codes for this host's row window
        to every raw block: owned columns slice out of the block itself,
        non-owned ones ride a synchronous single-column shard stream off
        the base source (same ``eff_bo``, same row window — aligned
        block-for-block by construction, and checked)."""
        plans, streams = [], []
        try:
            for c in cols:
                c = int(c)
                if spec.owns_col(c):
                    plans.append(("own", c - clo))
                else:
                    it = source.iter_shard_blocks(
                        eff_bo, spec.obs_range, (c, c + 1)
                    )
                    plans.append(("stream", it))
                    streams.append(it)
            for X_blk, y_blk in raw:
                X_blk = np.asarray(X_blk)
                extra = []
                for kind, v in plans:
                    if kind == "own":
                        extra.append(X_blk[:, v : v + 1])
                    else:
                        Xc, _ = next(v)
                        if Xc.shape[0] != X_blk.shape[0]:
                            raise RuntimeError(
                                "target-column stream misaligned with the "
                                f"shard stream ({Xc.shape[0]} vs "
                                f"{X_blk.shape[0]} rows)"
                            )
                        extra.append(np.asarray(Xc))
                yield np.concatenate([X_blk] + extra, axis=1), y_blk
        finally:
            for it in streams:
                close = getattr(it, "close", None)
                if close is not None:
                    close()

    io = _PassIO()
    next_raw, reader = _pass_reader(
        stream_src, eff_bo, io, readahead, _max_passes(crit, num_select)
    )
    if reader is not None:
        prefetch = 0

    def run_pass(target_cols, batch=None):
        cond = needs_cond and target_cols is not None
        if target_cols is None or not spec.partitions_cols:
            # Relevance everywhere, and tall-regime redundancy: every
            # column is local, so global target ids index the block.
            placer, raw, local_targets, aug = (
                placer_rel, next_raw(), target_cols, 0
            )
        else:
            cols = (
                [int(target_cols)]
                if batch is None
                else [int(c) for c in target_cols]
            )
            aug = len(cols)
            placer = red_placer(aug)
            local_targets = (
                n_local
                if batch is None
                else list(range(n_local, n_local + aug))
            )
            raw = aug_blocks(next_raw(), cols)
        merge = None
        if spec.partitions_obs:
            if not spec.partitions_cols:
                merge = coll.psum
            else:
                fa = 0 if batch is None else 1
                lw, pt = n_local + aug, spec.max_col_width + aug
                merge = lambda st: coll.psum_obs(
                    st, feat_axis=fa, local_width=lw, pad_to=pt
                )
        acc = _cached_acc_fn(score, placer, mesh, batch=batch)
        res = _score_pass(
            raw, stream_src, score, acc, placer, local_targets, prefetch,
            io, None, batch, conditional=cond, merge_state=merge,
            keep=n_local if spec.partitions_cols else n,
        )
        return coll.assemble(res) if spec.partitions_cols else res

    try:
        rel, selected, gains = _greedy_select(
            run_pass, crit, n, num_select, q, io
        )
    finally:
        if reader is not None:
            reader.close()
    io_report = io.as_dict()
    if spill is not None:
        io_report["cache"] = dict(spill.counters)
    io_report["host"] = dict(
        id=spec.host_id,
        grid=list(spec.grid),
        obs_range=list(spec.obs_range),
        col_range=list(spec.col_range),
    )
    # Exact cross-host ledger exchange (int64 as two int32 halves — byte
    # counts must not round): per-host rows plus the cluster aggregate.
    per = coll.allgather_counts(
        [io.passes, io.blocks_read, io.bytes_read, io.state_bytes]
    )
    names = ("passes", "blocks_read", "bytes_read", "state_bytes")
    io_report["hosts"] = dict(
        grid=list(spec.grid),
        per_host=[
            {k: int(v) for k, v in zip(names, row)} for row in per
        ],
        aggregate=dict(
            # Passes run in lockstep (max == every host); the rest sum.
            passes=int(per[:, 0].max()),
            blocks_read=int(per[:, 1].sum()),
            bytes_read=int(per[:, 2].sum()),
            state_bytes=int(per[:, 3].sum()),
        ),
    )
    return MRMRResult(
        selected=jnp.asarray(selected),
        gains=jnp.asarray(gains),
        relevance=jnp.asarray(rel),
        criterion=crit.name,
        engine="streaming",
        io=io_report,
    )


@register_engine("streaming")
def _fit_streaming(source, y, *, num_select, plan, mesh) -> MRMRResult:
    del y  # targets come from the source's blocks
    shards = None
    if getattr(plan, "hosts", 1) > 1:
        from repro.dist.multihost import resolve_host_shards

        shards = resolve_host_shards(
            source.num_obs, source.num_features, plan.hosts,
            jax.process_index(),
        )
    return mrmr_streaming(
        source,
        num_select,
        plan.score,
        block_obs=plan.block_obs,
        mesh=mesh,
        obs_axes=plan.obs_axes,
        feat_axes=plan.feat_axes,
        prefetch=plan.prefetch,
        criterion=plan.criterion,
        batch_candidates=plan.batch_candidates,
        spill_dir=plan.spill_dir,
        spill_budget_bytes=plan.spill_budget_bytes,
        readahead=plan.readahead,
        shards=shards,
    )
