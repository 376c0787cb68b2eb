"""mRMR greedy drivers — single-device reference + three sharded layouts.

The paper distributes mRMR two ways, keyed by data layout (Section III/IV):

* **conventional** — rows are observations; the dataset is sharded over the
  observation axis.  Scoring = per-shard contingency tables, element-wise
  summed across the cluster (mapper+combiner+reducer -> one ``psum``).
  Discrete data only, MI score only (as in the paper).
* **alternative** — rows are features; the dataset is sharded over the
  feature axis.  The class vector and selected features are broadcast
  (replicated); scoring is entirely local (map-only job), any score fn.
* **grid** (beyond paper) — shard observations *and* features on a 2-D mesh;
  contingency tables psum over the observation axes, argmax over the
  feature axes.  Generalises both encodings and removes the paper's
  single-axis memory walls.

All drivers run the greedy loop as ONE compiled ``lax.fori_loop`` over
static shapes (selected sets become masks), instead of one Spark job per
iteration.  ``incremental=True`` carries the criterion's running fold
state (each iteration scores candidates against only the newly selected
feature — O(N·L) total pair scores); ``incremental=False`` is the
paper-faithful recomputation (O(N·L²)) kept as the reproduction baseline.

The greedy *objective* is pluggable (``criterion=``): every driver folds
per-candidate redundancy terms through a :class:`repro.core.criteria.
Criterion` (``init_state`` / ``update`` / ``objective``) instead of
hard-coding the paper's difference form — ``mid`` (the default, Eq. 1),
``miq`` (quotient) and ``maxrel`` (relevance only, skips pair scoring)
ship built-in; the distributed argmax/psum structure is criterion-
independent.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import threading
from collections import OrderedDict
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import contingency
from repro.core.criteria import Criterion, resolve_criterion
from repro.core.scores import (
    CustomScore,
    MIScore,
    ScoreFn,
    cmi_from_counts,
    mi_from_counts,
)
from repro.dist.sharding import axes_tuple as _axes_tuple
from repro.dist.sharding import pvary

Array = jax.Array

# Plain Python scalars, NOT jnp values: materialising a jnp constant at
# import time would initialise the XLA backend and lock the device count
# before launchers can set --xla_force_host_platform_device_count.
_NEG_INF = float("-inf")
_BIG_ID = 2**31 - 1


@dataclasses.dataclass
class MRMRResult:
    """Selection report: order, objective trajectory, relevance, provenance.

    ``selected[l]`` is the feature picked at iteration ``l`` and
    ``gains[l]`` the value of the criterion objective it was picked at —
    the per-iteration objective trajectory.  ``relevance`` is the full
    per-feature relevance vector from the fit's first scoring pass
    (NaN-filled for :class:`~repro.core.scores.CustomScore` fits, which
    have no relevance/redundancy decomposition; ``None`` from engines
    predating the richer report).  ``criterion`` and ``engine`` name what
    produced the result (empty when the producer did not say — the
    selector backfills both from the plan).

    ``io`` is the fit's I/O ledger — engines that stream a source report
    ``passes`` / ``blocks_read`` / ``bytes_read`` (plus a ``cache``
    sub-dict splitting parse-vs-replay traffic when a spill cache was
    on); in-memory engines leave it ``None``.
    """

    selected: Array
    gains: Array
    relevance: Array | None = None
    criterion: str = ""
    engine: str = ""
    io: dict | None = None

    @property
    def objective_trajectory(self) -> Array:
        """Alias of ``gains`` — the objective value of each pick."""
        return self.gains

    # -- serialization ---------------------------------------------------
    # The result cache persists entries as JSON and launch/select.py can
    # write one to --output; non-finite floats (CustomScore relevance is
    # NaN-filled) are encoded as the strings "nan"/"inf"/"-inf" so the
    # payload stays strict JSON.

    def to_json(self) -> str:
        """Serialise to a strict-JSON string (``from_json`` round-trips)."""

        def enc(a):
            if a is None:
                return None
            x = np.asarray(a)
            if np.issubdtype(x.dtype, np.floating):
                return [
                    float(v) if math.isfinite(v) else repr(float(v))
                    for v in x.tolist()
                ]
            return x.tolist()

        return json.dumps(
            dict(
                version=1,
                selected=enc(self.selected),
                gains=enc(self.gains),
                relevance=enc(self.relevance),
                criterion=self.criterion,
                engine=self.engine,
                io=self.io,
            )
        )

    @classmethod
    def from_json(cls, payload: str) -> "MRMRResult":
        """Rebuild a result serialised by :meth:`to_json`."""
        d = json.loads(payload)

        def dec(vals, dtype):
            if vals is None:
                return None
            return jnp.asarray(
                [float(v) if isinstance(v, str) else v for v in vals], dtype
            )

        return cls(
            selected=dec(d["selected"], jnp.int32),
            gains=dec(d["gains"], jnp.float32),
            relevance=dec(d.get("relevance"), jnp.float32),
            criterion=d.get("criterion", ""),
            engine=d.get("engine", ""),
            io=d.get("io"),
        )


# ---------------------------------------------------------------------------
# warm jit cache
# ---------------------------------------------------------------------------

class WarmJitCache:
    """Bounded LRU of built (jit-wrapped) callables, keyed by hashables.

    ``jax.jit`` memoises traces/executables *per wrapper object*: a fresh
    ``jax.jit(fn)`` on every fit recompiles even when the job is identical.
    Keeping the wrapper alive across fits keyed by what actually shapes the
    computation (engine × criterion × score × block shape × mesh) means
    repeat traffic — the selection service's whole diet — never pays
    trace or compile again.  Unhashable keys (e.g. a custom criterion
    holding a list) bypass the cache rather than erroring.
    """

    def __init__(self, capacity: int = 32):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._entries: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.uncacheable = 0

    def get_or_build(self, key, build):
        try:
            hash(key)
        except TypeError:
            with self._lock:
                self.uncacheable += 1
            return build()
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                self.hits += 1
                return self._entries[key]
        fn = build()
        with self._lock:
            self.misses += 1
            self._entries[key] = fn
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1
        return fn

    def stats(self) -> dict:
        with self._lock:
            return dict(
                size=len(self._entries), capacity=self.capacity,
                hits=self.hits, misses=self.misses,
                evictions=self.evictions, uncacheable=self.uncacheable,
            )

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = self.misses = self.evictions = self.uncacheable = 0


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _flat_axis_index(axes: Sequence[str], mesh_axis_sizes: dict) -> Array:
    """Row-major flattened index of this shard along ``axes``."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh_axis_sizes[a] + lax.axis_index(a)
    return idx


def _distributed_argmax(values: Array, ids: Array, axes: tuple):
    """Global (argmax-id, max) of per-shard score slices.

    Ties break toward the smallest global feature id, making the result
    independent of the shard layout (tested property).
    """
    arg_local = jnp.argmax(values)
    best_local = values[arg_local]
    id_local = ids[arg_local]
    if axes:
        best = lax.pmax(best_local, axes)
        cand = jnp.where(best_local >= best, id_local, _BIG_ID)
        k = lax.pmin(cand, axes)
    else:
        best, k = best_local, id_local
    return k, best


def _loop_state(n_local: int, num_select: int):
    return dict(
        mask=jnp.zeros((n_local,), jnp.bool_),
        selected=jnp.full((num_select,), -1, jnp.int32),
        gains=jnp.zeros((num_select,), jnp.float32),
    )


def _check_custom_criterion(score: ScoreFn, crit: Criterion) -> None:
    """CustomScore computes the complete objective itself (Listing 7), so
    it bypasses the criterion fold; any non-default criterion would be
    silently ignored — fail instead."""
    if isinstance(score, CustomScore) and crit.name != "mid":
        raise ValueError(
            f"criterion {crit.name!r} cannot be combined with CustomScore: "
            "a custom get_result computes the complete objective itself "
            "(paper Listing 7); use the default 'mid' criterion"
        )


def _nan_relevance(n: int) -> Array:
    """Relevance placeholder for CustomScore fits (no rel/red split)."""
    return jnp.full((n,), jnp.nan, jnp.float32)


def check_conditional_support(score: ScoreFn, crit: Criterion) -> None:
    """Conditional criteria (JMI/CMIM) need a score whose pair statistic
    decomposes per class; fail at build time with the fix, not with an
    opaque error from inside a traced engine body."""
    if crit.needs_conditional_redundancy and not getattr(
        score, "supports_conditional", False
    ):
        raise ValueError(
            f"criterion {crit.name!r} needs class-conditioned pair "
            f"statistics I(x_k; x_j | y), but {type(score).__name__} has "
            "no conditional decomposition; score with MIScore (pass "
            "bins= to discretise continuous data first)"
        )


# ---------------------------------------------------------------------------
# single-device reference driver (feature-major), any score fn
# ---------------------------------------------------------------------------

def mrmr_reference(
    X_rows: Array,
    y: Array,
    num_select: int,
    score: ScoreFn,
    *,
    incremental: bool = True,
    criterion: Criterion | str = "mid",
) -> MRMRResult:
    """Pure-jnp mRMR on one device. ``X_rows`` is feature-major (N, M)."""
    crit = resolve_criterion(criterion)
    _check_custom_criterion(score, crit)
    check_conditional_support(score, crit)
    n, m = X_rows.shape
    custom = isinstance(score, CustomScore)
    use_incr = incremental and score.incremental_safe and not custom
    fold = crit.needs_redundancy and not custom
    cond = fold and crit.needs_conditional_redundancy

    def red_terms(row):
        return score.redundancy_terms(X_rows, row, y, conditional=cond)

    rel = None if custom else score.relevance(X_rows, y)
    state = _loop_state(n, num_select)
    # Custom scores accumulate selected rows in f32, matching the
    # alternative body (whose psum-gathered rows are always f32).
    sel_dtype = jnp.float32 if custom else X_rows.dtype
    state["sel_rows"] = jnp.zeros((num_select, m), sel_dtype)
    if use_incr and fold:
        state["crit"] = crit.init_state(n)

    def body(l, st):
        if custom:
            g = score.full_score(X_rows, y, st["sel_rows"], l)
        elif not fold:
            g = crit.objective(rel, crit.init_state(n), l)
        elif use_incr:
            g = crit.objective(rel, st["crit"], l)
        else:
            def inner(j, cs):
                return crit.update(cs, red_terms(st["sel_rows"][j]), j)

            cs = lax.fori_loop(0, l, inner, crit.init_state(n))
            g = crit.objective(rel, cs, l)
        g = jnp.where(st["mask"], _NEG_INF, g)
        k = jnp.argmax(g)
        xk = X_rows[k]
        st = dict(st)
        st["mask"] = st["mask"].at[k].set(True)
        st["selected"] = st["selected"].at[l].set(k.astype(jnp.int32))
        st["gains"] = st["gains"].at[l].set(g[k])
        st["sel_rows"] = lax.dynamic_update_slice(
            st["sel_rows"], xk[None].astype(sel_dtype), (l, 0)
        )
        if use_incr and fold:
            st["crit"] = crit.update(st["crit"], red_terms(xk), l)
        return st

    state = lax.fori_loop(0, num_select, body, state)
    return MRMRResult(
        selected=state["selected"],
        gains=state["gains"],
        relevance=_nan_relevance(n) if custom else rel.astype(jnp.float32),
        criterion=crit.name,
        engine="reference",
    )


# ---------------------------------------------------------------------------
# conventional encoding: observations sharded, contingency-table psum
# ---------------------------------------------------------------------------

def _conventional_body(
    X_loc: Array,  # (M_loc, N) int, padded rows hold out-of-range values
    y_loc: Array,  # (M_loc,)
    *,
    num_select: int,
    score: MIScore,
    criterion: Criterion,
    obs_axes: tuple,
    incremental: bool,
    block: int,
    onehot_dtype=jnp.bfloat16,
    static_inner: bool = False,
):
    n = X_loc.shape[1]
    v, c = score.num_values, score.num_classes
    crit = criterion

    def counts_vs(tgt_loc: Array, vy: int) -> Array:
        """Local map+combine, then the reduce: one psum over the obs axes."""
        cnt = contingency.batched_counts(
            X_loc, tgt_loc, v, vy, block=block, onehot_dtype=onehot_dtype
        )
        return lax.psum(cnt, obs_axes) if obs_axes else cnt

    def pair_terms(tgt_loc: Array) -> dict:
        """The criterion's redundancy terms for one selected column.

        Marginal-only criteria keep the exact pre-conditional graph (a
        (N, v, v) count + MI — bitwise-identical selections, no class
        axis).  Conditional criteria fuse the class into the target, so
        ONE psummed (N, v, v*c) count yields both terms.
        """
        if not crit.needs_conditional_redundancy:
            return dict(
                marginal=mi_from_counts(counts_vs(tgt_loc, v)), conditional=None
            )
        fused = contingency.fuse_targets(tgt_loc, y_loc, v, c)
        cnt = counts_vs(fused, v * c).reshape(n, v, v, c)
        return dict(
            marginal=mi_from_counts(cnt.sum(-1)),
            conditional=cmi_from_counts(cnt),
        )

    rel = mi_from_counts(counts_vs(y_loc, c))  # (N,) replicated
    state = _loop_state(n, num_select)
    if incremental and crit.needs_redundancy:
        state["crit"] = crit.init_state(n)

    # Selected *column indices* stand in for the paper's broadcast tables.
    def body(l, st):
        if not crit.needs_redundancy:
            g = crit.objective(rel, crit.init_state(n), l)
        elif incremental:
            g = crit.objective(rel, st["crit"], l)
        else:
            # static_inner trades the data-dependent trip count (paper: l-1
            # passes at step l) for a fixed L-pass masked loop, so the
            # dry-run HLO carries the recompute cost explicitly.
            def inner(j, cs):
                xj = jnp.take(X_loc, st["selected"][j], axis=1)
                folded = crit.update(cs, pair_terms(xj), j)
                if static_inner:
                    # Fold unconditionally (the dry-run carries the cost),
                    # keep the state only for the real j < l iterations.
                    return jax.tree.map(
                        lambda a, b: jnp.where(j < l, b, a), cs, folded
                    )
                return folded

            hi = num_select if static_inner else l
            cs = lax.fori_loop(0, hi, inner, crit.init_state(n))
            g = crit.objective(rel, cs, l)
        g = jnp.where(st["mask"], _NEG_INF, g)
        k = jnp.argmax(g).astype(jnp.int32)
        st = dict(st)
        st["mask"] = st["mask"].at[k].set(True)
        st["selected"] = st["selected"].at[l].set(k)
        st["gains"] = st["gains"].at[l].set(g[k])
        if incremental and crit.needs_redundancy:
            xk = jnp.take(X_loc, k, axis=1)
            st["crit"] = crit.update(st["crit"], pair_terms(xk), l)
        return st

    state = lax.fori_loop(0, num_select, body, state)
    return state["selected"], state["gains"], rel


def mrmr_conventional(
    X: Array,  # (M, N) conventional layout
    y: Array,  # (M,)
    num_select: int,
    score: MIScore,
    *,
    mesh: Mesh | None = None,
    obs_axes=("data",),
    incremental: bool = True,
    block: int = 64,
    criterion: Criterion | str = "mid",
) -> MRMRResult:
    """Paper's conventional-encoding MapReduce job on a device mesh.

    The dataset is sharded over observations (`obs_axes`); contingency
    tables are locally combined and globally summed with one all-reduce per
    scoring pass — the MapReduce shuffle collapsed onto the ICI ring.
    """
    crit = resolve_criterion(criterion)
    fn = make_conventional_fn(
        num_select, score, mesh=mesh, obs_axes=obs_axes,
        incremental=incremental, block=block, criterion=crit,
    )
    sel, gains, rel = fn(X, y)
    return MRMRResult(sel, gains, relevance=rel, criterion=crit.name,
                      engine="conventional")


def make_conventional_fn(
    num_select: int,
    score: MIScore,
    *,
    mesh: Mesh | None = None,
    obs_axes=("data",),
    incremental: bool = True,
    block: int = 64,
    onehot_dtype=jnp.bfloat16,
    static_inner: bool = False,
    criterion: Criterion | str = "mid",
):
    """Jitted (X, y) -> (selected, gains, relevance) for the conventional
    encoding.

    Exposed separately so benchmarks can ``.lower().compile()`` the job and
    run the same HLO collective analysis as the LM dry-run cells.
    """
    if not isinstance(score, MIScore):
        raise ValueError(
            "conventional encoding works with discrete MI only (paper §IV.B); "
            "use the alternative encoding for custom scores"
        )
    kwargs = dict(
        num_select=num_select,
        score=score,
        criterion=resolve_criterion(criterion),
        incremental=incremental,
        block=block,
        onehot_dtype=onehot_dtype,
        static_inner=static_inner,
    )
    if mesh is None:
        return jax.jit(functools.partial(_conventional_body, obs_axes=(), **kwargs))
    obs_axes = _axes_tuple(obs_axes)
    body = functools.partial(_conventional_body, obs_axes=obs_axes, **kwargs)
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(obs_axes, None), P(obs_axes)),
            out_specs=P(),
        )
    )


# ---------------------------------------------------------------------------
# alternative encoding: features sharded, broadcast class/selected, map-only
# ---------------------------------------------------------------------------

def _alternative_body(
    X_loc: Array,  # (N_loc, M) feature-major shard
    y: Array,  # (M,) replicated (the paper's broadcast v_class)
    *,
    num_select: int,
    n_features: int,
    score: ScoreFn,
    criterion: Criterion,
    feat_axes: tuple,
    axis_sizes: dict,
    incremental: bool,
):
    n_loc, m = X_loc.shape
    crit = criterion
    shard = _flat_axis_index(feat_axes, axis_sizes) if feat_axes else jnp.int32(0)
    ids = shard * n_loc + jnp.arange(n_loc, dtype=jnp.int32)
    valid = ids < n_features
    custom = isinstance(score, CustomScore)
    use_incr = incremental and score.incremental_safe and not custom
    fold = crit.needs_redundancy and not custom
    cond = fold and crit.needs_conditional_redundancy

    def red_terms(row):
        # y is replicated (the paper's broadcast v_class), so the
        # class-conditioned pair statistic stays a map-only local job.
        return score.redundancy_terms(X_loc, row, y, conditional=cond)

    rel = None if custom else score.relevance(X_loc, y)
    state = _loop_state(n_loc, num_select)
    # mask and the criterion's fold state are per-shard slices -> varying
    # along the feature axes.
    state["mask"] = pvary(state["mask"], feat_axes)
    if use_incr and fold:
        state["crit"] = pvary(crit.init_state(n_loc), feat_axes)
    # The paper's broadcast v_s: replicated buffer of selected feature rows.
    state["sel_rows"] = jnp.zeros((num_select, m), jnp.float32)

    def fetch_row(k):
        """getEntry: psum of the masked local rows -> replicated (M,)."""
        mine = (ids == k).astype(jnp.float32)
        row = (X_loc.astype(jnp.float32) * mine[:, None]).sum(axis=0)
        return lax.psum(row, feat_axes) if feat_axes else row

    def body(l, st):
        if custom:
            g = score.full_score(X_loc, y, st["sel_rows"], l)
        elif not fold:
            g = crit.objective(rel, pvary(crit.init_state(n_loc), feat_axes), l)
        elif use_incr:
            g = crit.objective(rel, st["crit"], l)
        else:
            def inner(j, cs):
                return crit.update(cs, red_terms(st["sel_rows"][j]), j)

            cs0 = pvary(crit.init_state(n_loc), feat_axes)
            cs = lax.fori_loop(0, l, inner, cs0)
            g = crit.objective(rel, cs, l)
        g = jnp.where(st["mask"] | ~valid, _NEG_INF, g)
        k, best = _distributed_argmax(g, ids, feat_axes)
        xk = fetch_row(k)
        st = dict(st)
        st["mask"] = st["mask"] | (ids == k)
        st["selected"] = st["selected"].at[l].set(k)
        st["gains"] = st["gains"].at[l].set(best)
        st["sel_rows"] = lax.dynamic_update_slice(st["sel_rows"], xk[None], (l, 0))
        if use_incr and fold:
            st["crit"] = crit.update(st["crit"], red_terms(xk), l)
        return st

    state = lax.fori_loop(0, num_select, body, state)
    rel_out = _nan_relevance(n_loc) if custom else rel.astype(jnp.float32)
    return state["selected"], state["gains"], rel_out


def mrmr_alternative(
    X_rows: Array,  # (N, M) alternative layout (rows = features)
    y: Array,
    num_select: int,
    score: ScoreFn,
    *,
    mesh: Mesh | None = None,
    feat_axes=("model",),
    incremental: bool = True,
    n_features: int | None = None,
    criterion: Criterion | str = "mid",
) -> MRMRResult:
    """Paper's alternative-encoding job: feature-sharded, map-only scoring."""
    crit = resolve_criterion(criterion)
    n_features = int(n_features if n_features is not None else X_rows.shape[0])
    fn = make_alternative_fn(
        num_select, score, n_features, mesh=mesh, feat_axes=feat_axes,
        incremental=incremental, criterion=crit,
    )
    sel, gains, rel = fn(X_rows, y)
    return MRMRResult(sel, gains, relevance=rel[:n_features],
                      criterion=crit.name, engine="alternative")


def make_alternative_fn(
    num_select: int,
    score: ScoreFn,
    n_features: int,
    *,
    mesh: Mesh | None = None,
    feat_axes=("model",),
    incremental: bool = True,
    criterion: Criterion | str = "mid",
):
    """Jitted (X_rows, y) -> (selected, gains, relevance) for the
    alternative encoding.  The relevance output covers the PADDED feature
    extent (callers slice ``[:n_features]``)."""
    crit = resolve_criterion(criterion)
    _check_custom_criterion(score, crit)
    check_conditional_support(score, crit)
    kwargs = dict(
        num_select=num_select,
        n_features=int(n_features),
        score=score,
        criterion=crit,
        incremental=incremental,
    )
    if mesh is None:
        return jax.jit(
            functools.partial(
                _alternative_body, feat_axes=(), axis_sizes={}, **kwargs
            )
        )
    feat_axes = _axes_tuple(feat_axes)
    axis_sizes = {a: mesh.shape[a] for a in feat_axes}
    body = functools.partial(
        _alternative_body, feat_axes=feat_axes, axis_sizes=axis_sizes, **kwargs
    )
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(feat_axes, None), P()),
            # selected/gains replicate; the relevance slices concatenate
            # back to the (padded) global feature extent.
            out_specs=(P(), P(), P(feat_axes)),
        )
    )


# ---------------------------------------------------------------------------
# grid encoding (beyond paper): shard observations AND features
# ---------------------------------------------------------------------------

def _grid_body(
    X_loc: Array,  # (M_loc, N_loc) conventional-layout tile
    y_loc: Array,  # (M_loc,)
    *,
    num_select: int,
    n_features: int,
    score: MIScore,
    criterion: Criterion,
    obs_axes: tuple,
    feat_axes: tuple,
    axis_sizes: dict,
    block: int,
    incremental: bool,
):
    m_loc, n_loc = X_loc.shape
    v, c = score.num_values, score.num_classes
    crit = criterion
    shard = _flat_axis_index(feat_axes, axis_sizes) if feat_axes else jnp.int32(0)
    ids = shard * n_loc + jnp.arange(n_loc, dtype=jnp.int32)
    valid = ids < n_features

    def counts_vs(tgt_loc: Array, vy: int) -> Array:
        cnt = contingency.batched_counts(X_loc, tgt_loc, v, vy, block=block)
        return lax.psum(cnt, obs_axes) if obs_axes else cnt

    def pair_terms(tgt_loc: Array) -> dict:
        """Redundancy terms for one fetched column — the class fuses into
        the target locally (y_loc is this tile's row slice), so the 3-way
        counts ride the same single psum as the marginal counts."""
        if not crit.needs_conditional_redundancy:
            return dict(
                marginal=mi_from_counts(counts_vs(tgt_loc, v)), conditional=None
            )
        fused = contingency.fuse_targets(tgt_loc, y_loc, v, c)
        cnt = counts_vs(fused, v * c).reshape(n_loc, v, v, c)
        return dict(
            marginal=mi_from_counts(cnt.sum(-1)),
            conditional=cmi_from_counts(cnt),
        )

    def fetch_col(k):
        """Local rows of global column k, replicated across feature axes."""
        k_loc = k - shard * n_loc
        own = (k_loc >= 0) & (k_loc < n_loc)
        col = jnp.take(X_loc, jnp.clip(k_loc, 0, n_loc - 1), axis=1)
        col = jnp.where(own, col, 0).astype(jnp.float32)
        col = lax.psum(col, feat_axes) if feat_axes else col
        return col.astype(X_loc.dtype)

    rel = mi_from_counts(counts_vs(y_loc, c))
    state = _loop_state(n_loc, num_select)
    state["mask"] = pvary(state["mask"], feat_axes)
    if incremental and crit.needs_redundancy:
        state["crit"] = pvary(crit.init_state(n_loc), feat_axes)

    def body(l, st):
        if not crit.needs_redundancy:
            g = crit.objective(rel, pvary(crit.init_state(n_loc), feat_axes), l)
        elif incremental:
            g = crit.objective(rel, st["crit"], l)
        else:
            def inner(j, cs):
                xj = fetch_col(st["selected"][j])
                return crit.update(cs, pair_terms(xj), j)

            cs0 = pvary(crit.init_state(n_loc), feat_axes)
            cs = lax.fori_loop(0, l, inner, cs0)
            g = crit.objective(rel, cs, l)
        g = jnp.where(st["mask"] | ~valid, _NEG_INF, g)
        k, best = _distributed_argmax(g, ids, feat_axes)
        st = dict(st)
        st["mask"] = st["mask"] | (ids == k)
        st["selected"] = st["selected"].at[l].set(k)
        st["gains"] = st["gains"].at[l].set(best)
        if incremental and crit.needs_redundancy:
            xk = fetch_col(k)
            st["crit"] = crit.update(st["crit"], pair_terms(xk), l)
        return st

    state = lax.fori_loop(0, num_select, body, state)
    return state["selected"], state["gains"], rel


def mrmr_grid(
    X: Array,  # (M, N) conventional layout, sharded both ways
    y: Array,
    num_select: int,
    score: MIScore,
    *,
    mesh: Mesh,
    obs_axes=("data",),
    feat_axes=("model",),
    incremental: bool = True,
    block: int = 64,
    n_features: int | None = None,
    criterion: Criterion | str = "mid",
) -> MRMRResult:
    """2-D sharded mRMR: observation axes × feature axes (beyond paper)."""
    crit = resolve_criterion(criterion)
    n_features = int(n_features if n_features is not None else X.shape[1])
    fn = make_grid_fn(
        num_select, score, n_features, mesh=mesh, obs_axes=obs_axes,
        feat_axes=feat_axes, incremental=incremental, block=block,
        criterion=crit,
    )
    sel, gains, rel = fn(X, y)
    return MRMRResult(sel, gains, relevance=rel[:n_features],
                      criterion=crit.name, engine="grid")


def make_grid_fn(
    num_select: int,
    score: MIScore,
    n_features: int,
    *,
    mesh: Mesh,
    obs_axes=("data",),
    feat_axes=("model",),
    incremental: bool = True,
    block: int = 64,
    criterion: Criterion | str = "mid",
):
    """Jitted (X, y) -> (selected, gains, relevance) for the grid encoding.
    The relevance output covers the PADDED feature extent."""
    if not isinstance(score, MIScore):
        raise ValueError("grid encoding is discrete/MI only")
    obs_axes, feat_axes = _axes_tuple(obs_axes), _axes_tuple(feat_axes)
    axis_sizes = {a: mesh.shape[a] for a in feat_axes}
    body = functools.partial(
        _grid_body,
        num_select=num_select,
        n_features=int(n_features),
        score=score,
        criterion=resolve_criterion(criterion),
        obs_axes=obs_axes,
        feat_axes=feat_axes,
        axis_sizes=axis_sizes,
        block=block,
        incremental=incremental,
    )
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(obs_axes, feat_axes), P(obs_axes)),
            out_specs=(P(), P(), P(feat_axes)),
        )
    )
