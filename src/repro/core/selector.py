"""The front-door selection API: ``MRMRSelector`` / ``SelectionPlan``.

One estimator-style entry point for every distribution strategy in the
repo.  The design splits feature selection into three layers:

1. **Planning** — ``plan_selection`` implements the paper's §III rule
   (tall/narrow -> conventional encoding, wide/short -> alternative,
   both-large -> 2-D grid) and factors the available devices into a mesh
   shape.  The result is a ``SelectionPlan``: a frozen, inspectable record
   of encoding, mesh axes/shape, block size, incremental flag and score.
2. **Engines** — a registry mapping encoding names to fit functions.  The
   four built-in drivers (reference / conventional / alternative / grid)
   register here; new strategies (streaming shards, other score layouts)
   drop in via ``register_engine`` without touching the drivers.
3. **The selector** — ``MRMRSelector.fit(X, y)`` resolves the plan, builds
   the mesh, and hands off to the engine.  Padding to mesh divisibility,
   layout transposition (inputs are ALWAYS observations × features),
   device placement and result unpadding are all owned here; callers never
   see ``shard_map``.

    >>> from repro import MRMRSelector
    >>> sel = MRMRSelector(num_select=10).fit(X, y)
    >>> X_reduced = sel.transform(X)          # columns in selection order
    >>> sel.plan_                             # the resolved SelectionPlan
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import mrmr as mrmr_mod
from repro.core.criteria import Criterion, resolve_criterion
from repro.core.mrmr import MRMRResult, WarmJitCache
from repro.core.scores import MIScore, PearsonMIScore, ScoreFn, _OOR
from repro.data.binning import BinnedSource
from repro.data.sources import (
    ArraySource,
    DataSource,
    SourceStats,
    needs_category_scan,
)
from repro.dist.meshes import factor_mesh, make_mesh
from repro.dist.sharding import axes_tuple as _axes_tuple, mesh_extent
from repro.dist.streaming import effective_block_obs, resolve_prefetch
from repro.runtime import tracing

Array = jax.Array

# Paper §III aspect-ratio rule: beyond these ratios one axis dominates and
# single-axis sharding wins; between them (and with enough devices and
# data) the 2-D grid removes both memory walls at once.
TALL_RATIO = 4.0      # obs/feat >= this -> conventional (observation-sharded)
WIDE_RATIO = 0.25     # obs/feat <= this -> alternative (feature-sharded)
GRID_MIN_DIM = 512    # both dims at least this before a grid pays off
GRID_MIN_DEVICES = 4  # a 2-D mesh needs at least a 2x2 factorisation


def check_num_select(num_select, n_features: int) -> None:
    """Shared fit-time bounds check: ``1 <= num_select <= num_features``.

    Raised by the front door (both array and DataSource paths) and the
    streaming driver, so an oversized ask fails with one clear message
    instead of an opaque shape error deep inside an engine loop.
    """
    if not 1 <= int(num_select) <= n_features:
        raise ValueError(
            f"num_select={num_select} out of range: need "
            f"1 <= num_select <= num_features ({n_features})"
        )


def score_of_stats(st: SourceStats) -> ScoreFn:
    """The default score of data whose stats are ``st``: exact MI sized
    by its category counts where it is discrete, else Pearson-MI."""
    if st.discrete:
        return MIScore(num_values=st.num_values, num_classes=st.num_classes)
    return PearsonMIScore()


@dataclasses.dataclass(frozen=True)
class SelectionPlan:
    """Resolved distribution strategy for one ``fit``.

    ``mesh_shape`` aligns with ``obs_axes + feat_axes``; empty means run
    unsharded.  ``score=None`` means "resolve from the data at fit time"
    (discrete -> exact MI, continuous -> Pearson-MI).  ``criterion`` is
    the greedy objective — a registered name or a
    :class:`~repro.core.criteria.Criterion` instance (resolved at use).
    """

    encoding: str                     # reference|conventional|alternative|grid|streaming
    obs_axes: tuple = ()              # mesh axes sharding observations
    feat_axes: tuple = ()             # mesh axes sharding features
    mesh_shape: tuple = ()            # extents, aligned with mesh_axes
    block: int = 64                   # contingency feature-block size
    incremental: bool = True          # running criterion fold vs recompute
    score: ScoreFn | None = None      # score spec (None = auto from data)
    onehot_dtype: str = "bfloat16"    # contingency one-hot storage dtype
    block_obs: int = 65536            # streaming: EFFECTIVE observations per
                                      # block (rounded up to the obs extent)
    prefetch: int = 2                 # streaming: blocks placed ahead of
                                      # device accumulation (0 = synchronous;
                                      # the selector resolves "auto" to an
                                      # int before the plan is recorded)
    criterion: object = "mid"         # greedy objective (name or Criterion);
                                      # appended last for positional compat
    bins: int | None = None           # quantile-binned fit: codes per
                                      # feature (None = data was discrete)
    batch_candidates: int = 1         # streaming: redundancy vectors
                                      # speculated per pass (q; 1 = classic)
    spill_dir: str | None = None      # streaming: encoded-block spill cache
                                      # directory (None = off)
    spill_budget_bytes: int | None = None  # LRU byte budget for spill_dir
    readahead: int = 0                # streaming: raw blocks read across
                                      # pass boundaries (0 = off)
    hosts: int = 1                    # streaming: jax.distributed processes
                                      # sharing the fit (1 = single-host)

    @property
    def mesh_axes(self) -> tuple:
        return self.obs_axes + self.feat_axes

    @property
    def num_shards(self) -> int:
        return math.prod(self.mesh_shape) if self.mesh_shape else 1


def _grid_worthwhile(m: int, n: int, n_dev: int) -> bool:
    """§III both-large gate, shared by the in-memory and streaming
    planners: enough devices for a 2-D factorisation, both dims big
    enough to shard, and no axis dominant enough for 1-D to win."""
    aspect = m / max(n, 1)
    return (
        n_dev >= GRID_MIN_DEVICES
        and min(m, n) >= GRID_MIN_DIM
        and WIDE_RATIO < aspect < TALL_RATIO
    )


def _grid_factor(m: int, n: int, n_dev: int) -> tuple | None:
    """The (obs, feat) device factorisation when a 2-D grid pays off for
    an (m, n) dataset on ``n_dev`` devices, else None (grid not
    worthwhile, or the device count only factors 1-D)."""
    if not _grid_worthwhile(m, n, n_dev):
        return None
    # Weight the device split by the aspect ratio: a taller dataset gets
    # more observation shards.
    od, fd = factor_mesh(n_dev, bias=max(m / max(n, 1), 1e-6))
    return None if min(od, fd) == 1 else (od, fd)


def _device_count(devices) -> int:
    if devices is None:
        return len(jax.devices())
    if isinstance(devices, Mesh):
        return devices.size
    if isinstance(devices, int):
        return devices
    return len(devices)


def plan_selection(
    shape: tuple,
    devices=None,
    score: ScoreFn | None = None,
    *,
    obs_axes: Sequence[str] | str = ("data",),
    feat_axes: Sequence[str] | str = ("model",),
    incremental: bool = True,
    block: int = 64,
    criterion: Criterion | str = "mid",
) -> SelectionPlan:
    """Pick encoding + mesh for a dataset shape (paper §III).

    Args:
      shape: (observations, features) of the conventional-orientation input.
      devices: device budget — an int, a device list, a ``Mesh`` (planning
        is then constrained to its axes), or None for all local devices.
      score: the score spec.  Non-MI scores force the alternative encoding
        (the only map-only layout that supports arbitrary scores, §IV.D).
      criterion: greedy objective name or Criterion — orthogonal to the
        encoding choice; recorded on the plan for the engines.
    """
    criterion = resolve_criterion(criterion)
    m, n = int(shape[0]), int(shape[1])
    obs_axes, feat_axes = _axes_tuple(obs_axes), _axes_tuple(feat_axes)
    n_dev = _device_count(devices)
    mesh = devices if isinstance(devices, Mesh) else None
    if mesh is not None:
        obs_axes = tuple(a for a in obs_axes if a in mesh.shape)
        feat_axes = tuple(a for a in feat_axes if a in mesh.shape)

    mi_ok = score is None or isinstance(score, MIScore)
    aspect = m / max(n, 1)
    can_grid = (
        mi_ok
        and _grid_worthwhile(m, n, n_dev)
        and (mesh is None or (obs_axes and feat_axes))
    )
    if not mi_ok:
        encoding = "alternative"
    elif can_grid:
        encoding = "grid"
    elif aspect >= 1.0:
        encoding = "conventional"
    else:
        encoding = "alternative"

    common = dict(block=block, incremental=incremental, score=score,
                  criterion=criterion)
    if n_dev <= 1 and mesh is None:
        # Single device: encoding still follows the shape (the drivers run
        # unsharded), so plans are stable as the fleet scales.
        if encoding == "grid":
            encoding = "conventional" if aspect >= 1.0 else "alternative"
        return SelectionPlan(encoding=encoding, **common)

    if mesh is not None:
        if encoding == "conventional" and not obs_axes:
            encoding = "alternative" if feat_axes else "reference"
        if encoding == "alternative" and not feat_axes:
            # Only MI scores may reroute to the conventional engine; any
            # other score falls back to the score-agnostic reference.
            encoding = "conventional" if (obs_axes and mi_ok) else "reference"
        if encoding == "reference":
            return SelectionPlan("reference", **common)
        shape_of = lambda axes: tuple(mesh.shape[a] for a in axes)
        if encoding == "conventional":
            return SelectionPlan(
                encoding, obs_axes=obs_axes, mesh_shape=shape_of(obs_axes),
                **common,
            )
        if encoding == "alternative":
            return SelectionPlan(
                encoding, feat_axes=feat_axes, mesh_shape=shape_of(feat_axes),
                **common,
            )
        return SelectionPlan(
            encoding, obs_axes=obs_axes, feat_axes=feat_axes,
            mesh_shape=shape_of(obs_axes + feat_axes), **common,
        )

    if encoding == "grid":
        gf = _grid_factor(m, n, n_dev)
        if gf is None:  # prime device count: grid degenerates
            encoding = "conventional" if aspect >= 1.0 else "alternative"
        else:
            return SelectionPlan(
                "grid", obs_axes=obs_axes[:1] or ("data",),
                feat_axes=feat_axes[:1] or ("model",),
                mesh_shape=gf, **common,
            )
    if encoding == "conventional":
        return SelectionPlan(
            "conventional", obs_axes=obs_axes[:1] or ("data",),
            mesh_shape=(n_dev,), **common,
        )
    return SelectionPlan(
        "alternative", feat_axes=feat_axes[:1] or ("model",),
        mesh_shape=(n_dev,), **common,
    )


# ---------------------------------------------------------------------------
# engine registry
# ---------------------------------------------------------------------------

# name -> fit(X, y, *, num_select, plan, mesh) -> MRMRResult, with X in
# conventional orientation (observations × features) and global feature ids
# in the result.  Engines own their padding / transposition / placement.
_ENGINES: dict = {}


def register_engine(name: str) -> Callable:
    """Register a selection engine under an encoding name (decorator)."""

    def deco(fn):
        _ENGINES[name] = fn
        return fn

    return deco


def get_engine(name: str):
    try:
        return _ENGINES[name]
    except KeyError:
        raise ValueError(
            f"unknown encoding {name!r}; registered: {sorted(_ENGINES)}"
        ) from None


def available_encodings() -> tuple:
    return tuple(sorted(_ENGINES))


def build_engine_fn(
    plan: SelectionPlan, mesh: Mesh | None, num_select: int, n_features: int
):
    """Jitted (X, y) -> (selected, gains, relevance) in the engine's
    NATIVE layout.

    Native layouts: conventional/grid take (obs, feat) [padded to mesh
    divisibility]; reference/alternative take feature-major (feat, obs).
    The relevance output covers the engine's (padded) feature extent.
    ``.lower().compile()`` on it compiles ahead of time the exact job the
    selector would run.
    """
    enc, score = plan.encoding, plan.score
    crit = resolve_criterion(plan.criterion)
    oh_dt = jnp.dtype(plan.onehot_dtype)
    if enc == "reference":

        def ref_fn(Xr, y):
            res = mrmr_mod.mrmr_reference(
                Xr, y, num_select, score, incremental=plan.incremental,
                criterion=crit,
            )
            return res.selected, res.gains, res.relevance

        return jax.jit(ref_fn)
    if enc == "conventional":
        return mrmr_mod.make_conventional_fn(
            num_select, score, mesh=mesh, obs_axes=plan.obs_axes,
            incremental=plan.incremental, block=plan.block,
            onehot_dtype=oh_dt, criterion=crit,
        )
    if enc == "alternative":
        return mrmr_mod.make_alternative_fn(
            num_select, score, n_features, mesh=mesh,
            feat_axes=plan.feat_axes, incremental=plan.incremental,
            criterion=crit,
        )
    if enc == "grid":
        if mesh is None:
            raise ValueError("grid encoding requires a mesh")
        return mrmr_mod.make_grid_fn(
            num_select, score, n_features, mesh=mesh,
            obs_axes=plan.obs_axes, feat_axes=plan.feat_axes,
            incremental=plan.incremental, block=plan.block,
            criterion=crit,
        )
    raise ValueError(f"unknown encoding {enc!r}")


# Warm engine-fn cache: the built (jit-wrapped) engine callables, keyed by
# everything that shapes the computation.  jax memoises executables per
# wrapper object, so reusing the wrapper across fits makes a repeat fit
# (same engine × criterion × score × geometry — the selection service's
# steady state) skip trace AND compile entirely.
_ENGINE_FN_CACHE = WarmJitCache(capacity=32)


def _engine_fn_key(plan: SelectionPlan, mesh, num_select: int, n_features: int):
    return (
        "engine_fn", plan.encoding, plan.score,
        resolve_criterion(plan.criterion), num_select, n_features, mesh,
        plan.block, plan.incremental, plan.obs_axes, plan.feat_axes,
        plan.onehot_dtype,
    )


def cached_engine_fn(
    plan: SelectionPlan, mesh: Mesh | None, num_select: int, n_features: int
):
    """:func:`build_engine_fn` through the warm jit cache.

    Unhashable plan ingredients (a custom criterion or score holding
    mutable state) fall back to an uncached build.
    """
    return _ENGINE_FN_CACHE.get_or_build(
        _engine_fn_key(plan, mesh, num_select, n_features),
        lambda: build_engine_fn(plan, mesh, num_select, n_features),
    )


def engine_fn_cache_stats() -> dict:
    """Hit/miss/eviction counters of the warm engine-fn cache."""
    return _ENGINE_FN_CACHE.stats()


def clear_engine_fn_cache() -> None:
    """Drop every warmed engine fn (tests; frees compiled executables)."""
    _ENGINE_FN_CACHE.clear()


def _pad_axis(x: Array, axis: int, multiple: int, fill) -> Array:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _place(x: Array, mesh: Mesh | None, spec: P) -> Array:
    if mesh is None:
        return x
    return jax.device_put(x, NamedSharding(mesh, spec))


# _OOR (imported from scores): out-of-range category -> zero one-hot row,
# the one padding sentinel shared by the in-memory and streaming paths.


def _result(plan: SelectionPlan, engine: str, sel, gains, rel, n: int):
    """Assemble the rich result: slice feature padding off the relevance."""
    return MRMRResult(
        sel, gains, relevance=rel[:n],
        criterion=resolve_criterion(plan.criterion).name, engine=engine,
    )


@register_engine("reference")
def _fit_reference(X, y, *, num_select, plan, mesh) -> MRMRResult:
    del mesh
    fn = cached_engine_fn(plan, None, num_select, X.shape[1])
    sel, gains, rel = fn(jnp.asarray(X).T, y)
    return _result(plan, "reference", sel, gains, rel, X.shape[1])


@register_engine("conventional")
def _fit_conventional(X, y, *, num_select, plan, mesh) -> MRMRResult:
    ext = mesh_extent(mesh, plan.obs_axes)
    # Padded observations carry out-of-range categories: their one-hot rows
    # are all-zero, so contingency tables stay exact without masking.
    Xp = _pad_axis(X.astype(jnp.int32), 0, ext, fill=_OOR)
    yp = _pad_axis(y, 0, ext, fill=_OOR)
    Xp = _place(Xp, mesh, P(plan.obs_axes, None))
    yp = _place(yp, mesh, P(plan.obs_axes))
    fn = cached_engine_fn(plan, mesh, num_select, X.shape[1])
    sel, gains, rel = fn(Xp, yp)
    return _result(plan, "conventional", sel, gains, rel, X.shape[1])


@register_engine("alternative")
def _fit_alternative(X, y, *, num_select, plan, mesh) -> MRMRResult:
    n = X.shape[1]
    ext = mesh_extent(mesh, plan.feat_axes)
    # Feature-major storage; padded feature rows are masked out of the
    # argmax by the driver (ids >= n_features).
    Xr = _pad_axis(jnp.asarray(X).T, 0, ext, fill=0)
    Xr = _place(Xr, mesh, P(plan.feat_axes, None))
    yb = _place(y, mesh, P())
    fn = cached_engine_fn(plan, mesh, num_select, n)
    sel, gains, rel = fn(Xr, yb)
    return _result(plan, "alternative", sel, gains, rel, n)


@register_engine("grid")
def _fit_grid(X, y, *, num_select, plan, mesh) -> MRMRResult:
    if mesh is None:
        raise ValueError("grid encoding requires a mesh")
    n = X.shape[1]
    oext = mesh_extent(mesh, plan.obs_axes)
    fext = mesh_extent(mesh, plan.feat_axes)
    Xp = _pad_axis(X.astype(jnp.int32), 0, oext, fill=_OOR)
    Xp = _pad_axis(Xp, 1, fext, fill=0)
    yp = _pad_axis(y, 0, oext, fill=_OOR)
    Xp = _place(Xp, mesh, P(plan.obs_axes, plan.feat_axes))
    yp = _place(yp, mesh, P(plan.obs_axes))
    fn = cached_engine_fn(plan, mesh, num_select, n)
    sel, gains, rel = fn(Xp, yp)
    return _result(plan, "grid", sel, gains, rel, n)


# ---------------------------------------------------------------------------
# the selector
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MRMRSelector:
    """mRMR feature selection with auto-planned distribution.

    Scikit-learn-style estimator: ``fit(X, y)`` -> self with ``selected_``
    / ``gains_`` / ``plan_``; ``transform(X)`` returns the selected columns
    in selection order.  ``X`` is always (observations × features); the
    encoding only changes how the work is distributed, never the input
    orientation.

    Out-of-core data fits through the same front door: pass a
    :class:`repro.data.sources.DataSource` as the sole argument —
    ``fit(NpySource("X.npy", "y.npy"))`` — and the ``"streaming"`` engine
    runs the selection block-by-block with peak device memory bounded by
    ``block_obs`` rows instead of ``num_obs`` (the streaming engine always
    uses the running criterion fold; selections are identical to the
    recompute baseline for the built-in scores).

    After a fit the selector exposes the sklearn-style read side:
    ``selected_`` (ids in pick order), ``gains_`` (the per-iteration
    objective trajectory), ``scores_`` (the per-feature relevance vector;
    NaN for CustomScore fits, None for custom engines that predate the
    rich report), ``ranking_`` (1-based selection rank, unselected
    features share rank ``num_select + 1``), ``get_support()`` (boolean
    mask, or ascending indices with ``indices=True``) and ``result_``
    (the full :class:`~repro.core.mrmr.MRMRResult` report).

    Args:
      num_select: L, number of features to pick; must satisfy
        ``1 <= num_select <= num_features`` (checked at fit time).
      score: a ``ScoreFn``; None resolves from the data (discrete -> exact
        MI with inferred cardinalities, continuous -> Pearson-MI).
      criterion: the greedy objective — a registered name (``"mid"`` the
        paper's difference form, ``"miq"`` quotient, ``"maxrel"``
        relevance-only, ``"jmi"``/``"cmim"`` the class-conditioned
        objectives) or a :class:`~repro.core.criteria.Criterion`
        instance.  Orthogonal to ``encoding``: any criterion runs on any
        engine, in-memory or streaming.  Conditional criteria need a
        score with a class-conditioned decomposition (``MIScore``; pass
        ``bins=`` to discretise continuous data first).
      encoding: "auto" (paper §III rule via ``plan_selection``) or one of
        ``available_encodings()``.
      mesh: an existing device mesh to run on; None lets the planner build
        one from ``devices``.
      devices: device budget for auto-planning (int, device list, or None
        for all local devices).  Ignored when ``mesh`` is given.
      obs_axes / feat_axes: mesh axis names for observation / feature
        sharding (intersected with the mesh's axes).
      incremental: False reproduces the paper's per-iteration redundancy
        recomputation; True carries the criterion's running fold state
        (identical selections).
      block: contingency feature-block size.
      block_obs: observations per streaming block (``DataSource`` fits) —
        the peak-device-memory knob; larger blocks amortise dispatch and
        host-to-device transfer, smaller blocks cap memory.  The resolved
        ``plan_.block_obs`` records the effective size after rounding up
        to the observation-axes extent.
      prefetch: streaming fits only — host blocks read, padded and placed
        ahead of device accumulation on a background thread (double
        buffering); 0 restores the synchronous placer and the ``"auto"``
        default resolves per backend (off on CPU, where the staging
        thread measurably loses to async dispatch; 2 elsewhere — see
        :func:`repro.dist.streaming.resolve_prefetch`).
      batch_candidates: streaming fits only — redundancy vectors
        speculated per pass (``q``).  Each redundancy pass scores the
        needed column plus the top ``q-1`` remaining candidates in one
        sweep, cutting ``num_select=L`` from ``L-1`` redundancy passes
        toward ``⌈(L-1)/q⌉`` at ``q×`` the statistics memory.
        Selections are bitwise-identical to the default ``q=1``.
      spill_dir: streaming fits only — directory for the encoded-block
        spill cache (:class:`repro.data.block_cache.BlockCacheSource`).
        Pass 1 spills each parsed/encoded block as compact ``.npy``
        chunks; passes 2..L replay them memmapped, so CSV parse and bin
        encode are paid once per dataset instead of once per pass.
      readahead: streaming fits only — raw blocks the cross-pass reader
        streams ahead of the consumer, across pass boundaries, hiding
        each pass's cold-start I/O bubble (0 = off; supersedes
        ``prefetch`` when positive).
      hosts: streaming fits only — run the fit across this many
        ``jax.distributed`` processes (``"auto"`` = ``jax.process_count()``
        after :func:`repro.dist.init_multihost`).  The §III rule then
        applies across *hosts*: each process reads only its block/column
        ranges and per-pass statistics merge with explicit collectives;
        every host returns the identical result.  Per-host devices still
        shard each local block over ``obs_axes``; device feature-sharding
        is disabled under multi-host so cross-host state shapes align.
        ``None``/1 keeps today's single-process behaviour.
      bins: discretise continuous features on the fly into this many
        equal-frequency bins (one streaming quantile-sketch pass; see
        :mod:`repro.data.binning`), so float data runs the exact discrete
        MI path instead of the Pearson approximation.  Applies to float
        arrays and continuous ``DataSource``s when the score is MI (or
        auto); discrete data and explicit non-MI scores ignore it.  The
        resolved ``plan_.bins`` records what ran.

    Streamed fits follow the same §III aspect rule as in-memory plans:
    tall sources shard blocks over ``obs_axes``, wide sources shard blocks
    *and the per-pair statistics state* over ``feat_axes`` (bounding
    per-device statistics memory by ``N/shards`` pairs), and both-large
    sources run a 2-D (obs × feat) grid.  A user ``mesh`` overrides the
    rule with whatever obs/feat axes it carries.
    """

    num_select: int
    score: ScoreFn | None = None
    encoding: str = "auto"
    mesh: Mesh | None = None
    devices: object = None
    obs_axes: Sequence[str] | str = ("data",)
    feat_axes: Sequence[str] | str = ("model",)
    incremental: bool = True
    block: int = 64
    block_obs: int = 65536
    prefetch: int | str = "auto"
    # appended after the pre-1.2 fields so positional construction keeps
    # its old meaning
    criterion: Criterion | str = "mid"
    bins: int | None = None
    batch_candidates: int = 1
    spill_dir: str | None = None
    spill_budget_bytes: int | None = None
    readahead: int = 0
    hosts: int | str | None = None

    selected_: np.ndarray | None = None
    gains_: np.ndarray | None = None
    scores_: np.ndarray | None = None
    ranking_: np.ndarray | None = None
    result_: MRMRResult | None = None
    n_features_in_: int | None = None
    plan_: SelectionPlan | None = None
    mesh_: Mesh | None = None

    def _resolve_score(self, X: Array, y: Array) -> ScoreFn:
        if self.score is not None:
            return self.score
        discrete = (
            jnp.issubdtype(X.dtype, jnp.integer) or X.dtype == jnp.bool_
        )
        if discrete:
            if int(jnp.min(X)) < 0 or int(jnp.min(y)) < 0:
                # One-hot contingency rows for negative categories are
                # all-zero, so those observations would silently vanish
                # from the MI counts — fail instead of scoring wrong.
                raise ValueError(
                    "negative category values in discrete data: one-hot "
                    "contingency counts drop them silently; remap "
                    "categories to 0..K-1 before fitting"
                )
            return MIScore(
                num_values=int(jnp.max(X)) + 1,
                num_classes=int(jnp.max(y)) + 1,
            )
        return PearsonMIScore()

    def _resolve_plan(self, shape: tuple, score: ScoreFn) -> SelectionPlan:
        if self.encoding == "auto":
            devices = self.mesh if self.mesh is not None else self.devices
            return plan_selection(
                shape, devices, score,
                obs_axes=self.obs_axes, feat_axes=self.feat_axes,
                incremental=self.incremental, block=self.block,
                criterion=self.criterion,
            )
        obs = _axes_tuple(self.obs_axes)
        feat = _axes_tuple(self.feat_axes)
        if self.mesh is not None:
            obs = tuple(a for a in obs if a in self.mesh.shape)
            feat = tuple(a for a in feat if a in self.mesh.shape)
        axes = {
            "reference": ((), ()),
            "conventional": (obs, ()),
            "alternative": ((), feat),
            "grid": (obs, feat),
        }.get(self.encoding, (obs, feat))
        if self.mesh is not None:
            shape_of = tuple(self.mesh.shape[a] for a in axes[0] + axes[1])
        else:
            # No mesh given: build one from the device budget, so an
            # explicitly requested encoding still scales out.
            n_dev = _device_count(self.devices)
            m, n = shape
            if self.encoding == "grid":
                # Degenerate 1x1 grid on a single device: the encoding
                # always runs rather than erroring on small hosts.
                axes = (axes[0][:1] or ("data",), axes[1][:1] or ("model",))
                shape_of = (
                    factor_mesh(n_dev, bias=max(m / max(n, 1), 1e-6))
                    if n_dev > 1
                    else (1, 1)
                )
            elif n_dev <= 1 or self.encoding == "reference":
                axes, shape_of = ((), ()), ()
            elif self.encoding == "conventional":
                axes = (axes[0][:1] or ("data",), ())
                shape_of = (n_dev,)
            elif self.encoding == "alternative":
                axes = ((), axes[1][:1] or ("model",))
                shape_of = (n_dev,)
            else:  # custom-registered engine: runs unsharded unless a
                shape_of = ()  # mesh is passed in explicitly

        return SelectionPlan(
            encoding=self.encoding, obs_axes=axes[0], feat_axes=axes[1],
            mesh_shape=shape_of, block=self.block,
            incremental=self.incremental, score=score,
            criterion=resolve_criterion(self.criterion),
        )

    def _resolve_mesh(self, plan: SelectionPlan) -> Mesh | None:
        if self.mesh is not None:
            return self.mesh if plan.mesh_axes else None
        if not plan.mesh_shape:
            return None
        devices = self.devices if not isinstance(self.devices, int) else None
        if getattr(plan, "hosts", 1) > 1 and devices is None:
            # Multi-host: the per-host block mesh is LOCAL — jax.devices()
            # spans every process under jax.distributed, and a mesh over
            # non-addressable devices cannot place host blocks.
            devices = jax.local_devices()
        return make_mesh(plan.mesh_shape, plan.mesh_axes, devices=devices)

    def _resolve_source_score(self, source: DataSource) -> ScoreFn:
        if self.score is not None:
            return self.score
        # the scan honours the memory knob
        return score_of_stats(source.stats(self.block_obs))

    def _continuous_mi_error(self, what: str) -> ValueError:
        return ValueError(
            f"MIScore needs discrete categories but {what} holds continuous "
            "values: pass bins= to quantile-discretise on the fly — "
            "MRMRSelector(num_select=..., bins=32) — or score with "
            "PearsonMIScore()"
        )

    def _maybe_bin_source(self, source: DataSource) -> DataSource:
        """Wrap a continuous source for on-the-fly discretisation when
        ``bins=`` is set and the fit is headed down the discrete MI path
        (score None or MI).  Discrete sources and explicit non-MI scores
        pass through untouched."""
        if self.bins is None or isinstance(source, BinnedSource):
            return source
        if self.score is not None and not isinstance(self.score, MIScore):
            return source  # Pearson/custom consume continuous data natively
        if self._source_is_discrete(source):
            return source
        return BinnedSource(source, self.bins, fit_block_obs=self.block_obs)

    def _source_is_discrete(self, source: DataSource) -> bool:
        """Discrete-vs-continuous routing, free when the source's
        ``feature_dtype`` is statically known (no ``iter_blocks`` pass —
        the maxrel path's single-pass I/O promise depends on this)."""
        dt = source.feature_dtype
        if dt is not None:
            return not np.issubdtype(dt, np.floating)
        return source.stats(self.block_obs).discrete

    def _bin_score(self, binned: BinnedSource) -> ScoreFn:
        """Score for a binned fit: auto-sized MI, or the user's MIScore
        checked against the code range (codes land in [0, bins))."""
        if self.score is None:
            return MIScore(
                num_values=binned.bins,
                num_classes=binned.stats().num_classes,
            )
        if isinstance(self.score, MIScore) and self.score.num_values < binned.bins:
            raise ValueError(
                f"score num_values={self.score.num_values} < bins="
                f"{binned.bins}: bin codes in [0, {binned.bins}) would "
                "one-hot to all-zero rows and vanish from the counts; "
                "drop the explicit score or set num_values >= bins"
            )
        return self.score

    def _resolve_hosts(self) -> int:
        """The multi-host process count: ``None``/1 single-host, ``"auto"``
        whatever ``jax.distributed`` reports, an int taken at face value
        (mismatches against the actual cluster fail in the collectives)."""
        if self.hosts in (None, 1):
            return 1
        if self.hosts == "auto":
            return int(jax.process_count())
        h = int(self.hosts)
        if h < 1:
            raise ValueError(f"hosts must be >= 1 or 'auto', got {self.hosts!r}")
        return h

    def _resolve_stream_plan(
        self, source: DataSource, score: ScoreFn
    ) -> SelectionPlan:
        """Streaming layout per the paper's §III aspect-ratio rule: tall
        shards blocks over observations, wide shards blocks AND statistics
        over features, both-large runs a 2-D (obs × feat) grid.  A user
        mesh overrides the rule: whatever obs/feat axes it carries are
        used (both present -> 2-D).

        With ``hosts > 1`` the §III rule is applied across *processes*
        (see :func:`repro.dist.multihost.resolve_host_shards`); the
        device layout here is then per-host — blocks shard over this
        host's LOCAL devices on the observation axes only, since device
        feature-sharding would pad the statistics width past the exact
        shard width and break cross-host state alignment."""
        m, n = source.num_obs, source.num_features
        aspect = m / max(n, 1)
        obs = _axes_tuple(self.obs_axes)
        feat = _axes_tuple(self.feat_axes)
        hosts = self._resolve_hosts()
        if hosts > 1:
            if self.mesh is not None:
                raise ValueError(
                    "hosts > 1 plans the per-host device mesh from local "
                    "devices; pass devices= instead of mesh="
                )
            n_dev = (
                len(jax.local_devices())
                if self.devices is None
                else _device_count(self.devices)
            )
            if n_dev <= 1:
                obs, feat, shape = (), (), ()
            else:
                obs, feat, shape = obs[:1] or ("data",), (), (n_dev,)
            block_obs = effective_block_obs(
                self.block_obs, math.prod(shape) if obs else 1
            )
            q = int(self.batch_candidates)
            if q < 1:
                raise ValueError(f"batch_candidates must be >= 1, got {q}")
            if int(self.readahead) < 0:
                raise ValueError(
                    f"readahead must be >= 0, got {self.readahead}"
                )
            return SelectionPlan(
                encoding="streaming", obs_axes=obs, feat_axes=feat,
                mesh_shape=shape, block=self.block, block_obs=block_obs,
                incremental=True, prefetch=resolve_prefetch(self.prefetch),
                score=score, criterion=resolve_criterion(self.criterion),
                batch_candidates=q, spill_dir=self.spill_dir,
                spill_budget_bytes=self.spill_budget_bytes,
                readahead=int(self.readahead), hosts=hosts,
            )
        if self.mesh is not None:
            obs = tuple(a for a in obs if a in self.mesh.shape)
            feat = tuple(a for a in feat if a in self.mesh.shape)
            if not obs and not feat:
                # Silently running unsharded on a user-supplied mesh would
                # betray the device budget; streaming has no fallback
                # engine to reroute to, so fail loudly.
                raise ValueError(
                    f"mesh axes {tuple(self.mesh.shape)} share no axis with "
                    f"obs_axes {_axes_tuple(self.obs_axes)} or feat_axes "
                    f"{_axes_tuple(self.feat_axes)}; streaming shards "
                    "blocks over observation and/or feature axes"
                )
            shape = tuple(self.mesh.shape[a] for a in obs + feat)
        else:
            n_dev = _device_count(self.devices)
            if n_dev <= 1:
                obs, feat, shape = (), (), ()
            elif aspect >= TALL_RATIO:
                obs, feat, shape = obs[:1] or ("data",), (), (n_dev,)
            elif aspect <= WIDE_RATIO:
                obs, feat, shape = (), feat[:1] or ("model",), (n_dev,)
            else:
                gf = _grid_factor(m, n, n_dev)
                if gf is not None:
                    obs = obs[:1] or ("data",)
                    feat = feat[:1] or ("model",)
                    shape = gf
                elif aspect >= 1.0:
                    obs, feat, shape = obs[:1] or ("data",), (), (n_dev,)
                else:
                    obs, feat, shape = (), feat[:1] or ("model",), (n_dev,)
        # Record the EFFECTIVE block size: the placer rounds blocks up to
        # the observation extent, and plan_ must report what actually runs
        # (same rule, one implementation).
        block_obs = effective_block_obs(
            self.block_obs, math.prod(shape[: len(obs)]) if obs else 1
        )
        q = int(self.batch_candidates)
        if q < 1:
            raise ValueError(f"batch_candidates must be >= 1, got {q}")
        if int(self.readahead) < 0:
            raise ValueError(
                f"readahead must be >= 0, got {self.readahead}"
            )
        # Streaming always uses the running criterion fold: the recompute
        # baseline would multiply the number of passes over the data by L.
        # prefetch resolves here ("auto" -> backend heuristic) so plan_
        # records the int that actually ran, like effective block_obs.
        return SelectionPlan(
            encoding="streaming", obs_axes=obs, feat_axes=feat,
            mesh_shape=shape, block=self.block, block_obs=block_obs,
            incremental=True, prefetch=resolve_prefetch(self.prefetch),
            score=score, criterion=resolve_criterion(self.criterion),
            batch_candidates=q, spill_dir=self.spill_dir,
            spill_budget_bytes=self.spill_budget_bytes,
            readahead=int(self.readahead),
        )

    def _finish_fit(
        self, res: MRMRResult, plan: SelectionPlan, mesh: Mesh | None,
        n_features: int,
    ) -> "MRMRSelector":
        """Populate the read side from an engine's result (every fit path)."""
        # Custom-registered engines may omit provenance: backfill both the
        # engine and the criterion from the plan that drove the fit.
        if not res.engine:
            res = dataclasses.replace(res, engine=plan.encoding)
        if not res.criterion:
            res = dataclasses.replace(
                res, criterion=resolve_criterion(plan.criterion).name
            )
        self.selected_ = np.asarray(res.selected)
        self.gains_ = np.asarray(res.gains)
        self.scores_ = (
            None if res.relevance is None else np.asarray(res.relevance)
        )
        ranking = np.full((n_features,), len(self.selected_) + 1, np.int32)
        ranking[self.selected_] = np.arange(1, len(self.selected_) + 1)
        self.ranking_ = ranking
        self.n_features_in_ = int(n_features)
        self.result_ = res
        self.plan_ = plan
        self.mesh_ = mesh
        return self

    def get_support(self, indices: bool = False) -> np.ndarray:
        """Selected-feature mask (or ascending indices), sklearn-style.

        ``indices=False`` returns a ``(num_features,)`` boolean mask;
        ``indices=True`` the selected ids in ASCENDING order (use
        ``selected_`` for selection order).
        """
        if self.selected_ is None or self.n_features_in_ is None:
            raise RuntimeError("fit() first")
        mask = np.zeros((self.n_features_in_,), bool)
        mask[self.selected_] = True
        return np.flatnonzero(mask) if indices else mask

    def _fit_source(self, source: DataSource) -> "MRMRSelector":
        with tracing.fit_span() as fit:
            with tracing.span(tracing.PLAN, fit=fit):
                source, plan, mesh = self._plan_source(source)
            engine = get_engine("streaming")
            res = engine(source, None, num_select=self.num_select, plan=plan,
                         mesh=mesh)
            if plan.score is None:
                # The engine sized the default score and left its stats on
                # the source: this reads them back without I/O.
                plan = dataclasses.replace(
                    plan, score=self._resolve_source_score(source)
                )
            return self._finish_fit(res, plan, mesh, source.num_features)

    def _plan_source(self, source: DataSource):
        """-> ``(source, plan, mesh)`` of a source fit: the source as the
        engine streams it (binned where asked), its score, plan and mesh."""
        if self.encoding not in ("auto", "streaming"):
            raise ValueError(
                f"encoding {self.encoding!r} needs in-memory arrays; "
                "DataSource inputs run the 'streaming' engine "
                "(materialise the source yourself to force another engine)"
            )
        check_num_select(self.num_select, source.num_features)
        source = self._maybe_bin_source(source)
        if isinstance(source, BinnedSource):
            score = self._bin_score(source)
        elif self.score is None and needs_category_scan(source):
            # Exact MI whose category counts nothing has read yet: the
            # engine takes them from the blocks it keeps on the device, or
            # scans where it streams.  An MIScore passes every check below.
            score = None
        else:
            score = self._resolve_source_score(source)
            if isinstance(score, MIScore) and not self._source_is_discrete(
                source
            ):
                # Explicit MI on float blocks would silently truncate to
                # int32 inside the one-hot encode — fail actionably here.
                raise self._continuous_mi_error("the source")
        # Conditional criteria (jmi/cmim) need a score with a class-
        # conditioned decomposition — fail before the first I/O pass.
        if score is not None:
            mrmr_mod.check_conditional_support(
                score, resolve_criterion(self.criterion)
            )
        plan = self._resolve_stream_plan(source, score)
        if isinstance(source, BinnedSource):
            plan = dataclasses.replace(plan, bins=source.bins)
        return source, plan, self._resolve_mesh(plan)

    def fit(self, X, y=None) -> "MRMRSelector":
        """X: (observations, features) array + y: (observations,) targets,
        or a ``DataSource`` alone (targets come from its blocks)."""
        if (
            not isinstance(X, DataSource)
            and self.encoding == "streaming"
            and y is not None
        ):
            # Arrays through the streaming engine: wrap in the adapter so
            # one code path owns the block walk.
            X, y = ArraySource(X, y), None
        if isinstance(X, DataSource):
            if y is not None:
                raise ValueError(
                    "y comes from the DataSource; call fit(source) alone"
                )
            return self._fit_source(X)
        if y is None:
            raise ValueError(
                "y is required for array inputs (only DataSource fits "
                "carry their own targets)"
            )
        if self._resolve_hosts() > 1:
            raise ValueError(
                "hosts > 1 runs the streaming engine: pass a DataSource, "
                "or arrays with encoding='streaming'"
            )
        X = jnp.asarray(X)
        y = jnp.asarray(y)
        if X.ndim != 2 or y.shape[0] != X.shape[0]:
            raise ValueError(f"bad shapes X{X.shape} y{y.shape}")
        check_num_select(self.num_select, X.shape[1])
        discrete_X = bool(
            jnp.issubdtype(X.dtype, jnp.integer) or X.dtype == jnp.bool_
        )
        plan_bins = None
        if (
            self.bins is not None
            and not discrete_X
            and (self.score is None or isinstance(self.score, MIScore))
        ):
            # In-memory binned fit: one sketch pass over the wrapped array,
            # then the discrete engines consume the int codes — same edges
            # (and hence same selection) as the streaming path.
            binned = BinnedSource(
                ArraySource(np.asarray(X), np.asarray(y)),
                self.bins,
                fit_block_obs=self.block_obs,
            )
            score = self._bin_score(binned)
            codes, labels = binned.materialize(self.block_obs)
            X, y = jnp.asarray(codes), jnp.asarray(labels)
            plan_bins = binned.bins
        else:
            score = self._resolve_score(X, y)
            if isinstance(score, MIScore) and not discrete_X:
                # The conventional engine would silently astype(int32) the
                # float columns — truncated categories, wrong MI.
                raise self._continuous_mi_error("X")
        # Discrete MI scores need integral class labels; every other score
        # (Pearson, custom) keeps continuous targets intact.
        y = y.astype(jnp.int32 if isinstance(score, MIScore) else jnp.float32)
        # Conditional criteria (jmi/cmim) need a score with a class-
        # conditioned decomposition — fail before planning/compiling.
        mrmr_mod.check_conditional_support(
            score, resolve_criterion(self.criterion)
        )
        plan = self._resolve_plan(X.shape, score)
        if plan.score is None:
            plan = dataclasses.replace(plan, score=score)
        if plan_bins is not None:
            plan = dataclasses.replace(plan, bins=plan_bins)
        mesh = self._resolve_mesh(plan)
        engine = get_engine(plan.encoding)
        res = engine(X, y, num_select=self.num_select, plan=plan, mesh=mesh)
        return self._finish_fit(res, plan, mesh, X.shape[1])

    def transform(self, X):
        """Selected columns of ``X``, ordered by selection rank.

        Accepts a ``DataSource`` too: blocks stream through and only the
        ``(num_obs, num_select)`` result materialises."""
        if self.selected_ is None:
            raise RuntimeError("fit() first")
        if isinstance(X, DataSource):
            return np.concatenate(
                [blk[:, self.selected_]
                 for blk, _ in X.iter_blocks(self.block_obs)]
            )
        return np.asarray(X)[:, self.selected_]

    def fit_transform(self, X, y=None):
        return self.fit(X, y).transform(X)


__all__ = [
    "MRMRSelector",
    "SelectionPlan",
    "check_num_select",
    "plan_selection",
    "register_engine",
    "score_of_stats",
    "get_engine",
    "available_encodings",
    "build_engine_fn",
]
