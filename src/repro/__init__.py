"""repro — distributed mRMR feature selection (Reggiani et al., 2017) in JAX.

A production-grade JAX framework reproducing and extending
"Feature selection in high-dimensional dataset using MapReduce".

Quickstart
----------

One front door, ``MRMRSelector`` — inputs are always (observations ×
features); the distribution strategy is planned from the dataset's aspect
ratio and the available devices (paper §III: tall/narrow -> observation
sharding, wide/short -> feature sharding, both-large -> 2-D grid)::

    from repro import MRMRSelector
    from repro.data.synthetic import corral_dataset

    X, y = corral_dataset(20_000, 64, seed=0)
    sel = MRMRSelector(num_select=10).fit(X, y)
    print(sel.selected_)        # feature ids, in selection order
    print(sel.plan_)            # the resolved SelectionPlan
    X_small = sel.transform(X)  # selected columns, selection order

Force an encoding or a mesh instead of auto-planning::

    from repro.dist import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    sel = MRMRSelector(num_select=10, encoding="grid", mesh=mesh).fit(X, y)

Out-of-core data — the paper's actual regime — fits from disk in 4 lines.
A ``DataSource`` streams observation-blocks (memmapped ``.npy``, CSV, or
the synthetic generators) and the ``streaming`` engine accumulates each
score's sufficient statistics block-by-block, so peak device memory is
``block_obs × num_features``, never ``num_obs × num_features``::

    from repro.data.sources import NpySource

    source = NpySource("X.npy", "y.npy")   # memmapped, never loaded whole
    sel = MRMRSelector(num_select=10, block_obs=65_536).fit(source)
    X_small = sel.transform(source)        # also streams

``block_obs`` is the memory/throughput dial: larger blocks amortise
per-block dispatch and host-to-device transfer (faster, more device
memory), smaller blocks cap memory for a fixed ~``L`` passes of I/O over
the source.  Selections are identical to the in-memory engines at every
block size.

Streamed fits follow the same §III aspect rule as in-memory plans: a tall
source shards blocks over observations, a **wide** source (``m/n <=
0.25``, the bioinformatics case) shards blocks *and the per-pair
statistics state* over features — bounding per-device statistics memory
by ``N/shards`` pairs — and a both-large source runs a 2-D grid.
``prefetch`` (default ``"auto"``: off on CPU, 2 elsewhere) double-buffers
placement: a host thread reads and pads the next block while the device
accumulates the current one (``prefetch=0`` restores the synchronous
placer).

The I/O tax
-----------

A streamed fit reads the source ``L`` times — 1 relevance pass plus
``num_select - 1`` redundancy passes — and at production scale that pass
count, not FLOPs, is the wall-clock story.  Three composable knobs attack
it; under every combination selections stay **bitwise-identical** to the
plain engine (a tested invariant, so the service's result cache treats
all execution geometries of one fit as the same content)::

    sel = MRMRSelector(
        num_select=32,
        batch_candidates=8,        # ~ceil(31/8) redundancy passes, not 31
        spill_dir="/tmp/spill",    # parse/encode paid once, then replay
        readahead=2,               # pass l+1 reads overlap pass l's tail
    ).fit(source)
    sel.result_.io                 # {'passes': 5, 'blocks_read': ...,
                                   #  'bytes_read': ..., 'cache': {...}}

``batch_candidates=q`` makes each redundancy pass score the needed column
plus the top ``q-1`` current candidates in one sweep (the statistics
state grows a ``q``-sized leading axis, sharded like the rest), then
commits picks with exact criterion folds — a speculated redundancy vector
is a pairwise property of the data, never invalidated by later picks.
``spill_dir=`` wraps the source in :class:`~repro.data.block_cache.
BlockCacheSource`: pass 1 spills each parsed/encoded block as compact
``.npy`` chunks (atomic rename, manifest-last, corruption-checked on
replay, LRU byte budget), passes 2..L replay them memmapped — a binned
source spills its *int codes*, so quantile-encode is also paid once.
``readahead=`` starts reading the next pass's blocks before the current
pass drains (block reads never depend on the just-picked column).  Every
streamed ``MRMRResult`` carries the measured ``io`` ledger, so the pass
math is asserted by tests and benchmarks, not eyeballed; under a
``jax.profiler`` trace each layer of the fit shows as a ``mrmr.*`` span
(:mod:`repro.runtime.tracing`).  (CLI: ``python
-m repro.launch.select --batch-candidates 8 --spill-dir /tmp/spill
--readahead 2``.)

Multi-host
----------

The paper's headline regime is *cluster* scale: MapReduce workers each
reading only their partition, one reduce merging the per-partition
statistics.  ``repro.dist.multihost`` is that layer on
``jax.distributed``: ``hosts=N`` (or ``"auto"`` under a launcher) applies
the same §III aspect rule across *processes* — tall partitions the
observation range, wide partitions the column range, both-large gets the
2-D host grid — and each host's block iteration walks ONLY its own
ranges (:meth:`~repro.data.sources.DataSource.iter_shard_blocks`), so a
host streams ``1/N`` of the bytes.  The per-pass reduce is an explicit
``shard_map``-ped psum of the exact integer statistics
(:class:`~repro.dist.multihost.HostCollectives`), after which every host
folds the criterion identically and commits the identical pick — a
genuine map-reduce with no designated master, and selections stay
**bitwise-identical** to the single-process streaming engine (a tested
invariant, including under ``spill_dir`` + ``batch_candidates``, whose
spill entries are namespaced per process)::

    # per process, after jax.distributed is up (or init_multihost()):
    from repro.dist.multihost import init_multihost
    init_multihost()                        # env-driven; idempotent
    sel = MRMRSelector(num_select=10, hosts="auto").fit(source)
    sel.result_.io["host"]                  # this host's shard ranges
    sel.result_.io["hosts"]["aggregate"]    # exact cluster-wide ledger

``python -m repro.launch.select_multihost --num-processes N ...`` spawns
an N-process loopback cluster (or joins a real one via ``--coordinator``
/ ``--process-id`` or the ``REPRO_*`` env vars) and asserts every host
committed the same selection.

Custom scores (paper §IV.D) run through the same front door::

    from repro import CustomScore
    sel = MRMRSelector(5, score=CustomScore(get_result=my_score)).fit(X, y)

Criteria
--------

The greedy *objective* is a pluggable :class:`~repro.core.criteria.
Criterion`, orthogonal to both the score function and the encoding: the
engines compute relevance/redundancy statistics and the criterion folds
them into the per-candidate objective that is argmaxed.  Built-ins:
``mid`` (the paper's difference form, Eq. 1 — the default), ``miq``
(the quotient form), ``maxrel`` (relevance only; the streaming engine
then needs a single pass of I/O), and the class-conditioned pair —
``jmi`` (joint mutual information: mean of ``I(x_k; x_j | y) -
I(x_k; x_j)`` over the selected set, added to relevance) and ``cmim``
(Fleuret's conditional MI maximisation: the *min* of those gaps — a
candidate is only as good as its most-redundant pairing).  Every
criterion runs on every engine, in-memory or streaming, and selections
agree engine-for-engine::

    sel = MRMRSelector(num_select=10, criterion="miq").fit(X, y)
    sel.result_.criterion, sel.result_.engine   # ("miq", "conventional")
    sel.scores_                                 # per-feature relevance
    sel.ranking_                                # 1-based selection rank
    sel.get_support()                           # boolean feature mask

    sel = MRMRSelector(num_select=10, criterion="jmi").fit(X, y)
    sel = MRMRSelector(num_select=10, criterion="cmim", bins=32).fit(src)

``jmi``/``cmim`` declare ``needs_conditional_redundancy = True``: each
redundancy sweep then counts the 3-way ``(x_k value, x_j value, class)``
table — the pair target fuses with the class into one code, so it is the
SAME blocked one-hot einsum (and the same Pallas kernel tiling), just
``d_c×`` wider — and both ``I(x_k; x_j)`` (class-summed) and ``I(x_k;
x_j | y)`` fall out of that one sweep.  Criteria that never ask (mid/
miq/maxrel) keep the exact pre-conditional graph: same state shapes,
same bytes (streamed fits assert it via ``result_.io["state_bytes"]``).
They need a score with a conditional decomposition — ``MIScore``, or
``bins=`` to discretise first; anything else fails actionably at fit
time.  (CLI: ``python -m repro.launch.select --criterion miq|jmi|
cmim``.)

Writing a criterion
~~~~~~~~~~~~~~~~~~~

Register your own fold with :func:`~repro.core.criteria.
register_criterion`.  A criterion is three pure-jnp hooks — ``init_state
(n)`` (per-candidate running state), ``update(state, terms, l)`` (fold
redundancy statistics of pick ``l`` in), ``objective(rel, state, l)``
(the vector that is argmaxed) — plus two declarative flags.  ``terms``
is the marginal redundancy vector, or a ``{"marginal", "conditional"}``
dict when the criterion declares ``needs_conditional_redundancy``; the
helpers accept both forms::

    from repro import Criterion, register_criterion
    from repro.core.criteria import conditional_terms, marginal_terms

    @register_criterion
    class WorstGap(Criterion):
        name = "worstgap"  # then: MRMRSelector(10, criterion="worstgap")
        needs_conditional_redundancy = True   # ask for I(x_k; x_j | y)
        def init_state(self, n): ...          # pytree of (n,) leaves
        def update(self, state, terms, l):
            gap = conditional_terms(terms) - marginal_terms(terms)
            ...                               # fold, pure jnp
        def objective(self, rel, state, l): ...

Interop
-------

``repro.interop.sklearn`` adapts the selector to scikit-learn's
composition machinery (soft dependency — the import tells you to
install sklearn if missing)::

    from repro.interop.sklearn import MRMRTransformer
    from sklearn.pipeline import make_pipeline

    pipe = make_pipeline(
        MRMRTransformer(num_select=10, criterion="jmi", bins=32), clf
    )
    pipe.fit(X, y)          # SelectorMixin: get_support / transform
    GridSearchCV(pipe, {"mrmrtransformer__num_select": [5, 10, 20]})

Columnar data streams natively (soft-gated on pyarrow):
:class:`~repro.data.sources.ParquetSource` decodes Parquet row batches
block-by-block from the file's row groups (geometry from the footer, no
data read before the first pass) and :class:`~repro.data.sources.
ArrowSource` wraps an in-memory Arrow table; both compose with
``bins=``, ``spill_dir=`` and the rest of the streaming stack.  (CLI:
``python -m repro.launch.select --input data.parquet``.)

Binning
-------

MI scoring is discrete, but most numeric-tabular data is continuous.
``bins=`` discretises on the fly at streaming scale: one cheap pass
accumulates a mergeable per-feature quantile sketch
(:class:`~repro.data.binning.QuantileSketch` — KLL-style bounded buffers,
``merge()``-able across blocks and shards), ``bins - 1`` equal-frequency
edges are cut from it, and every subsequent block encodes to int codes in
``[0, bins)`` on the way into the contingency sums — on the device, fused
with the accumulate (Pallas searchsorted kernel on TPU), so raw float
blocks never round-trip through host memory as codes::

    sel = MRMRSelector(num_select=10, bins=32).fit(source)   # float source
    sel = MRMRSelector(num_select=10, bins=32).fit(X, y)     # float array
    sel.plan_.bins                                           # 32

Selections agree between the in-memory and streaming paths at every block
size (the sketch compacts at exact capacity boundaries, so the edges are
a pure function of the row stream).  Wrap explicitly with
:class:`~repro.data.binning.BinnedSource` to reuse one fitted binner; its
``fingerprint()`` derives from the base source's fingerprint × the bin
config, so the service's result cache distinguishes ``bins=16`` from
``bins=64`` for free, and fitted binners are memoised per fingerprint
(repeat submissions never re-sketch).  A float input headed down the MI
path *without* ``bins=`` fails at fit time with a pointer here instead of
scoring truncated categories.  (CLI: ``python -m repro.launch.select
--input floats.csv --bins 32``.)

Service
-------

Selection-as-a-service: :class:`~repro.serve.selection.SelectionService`
runs fits as managed jobs behind a bounded work queue, a worker pool, a
content-addressed result cache and idempotency-key request coalescing.
Identical requests (same source *content*, score, criterion and
``num_select`` — execution geometry like ``block_obs`` deliberately
excluded) share one cache line; a stampede of identical in-flight
submissions runs the engine exactly once; a full queue rejects with
``Backpressure(retry_after_s=...)`` instead of blocking::

    from repro.serve import SelectionService

    with SelectionService(workers=2, cache_dir="/tmp/selcache") as svc:
        job = svc.submit("X.npy::y.npy", num_select=10)
        result = svc.result(job)     # blocks; MRMRResult
        again = svc.submit("X.npy::y.npy", num_select=10)
        svc.poll(again).cache_hit    # True — zero engine or I/O passes
        svc.stats()                  # queue / coalescing / cache counters

The cache is backed by every ``DataSource``'s ``fingerprint()`` (content
hash for in-memory arrays, ``(path, size, mtime)`` for file-backed
sources, generator params for synthetics) — the same fingerprint that
memoises repeated ``stats()`` scans.  ``MRMRResult.to_json()`` /
``from_json()`` round-trip results for the persistent cache and the
``--output`` flag of ``python -m repro.launch.select``; transient worker
failures retry with exponential backoff
(:func:`~repro.runtime.resilience.retry_with_backoff`).  (CLI demo:
``python -m repro.launch.serve_select --repeat 2 --distinct-select 3``.)

Layers
------

* ``repro.core``    — the paper's contribution: ``MRMRSelector`` /
  ``SelectionPlan`` / ``plan_selection`` on top of the five drivers
  (reference, conventional, alternative, grid, streaming) in an open
  engine registry; pluggable feature-score functions AND pluggable
  selection criteria (``repro.core.criteria``); incremental fold
  optimisation.
* ``repro.data``    — data sources (arrays, ``.npy``, CSV, Parquet,
  CorrAL), the spill cache and quantile binning.
* ``repro.dist``    — the distribution substrate: named meshes, block
  placement and device-resident blocks for streamed fits
  (``repro.dist.streaming``), multi-host map-reduce
  (``repro.dist.multihost``).
* ``repro.kernels`` — Pallas TPU kernels for the scoring hot spots
  (contingency counts, MI score, binning, Pearson), their dispatch and
  their jnp oracle.
* ``repro.runtime`` — compile cache, profiler spans, retry, checkpoints.
* ``repro.interop`` — the scikit-learn adapter (``MRMRTransformer``).
* ``repro.serve``   — selection-as-a-service: job manager, coalescing
  work queue, content-addressed result cache.
* ``repro.launch``  — CLIs (``python -m repro.launch.select`` runs
  selection end-to-end, ``python -m repro.launch.select_multihost`` runs
  it across processes, ``python -m repro.launch.serve_select`` drives the
  service).
"""

from repro.core import (  # noqa: F401
    CIFECriterion,
    CMIMCriterion,
    Criterion,
    CustomScore,
    FeatureSelector,
    ICAPCriterion,
    JMICriterion,
    MIDCriterion,
    MIFSCriterion,
    MIQCriterion,
    MIScore,
    MRMRResult,
    MRMRSelector,
    MaxRelCriterion,
    PearsonMIScore,
    ScoreFn,
    SelectionPlan,
    available_criteria,
    available_encodings,
    mrmr_select,
    plan_selection,
    register_criterion,
    register_engine,
)

__version__ = "1.6.0"

__all__ = [
    "CIFECriterion",
    "CMIMCriterion",
    "Criterion",
    "CustomScore",
    "FeatureSelector",
    "ICAPCriterion",
    "JMICriterion",
    "MIDCriterion",
    "MIFSCriterion",
    "MIQCriterion",
    "MIScore",
    "MRMRResult",
    "MRMRSelector",
    "MaxRelCriterion",
    "PearsonMIScore",
    "ScoreFn",
    "SelectionPlan",
    "available_criteria",
    "available_encodings",
    "mrmr_select",
    "plan_selection",
    "register_criterion",
    "register_engine",
    "__version__",
]
