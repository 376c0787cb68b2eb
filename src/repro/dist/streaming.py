"""Observation-block placement for the streaming engine.

The streaming fit path moves host blocks onto devices one at a time; this
module owns that placement the same way ``repro.core.selector`` owns it
for in-memory fits.  ``BlockPlacer`` pads every incoming block to one
fixed row count (so the engine's accumulate step compiles exactly once)
and, given a mesh, lands the block sharded per the plan's regime:

* **obs-sharded** (tall datasets) — rows split over ``obs_axes``, each
  device accumulating statistics for every feature on its row slice; the
  engine's ``shard_map``-ped accumulate sums them with one ``psum`` per block.
* **feature-sharded** (wide datasets) — columns split over ``feat_axes``
  and the *statistics state itself* lives sharded over features
  (``place_state`` / ``state_shardings``), so per-device statistics memory
  is ``O(N/shards · d_v · d_c)`` instead of the full per-pair state.
* **2-D grid** — both at once: rows over ``obs_axes``, columns and state
  over ``feat_axes``; each device counts its (rows, columns) tile and the
  counts reduce over the observation axes only.

Padded rows are reported through a ``valid`` mask; what a score does with
it (out-of-range categories, zero-weighted moments) is the score's
business.  Padded feature columns produce junk statistics rows that the
engine slices off after ``finalize``.

``PrefetchPlacer`` is the double-buffered face of the same placement: a
bounded host thread reads and pads block ``i+1`` while the consumer
places (async ``device_put``) and the device accumulates block ``i``, so
streaming throughput approaches the device-bound in-memory rate instead
of serialising source I/O with placement.

``CrossPassReader`` extends the same overlap across *pass boundaries*:
the streaming engine visits the source once per selection, and between
passes the synchronous path stalls — finalize, host argmax, then pass
``l+1`` starts reading from byte zero.  But block *reads* never depend
on the just-picked column (only the pass-target extraction does, and
that is a cheap host slice at consume time), so a reader thread can keep
streaming blocks of pass ``l+1`` while the device finishes pass ``l``.

Batched redundancy passes (``batch_candidates > 1``) reuse all of this
unchanged except for a leading candidate axis: targets become ``(q, B)``
and statistics leaves ``(q, N, ...)`` — ``stage``/``place`` and
``state_shardings`` recognise both layouts.
"""

from __future__ import annotations

import dataclasses
import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist.sharding import axes_tuple, mesh_extent

# End-of-stream sentinel for the prefetch queue.
_DONE = object()

# End-of-pass sentinel for the cross-pass read-ahead queue.
_PASS_END = object()


def resolve_prefetch(prefetch, backend: str | None = None) -> int:
    """Resolve the ``prefetch`` knob: an int passes through, ``"auto"``
    applies the measured heuristic.

    Heuristic: the staging thread only pays off when placement blocks the
    consumer — i.e. on backends with *blocking* host-to-device transfers
    (GPU/TPU), where overlapping the numpy stage with the transfer hides
    real latency.  On the CPU backend ``device_put`` and the accumulate
    dispatch are already asynchronous, so the synchronous placer never
    stalls and the extra thread only buys queue handoffs plus GIL/
    threadpool contention with XLA's own workers — measured ~15% *slower*
    (``BENCH_streaming.json``: streaming@16384+pf2 755k rows/s vs pf0's
    881k on the 200k x 256 case).  So ``"auto"`` = 0 on CPU, 2 elsewhere.
    """
    if prefetch != "auto":
        try:
            p = int(prefetch)
        except (TypeError, ValueError):
            raise ValueError(
                f"prefetch must be an int >= 0 or 'auto', got {prefetch!r}"
            ) from None
        if p < 0:
            raise ValueError(f"prefetch must be >= 0 or 'auto', got {p}")
        return p
    if backend is None:
        import jax  # local: keep module importable pre-XLA-init

        backend = jax.default_backend()
    return 0 if backend == "cpu" else 2


def effective_block_obs(block_obs: int, obs_extent: int) -> int:
    """The placer's one block-rounding rule — blocks round UP to a
    multiple of the observation-axes extent so every shard gets equal
    rows.  Shared with ``MRMRSelector._resolve_stream_plan`` so
    ``plan_.block_obs`` always reports exactly what the placer runs."""
    ext = max(int(obs_extent), 1)
    return -(-int(block_obs) // ext) * ext


@dataclasses.dataclass
class BlockPlacer:
    """Pad-and-place for observation blocks.

    Args:
      block_obs: requested rows per block; rounded UP to a multiple of the
        observation-axes extent so every shard gets equal rows.
      mesh: device mesh, or None for single-device placement.
      obs_axes: mesh axes to shard observations over (intersected with the
        mesh's axes).
      feat_axes: mesh axes to shard features — and the statistics state —
        over (intersected with the mesh's axes).
      num_features: global feature count; required for feature sharding,
        where columns are padded up to a multiple of the feature-axes
        extent (``padded_features``) so every shard gets equal columns.
    """

    block_obs: int
    mesh: Mesh | None = None
    obs_axes: tuple = ()
    feat_axes: tuple = ()
    num_features: int | None = None

    def __post_init__(self):
        obs = axes_tuple(self.obs_axes)
        feat = axes_tuple(self.feat_axes)
        if self.mesh is not None:
            obs = tuple(a for a in obs if a in self.mesh.shape)
            feat = tuple(a for a in feat if a in self.mesh.shape)
            if not obs and not feat:
                # A mesh the blocks can't shard over would silently run
                # single-device against the caller's device budget — guard
                # here so the direct engine API fails like the selector.
                raise ValueError(
                    f"mesh axes {tuple(self.mesh.shape)} share no axis "
                    f"with obs_axes {axes_tuple(self.obs_axes)} or "
                    f"feat_axes {axes_tuple(self.feat_axes)}"
                )
            if feat and self.num_features is None:
                # Without the global feature count the placer can neither
                # pad columns to the shard extent nor shard the statistics
                # state — feature sharding would fail late (opaque
                # device_put error) or silently replicate the state it
                # exists to split.
                raise ValueError(
                    "feature sharding requires num_features "
                    f"(feat_axes={feat} on mesh {tuple(self.mesh.shape)})"
                )
        self.obs_axes, self.feat_axes = obs, feat
        oext = mesh_extent(self.mesh, obs)
        fext = mesh_extent(self.mesh, feat)
        self.block_obs = effective_block_obs(self.block_obs, oext)
        self._feat_pad = (
            -(-int(self.num_features) // fext) * fext
            if self.num_features is not None
            else None
        )
        if self.mesh is not None:
            ospec = obs if obs else None
            fspec = feat if feat else None
            self._shard_mat = NamedSharding(self.mesh, P(ospec, fspec))
            self._shard_vec = NamedSharding(self.mesh, P(ospec))
            self._shard_tgt2 = NamedSharding(self.mesh, P(None, ospec))
        else:
            self._shard_mat = self._shard_vec = self._shard_tgt2 = None

    @property
    def padded_features(self) -> int:
        """Feature count after padding to the feature-axes extent."""
        if self._feat_pad is None:
            raise ValueError("BlockPlacer was built without num_features")
        return self._feat_pad

    # -- statistics-state placement -------------------------------------

    def state_shardings(self, state):
        """Shardings for a statistics pytree (None when there is no mesh):
        leaves with a ``padded_features`` dim in position 0 — or position 1
        behind a leading candidate-batch axis (batched redundancy passes
        carry ``(q, N, ...)`` statistics) — shard over ``feat_axes``;
        everything else (scalars, running counts) is replicated.  Used both
        to place the initial state and as the accumulate step's
        ``out_shardings``, pinning the state layout so per-device
        statistics memory scales with ``1/feature-shards``."""
        if self.mesh is None:
            return None

        def sh(leaf):
            leaf = jnp.asarray(leaf)
            if self.feat_axes and self._feat_pad is not None:
                if leaf.ndim >= 1 and leaf.shape[0] == self._feat_pad:
                    spec = P(self.feat_axes, *([None] * (leaf.ndim - 1)))
                    return NamedSharding(self.mesh, spec)
                if leaf.ndim >= 2 and leaf.shape[1] == self._feat_pad:
                    # (q, N, ...) batched statistics: replicate the small
                    # candidate axis, split the feature axis as usual.
                    spec = P(None, self.feat_axes, *([None] * (leaf.ndim - 2)))
                    return NamedSharding(self.mesh, spec)
            return NamedSharding(self.mesh, P())

        return jax.tree.map(sh, state)

    def place_state(self, state):
        """Land a freshly initialised statistics pytree per
        :meth:`state_shardings` (identity without a mesh)."""
        shardings = self.state_shardings(state)
        if shardings is None:
            return jax.tree.map(jnp.asarray, state)
        return jax.tree.map(
            lambda leaf, s: jax.device_put(jnp.asarray(leaf), s),
            state,
            shardings,
        )

    def stage(self, X_block: np.ndarray, target: np.ndarray):
        """Host half: pad a (B, N) block + its target to the fixed
        (block_obs, padded-features) shape and build the valid mask.  The
        target is ``(B,)`` for single-target passes or ``(q, B)`` for
        batched redundancy passes (padded along its observation axis
        either way).  Pure numpy — safe to run on a background thread
        (``PrefetchPlacer`` does)."""
        b, nf = X_block.shape
        if b > self.block_obs:
            raise ValueError(
                f"block of {b} rows exceeds block_obs={self.block_obs}"
            )
        if self.num_features is not None and nf != self.num_features:
            raise ValueError(
                f"block has {nf} features, placer expects {self.num_features}"
            )
        if b < self.block_obs:
            pad = self.block_obs - b
            X_block = np.concatenate(
                [X_block, np.zeros((pad,) + X_block.shape[1:], X_block.dtype)]
            )
            tpad = np.zeros(target.shape[:-1] + (pad,), target.dtype)
            target = np.concatenate([target, tpad], axis=-1)
        if self._feat_pad is not None and nf < self._feat_pad:
            # Zero-filled pad columns: their statistics rows are junk by
            # construction and the engine slices them off after finalize.
            X_block = np.concatenate(
                [
                    X_block,
                    np.zeros(
                        (X_block.shape[0], self._feat_pad - nf), X_block.dtype
                    ),
                ],
                axis=1,
            )
        valid = np.arange(self.block_obs) < b
        return X_block, target, valid

    def place(self, staged):
        """Device half: land a staged (X, target, valid) triple per the
        mesh plan.  ``device_put`` is async — it enqueues and returns.
        A 2-D ``(q, B)`` batched target shards its observation axis like
        the 1-D case, with the candidate axis replicated."""
        X_block, target, valid = staged
        if self._shard_mat is not None:
            tgt_sh = self._shard_vec if target.ndim == 1 else self._shard_tgt2
            return (
                jax.device_put(X_block, self._shard_mat),
                jax.device_put(target, tgt_sh),
                jax.device_put(valid, self._shard_vec),
            )
        return jnp.asarray(X_block), jnp.asarray(target), jnp.asarray(valid)

    def __call__(self, X_block: np.ndarray, target: np.ndarray):
        """(B, N), (B,) host block -> placed (X, target, valid), B' fixed."""
        return self.place(self.stage(X_block, target))


@dataclasses.dataclass
class PrefetchPlacer:
    """Double-buffered placement: a host thread runs the wrapped placer's
    *staging* half (source read + pad — pure numpy) up to ``depth`` blocks
    ahead, while the consumer thread runs the *placement* half
    (``device_put``, async) and the device accumulates the previous block.
    The worker never touches jax, so it cannot contend with the XLA
    runtime's own thread pool.  Exceptions raised while reading or staging
    re-raise in the consumer, and abandoning the iterator stops the
    thread.
    """

    placer: BlockPlacer
    depth: int = 2

    def __post_init__(self):
        if self.depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {self.depth}")

    def stream(self, host_blocks):
        """``(X_block, target)`` host iterator -> placed-tuple iterator."""
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        stop = threading.Event()

        def produce():
            # Plain blocking puts: zero handoff latency in steady state.
            # On early consumer exit the finally-block below sets ``stop``
            # and drains the queue until this thread observes it and dies.
            try:
                for X_block, target in host_blocks:
                    if stop.is_set():
                        return
                    q.put((self.placer.stage(X_block, target), None))
                q.put((_DONE, None))
            except BaseException as exc:  # re-raised by the consumer
                q.put((None, exc))

        worker = threading.Thread(
            target=produce, name="block-prefetch", daemon=True
        )
        worker.start()
        try:
            while True:
                staged, exc = q.get()
                if exc is not None:
                    raise exc
                if staged is _DONE:
                    return
                yield self.placer.place(staged)
        finally:
            stop.set()
            while worker.is_alive():
                try:  # unblock a producer waiting on a full queue
                    q.get_nowait()
                except queue.Empty:
                    pass
                worker.join(timeout=0.01)


class CrossPassReader:
    """Read blocks ahead *across pass boundaries* on one reader thread.

    The streaming engine's pass loop has a structural bubble: while the
    device finalizes pass ``l`` and the host folds/argmaxes, nobody is
    reading pass ``l+1`` — yet which blocks a pass reads never depends on
    the pick (only the target-column *extraction* does, and the engine
    extracts at consume time).  This reader keeps one thread iterating
    ``make_pass()`` — a fresh raw ``(X, y)`` host-block iterator per call
    — pass after pass, up to ``depth`` blocks ahead through a bounded
    queue, so the tail of pass ``l`` overlaps the head of pass ``l+1``.

    The consumer pulls whole passes in order via :meth:`next_pass` and
    must call :meth:`close` (or exhaust ``max_passes``) to stop the
    thread.  Read/parse exceptions re-raise in the consumer at the block
    they correspond to.
    """

    def __init__(self, make_pass, depth: int = 2, max_passes: int | None = None):
        if depth < 1:
            raise ValueError(f"read-ahead depth must be >= 1, got {depth}")
        if max_passes is not None and max_passes < 1:
            raise ValueError(f"max_passes must be >= 1, got {max_passes}")
        self._make_pass = make_pass
        self._max_passes = max_passes
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._passes_started = 0
        self._worker = threading.Thread(
            target=self._produce, name="cross-pass-readahead", daemon=True
        )
        self._worker.start()

    def _produce(self):
        try:
            p = 0
            while self._max_passes is None or p < self._max_passes:
                self._passes_started += 1
                for blk in self._make_pass():
                    if self._stop.is_set():
                        return
                    self._q.put((blk, None))
                self._q.put((_PASS_END, None))
                if self._stop.is_set():
                    return
                p += 1
            self._q.put((_DONE, None))
        except BaseException as exc:  # re-raised by the consumer
            self._q.put((None, exc))

    def next_pass(self):
        """Iterator over the next pass's raw ``(X, y)`` host blocks."""
        while True:
            item, exc = self._q.get()
            if exc is not None:
                raise exc
            if item is _PASS_END:
                return
            if item is _DONE:
                raise RuntimeError(
                    "CrossPassReader exhausted: next_pass() called after "
                    f"max_passes={self._max_passes} passes were consumed"
                )
            yield item

    def close(self):
        """Stop the reader thread and drop any read-ahead blocks."""
        self._stop.set()
        while self._worker.is_alive():
            try:  # unblock a producer waiting on a full queue
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._worker.join(timeout=0.01)

    def __enter__(self) -> "CrossPassReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
