"""Multi-host map-reduce — each host reads only its shard.

The paper's headline claim is *cluster* scale: millions of observations
or features spread over MapReduce workers, each reading only its
partition, with one reduce merging the per-partition sufficient
statistics.  This module is that layer for the streaming engine, on
``jax.distributed``:

* :func:`init_multihost` — process bootstrap wrapping
  ``jax.distributed.initialize`` (explicit args or ``REPRO_COORDINATOR``
  / ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` env vars), with the
  CPU collectives implementation pinned to gloo so loopback CI clusters
  work out of the box.
* :class:`HostShardSpec` / :func:`resolve_host_shards` — the paper's
  §III sharding rule applied across *hosts*: tall fits partition the
  observation (block) range, wide fits partition the column range, and
  both-large gets the 2-D (obs × feat) host grid.  Each host's block
  iteration walks ONLY its own ranges
  (:meth:`repro.data.sources.DataSource.iter_shard_blocks`).
* :class:`HostCollectives` — the per-pass reduce as explicit
  ``shard_map``-ped ``psum``\\ s over a global mesh built with one
  representative device per process, so the 2-D grid's collective
  placement is pinned rather than left to GSPMD propagation:

  - ``psum`` merges host-local contingency states over every host
    (tall regime: exact integer count sums, hence bitwise-identical
    finalised scores on every host);
  - ``psum_obs`` merges over the observation-host axis only, keeping
    the per-pair statistics column-sharded (the 2-D grid's reduce);
  - ``assemble`` scatters each column group's finalised score slice
    into the full ``(N,)`` vector and sums the disjoint pieces (the
    wide regime's reduce — float adds against zeros, exact).

  After the reduce every host holds identical full-width vectors, folds
  the criterion identically and commits the identical pick — a genuine
  map-reduce with no designated master.

Module imports stay jax+numpy only (no ``repro.core`` at import time):
``repro.core.selector`` imports ``repro.dist``, and the §III thresholds
are borrowed lazily inside :func:`resolve_host_shards` to keep the two
planners literally rule-identical without an import cycle.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist.meshes import factor_mesh, host_mesh

_OBS_AXIS, _FEAT_AXIS = "oh", "fh"  # host-mesh axis names (obs / feature)


# ---------------------------------------------------------------------------
# bootstrap
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MultihostContext:
    """What :func:`init_multihost` resolved: this process's place in the
    cluster (``num_processes == 1`` means single-process, no collectives)."""

    process_id: int
    num_processes: int
    coordinator: str | None


_CONTEXT: MultihostContext | None = None


def _env_int(name: str) -> int | None:
    v = os.environ.get(name)
    return int(v) if v not in (None, "") else None


def init_multihost(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    *,
    cpu_collectives: str = "gloo",
) -> MultihostContext:
    """Join (or skip joining) a ``jax.distributed`` cluster — idempotent.

    Args default from the environment — ``REPRO_COORDINATOR`` (e.g.
    ``"10.0.0.1:12355"``), ``REPRO_NUM_PROCESSES``, ``REPRO_PROCESS_ID``
    — so launchers can configure workers without threading flags.  With
    no coordinator (or ``num_processes <= 1``) this is a no-op returning
    a single-process context: the same selection code runs unsharded.

    Must run before any jax computation (backend init locks the device
    set); calling again after a successful init returns the cached
    context.  ``cpu_collectives`` pins the CPU cross-process collectives
    backend (gloo) — required for multi-process CPU psums; harmless on
    accelerator backends.
    """
    global _CONTEXT
    if _CONTEXT is not None:
        return _CONTEXT
    coordinator = coordinator or os.environ.get("REPRO_COORDINATOR") or None
    if num_processes is None:
        num_processes = _env_int("REPRO_NUM_PROCESSES")
    if process_id is None:
        process_id = _env_int("REPRO_PROCESS_ID")
    if coordinator is None or (num_processes or 1) <= 1:
        _CONTEXT = MultihostContext(
            process_id=jax.process_index(),
            num_processes=jax.process_count(),
            coordinator=None,
        )
        return _CONTEXT
    if num_processes is None or process_id is None:
        raise ValueError(
            "multi-host init needs all three of coordinator, num_processes "
            f"and process_id (got coordinator={coordinator!r}, "
            f"num_processes={num_processes!r}, process_id={process_id!r})"
        )
    # Only affects the CPU backend; must land before backend init.
    jax.config.update("jax_cpu_collectives_implementation", cpu_collectives)
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=int(num_processes),
            process_id=int(process_id),
        )
    except RuntimeError as e:
        # Already initialised (a launcher beat us to it): verify instead
        # of failing — idempotence is the contract.
        if "already" not in str(e).lower():
            raise
    if jax.process_count() != int(num_processes):
        raise RuntimeError(
            f"jax.distributed reports {jax.process_count()} processes, "
            f"expected {num_processes}"
        )
    _CONTEXT = MultihostContext(
        process_id=int(jax.process_index()),
        num_processes=int(jax.process_count()),
        coordinator=coordinator,
    )
    return _CONTEXT


# ---------------------------------------------------------------------------
# shard resolution — the §III rule across hosts
# ---------------------------------------------------------------------------

def split_range(total: int, parts: int, index: int) -> tuple[int, int]:
    """Balanced contiguous split of ``range(total)`` into ``parts``:
    the first ``total % parts`` shards get one extra element, so shard
    sizes never differ by more than one."""
    if not 0 <= index < parts:
        raise ValueError(f"index {index} out of range for {parts} parts")
    base, extra = divmod(int(total), int(parts))
    lo = index * base + min(index, extra)
    return lo, lo + base + (1 if index < extra else 0)


@dataclasses.dataclass(frozen=True)
class HostShardSpec:
    """One host's slice of the dataset under the §III host grid.

    ``grid = (obs_hosts, feat_hosts)`` with hosts laid out row-major:
    host ``i`` sits at ``(i // feat_hosts, i % feat_hosts)`` — the same
    order :func:`repro.dist.meshes.host_mesh` lays processes onto the
    collective mesh, so shard ranges and psum axes always agree.
    """

    num_obs: int
    num_features: int
    grid: tuple          # (obs_hosts, feat_hosts)
    host_id: int
    obs_range: tuple     # [lo, hi) rows this host reads
    col_range: tuple     # [lo, hi) columns this host reads

    @property
    def num_hosts(self) -> int:
        return self.grid[0] * self.grid[1]

    @property
    def obs_coord(self) -> int:
        return self.host_id // self.grid[1]

    @property
    def feat_coord(self) -> int:
        return self.host_id % self.grid[1]

    @property
    def local_obs(self) -> int:
        return self.obs_range[1] - self.obs_range[0]

    @property
    def local_cols(self) -> int:
        return self.col_range[1] - self.col_range[0]

    @property
    def partitions_obs(self) -> bool:
        return self.grid[0] > 1

    @property
    def partitions_cols(self) -> bool:
        return self.grid[1] > 1

    @property
    def is_single_host(self) -> bool:
        return self.num_hosts == 1

    @property
    def max_col_width(self) -> int:
        """Widest column group (group 0 under the balanced split) — the
        common padded width for cross-group state collectives."""
        lo, hi = split_range(self.num_features, self.grid[1], 0)
        return hi - lo

    def owns_col(self, c: int) -> bool:
        return self.col_range[0] <= int(c) < self.col_range[1]


def resolve_host_shards(
    num_obs: int,
    num_features: int,
    num_hosts: int,
    host_id: int,
    *,
    grid: tuple | None = None,
) -> HostShardSpec:
    """The §III sharding rule applied to hosts: tall partitions the
    observation range, wide partitions the column range, both-large gets
    the aspect-biased 2-D factorisation (same thresholds as the device
    planner — literally the selector's constants).  ``grid=(oh, fh)``
    overrides the rule.  ``num_hosts == 1`` degenerates to the full
    ranges (today's single-process path)."""
    m, n = int(num_obs), int(num_features)
    H = int(num_hosts)
    if H < 1:
        raise ValueError(f"num_hosts must be >= 1, got {H}")
    if not 0 <= int(host_id) < H:
        raise ValueError(f"host_id {host_id} out of range for {H} hosts")
    if grid is not None:
        oh, fh = int(grid[0]), int(grid[1])
        if oh * fh != H:
            raise ValueError(f"grid {grid} does not factor {H} hosts")
    elif H == 1:
        oh, fh = 1, 1
    else:
        # Borrowed lazily so this module never imports repro.core at
        # import time (selector imports repro.dist) — one rule, two
        # planners, zero drift.
        from repro.core.selector import (
            TALL_RATIO, WIDE_RATIO, _grid_factor,
        )

        aspect = m / max(n, 1)
        if aspect >= TALL_RATIO:
            oh, fh = H, 1
        elif aspect <= WIDE_RATIO:
            oh, fh = 1, H
        else:
            gf = _grid_factor(m, n, H)
            if gf is not None:
                oh, fh = gf
            elif aspect >= 1.0:
                oh, fh = H, 1
            else:
                oh, fh = 1, H
    if oh > max(m, 1) or fh > max(n, 1):
        raise ValueError(
            f"host grid ({oh}, {fh}) over-partitions a {m}x{n} dataset: "
            "some hosts would hold an empty shard; use fewer hosts or an "
            "explicit grid="
        )
    oc, fc = int(host_id) // fh, int(host_id) % fh
    return HostShardSpec(
        num_obs=m,
        num_features=n,
        grid=(oh, fh),
        host_id=int(host_id),
        obs_range=split_range(m, oh, oc),
        col_range=split_range(n, fh, fc),
    )


def factor_host_grid(num_obs: int, num_features: int, num_hosts: int) -> tuple:
    """The (obs_hosts, feat_hosts) factorisation ``resolve_host_shards``
    would pick — exposed for planners and tests."""
    return resolve_host_shards(num_obs, num_features, num_hosts, 0).grid


# ---------------------------------------------------------------------------
# explicit cross-host collectives
# ---------------------------------------------------------------------------

class HostCollectives:
    """The per-pass reduce: explicit psums over the global host mesh.

    Built once per fit from a :class:`HostShardSpec`; every merge is a
    ``shard_map``-ped ``lax.psum`` with pinned in/out specs over a
    ``(obs_hosts, feat_hosts)`` mesh holding ONE representative device
    per process (ordered by process index, so mesh coordinates equal
    shard coordinates).  Single-host specs short-circuit every method to
    the identity — the degenerate path never touches ``jax.distributed``.

    Compiled merge fns are cached per (op × tree signature), so passes
    after the first pay zero trace/compile.
    """

    def __init__(self, spec: HostShardSpec):
        self.spec = spec
        self._fns: dict = {}
        self._mesh: Mesh | None = None
        self._device = None
        if not spec.is_single_host:
            if jax.process_count() != spec.num_hosts:
                raise RuntimeError(
                    f"HostShardSpec wants {spec.num_hosts} hosts but "
                    f"jax.distributed reports {jax.process_count()} "
                    "processes; call init_multihost() first"
                )
            self._mesh = host_mesh(spec.grid, (_OBS_AXIS, _FEAT_AXIS))
            self._device = jax.local_devices()[0]

    # -- plumbing --------------------------------------------------------

    def _global_leaf(self, leaf: np.ndarray):
        """This host's leaf as its (1, 1, *s) shard of the (O, F, *s)
        global array — the make_array construction verified to feed
        cross-process shard_map psums."""
        a = np.ascontiguousarray(leaf)
        gshape = (self.spec.grid[0], self.spec.grid[1]) + a.shape
        sharding = NamedSharding(self._mesh, P(_OBS_AXIS, _FEAT_AXIS))
        local = jax.device_put(a[None, None], self._device)
        return jax.make_array_from_single_device_arrays(
            gshape, sharding, [local]
        )

    def _merged(self, leaves: list, axes: tuple) -> list:
        """psum every leaf over the given mesh axes; returns host numpy
        arrays (the local block, leading host dims dropped)."""
        sig = (
            axes,
            tuple((tuple(l.shape), str(l.dtype)) for l in leaves),
        )
        fn = self._fns.get(sig)
        if fn is None:
            n = len(leaves)
            in_spec = P(_OBS_AXIS, _FEAT_AXIS)
            out_spec = P(
                None if _OBS_AXIS in axes else _OBS_AXIS,
                None if _FEAT_AXIS in axes else _FEAT_AXIS,
            )

            def merge(*xs):
                return tuple(jax.lax.psum(x, axes) for x in xs)

            fn = jax.jit(
                jax.shard_map(
                    merge,
                    mesh=self._mesh,
                    in_specs=(in_spec,) * n,
                    out_specs=(out_spec,) * n,
                )
            )
            self._fns[sig] = fn
        out = fn(*[self._global_leaf(l) for l in leaves])
        return [np.asarray(o.addressable_data(0))[0, 0] for o in out]

    def _tree_merge(self, tree, axes: tuple):
        leaves, treedef = jax.tree.flatten(tree)
        host = [np.asarray(l) for l in leaves]
        return jax.tree.unflatten(treedef, self._merged(host, axes))

    # -- the three reduces ----------------------------------------------

    def psum(self, tree):
        """Sum a pytree over EVERY host — the tall regime's state merge.
        Contingency counts are exact integers, so the merged statistics
        (and everything finalised from them) are bitwise-identical to a
        single process having seen every block."""
        if self.spec.is_single_host:
            return tree
        return self._tree_merge(tree, (_OBS_AXIS, _FEAT_AXIS))

    def psum_obs(
        self,
        tree,
        feat_axis: int = 0,
        local_width: int | None = None,
        pad_to: int | None = None,
    ):
        """Sum over the observation-host axis only — the 2-D grid's state
        merge: per-pair statistics stay column-sharded (the wide memory
        wall never re-forms) while row partitions collapse.  Column
        groups may differ in width under a ragged split, so leaves whose
        ``feat_axis`` is exactly ``local_width`` wide (default: this
        host's column count; augmented redundancy states pass their
        target-extended width) are zero-padded to ``pad_to`` (default:
        the widest group) before the psum and sliced back after — zeros
        are the additive identity, so padding never changes a sum.
        Leaves that don't match the width (scalars, counters) ride
        unpadded; the match is decided per-leaf BEFORE the merge so an
        unpadded leaf that happens to come out ``pad_to`` wide is never
        mis-sliced."""
        if self.spec.grid[0] == 1:
            return tree
        mine = self.spec.local_cols if local_width is None else int(local_width)
        w = self.spec.max_col_width if pad_to is None else int(pad_to)
        leaves, treedef = jax.tree.flatten(tree)
        host = [np.asarray(l) for l in leaves]
        flags = [
            a.ndim > feat_axis and a.shape[feat_axis] == mine and mine != w
            for a in host
        ]

        def pad(a):
            widths = [(0, 0)] * a.ndim
            widths[feat_axis] = (0, w - a.shape[feat_axis])
            return np.pad(a, widths)

        padded = [pad(a) if f else a for a, f in zip(host, flags)]
        merged = self._merged(padded, (_OBS_AXIS,))

        def unpad(a):
            sl = [slice(None)] * a.ndim
            sl[feat_axis] = slice(0, mine)
            return a[tuple(sl)]

        out = [unpad(a) if f else a for a, f in zip(merged, flags)]
        return jax.tree.unflatten(treedef, out)

    def assemble(self, tree):
        """Scatter each column group's ``(..., local_cols)`` score slice
        into zeros of full width ``(..., N)`` and sum across hosts — the
        wide / 2-D vector reduce.  Only ``obs_coord == 0`` contributes
        (after :meth:`psum_obs` every row in a column group holds the
        identical slice), so each output column receives exactly one
        non-zero addend: float adds against zeros, exact, and every host
        ends with the identical full vector."""
        if not self.spec.partitions_cols:
            return self.psum(tree) if self.spec.grid[0] > 1 else tree
        lo, hi = self.spec.col_range

        def scatter(leaf):
            a = np.asarray(leaf)
            full = np.zeros(a.shape[:-1] + (self.spec.num_features,), a.dtype)
            if self.spec.obs_coord == 0:
                full[..., lo:hi] = a
            return full

        return self._tree_merge(
            jax.tree.map(scatter, tree), (_OBS_AXIS, _FEAT_AXIS)
        )

    # -- ledger exchange -------------------------------------------------

    def allgather_counts(self, values) -> np.ndarray:
        """Every host's integer vector, exactly: ``(num_hosts, k)`` from
        each host's ``(k,)`` counters.  Values ride as two int32 halves
        (x64 is typically disabled, and f32 would round byte counts), so
        counts are exact up to 2**62."""
        v = np.asarray(values, np.int64).reshape(-1)
        if self.spec.is_single_host:
            return v[None, :]
        H, k = self.spec.num_hosts, v.shape[0]
        lo = np.zeros((H, k), np.int32)
        hi = np.zeros((H, k), np.int32)
        lo[self.spec.host_id] = (v & 0x7FFFFFFF).astype(np.int32)
        hi[self.spec.host_id] = (v >> 31).astype(np.int32)
        mlo, mhi = self._merged([lo, hi], (_OBS_AXIS, _FEAT_AXIS))
        return (mhi.astype(np.int64) << 31) | mlo.astype(np.int64)


__all__ = [
    "HostCollectives",
    "HostShardSpec",
    "MultihostContext",
    "factor_host_grid",
    "factor_mesh",
    "init_multihost",
    "resolve_host_shards",
    "split_range",
]
