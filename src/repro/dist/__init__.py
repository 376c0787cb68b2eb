"""repro.dist — the distribution substrate.

Everything that touches device topology lives here, so the rest of the
repo (engines, launchers) never talks to raw jax device state:

* ``repro.dist.meshes``   — ``make_mesh``: named device meshes from a
  (shape, axis-names) pair, the single mesh constructor in the repo.
* ``repro.dist.sharding`` — mesh-axis helpers: ``axes_tuple``,
  ``mesh_extent`` (shards over a set of axes) and ``pvary`` (marks values
  varying inside ``shard_map``).
* ``repro.dist.streaming`` — ``BlockPlacer``: pad-and-shard placement of
  streamed observation-blocks (obs-sharded, feature-sharded or 2-D grid)
  for the out-of-core fit path, plus ``PrefetchPlacer``, its
  double-buffered wrapper overlapping host reads with device compute.
* ``repro.dist.multihost`` — cross-process map-reduce: ``init_multihost``
  bootstrap over ``jax.distributed``, ``HostShardSpec`` (the paper's §III
  sharding rule applied to hosts — each host reads only its block/column
  ranges) and ``HostCollectives`` (the per-pass reduce as explicit
  ``shard_map``-ped psums over a one-device-per-process mesh).
"""

from repro.dist.meshes import factor_mesh, host_mesh, make_mesh  # noqa: F401
from repro.dist.multihost import (  # noqa: F401
    HostCollectives,
    HostShardSpec,
    init_multihost,
    resolve_host_shards,
    split_range,
)
from repro.dist.streaming import BlockPlacer, PrefetchPlacer  # noqa: F401
from repro.dist.sharding import (  # noqa: F401
    axes_tuple,
    mesh_extent,
    pvary,
)
