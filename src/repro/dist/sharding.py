"""Logical-axis sharding rules.

Parameters declare *logical* axis names (``("vocab", "fsdp")``); a
``ShardingRules`` instance maps each logical name to a mesh axis (or to
``None`` = replicate), and ``logical_to_spec`` resolves a def's logical
tuple to a concrete ``PartitionSpec`` — dropping any mapping whose mesh
axis is absent, already used by an earlier dim, or does not divide the dim
size.  That makes one rule set safe across every (arch × shape × mesh)
cell: smoke configs with tiny dims simply come out replicated.
"""

from __future__ import annotations

import dataclasses

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

# mesh axis name, tuple of names (sharded over their product), or None
AxisSel = "str | tuple[str, ...] | None"


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical parameter axis -> mesh axis mapping.

    Defaults are fully replicated (the single-device rules); ``rules_for``
    builds the production mapping from a mesh.
    """

    fsdp: AxisSel = None        # weight shards spread over data parallelism
    ff: AxisSel = None          # MLP hidden (Megatron TP)
    heads: AxisSel = None       # attention query heads
    kv_heads: AxisSel = None    # attention kv heads
    ssm_heads: AxisSel = None   # mamba state heads
    vocab: AxisSel = None       # embed/unembed vocab dim
    experts: AxisSel = None     # MoE expert parallelism
    expert_ff: AxisSel = None   # weight-stationary second EP level
    act_seq: AxisSel = None     # sequence-sharded activations (Megatron-SP)

    def axis_for(self, logical: str) -> AxisSel:
        if logical == "none":
            return None
        return getattr(self, logical, None)


def rules_for(
    mesh: Mesh, *, fsdp: bool = True, seq_shard: bool = False
) -> ShardingRules:
    """Production rules for a mesh: tensor-parallel dims on ``model``,
    FSDP weight shards on ``data`` (when enabled and present)."""
    tp = "model" if "model" in mesh.shape else None
    dp = "data" if (fsdp and "data" in mesh.shape) else None
    return ShardingRules(
        fsdp=dp,
        ff=tp,
        heads=tp,
        kv_heads=tp,
        ssm_heads=tp,
        vocab=tp,
        experts=tp,
        # Expert matrices keep their d_ff storage shards in place (tokens
        # move instead) — mirrors the ff_axis level in moe_apply.
        expert_ff=dp,
        act_seq=(tp if seq_shard else None),
    )


def axes_tuple(axes) -> tuple:
    """Normalise a mesh-axis selection (None | str | sequence) to a tuple."""
    if axes is None:
        return ()
    if isinstance(axes, (list, tuple)):
        return tuple(axes)
    return (axes,)


def pvary(x, axes):
    """Mark every leaf of ``x`` as varying over ``axes`` inside
    ``shard_map`` (identity for no axes)."""
    if not axes:
        return x
    return jax.tree.map(lambda v: lax.pcast(v, tuple(axes), to="varying"), x)


def mesh_extent(mesh: Mesh | None, axes) -> int:
    """Product of the mesh extents of ``axes`` (1 for no mesh)."""
    if mesh is None:
        return 1
    ext = 1
    for a in axes_tuple(axes):
        ext *= mesh.shape[a]
    return ext


def logical_to_spec(
    logical: tuple, shape: tuple, mesh: Mesh, rules: ShardingRules
) -> P:
    """Resolve a logical axis tuple to a PartitionSpec for ``mesh``.

    Guards applied per dim, in order: mapping exists, all mesh axes present,
    no mesh axis reused by an earlier dim, dim size divisible by the shard
    extent. A dim failing any guard is replicated.
    """
    used: set = set()
    entries = []
    for name, dim in zip(logical, shape):
        sel = rules.axis_for(name)
        axes = (sel,) if isinstance(sel, str) else tuple(sel or ())
        ok = (
            axes
            and all(a in mesh.shape for a in axes)
            and not (set(axes) & used)
            and dim % mesh_extent(mesh, axes) == 0
        )
        if ok:
            used.update(axes)
            entries.append(axes[0] if len(axes) == 1 else axes)
        else:
            entries.append(None)
    return P(*entries)
