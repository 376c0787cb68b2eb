"""Named device meshes.

``make_mesh`` is the one mesh constructor in the repo: everything from the
2-device CPU debug mesh to a multi-chip TPU host goes through it, so
device selection and axis naming cannot drift between launchers, tests and
benchmarks.
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(
    shape: Sequence[int],
    axes: Sequence[str],
    *,
    devices=None,
) -> Mesh:
    """Build a named ``Mesh`` of the given shape.

    Args:
      shape: extent per mesh axis, e.g. ``(16, 16)``.
      axes: axis name per extent, e.g. ``("data", "model")``.
      devices: devices to lay out (default: all local ``jax.devices()``).
        Exactly ``prod(shape)`` leading devices are used.
    """
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(f"shape {shape} and axes {axes} length mismatch")
    n = math.prod(shape)
    if devices is None:
        devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {n} devices, "
            f"have {len(devices)}"
        )
    devices = list(devices)[:n]
    # Auto axes: the placer and engines hand XLA input/output shardings and
    # let it partition the rest (jax.make_mesh defaults to Explicit).
    return jax.make_mesh(
        shape, axes, devices=devices, axis_types=(AxisType.Auto,) * len(axes)
    )


def host_mesh(
    shape: Sequence[int] | None = None,
    axes: Sequence[str] = ("hosts",),
    *,
    devices=None,
) -> Mesh:
    """A global mesh with ONE representative device per process.

    Cross-host collectives over host-local sufficient statistics only
    need one device per host (the statistics already live on a single
    local device); the mesh must place process ``p`` at row-major mesh
    position ``p`` so shard coordinates equal mesh coordinates.
    ``jax.make_mesh`` may reorder devices for transfer performance,
    which would silently break that mapping — hence the raw ``Mesh``
    constructor here.

    Args:
      shape: extent per axis (default ``(num_processes,)``); must
        multiply out to the process count.
      axes: axis name per extent.
      devices: override the representative devices (tests); default is
        the lowest-id device of each process, ordered by process index.
    """
    if devices is None:
        by_proc: dict[int, object] = {}
        for d in jax.devices():
            p = d.process_index
            if p not in by_proc or d.id < by_proc[p].id:
                by_proc[p] = d
        devices = [by_proc[p] for p in sorted(by_proc)]
    devices = list(devices)
    if shape is None:
        shape = (len(devices),)
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    if math.prod(shape) != len(devices):
        raise ValueError(
            f"host mesh shape {dict(zip(axes, shape))} needs "
            f"{math.prod(shape)} hosts, have {len(devices)}"
        )
    return Mesh(np.asarray(devices, dtype=object).reshape(shape), axes)


def factor_mesh(n_devices: int, *, bias: float = 1.0) -> tuple[int, int]:
    """Split ``n_devices`` into a 2-D grid ``(a, b)``, ``a*b == n_devices``.

    ``bias`` > 1 pushes devices toward the first axis (used by the selection
    planner to give the longer data axis more shards).  Prefers balanced
    factorisations; falls back to ``(n, 1)`` for primes.
    """
    if n_devices <= 0:
        raise ValueError(f"n_devices must be positive, got {n_devices}")
    target = math.sqrt(n_devices * bias)
    best = (n_devices, 1)
    best_err = float("inf")
    for a in range(1, n_devices + 1):
        if n_devices % a:
            continue
        err = abs(math.log(a / target)) if target > 0 else float(a)
        if err < best_err:
            best, best_err = (a, n_devices // a), err
    return best
