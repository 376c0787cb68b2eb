"""Compile rehearsal for a TPU v5e that is described, not attached.

The kernels of the main path, at real widths, and the sharded streaming
accumulate are compiled ahead of time by the TPU compiler for a v5e:2x2
topology.  Interpret-mode tests cannot see what this catches: blocks not
aligned to the (8, 128) tiling, kernels that overrun VMEM, and Pallas
calls that XLA cannot partition.  Nothing runs, so no result or time is
checked here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.scores import MIScore
from repro.core.streaming import _cached_acc_fn
from repro.dist.streaming import BlockPlacer
from repro.kernels import ops
from repro.kernels.binning import bin_codes_pallas
from repro.kernels.contingency import (
    conditional_tables_pallas,
    contingency_tables_pallas,
)
from repro.kernels.mi_score import mi_scores_pallas

BLOCK = 65536


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _kernel_case(name):
    """-> (fn, [(shape, dtype)]) for one kernel at a main-path size."""
    i8, i32, f32 = jnp.int8, jnp.int32, jnp.float32
    if name.startswith("contingency"):
        f, v, c = {
            "contingency_v2": (256, 2, 2),
            "contingency_v3": (256, 3, 2),
            "contingency_v16": (64, 16, 2),
            "contingency_v32": (256, 32, 2),
        }[name]
        return (
            lambda X, y: contingency_tables_pallas(X, y, v, c),
            [((BLOCK, f), i8), ((BLOCK,), i32)],
        )
    if name.startswith("conditional"):
        # The class is fused into the target: C = V * 2 (up to 64 at V=32).
        v = int(name.removeprefix("conditional_v"))
        return (
            lambda X, xj, y: conditional_tables_pallas(X, xj, y, v, 2),
            [((BLOCK, 256), i8), ((BLOCK,), i32), ((BLOCK,), i32)],
        )
    if name == "bin_codes":
        return bin_codes_pallas, [((BLOCK, 64), f32), ((64, 15), f32)]
    if name == "mi_scores":
        return mi_scores_pallas, [((256, 2, 2), f32)]
    raise KeyError(name)


@pytest.mark.parametrize(
    "name",
    [
        "contingency_v2",
        "contingency_v3",
        "contingency_v16",
        "contingency_v32",
        "conditional_v2",
        "conditional_v16",
        "conditional_v32",
        "bin_codes",
        "mi_scores",
    ],
)
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, args = _kernel_case(name)
    shapes = [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args
    ]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "layout,rows,cols",
    [("obs", BLOCK, 256), ("feat", 4096, 16384)],
)
def test_sharded_accumulate_compiles_for_2x2(
    layout, rows, cols, topo, no_persistent_cache, monkeypatch
):
    # The library picks Pallas by asking for the default backend, which is
    # the CPU here; steer it to the TPU path the described chips run.
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    axis = "data" if layout == "obs" else "model"
    mesh = Mesh(
        np.asarray(topo.devices).reshape(4), (axis,),
        axis_types=(AxisType.Auto,),
    )
    obs, feat = ((axis,), ()) if layout == "obs" else ((), (axis,))
    placer = BlockPlacer(BLOCK, mesh, obs, feat, num_features=cols)
    score = MIScore(num_values=2, num_classes=2)
    acc = _cached_acc_fn(score, placer, mesh)

    state = score.init_state(placer.padded_features)
    o, f = obs[0] if obs else None, feat[0] if feat else None
    shapes = [
        jax.ShapeDtypeStruct(
            state.shape, state.dtype,
            sharding=placer.state_shardings(state),
        ),
        jax.ShapeDtypeStruct(
            (rows, cols), jnp.int8, sharding=NamedSharding(mesh, P(o, f))
        ),
        jax.ShapeDtypeStruct(
            (rows,), jnp.int8, sharding=NamedSharding(mesh, P(o))
        ),
        jax.ShapeDtypeStruct(
            (rows,), jnp.bool_, sharding=NamedSharding(mesh, P(o))
        ),
    ]
    text = acc.lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    if layout == "obs":
        assert "all-reduce" in text  # the per-block psum of the counts
