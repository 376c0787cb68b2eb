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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.scores import MIScore
from repro.core.streaming import _cached_acc_fn, _widen_extrema
from repro.dist.streaming import BlockPlacer, _cut_target
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
            "contingency_v2_f1000": (1000, 2, 2),  # a ragged last tile
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
        "contingency_v2_f1000",
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


@pytest.mark.parametrize(
    "layout,rows,cols",
    [("one", BLOCK, 1000), ("one", 2048, 50000), ("obs", BLOCK, 1000)],
)
def test_accumulate_counts_the_int8_block(
    layout, rows, cols, topo, one_chip, no_persistent_cache, monkeypatch
):
    # The count kernel reads the placed int8 block itself: no widened,
    # relaid or padded int32 copy of it is made in HBM ahead of the
    # kernel, at the benchmark's block shapes.
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    if layout == "one":
        mesh, obs, st_sh = None, (), one_chip
        x_sh = t_sh = one_chip
    else:
        mesh = Mesh(
            np.asarray(topo.devices).reshape(4), ("data",),
            axis_types=(AxisType.Auto,),
        )
        obs = ("data",)
        x_sh = NamedSharding(mesh, P("data", None))
        t_sh = NamedSharding(mesh, P("data"))
    placer = BlockPlacer(rows, mesh, obs, (), num_features=cols)
    score = MIScore(num_values=2, num_classes=2)
    acc = _cached_acc_fn(score, placer, mesh)
    state = score.init_state(placer.padded_features)
    if mesh is not None:
        st_sh = placer.state_shardings(state)
    shapes = [
        jax.ShapeDtypeStruct(state.shape, state.dtype, sharding=st_sh),
        jax.ShapeDtypeStruct((rows, cols), jnp.int8, sharding=x_sh),
        jax.ShapeDtypeStruct((rows,), jnp.int8, sharding=t_sh),
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=t_sh),
    ]
    text = acc.lower(*shapes).compile().as_text()
    local_rows = rows // (4 if mesh is not None else 1)
    kernels = re.findall(
        r'custom_call_target="tpu_custom_call", '
        r"operand_layout_constraints=\{(\w+)\[(\d+),(\d+)\]", text
    )
    assert kernels == [("s8", str(local_rows), str(cols))]
    widened = [
        line for line in text.splitlines()
        if (m := re.search(r"= s32\[(\d+),(\d+)\]\S* (\w+)\(", line))
        and int(m[1]) == local_rows and int(m[2]) >= cols
        and m[3] in ("pad", "convert", "copy", "fusion")
    ]
    assert not widened, widened


@pytest.mark.parametrize(
    "layout,rows,cols",
    [("one", BLOCK, 1000), ("obs", BLOCK, 1000), ("feat", 2048, 50000)],
)
def test_batched_accumulate_compiles_for_v5e(
    layout, rows, cols, topo, one_chip, no_persistent_cache, monkeypatch
):
    # A redundancy pass that counts q = 4 candidate columns in one sweep
    # (batch_candidates=4): the accumulate vmapped over (q, B) targets with
    # the block shared, at the benchmark's block shapes on one chip and
    # split by rows or by columns over four.
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    q = 4
    if layout == "one":
        mesh, obs, feat = None, (), ()
    else:
        axis = "data" if layout == "obs" else "model"
        mesh = Mesh(
            np.asarray(topo.devices).reshape(4), (axis,),
            axis_types=(AxisType.Auto,),
        )
        obs, feat = ((axis,), ()) if layout == "obs" else ((), (axis,))
    placer = BlockPlacer(rows, mesh, obs, feat, num_features=cols)
    score = MIScore(num_values=2, num_classes=2)
    acc = _cached_acc_fn(score, placer, mesh, batch=q)

    one = score.init_state(placer.padded_features, "feature")
    state = jnp.zeros((q,) + one.shape, one.dtype)
    if mesh is None:
        st_sh = x_sh = t_sh = v_sh = one_chip
    else:
        o, f = obs[0] if obs else None, feat[0] if feat else None
        st_sh = placer.state_shardings(state)
        x_sh = NamedSharding(mesh, P(o, f))
        t_sh = NamedSharding(mesh, P(None, o))
        v_sh = NamedSharding(mesh, P(o))
    shapes = [
        jax.ShapeDtypeStruct(state.shape, state.dtype, sharding=st_sh),
        jax.ShapeDtypeStruct(
            (rows, placer.padded_features), jnp.int8, sharding=x_sh
        ),
        jax.ShapeDtypeStruct((q, rows), jnp.int8, sharding=t_sh),
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=v_sh),
    ]
    compiled = acc.lower(*shapes).compile()
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == state.shape and out.dtype == jnp.int32
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if layout == "obs":
        assert "all-reduce" in text  # the per-block psum of the counts


@pytest.mark.parametrize(
    "layout,rows,cols,q,cond",
    [
        ("one", BLOCK, 1000, None, None),  # a mid redundancy target
        ("one", BLOCK, 1000, None, 2),  # jmi's class-fused target
        ("one", BLOCK, 1000, 4, None),  # a (q, B) batch of columns
        ("obs", BLOCK, 1000, None, 2),
        ("feat", 2048, 50000, 4, 2),
    ],
)
def test_target_cut_compiles_for_v5e(
    layout, rows, cols, q, cond, topo, one_chip, no_persistent_cache
):
    # The device cut of a resident pass's target, at the benchmark's
    # block shapes: one chip, and blocks split by rows or by columns
    # over four.
    if layout == "one":
        obs, feat, sharding = (), (), None
        x_sh = y_sh = c_sh = one_chip
    else:
        axis = "data" if layout == "obs" else "model"
        mesh = Mesh(
            np.asarray(topo.devices).reshape(4), (axis,),
            axis_types=(AxisType.Auto,),
        )
        obs, feat = ((axis,), ()) if layout == "obs" else ((), (axis,))
        placer = BlockPlacer(rows, mesh, obs, feat, num_features=cols)
        cols = placer.padded_features
        sharding = placer._shard_vec if q is None else placer._shard_tgt2
        o, f = obs[0] if obs else None, feat[0] if feat else None
        x_sh = NamedSharding(mesh, P(o, f))
        y_sh = NamedSharding(mesh, P(o))
        c_sh = NamedSharding(mesh, P())
    shapes = [
        jax.ShapeDtypeStruct((rows, cols), jnp.int8, sharding=x_sh),
        jax.ShapeDtypeStruct((rows,), jnp.int8, sharding=y_sh),
        jax.ShapeDtypeStruct(() if q is None else (q,), jnp.int32, sharding=c_sh),
    ]
    compiled = _cut_target.lower(*shapes, cond, sharding).compile()
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == ((rows,) if q is None else (q, rows))
    assert out.dtype == (jnp.int8 if cond is None else jnp.int32)
    if layout == "feat":
        assert "all-reduce" in compiled.as_text()  # the owner's column


@pytest.mark.parametrize(
    "layout,rows,cols",
    [("one", BLOCK, 1000), ("one", 2048, 50000), ("obs", BLOCK, 1000),
     ("feat", 2048, 50000)],
)
def test_block_extrema_compile_for_v5e(
    layout, rows, cols, topo, one_chip, no_persistent_cache
):
    # The reduce that sizes a default score from the resident blocks, at
    # the benchmark's block shapes: one chip, and blocks split by rows or
    # by columns over four, where it has to reduce across the chips.
    if layout == "one":
        x_sh = y_sh = e_sh = one_chip
    else:
        axis = "data" if layout == "obs" else "model"
        mesh = Mesh(
            np.asarray(topo.devices).reshape(4), (axis,),
            axis_types=(AxisType.Auto,),
        )
        o, f = (axis, None) if layout == "obs" else (None, axis)
        x_sh = NamedSharding(mesh, P(o, f))
        y_sh = NamedSharding(mesh, P(o))
        e_sh = NamedSharding(mesh, P())
    scalar = jax.ShapeDtypeStruct((), jnp.int8, sharding=e_sh)
    compiled = _widen_extrema.lower(
        (scalar,) * 4,
        jax.ShapeDtypeStruct((rows, cols), jnp.int8, sharding=x_sh),
        jax.ShapeDtypeStruct((rows,), jnp.int8, sharding=y_sh),
    ).compile()
    outs = jax.tree.leaves(compiled.out_info)
    assert [(o.shape, o.dtype) for o in outs] == [((), jnp.int8)] * 4
    if layout != "one":
        assert "all-reduce" in compiled.as_text()
