"""GPipe pipeline parallelism over a mesh axis.

``pipeline_apply`` runs a stack of S identical stages sharded over a
``stage`` mesh axis: each device holds S/n_stages consecutive stage
params, microbatches stream through the pipeline via ``ppermute``, and the
schedule is bit-equivalent to applying the stages sequentially (tested in
tests/multidevice/md_pipeline.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.sharding import pvary

Array = jax.Array


def pipeline_apply(
    stage_fn,
    params,
    x: Array,
    *,
    mesh: Mesh,
    axis: str = "stage",
    microbatches: int = 1,
):
    """Apply ``S`` stacked stages to ``x`` with GPipe over ``mesh[axis]``.

    Args:
      stage_fn: ``(stage_params, h) -> h`` for ONE stage.
      params: pytree whose leaves carry a leading stage axis of size S
        (divisible by the mesh axis extent; each shard applies its
        consecutive block of stages in order).
      x: (B, ...) global batch, replicated; B divisible by ``microbatches``.
      mesh: device mesh containing ``axis``.
      axis: pipeline mesh axis name.
      microbatches: number of in-flight microbatches (GPipe bubbles shrink
        as this grows; 1 = fully sequential).
    Returns:
      (B, ...) output, replicated — identical to folding all S stages.
    """
    n_stages = mesh.shape[axis]
    s_total = jax.tree.leaves(params)[0].shape[0]
    if s_total % n_stages:
        raise ValueError(f"{s_total} stages over {n_stages}-way axis {axis!r}")
    per = s_total // n_stages
    b = x.shape[0]
    if b % microbatches:
        raise ValueError(f"batch {b} not divisible by {microbatches} microbatches")
    mb = b // microbatches
    fwd = [(i, i + 1) for i in range(n_stages - 1)]

    def body(p_loc, x_rep):
        i = lax.axis_index(axis)
        mbs = x_rep.reshape((microbatches, mb) + x_rep.shape[1:])

        def local_apply(h):
            for j in range(per):
                h = stage_fn(jax.tree.map(lambda a: a[j], p_loc), h)
            return h

        carry = pvary(jnp.zeros_like(mbs[0]), (axis,))
        out = pvary(jnp.zeros_like(mbs), (axis,))
        last = n_stages - 1
        for t in range(microbatches + n_stages - 1):
            feed = mbs[min(t, microbatches - 1)]
            h = local_apply(jnp.where(i == 0, feed, carry))
            if t >= last:  # microbatch t-last drains from the last stage
                keep = jnp.where(i == last, h, jnp.zeros_like(h))
                out = out.at[t - last].set(keep)
            if fwd:
                carry = lax.ppermute(h, axis, fwd)
        out = lax.psum(out, axis)
        return out.reshape((b,) + x_rep.shape[1:])

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(axis), params),
            P(),
        ),
        out_specs=P(),
    )
    return fn(params, x)
