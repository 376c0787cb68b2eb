"""Gradient compression: exactness bounds + error-feedback convergence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.train.compression import GradCompression, compressed_psum


def test_compress_roundtrip_error_bound():
    key = jax.random.PRNGKey(0)
    grads = {
        "a": jax.random.normal(key, (64, 32)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (128,)) * 10,
    }
    state = GradCompression.init(grads)
    (q, s), state = state.compress(grads)
    for k in grads:
        deq = q[k].astype(jnp.float32) * s[k]
        err = np.abs(np.asarray(deq - grads[k]))
        # quantisation error bounded by half a step
        assert err.max() <= float(s[k]) * 0.5 + 1e-6
        # and exactly carried in the residual
        np.testing.assert_allclose(
            np.asarray(state.residual[k]), np.asarray(grads[k] - deq),
            rtol=0, atol=1e-6,
        )


def test_error_feedback_unbiased_over_time():
    """Repeatedly compressing the SAME gradient must sum (deq over steps)
    to ~steps * grad: the residual re-injects what quantisation dropped."""
    g = {"w": jnp.array([0.3, -0.004, 0.0021, 1.7], jnp.float32)}
    state = GradCompression.init(g)
    total = jnp.zeros_like(g["w"])
    steps = 50
    for _ in range(steps):
        (q, s), state = state.compress(g)
        total = total + q["w"].astype(jnp.float32) * s["w"]
    np.testing.assert_allclose(
        np.asarray(total / steps), np.asarray(g["w"]), rtol=0.02, atol=1e-4
    )


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >=2 devices")
def test_compressed_psum_matches_mean():
    from repro.dist.meshes import make_mesh

    n = jax.device_count()
    mesh = make_mesh((n,), ("data",))
    key = jax.random.PRNGKey(0)
    grads = jax.random.normal(key, (n, 256))

    def body(g):
        st = GradCompression.init({"g": g[0]})
        out, _ = compressed_psum({"g": g.reshape(256)}, ("data",), st, n)
        return out["g"]

    fn = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=P("data", None), out_specs=P()
        )
    )
    out = np.asarray(fn(grads))
    ref = np.asarray(grads.mean(axis=0))
    # int8 with shared scale: relative error ~1/127 of the max magnitude
    tol = float(np.abs(np.asarray(grads)).max()) / 127 * 1.01 + 1e-6
    assert np.abs(out - ref).max() <= tol
