"""Multi-device mRMR semantics — run under 8 forced host devices.

Executed as a subprocess by tests/test_multidevice.py (so the main pytest
process keeps a single device, per the dry-run isolation rule).
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    FeatureSelector,
    MIScore,
    MRMRSelector,
    PearsonMIScore,
    mrmr_alternative,
    mrmr_conventional,
    mrmr_grid,
    mrmr_reference,
)
from repro.data.synthetic import corral_dataset  # noqa: E402
from repro.dist.meshes import make_mesh  # noqa: E402


def main() -> None:
    assert jax.device_count() == 8, jax.devices()

    rng = np.random.default_rng(0)
    M, N, L = 512, 24, 8
    X = rng.integers(0, 3, (M, N)).astype(np.int32)
    y = (X[:, 5] % 2).astype(np.int32) ^ (rng.random(M) < 0.1)
    y = y.astype(np.int32)
    X[:, 6] = X[:, 5]  # exact duplicate: redundancy must suppress it
    score = MIScore(num_values=3, num_classes=2)

    ref = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), L, score)
    ref_sel = np.asarray(ref.selected)

    # --- conventional encoding over 8-way observation sharding ------------
    mesh8 = make_mesh((8,), ("data",))
    conv = mrmr_conventional(
        jnp.asarray(X), jnp.asarray(y), L, score, mesh=mesh8, obs_axes=("data",)
    )
    np.testing.assert_array_equal(np.asarray(conv.selected), ref_sel)
    np.testing.assert_allclose(conv.gains, ref.gains, rtol=1e-4, atol=1e-5)
    print("conventional 8-way: OK")

    # --- conventional over a 2-axis (pod, data) product --------------------
    mesh_pd = make_mesh((2, 4), ("pod", "data"))
    conv2 = mrmr_conventional(
        jnp.asarray(X), jnp.asarray(y), L, score,
        mesh=mesh_pd, obs_axes=("pod", "data"),
    )
    np.testing.assert_array_equal(np.asarray(conv2.selected), ref_sel)
    print("conventional (pod,data): OK")

    # --- alternative encoding over 8-way feature sharding ------------------
    mesh_m = make_mesh((8,), ("model",))
    alt = mrmr_alternative(
        jnp.asarray(X.T), jnp.asarray(y), L, score,
        mesh=mesh_m, feat_axes=("model",),
    )
    np.testing.assert_array_equal(np.asarray(alt.selected), ref_sel)
    print("alternative 8-way: OK")

    # --- alternative with non-divisible N via FeatureSelector padding ------
    fs = FeatureSelector(
        num_select=L, score=score, layout="alternative",
        mesh=mesh_m, feat_axes=("model",),
    ).fit(X[:, :23], y)  # 23 % 8 != 0
    ref23 = mrmr_reference(jnp.asarray(X[:, :23].T), jnp.asarray(y), L, score)
    np.testing.assert_array_equal(fs.selected_, np.asarray(ref23.selected))
    print("alternative padded: OK")

    # --- grid encoding: observations x features ----------------------------
    mesh_g = make_mesh((4, 2), ("data", "model"))
    grid = mrmr_grid(
        jnp.asarray(X), jnp.asarray(y), L, score,
        mesh=mesh_g, obs_axes=("data",), feat_axes=("model",),
    )
    np.testing.assert_array_equal(np.asarray(grid.selected), ref_sel)
    np.testing.assert_allclose(grid.gains, ref.gains, rtol=1e-4, atol=1e-5)
    print("grid 4x2: OK")

    # --- criterion layer on real meshes: miq agrees engine-for-engine ------
    miq_ref = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), L, score,
                             criterion="miq")
    miq_conv = mrmr_conventional(jnp.asarray(X), jnp.asarray(y), L, score,
                                 mesh=mesh8, criterion="miq")
    miq_alt = mrmr_alternative(jnp.asarray(X.T), jnp.asarray(y), L, score,
                               mesh=mesh_m, criterion="miq")
    miq_grid = mrmr_grid(jnp.asarray(X), jnp.asarray(y), L, score,
                         mesh=mesh_g, criterion="miq")
    for got in (miq_conv, miq_alt, miq_grid):
        np.testing.assert_array_equal(np.asarray(got.selected),
                                      np.asarray(miq_ref.selected))
    assert miq_conv.criterion == "miq" and miq_conv.engine == "conventional"
    print("criterion miq (8-way conv/alt/grid): OK")

    # --- paper-faithful (non-incremental) distributed path -----------------
    conv_f = mrmr_conventional(
        jnp.asarray(X), jnp.asarray(y), L, score,
        mesh=mesh8, incremental=False,
    )
    np.testing.assert_array_equal(np.asarray(conv_f.selected), ref_sel)
    print("conventional paper-faithful: OK")

    # --- Pearson score, feature-sharded, continuous data -------------------
    from repro.data.synthetic import continuous_wide_dataset

    Xc, yc = continuous_wide_dataset(256, 64, seed=3)
    p_ref = mrmr_reference(jnp.asarray(Xc.T), yc.astype(jnp.float32), 6,
                           PearsonMIScore())
    p_alt = mrmr_alternative(jnp.asarray(Xc.T), yc.astype(jnp.float32), 6,
                             PearsonMIScore(), mesh=mesh_m)
    np.testing.assert_array_equal(np.asarray(p_alt.selected),
                                  np.asarray(p_ref.selected))
    print("pearson alternative: OK")

    # --- CorrAL end-to-end on the grid --------------------------------------
    Xb, yb = corral_dataset(2048, 32, seed=7, flip_prob=0.02)
    res = FeatureSelector(
        num_select=8, score=MIScore(2, 2), layout="grid",
        mesh=mesh_g,
    ).fit(np.asarray(Xb, dtype=np.int32), np.asarray(yb))
    assert len(set(res.selected_.tolist()) & set(range(8))) >= 6
    print("corral grid e2e: OK")

    # --- MRMRSelector front door: every encoding on real 8-device meshes ---
    for encoding, msh in [
        ("conventional", mesh8),
        ("alternative", mesh_m),
        ("grid", mesh_g),
    ]:
        sel = MRMRSelector(
            num_select=L, score=score, encoding=encoding, mesh=msh
        ).fit(X, y)
        np.testing.assert_array_equal(sel.selected_, ref_sel)
        print(f"MRMRSelector {encoding} (explicit mesh): OK")

    # auto-planned: the selector builds its own mesh from the 8 devices
    for shape_hint, Xa, ya in [
        ("tall", X, y),
        ("wide", X[:20], y[:20]),
    ]:
        sel = MRMRSelector(num_select=4, score=score).fit(Xa, ya)
        want = mrmr_reference(
            jnp.asarray(Xa.T), jnp.asarray(ya), 4, score
        )
        np.testing.assert_array_equal(sel.selected_, np.asarray(want.selected))
        print(f"MRMRSelector auto ({shape_hint} -> "
              f"{sel.plan_.encoding}, mesh={sel.plan_.mesh_shape}): OK")

    print("ALL-MD-MRMR-OK")


if __name__ == "__main__":
    main()
