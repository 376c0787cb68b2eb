"""Multi-device mRMR semantics — run under 8 forced host devices.

Executed once per module as a subprocess by tests/test_multidevice.py, so
the main pytest process keeps a single device.  Each check is a function
registered in ``CHECKS``; the script runs every one of them and prints one
line per check, ``CHECK <name> OK`` or ``CHECK <name> FAIL`` followed by
its traceback, so a failing check hides none of the others.  The test
module imports this file only for the names in ``CHECKS``: the device
flag is set only when it runs as a script.
"""

import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", "")
    )

import sys  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402

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

CHECKS = {}


def check(fn):
    CHECKS[fn.__name__.removeprefix("check_")] = fn
    return fn


def _setup():
    """The shared dataset, its reference selection and the meshes."""
    rng = np.random.default_rng(0)
    M, N, L = 512, 24, 8
    X = rng.integers(0, 3, (M, N)).astype(np.int32)
    y = (X[:, 5] % 2).astype(np.int32) ^ (rng.random(M) < 0.1)
    y = y.astype(np.int32)
    X[:, 6] = X[:, 5]  # exact duplicate: redundancy must suppress it
    # A wide dataset for the resident-block checks: 203 columns pad to 208
    # over 8 feature shards.
    Xw = rng.integers(0, 3, (60, 203)).astype(np.int8)
    yw = ((Xw[:, 0] + Xw[:, 7]) % 2).astype(np.int8)
    score = MIScore(num_values=3, num_classes=2)
    ref = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), L, score)
    return types.SimpleNamespace(
        X=X, y=y, Xw=Xw, yw=yw, L=L, score=score, ref=ref,
        ref_sel=np.asarray(ref.selected),
        mesh8=make_mesh((8,), ("data",)),
        mesh_m=make_mesh((8,), ("model",)),
        mesh_g=make_mesh((4, 2), ("data", "model")),
    )


@check
def check_conventional_8way(d):
    conv = mrmr_conventional(
        jnp.asarray(d.X), jnp.asarray(d.y), d.L, d.score,
        mesh=d.mesh8, obs_axes=("data",),
    )
    np.testing.assert_array_equal(np.asarray(conv.selected), d.ref_sel)
    np.testing.assert_allclose(conv.gains, d.ref.gains, rtol=1e-4, atol=1e-5)


@check
def check_conventional_pod_data(d):
    mesh_pd = make_mesh((2, 4), ("pod", "data"))
    conv2 = mrmr_conventional(
        jnp.asarray(d.X), jnp.asarray(d.y), d.L, d.score,
        mesh=mesh_pd, obs_axes=("pod", "data"),
    )
    np.testing.assert_array_equal(np.asarray(conv2.selected), d.ref_sel)


@check
def check_alternative_8way(d):
    alt = mrmr_alternative(
        jnp.asarray(d.X.T), jnp.asarray(d.y), d.L, d.score,
        mesh=d.mesh_m, feat_axes=("model",),
    )
    np.testing.assert_array_equal(np.asarray(alt.selected), d.ref_sel)


@check
def check_alternative_padded(d):
    # 23 features over 8 devices: FeatureSelector pads the feature axis.
    fs = FeatureSelector(
        num_select=d.L, score=d.score, layout="alternative",
        mesh=d.mesh_m, feat_axes=("model",),
    ).fit(d.X[:, :23], d.y)
    ref23 = mrmr_reference(
        jnp.asarray(d.X[:, :23].T), jnp.asarray(d.y), d.L, d.score
    )
    np.testing.assert_array_equal(fs.selected_, np.asarray(ref23.selected))


@check
def check_grid_4x2(d):
    grid = mrmr_grid(
        jnp.asarray(d.X), jnp.asarray(d.y), d.L, d.score,
        mesh=d.mesh_g, obs_axes=("data",), feat_axes=("model",),
    )
    np.testing.assert_array_equal(np.asarray(grid.selected), d.ref_sel)
    np.testing.assert_allclose(grid.gains, d.ref.gains, rtol=1e-4, atol=1e-5)


@check
def check_criterion_miq(d):
    # The criterion layer on real meshes: miq agrees engine-for-engine.
    X, y = jnp.asarray(d.X), jnp.asarray(d.y)
    miq_ref = mrmr_reference(X.T, y, d.L, d.score, criterion="miq")
    miq_conv = mrmr_conventional(X, y, d.L, d.score, mesh=d.mesh8,
                                 criterion="miq")
    miq_alt = mrmr_alternative(X.T, y, d.L, d.score, mesh=d.mesh_m,
                               criterion="miq")
    miq_grid = mrmr_grid(X, y, d.L, d.score, mesh=d.mesh_g, criterion="miq")
    for got in (miq_conv, miq_alt, miq_grid):
        np.testing.assert_array_equal(np.asarray(got.selected),
                                      np.asarray(miq_ref.selected))
    assert miq_conv.criterion == "miq" and miq_conv.engine == "conventional"


@check
def check_conventional_paper_faithful(d):
    # The non-incremental (recompute) distributed path.
    conv_f = mrmr_conventional(
        jnp.asarray(d.X), jnp.asarray(d.y), d.L, d.score,
        mesh=d.mesh8, incremental=False,
    )
    np.testing.assert_array_equal(np.asarray(conv_f.selected), d.ref_sel)


@check
def check_pearson_alternative(d):
    # Pearson score, feature-sharded, continuous data.
    from repro.data.synthetic import continuous_wide_dataset

    Xc, yc = continuous_wide_dataset(256, 64, seed=3)
    p_ref = mrmr_reference(jnp.asarray(Xc.T), yc.astype(jnp.float32), 6,
                           PearsonMIScore())
    p_alt = mrmr_alternative(jnp.asarray(Xc.T), yc.astype(jnp.float32), 6,
                             PearsonMIScore(), mesh=d.mesh_m)
    np.testing.assert_array_equal(np.asarray(p_alt.selected),
                                  np.asarray(p_ref.selected))


@check
def check_corral_grid_e2e(d):
    Xb, yb = corral_dataset(2048, 32, seed=7, flip_prob=0.02)
    res = FeatureSelector(
        num_select=8, score=MIScore(2, 2), layout="grid", mesh=d.mesh_g,
    ).fit(np.asarray(Xb, dtype=np.int32), np.asarray(yb))
    assert len(set(res.selected_.tolist()) & set(range(8))) >= 6


@check
def check_selector_explicit_mesh(d):
    # The MRMRSelector front door: every encoding on real 8-device meshes.
    for encoding, msh in [
        ("conventional", d.mesh8),
        ("alternative", d.mesh_m),
        ("grid", d.mesh_g),
    ]:
        sel = MRMRSelector(
            num_select=d.L, score=d.score, encoding=encoding, mesh=msh
        ).fit(d.X, d.y)
        np.testing.assert_array_equal(sel.selected_, d.ref_sel, err_msg=encoding)


@check
def check_selector_auto(d):
    # Auto-planned: the selector builds its own mesh from the 8 devices.
    for shape_hint, Xa, ya in [
        ("tall", d.X, d.y),
        ("wide", d.X[:20], d.y[:20]),
    ]:
        sel = MRMRSelector(num_select=4, score=d.score).fit(Xa, ya)
        want = mrmr_reference(jnp.asarray(Xa.T), jnp.asarray(ya), 4, d.score)
        np.testing.assert_array_equal(
            sel.selected_, np.asarray(want.selected),
            err_msg=f"{shape_hint} -> {sel.plan_.encoding}, "
                    f"mesh={sel.plan_.mesh_shape}",
        )


@check
def check_resident_bitwise_streamed(d):
    # Streamed fits counting from device-resident blocks.  The CPU reports
    # no device memory, so the budget is set by hand: the resident fit
    # must be bitwise the streamed fit, on a 4-device obs-sharded mesh
    # (last block short) and on a feature-sharded wide mesh (203 columns
    # padded to 208).
    import repro.dist.streaming as dist
    from repro.data.sources import ArraySource

    mesh4 = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    Xw, yw = d.Xw, d.yw
    budget_fn = dist.resident_budget
    try:
        for name, msh, Xs, ys, bo in [
            ("obs 4-way", mesh4, d.X, d.y, 100),
            ("wide feature 8-way", d.mesh_m, Xw, yw, 16),
        ]:
            for crit in ("mid", "jmi"):
                for q in (1, 3):
                    fits = []
                    for budget in (None, 1 << 40):
                        dist.resident_budget = lambda devices, b=budget: b
                        fits.append(MRMRSelector(
                            num_select=5, score=d.score, criterion=crit,
                            mesh=msh, block_obs=bo, batch_candidates=q,
                        ).fit(ArraySource(Xs, ys)))
                    streamed, resident = fits
                    where = f"{name} {crit} q={q}"
                    np.testing.assert_array_equal(
                        resident.selected_, streamed.selected_, err_msg=where
                    )
                    for got, want in [(resident.gains_, streamed.gains_),
                                      (resident.scores_, streamed.scores_)]:
                        np.testing.assert_array_equal(
                            got.view(np.int32), want.view(np.int32),
                            err_msg=where,
                        )
                    io = resident.result_.io
                    assert io["resident_passes"] == io["passes"] - 1 > 0, io
                    assert streamed.result_.io["resident_passes"] == 0
    finally:
        dist.resident_budget = budget_fn


@check
def check_resident_target_cut_sharding(d):
    # The device-cut target carries the placer's target sharding.
    from repro.dist.streaming import BlockPlacer, ResidentBlocks

    mesh4 = make_mesh((4,), ("data",), devices=jax.devices()[:4])
    Xw, yw = d.Xw, d.yw
    for msh, obs, feat in [(mesh4, ("data",), ()), (d.mesh_m, (), ("model",)),
                           (d.mesh_g, ("data",), ("model",))]:
        placer = BlockPlacer(16, msh, obs, feat, num_features=203)
        Xh, yh = Xw[:13], yw[:13]  # 13 rows pad to 16
        triple = placer(Xh, yh)
        kept = ResidentBlocks(placer)
        list(kept.keep([triple]))
        for cols, cond in [(7, None), (202, 2), ([3, 150, 202], None),
                           ([3, 150, 202], 2)]:
            ((_, cut, _),) = kept.triples(placer.place_ids(cols), cond)
            host = Xh[:, cols].T
            if cond is not None:
                host = (host.astype(np.int64) * cond + yh).astype(np.int32)
            placed = placer.place(placer.stage(Xh, host))[1]
            assert cut.sharding.is_equivalent_to(placed.sharding, cut.ndim), (
                cut.sharding, placed.sharding,
            )
            assert cut.dtype == placed.dtype
            np.testing.assert_array_equal(np.asarray(cut), np.asarray(placed))
        kept.delete()


def main() -> int:
    assert jax.device_count() == 8, jax.devices()
    d = _setup()
    failed = 0
    for name, fn in CHECKS.items():
        try:
            fn(d)
        except Exception:  # noqa: BLE001 — report every check, then exit 1
            failed += 1
            print(f"CHECK {name} FAIL")
            traceback.print_exc(file=sys.stdout)
        else:
            print(f"CHECK {name} OK")
        sys.stdout.flush()
    print("ALL-MD-MRMR-OK" if not failed else f"{failed} CHECKS FAILED")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
