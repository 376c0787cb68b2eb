"""DataSource protocol + streaming engine: block-size invariance of
sources, streaming-vs-in-memory selection equivalence (the out-of-core
acceptance bar), placement, and the front-door API guards."""

import numpy as np
import jax
import pytest

from repro import CustomScore, MIScore, MRMRSelector, PearsonMIScore
from repro.core.criteria import resolve_criterion
from repro.core.streaming import mrmr_streaming
from repro.data.binning import BinnedSource
from repro.data.sources import (
    ArraySource,
    CSVSource,
    CorralSource,
    DataSource,
    NpySource,
    as_source,
)
from repro.dist import BlockPlacer, PrefetchPlacer, factor_mesh, make_mesh


@pytest.fixture(scope="module")
def corral():
    X, y = CorralSource(1500, 24, seed=3).materialize()
    return X, y


@pytest.fixture(scope="module")
def corral_selected(corral):
    X, y = corral
    sel = MRMRSelector(num_select=5, score=MIScore(2, 2)).fit(X, y)
    return sel.selected_, sel.gains_


class TestSources:
    @pytest.mark.parametrize("block_obs", [1, 7, 100, 1500, 4096])
    def test_array_source_blocks_concatenate(self, corral, block_obs):
        X, y = corral
        src = ArraySource(X, y)
        assert (src.num_obs, src.num_features) == X.shape
        blocks = list(src.iter_blocks(block_obs))
        assert all(b[0].shape[0] <= block_obs for b in blocks)
        np.testing.assert_array_equal(np.concatenate([b[0] for b in blocks]), X)
        np.testing.assert_array_equal(np.concatenate([b[1] for b in blocks]), y)

    def test_corral_block_size_invariance(self):
        # The generated dataset must be a pure function of (seed, shape),
        # independent of how it is blocked — including sizes that straddle
        # the internal generation-chunk boundary.
        src = CorralSource(10_000, 16, seed=7)
        a = src.materialize(block_obs=613)
        b = src.materialize(block_obs=8192)
        c = src.materialize(block_obs=10_000)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
        np.testing.assert_array_equal(a[0], c[0])

    def test_npy_source_memmap_roundtrip(self, tmp_path, corral):
        X, y = corral
        src = CorralSource(1500, 24, seed=3)
        xp, yp = src.to_npy(str(tmp_path / "X.npy"), str(tmp_path / "y.npy"),
                            block_obs=600)
        npy = NpySource(xp, yp)
        # The backing array must stay a memmap, not a loaded copy.
        assert isinstance(npy.X, np.memmap)
        Xr, yr = npy.materialize(block_obs=333)
        np.testing.assert_array_equal(Xr, X)
        np.testing.assert_array_equal(yr, y)

    def test_csv_source(self, tmp_path):
        rng = np.random.default_rng(0)
        X = rng.integers(0, 3, size=(57, 4))
        y = rng.integers(0, 2, size=57)
        path = tmp_path / "data.csv"
        header = "f0,f1,f2,f3,target\n"
        rows = "\n".join(
            ",".join(map(str, list(xr) + [yi])) for xr, yi in zip(X, y)
        )
        path.write_text(header + rows + "\n")
        src = CSVSource(str(path), dtype=np.int32)
        assert src.num_obs == 57 and src.num_features == 4
        Xr, yr = src.materialize(block_obs=13)
        np.testing.assert_array_equal(Xr, X)
        np.testing.assert_array_equal(yr, y)

    def test_csv_blank_runs_do_not_truncate(self, tmp_path):
        # A run of blank lines longer than the block must not read as EOF.
        X = np.arange(40).reshape(20, 2)
        y = np.arange(20)
        body = []
        for xr, yi in zip(X, y):
            body.append(",".join(map(str, list(xr) + [yi])))
            if yi == 9:
                body.extend([""] * 8)  # blank run wider than block_obs=5
        path = tmp_path / "gaps.csv"
        path.write_text("\n".join(body) + "\n")
        src = CSVSource(str(path), dtype=np.int32)
        Xr, yr = src.materialize(block_obs=5)
        np.testing.assert_array_equal(Xr, X)
        np.testing.assert_array_equal(yr, y)

    def test_stats_discrete(self, corral):
        X, y = corral
        st = ArraySource(X, y).stats(block_obs=256)
        assert st.discrete and st.num_values == 2 and st.num_classes == 2
        st2 = ArraySource(X.astype(np.float32), y).stats()
        assert not st2.discrete

    def test_as_source_guards(self, corral):
        X, y = corral
        src = ArraySource(X, y)
        assert as_source(src) is src
        with pytest.raises(ValueError, match="alone"):
            as_source(src, y)
        with pytest.raises(ValueError, match="target"):
            as_source(X)


class TestStreamingEquivalence:
    # 999 does not divide 1500; 4096 exceeds it — both must still match.
    @pytest.mark.parametrize("block_obs", [128, 999, 4096])
    def test_mi_matches_in_memory(self, corral, corral_selected, block_obs):
        X, y = corral
        sel = MRMRSelector(
            num_select=5, score=MIScore(2, 2), block_obs=block_obs
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(sel.selected_, corral_selected[0])
        np.testing.assert_allclose(sel.gains_, corral_selected[1],
                                   rtol=1e-4, atol=1e-5)
        assert sel.plan_.encoding == "streaming"

    @pytest.mark.parametrize("block_obs", [100, 257, 2048])
    def test_pearson_matches_in_memory(self, block_obs):
        from repro.data.synthetic import continuous_wide_dataset

        X, y = continuous_wide_dataset(1024, 32, seed=2)
        X, y = np.asarray(X), np.asarray(y)
        want = MRMRSelector(num_select=5, score=PearsonMIScore()).fit(X, y)
        got = MRMRSelector(
            num_select=5, score=PearsonMIScore(), block_obs=block_obs
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(got.selected_, want.selected_)
        np.testing.assert_allclose(got.gains_, want.gains_,
                                   rtol=1e-3, atol=1e-4)

    def test_pearson_large_mean_no_cancellation(self):
        # Uncentered f32 moments cancel catastrophically when |mean| >> std
        # (sxx ~ n·mu^2 swamps the signal); the shifted accumulation must
        # keep streaming selections identical to in-memory ones.
        rng = np.random.default_rng(9)
        X = (1e4 + rng.normal(size=(50_000, 12))).astype(np.float32)
        y = (0.5 * X[:, 3] + 0.3 * X[:, 7]
             + rng.normal(size=50_000)).astype(np.float32)
        want = MRMRSelector(num_select=4, score=PearsonMIScore()).fit(X, y)
        got = MRMRSelector(
            num_select=4, score=PearsonMIScore(), block_obs=8192
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(got.selected_, want.selected_)
        np.testing.assert_allclose(got.gains_, want.gains_,
                                   rtol=5e-2, atol=1e-3)

    def test_npy_memmap_end_to_end(self, tmp_path, corral_selected):
        # The acceptance bar: a memmapped on-disk dataset streamed in
        # blocks far smaller than the data selects identical features.
        src = CorralSource(1500, 24, seed=3)
        xp, yp = src.to_npy(str(tmp_path / "X.npy"), str(tmp_path / "y.npy"))
        sel = MRMRSelector(num_select=5, block_obs=256).fit(NpySource(xp, yp))
        np.testing.assert_array_equal(sel.selected_, corral_selected[0])
        assert sel.plan_.encoding == "streaming"
        assert sel.plan_.block_obs == 256
        # auto score resolution came from the source's streaming scan
        assert isinstance(sel.plan_.score, MIScore)

    def test_streaming_on_mesh(self, corral, corral_selected):
        X, y = corral
        n_dev = len(jax.devices())
        mesh = make_mesh((n_dev,), ("data",))
        sel = MRMRSelector(
            num_select=5, score=MIScore(2, 2), mesh=mesh, block_obs=200
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(sel.selected_, corral_selected[0])
        # block_obs is rounded up to the mesh extent by the placer
        assert sel.mesh_ is mesh

    def test_arrays_with_streaming_encoding(self, corral, corral_selected):
        X, y = corral
        sel = MRMRSelector(
            num_select=5, score=MIScore(2, 2), encoding="streaming",
            block_obs=512,
        ).fit(X, y)
        np.testing.assert_array_equal(sel.selected_, corral_selected[0])
        assert sel.plan_.encoding == "streaming"

    def test_transform_from_source(self, corral):
        X, y = corral
        sel = MRMRSelector(num_select=4, block_obs=300).fit(ArraySource(X, y))
        Xt = sel.transform(ArraySource(X, y))
        np.testing.assert_array_equal(Xt, X[:, sel.selected_])

    def test_fit_transform_from_source_alone(self, corral):
        X, y = corral
        Xt = MRMRSelector(num_select=3, block_obs=300).fit_transform(
            ArraySource(X, y)
        )
        assert Xt.shape == (X.shape[0], 3)

    def test_driver_function_direct(self, corral, corral_selected):
        X, y = corral
        res = mrmr_streaming((X, y), 5, MIScore(2, 2), block_obs=500)
        np.testing.assert_array_equal(np.asarray(res.selected),
                                      corral_selected[0])


class TestCriterionStreaming:
    """Criterion x streaming acceptance: every criterion's streamed
    selections match the same criterion's in-memory selections at every
    tested block size and mesh."""

    # 999 does not divide 1500; 4096 exceeds it — both must still match.
    @pytest.mark.parametrize("block_obs", [128, 999, 4096])
    def test_miq_matches_in_memory(self, corral, block_obs):
        X, y = corral
        want = MRMRSelector(num_select=5, score=MIScore(2, 2),
                            criterion="miq").fit(X, y)
        got = MRMRSelector(
            num_select=5, score=MIScore(2, 2), criterion="miq",
            block_obs=block_obs,
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(got.selected_, want.selected_)
        # gains: the quotient amplifies the tiny bf16-onehot-vs-int32-counts
        # MI differences when mean redundancy is near zero; selection
        # identity is the acceptance bar
        np.testing.assert_allclose(got.gains_, want.gains_,
                                   rtol=5e-2, atol=1e-5)
        assert got.plan_.encoding == "streaming"
        assert got.result_.criterion == "miq"

    def test_miq_on_obs_mesh(self, corral):
        X, y = corral
        n_dev = len(jax.devices())
        mesh = make_mesh((n_dev,), ("data",))
        want = MRMRSelector(num_select=5, score=MIScore(2, 2),
                            criterion="miq").fit(X, y)
        got = MRMRSelector(
            num_select=5, score=MIScore(2, 2), criterion="miq", mesh=mesh,
            block_obs=200,
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(got.selected_, want.selected_)

    def test_miq_feature_sharded_wide(self):
        # wide regime: statistics state sharded over features, miq fold on
        # the host — must match the in-memory alternative engine.
        X, y = CorralSource(256, 1024, seed=5).materialize()
        want = MRMRSelector(num_select=5, score=MIScore(2, 2),
                            criterion="miq", encoding="alternative").fit(X, y)
        mesh = make_mesh((len(jax.devices()),), ("model",))
        got = MRMRSelector(
            num_select=5, score=MIScore(2, 2), criterion="miq", mesh=mesh,
            block_obs=100,
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(got.selected_, want.selected_)

    def test_maxrel_single_pass_io(self, corral):
        # needs_redundancy=False must collapse streaming I/O to ONE pass
        # over the source (plus nothing else: score given explicitly, so
        # no stats() scan either).
        X, y = corral
        passes = []

        class Counting(ArraySource):
            def iter_blocks(self, block_obs):
                passes.append(block_obs)
                return super().iter_blocks(block_obs)

        sel = MRMRSelector(
            num_select=5, score=MIScore(2, 2), criterion="maxrel",
            block_obs=300,
        ).fit(Counting(X, y))
        assert len(passes) == 1
        want = MRMRSelector(num_select=5, score=MIScore(2, 2),
                            criterion="maxrel").fit(X, y)
        np.testing.assert_array_equal(sel.selected_, want.selected_)

    def test_mid_trajectory_identical_to_in_memory(self, corral,
                                                   corral_selected):
        # mid through the criterion layer keeps the pre-criterion
        # streaming contract: selections equal the in-memory engines.
        X, y = corral
        sel = MRMRSelector(
            num_select=5, score=MIScore(2, 2), criterion="mid",
            block_obs=300,
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(sel.selected_, corral_selected[0])
        np.testing.assert_allclose(sel.gains_, corral_selected[1],
                                   rtol=1e-4, atol=1e-5)


class TestStreamingPrimitives:
    def test_mi_accumulate_equals_batch(self, corral):
        import jax.numpy as jnp

        X, y = corral
        score = MIScore(2, 2)
        state = score.init_state(X.shape[1], "class")
        state = score.accumulate(state, jnp.asarray(X[:700]), jnp.asarray(y[:700]))
        state = score.accumulate(state, jnp.asarray(X[700:]), jnp.asarray(y[700:]))
        got = np.asarray(score.finalize(state))
        want = np.asarray(score.relevance(jnp.asarray(X.T), jnp.asarray(y)))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_pearson_valid_mask_drops_padding(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        X = rng.normal(size=(64, 6)).astype(np.float32)
        t = rng.normal(size=64).astype(np.float32)
        score = PearsonMIScore()
        full = score.accumulate(score.init_state(6), jnp.asarray(X),
                                jnp.asarray(t))
        Xp = np.concatenate([X, np.full((16, 6), 1e6, np.float32)])
        tp = np.concatenate([t, np.full((16,), -1e6, np.float32)])
        valid = np.arange(80) < 64
        masked = score.accumulate(
            score.init_state(6), jnp.asarray(Xp), jnp.asarray(tp),
            jnp.asarray(valid),
        )
        np.testing.assert_allclose(
            np.asarray(score.finalize(masked)),
            np.asarray(score.finalize(full)), rtol=1e-5, atol=1e-6,
        )

    def test_block_placer_rounds_up_to_mesh(self):
        n_dev = len(jax.devices())
        mesh = make_mesh((n_dev,), ("data",))
        placer = BlockPlacer(100, mesh, ("data",))
        assert placer.block_obs % n_dev == 0
        X, t, valid = placer(np.zeros((37, 3), np.int8), np.zeros(37, np.int8))
        assert X.shape[0] == placer.block_obs
        assert int(np.asarray(valid).sum()) == 37

    def test_block_placer_rejects_oversized(self):
        placer = BlockPlacer(16)
        with pytest.raises(ValueError, match="exceeds"):
            placer(np.zeros((17, 2), np.int8), np.zeros(17, np.int8))

    def test_block_placer_rejects_axisless_mesh(self):
        mesh = make_mesh((1,), ("model",))
        with pytest.raises(ValueError, match="no axis"):
            BlockPlacer(16, mesh, ("data",))

    def test_block_placer_pads_features(self):
        mesh = make_mesh((len(jax.devices()),), ("model",))
        placer = BlockPlacer(8, mesh, (), ("model",), num_features=5)
        n_pad = placer.padded_features
        assert n_pad % len(jax.devices()) == 0 and n_pad >= 5
        X, t, valid = placer(np.ones((8, 5), np.int8), np.zeros(8, np.int8))
        assert X.shape == (8, n_pad)
        # pad columns are zero-filled, real columns intact
        assert np.asarray(X)[:, :5].all()
        assert not np.asarray(X)[:, 5:].any()

    def test_block_placer_rejects_feature_mismatch(self):
        placer = BlockPlacer(8, num_features=5)
        with pytest.raises(ValueError, match="features"):
            placer(np.zeros((4, 7), np.int8), np.zeros(4, np.int8))

    def test_block_placer_feature_sharding_needs_num_features(self):
        # Feature sharding without the global feature count would fail
        # late (opaque device_put error) or silently replicate the state.
        mesh = make_mesh((len(jax.devices()),), ("model",))
        with pytest.raises(ValueError, match="num_features"):
            BlockPlacer(8, mesh, (), ("model",))

    def test_state_sharded_over_features(self):
        # The wide-regime memory claim: per-device statistics hold
        # padded_features / shards feature rows, not all of them.
        n_dev = len(jax.devices())
        mesh = make_mesh((n_dev,), ("model",))
        placer = BlockPlacer(64, mesh, (), ("model",), num_features=32)
        state = placer.place_state(
            MIScore(2, 2).init_state(placer.padded_features)
        )
        shard_rows = {s.data.shape[0] for s in state.addressable_shards}
        assert shard_rows == {placer.padded_features // n_dev}

    @pytest.mark.parametrize(
        "criterion,programs,state_shapes",
        [
            ("mid", 1, [(24, 2, 2)]),
            # jmi's redundancy passes fuse the class into the pair target.
            ("jmi", 2, [(24, 2, 2), (24, 2, 4)]),
        ],
    )
    def test_accumulate_log_records_what_the_fit_ran(
        self, corral, criterion, programs, state_shapes
    ):
        from repro.core.streaming import AccumulateLog

        X, y = corral
        with AccumulateLog() as log:
            MRMRSelector(
                num_select=3, score=MIScore(2, 2), criterion=criterion,
                block_obs=512,
            ).fit(ArraySource(X, y))
        assert [e.args[0].shape for e in log.entries] == state_shapes
        texts = log.compiled_texts()
        assert len(texts) == programs and all("HloModule" in t for t in texts)
        assert log.entries[0].output.shape == state_shapes[0]

    def test_accumulate_log_off_outside_with(self, corral):
        from repro.core.streaming import AccumulateLog

        X, y = corral
        log = AccumulateLog()
        with log:
            pass
        MRMRSelector(num_select=2, score=MIScore(2, 2), block_obs=512).fit(
            ArraySource(X, y)
        )
        assert log.entries == []


@pytest.fixture(scope="module")
def wide():
    # 256 obs x 1024 feat: m/n = 0.25, the paper's wide/bioinformatics
    # regime where statistics must shard over features.
    X, y = CorralSource(256, 1024, seed=5).materialize()
    return X, y


@pytest.fixture(scope="module")
def wide_alternative(wide):
    X, y = wide
    sel = MRMRSelector(
        num_select=5, score=MIScore(2, 2), encoding="alternative"
    ).fit(X, y)
    return sel.selected_, sel.gains_


class TestWideStreaming:
    """Wide-regime acceptance: feature-sharded and 2-D streaming selections
    identical to the in-memory alternative engine at every block size."""

    # 64 divides 256; 100 doesn't; 999 exceeds it — all must match.
    @pytest.mark.parametrize("block_obs", [64, 100, 999])
    def test_feature_sharded_matches_alternative(
        self, wide, wide_alternative, block_obs
    ):
        X, y = wide
        mesh = make_mesh((len(jax.devices()),), ("model",))
        sel = MRMRSelector(
            num_select=5, score=MIScore(2, 2), mesh=mesh, block_obs=block_obs
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(sel.selected_, wide_alternative[0])
        np.testing.assert_allclose(sel.gains_, wide_alternative[1],
                                   rtol=1e-4, atol=1e-5)
        assert sel.plan_.encoding == "streaming"
        assert sel.plan_.obs_axes == () and sel.plan_.feat_axes == ("model",)

    def test_non_divisible_feature_count(self):
        # 30 features don't divide a multi-device feature mesh: the placer
        # pads columns, the engine slices the junk statistics rows off.
        X, y = CorralSource(200, 30, seed=1).materialize()
        want = MRMRSelector(
            num_select=4, score=MIScore(2, 2), encoding="alternative"
        ).fit(X, y)
        mesh = make_mesh((len(jax.devices()),), ("model",))
        got = MRMRSelector(
            num_select=4, score=MIScore(2, 2), mesh=mesh, block_obs=64
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(got.selected_, want.selected_)

    def test_grid_2d_matches_alternative(self, wide, wide_alternative):
        X, y = wide
        od, fd = factor_mesh(len(jax.devices()))
        mesh = make_mesh((od, fd), ("data", "model"))
        sel = MRMRSelector(
            num_select=5, score=MIScore(2, 2), mesh=mesh, block_obs=100
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(sel.selected_, wide_alternative[0])
        assert sel.plan_.obs_axes == ("data",)
        assert sel.plan_.feat_axes == ("model",)
        # the plan reports the EFFECTIVE block size (rounded to obs extent)
        assert sel.plan_.block_obs == -(-100 // od) * od

    def test_pearson_feature_sharded(self):
        rng = np.random.default_rng(7)
        X = rng.normal(size=(200, 600)).astype(np.float32)
        y = (0.5 * X[:, 3] + 0.3 * X[:, 10]
             + 0.1 * rng.normal(size=200)).astype(np.float32)
        want = MRMRSelector(
            num_select=4, score=PearsonMIScore(), encoding="alternative"
        ).fit(X, y)
        mesh = make_mesh((len(jax.devices()),), ("model",))
        got = MRMRSelector(
            num_select=4, score=PearsonMIScore(), mesh=mesh, block_obs=64
        ).fit(ArraySource(X, y))
        np.testing.assert_array_equal(got.selected_, want.selected_)
        np.testing.assert_allclose(got.gains_, want.gains_,
                                   rtol=1e-3, atol=1e-4)

    def test_auto_wide_plan_runs_feature_sharded(self, wide, wide_alternative):
        # No user mesh: the aspect rule itself must route a wide source to
        # feature sharding (or unsharded on one device) and still match.
        X, y = wide
        sel = MRMRSelector(num_select=5, score=MIScore(2, 2),
                           block_obs=100).fit(ArraySource(X, y))
        np.testing.assert_array_equal(sel.selected_, wide_alternative[0])
        assert sel.plan_.obs_axes == ()
        if len(jax.devices()) > 1:
            assert sel.plan_.feat_axes == ("model",)

    def test_stream_plan_aspect_rule(self):
        # §III rule on an 8-device budget (plan-only, no mesh built):
        # tall -> obs-sharded, wide -> feat-sharded, both-large -> 2-D.
        score = MIScore(2, 2)
        sel = MRMRSelector(num_select=2, devices=8)
        z = lambda m, n: ArraySource(
            np.zeros((m, n), np.int8), np.zeros(m, np.int8)
        )
        tall = sel._resolve_stream_plan(z(4096, 64), score)
        assert tall.obs_axes == ("data",) and tall.feat_axes == ()
        assert tall.mesh_shape == (8,)
        wide = sel._resolve_stream_plan(z(64, 4096), score)
        assert wide.obs_axes == () and wide.feat_axes == ("model",)
        assert wide.mesh_shape == (8,)
        grid = sel._resolve_stream_plan(z(1024, 1024), score)
        assert grid.obs_axes == ("data",) and grid.feat_axes == ("model",)
        assert len(grid.mesh_shape) == 2 and min(grid.mesh_shape) > 1

    def test_plan_records_effective_block_obs(self):
        # Satellite: plan_ must report the placer's rounded block size,
        # not the user's requested one.
        score = MIScore(2, 2)
        sel = MRMRSelector(num_select=2, devices=8, block_obs=100)
        src = ArraySource(np.zeros((4096, 64), np.int8),
                          np.zeros(4096, np.int8))
        plan = sel._resolve_stream_plan(src, score)
        assert plan.block_obs == 104  # rounded up to the 8-way obs extent

    def test_effective_block_obs_end_to_end(self, corral):
        X, y = corral
        n_dev = len(jax.devices())
        mesh = make_mesh((n_dev,), ("data",))
        sel = MRMRSelector(num_select=2, score=MIScore(2, 2), mesh=mesh,
                           block_obs=200).fit(ArraySource(X, y))
        assert sel.plan_.block_obs == -(-200 // n_dev) * n_dev


class TestPrefetch:
    def test_prefetch_depths_match_synchronous(self, corral, corral_selected):
        X, y = corral
        for prefetch in (0, 1, 3):
            sel = MRMRSelector(
                num_select=5, score=MIScore(2, 2), block_obs=300,
                prefetch=prefetch,
            ).fit(ArraySource(X, y))
            np.testing.assert_array_equal(sel.selected_, corral_selected[0])

    def test_prefetch_propagates_source_errors(self, corral):
        X, y = corral

        class Boom(ArraySource):
            def iter_blocks(self, block_obs):
                it = super().iter_blocks(block_obs)
                yield next(it)
                raise RuntimeError("disk died")

        with pytest.raises(RuntimeError, match="disk died"):
            MRMRSelector(
                num_select=2, score=MIScore(2, 2), block_obs=300, prefetch=2
            ).fit(Boom(X, y))

    def test_prefetch_placer_stream(self):
        placer = BlockPlacer(4, num_features=3)
        blocks = [
            (np.full((4, 3), i, np.int8), np.full((4,), i, np.int8))
            for i in range(5)
        ]
        staged = (placer.stage(X, t) for X, t in blocks)
        out = list(PrefetchPlacer(placer, depth=2).stream(staged))
        assert len(out) == 5
        for i, (X, t, valid) in enumerate(out):
            assert int(np.asarray(X)[0, 0]) == i
            assert np.asarray(valid).all()

    def test_prefetch_placer_abandoned_consumer_stops_worker(self):
        import threading

        placer = BlockPlacer(2, num_features=1)
        produced = []

        def blocks():
            for i in range(1000):
                produced.append(i)
                yield placer.stage(np.zeros((2, 1), np.int8), np.zeros(2, np.int8))

        stream = PrefetchPlacer(placer, depth=1).stream(blocks())
        next(stream)
        stream.close()  # abandon: the worker must stop, not run to 1000
        deadline = len(produced)
        assert deadline < 1000
        # no stray prefetch threads left running
        assert not any(
            t.name == "block-prefetch" and t.is_alive()
            for t in threading.enumerate()
        )

    def test_depth_guard(self):
        with pytest.raises(ValueError, match="depth"):
            PrefetchPlacer(BlockPlacer(8), depth=0)
        with pytest.raises(ValueError, match="prefetch"):
            mrmr_streaming(
                (np.zeros((8, 4), np.int8), np.zeros(8, np.int8)),
                2, MIScore(2, 2), prefetch=-1,
            )


class TestSatelliteRegressions:
    def test_array_source_rejects_2d_target(self, corral):
        # (M, k) targets used to slip through the leading-dim check and
        # mis-shape Pearson streaming accumulation downstream.
        X, y = corral
        with pytest.raises(ValueError, match="bad shapes"):
            ArraySource(X, np.stack([y, y], axis=1))
        with pytest.raises(ValueError, match="bad shapes"):
            ArraySource(X, y[:, None])

    def test_to_npy_closes_peek_iterator(self, tmp_path, corral):
        # The one-row dtype peek must close its block iterator explicitly
        # (an abandoned generator holds e.g. CSVSource's file open until
        # GC).  A non-generator iterator never gets auto-closed, so this
        # fails without the explicit close.
        X, y = corral
        closed = []

        class PeekTrackingSource(ArraySource):
            def iter_blocks(self, block_obs):
                inner = super().iter_blocks(block_obs)

                class It:
                    def __iter__(self):
                        return self

                    def __next__(self):
                        return next(inner)

                    def close(self):
                        closed.append(block_obs)

                return It()

        src = PeekTrackingSource(X, y)
        src.to_npy(str(tmp_path / "X.npy"), str(tmp_path / "y.npy"))
        assert 1 in closed  # the block_obs=1 peek iterator was closed

    def test_stats_rejects_negative_categories(self):
        y = np.array([0, 1], np.int32)
        bad_x = ArraySource(np.array([[0, 1], [-1, 2]], np.int32), y)
        with pytest.raises(ValueError, match="negative category"):
            bad_x.stats()
        bad_y = ArraySource(np.array([[0, 1], [1, 2]], np.int32),
                            np.array([0, -1], np.int32))
        with pytest.raises(ValueError, match="negative category"):
            bad_y.stats()
        # continuous data may be negative — no validation there
        ok = ArraySource(np.array([[-1.0, 1.0]], np.float32),
                         np.array([0.5], np.float32))
        assert not ok.stats().discrete

    def test_streaming_fit_rejects_negative_categories(self):
        X = np.array([[0, 1], [-1, 2], [1, 0]], np.int32)
        y = np.array([0, 1, 0], np.int32)
        with pytest.raises(ValueError, match="negative category"):
            MRMRSelector(num_select=1).fit(ArraySource(X, y))

    def test_in_memory_fit_rejects_negative_categories(self):
        X = np.array([[0, 1], [2, -3], [1, 0]], np.int32)
        y = np.array([0, 1, 0], np.int32)
        with pytest.raises(ValueError, match="negative category"):
            MRMRSelector(num_select=1).fit(X, y)


class TestFrontDoorGuards:
    def test_y_with_source_raises(self, corral):
        X, y = corral
        with pytest.raises(ValueError, match="alone"):
            MRMRSelector(num_select=2).fit(ArraySource(X, y), y)

    def test_missing_y_raises(self, corral):
        X, _ = corral
        with pytest.raises(ValueError, match="required"):
            MRMRSelector(num_select=2).fit(X)

    def test_in_memory_encoding_rejects_source(self, corral):
        X, y = corral
        with pytest.raises(ValueError, match="in-memory"):
            MRMRSelector(num_select=2, encoding="grid").fit(ArraySource(X, y))

    def test_custom_score_cannot_stream(self, corral):
        X, y = corral
        score = CustomScore(get_result=lambda v, c, s, n: 0.0)
        with pytest.raises(ValueError, match="stream"):
            MRMRSelector(num_select=2, score=score).fit(ArraySource(X, y))

    def test_num_select_out_of_range(self, corral):
        X, y = corral
        with pytest.raises(ValueError, match="out of range"):
            MRMRSelector(num_select=99).fit(ArraySource(X, y))

    def test_mesh_without_any_shardable_axis_raises(self, corral):
        # A user-supplied mesh the streaming engine can't shard over (no
        # observation OR feature axis) must fail loudly, not silently run
        # single-device.
        X, y = corral
        mesh = make_mesh((1,), ("pipe",))
        with pytest.raises(ValueError, match="obs_axes"):
            MRMRSelector(num_select=2, score=MIScore(2, 2),
                         mesh=mesh).fit(ArraySource(X, y))


class TestBinnedStreaming:
    """Binned (continuous -> on-the-fly codes) streaming equivalence: the
    fused device-side encode must reproduce the in-memory binned fit at
    every block size and mesh regime."""

    def _data(self, n=1800, f=12, seed=21):
        rng = np.random.default_rng(seed)
        y = rng.integers(0, 2, size=n)
        X = rng.normal(size=(n, f))
        for j in range(4):
            X[:, j] += y * (1.6 - 0.35 * j)
        return X, y

    @pytest.mark.parametrize("block_obs", [128, 999, 4096])
    def test_matches_in_memory(self, block_obs):
        X, y = self._data()
        want = MRMRSelector(num_select=4, bins=16).fit(X, y)
        got = MRMRSelector(num_select=4, bins=16, block_obs=block_obs).fit(
            ArraySource(X, y)
        )
        assert got.plan_.encoding == "streaming" and got.plan_.bins == 16
        np.testing.assert_array_equal(got.selected_, want.selected_)
        np.testing.assert_allclose(got.gains_, want.gains_, rtol=1e-5,
                                   atol=1e-6)

    def test_obs_sharded_mesh(self):
        X, y = self._data(seed=22)
        want = MRMRSelector(num_select=4, bins=8).fit(X, y)
        mesh = make_mesh((len(jax.devices()),), ("data",))
        got = MRMRSelector(num_select=4, bins=8, mesh=mesh,
                           block_obs=256).fit(ArraySource(X, y))
        np.testing.assert_array_equal(got.selected_, want.selected_)

    def test_feature_sharded_wide(self):
        # wide regime: raw float blocks AND the fitted edges shard over
        # feat_axes; device-side codes must still equal the host encode.
        rng = np.random.default_rng(23)
        n, f = 256, 1024
        y = rng.integers(0, 2, size=n)
        X = rng.normal(size=(n, f))
        for j in range(5):
            X[:, j] += y * (1.8 - 0.3 * j)
        want = MRMRSelector(num_select=5, bins=8).fit(X, y)
        mesh = make_mesh((len(jax.devices()),), ("model",))
        got = MRMRSelector(num_select=5, bins=8, mesh=mesh,
                           block_obs=64).fit(ArraySource(X, y))
        assert got.plan_.feat_axes == ("model",)
        np.testing.assert_array_equal(got.selected_, want.selected_)

    def test_grid_mesh(self):
        rng = np.random.default_rng(24)
        n, f = 400, 512
        y = rng.integers(0, 2, size=n)
        X = rng.normal(size=(n, f))
        for j in range(4):
            X[:, j] += y * (1.5 - 0.3 * j)
        want = MRMRSelector(num_select=4, bins=8).fit(X, y)
        n_dev = len(jax.devices())
        od = 2 if n_dev % 2 == 0 else 1
        mesh = make_mesh((od, n_dev // od), ("data", "model"))
        got = MRMRSelector(num_select=4, bins=8, mesh=mesh,
                           block_obs=100).fit(ArraySource(X, y))
        np.testing.assert_array_equal(got.selected_, want.selected_)

    def test_sketch_pass_costs_one_extra_io_pass(self):
        # Binning adds exactly ONE extra pass (the sketch) to streaming's
        # L scoring passes.  For an in-memory ArraySource the binner memo
        # key also reads once — the fingerprint content hash (iter at
        # 65536; file-backed sources hash stat() metadata instead).  The
        # discrete-vs-continuous routing itself is free: feature_dtype
        # answers without touching iter_blocks.
        from repro.data.binning import clear_binner_memo
        from repro.data.sources import clear_stats_memo

        clear_binner_memo()
        clear_stats_memo()
        X, y = self._data(seed=25)
        passes = []

        class Counting(ArraySource):
            def iter_blocks(self, block_obs):
                passes.append(block_obs)
                return super().iter_blocks(block_obs)

        MRMRSelector(num_select=3, bins=8, block_obs=300).fit(
            Counting(X, y)
        )
        # fingerprint + sketch + relevance + 2 redundancy (the scoring
        # passes may round 300 up to the mesh's obs extent)
        assert len(passes) == 5 and passes[0] == 65536, passes
        clear_binner_memo()

    def test_pearson_on_binned_codes_streams_unfused(self):
        # A non-MI score on a BinnedSource takes the host-encode path
        # (wrapper iter_blocks) and still fits fine.
        X, y = self._data(seed=26)
        src = BinnedSource(ArraySource(X, y), 8)
        got = MRMRSelector(num_select=3, score=PearsonMIScore(),
                           block_obs=500).fit(src)
        codes, labels = src.materialize()
        want = MRMRSelector(num_select=3, score=PearsonMIScore()).fit(
            codes.astype(np.float32), labels
        )
        np.testing.assert_array_equal(got.selected_, want.selected_)


class TestResidentBlocks:
    """A fit whose placed dataset fits the device budget keeps its blocks
    on the device after the relevance pass and counts every later pass
    from them.  The CPU backend reports no memory, so these tests set the
    budget by hand."""

    ROWS, COLS, BLOCK, SELECT = 1000, 13, 256, 6  # last block 232 rows

    @pytest.fixture(scope="class")
    def data(self):
        rng = np.random.default_rng(11)
        X = rng.integers(0, 3, size=(self.ROWS, self.COLS)).astype(np.int8)
        y = ((X[:, 0] + X[:, 1]) % 2).astype(np.int8)
        flip = rng.random(self.ROWS) < 0.1
        y[flip] = 1 - y[flip]
        return X, y

    @staticmethod
    def budget(monkeypatch, nbytes):
        import repro.dist.streaming as dist

        monkeypatch.setattr(dist, "resident_budget", lambda devices: nbytes)

    def fit(self, data, source=None, **kw):
        kw = dict(num_select=self.SELECT, block_obs=self.BLOCK) | kw
        return MRMRSelector(**kw).fit(source or ArraySource(*data))

    @staticmethod
    def assert_same(a, b):
        np.testing.assert_array_equal(a.selected_, b.selected_)
        np.testing.assert_array_equal(
            a.gains_.view(np.int32), b.gains_.view(np.int32)
        )
        np.testing.assert_array_equal(
            a.scores_.view(np.int32), b.scores_.view(np.int32)
        )

    def test_budget_is_a_share_of_the_least_free_memory(self, monkeypatch):
        import repro.dist.streaming as dist
        from repro.dist.streaming import RESIDENT_FRACTION, resident_budget

        class Device:
            def __init__(self, stats):
                self.stats = stats

            def memory_stats(self):
                return self.stats

        devices = [
            Device(dict(bytes_limit=1000, bytes_in_use=200)),
            Device(dict(bytes_limit=1000, bytes_in_use=600)),
        ]
        assert resident_budget(devices) == int(RESIDENT_FRACTION * 400)
        # bytes promised to a live fit's resident blocks are not free
        monkeypatch.setitem(dist._RESERVED, devices[1], 100)
        assert resident_budget(devices) == int(RESIDENT_FRACTION * 300)
        assert resident_budget(devices + [Device(None)]) is None
        assert resident_budget(jax.devices()[:1]) is None  # the CPU backend

    @pytest.mark.parametrize("q", [1, 4])
    @pytest.mark.parametrize(
        "criterion", ["mid", "miq", "jmi", "cmim", "mifs", "cife", "icap"]
    )
    def test_bitwise_the_streamed_fit(self, data, monkeypatch, criterion, q):
        streamed = self.fit(data, criterion=criterion, batch_candidates=q)
        self.budget(monkeypatch, 1 << 40)
        resident = self.fit(data, criterion=criterion, batch_candidates=q)
        self.assert_same(resident, streamed)

        io, was = resident.result_.io, streamed.result_.io
        X, y = data
        blocks = -(-self.ROWS // self.BLOCK)
        assert io["passes"] == was["passes"] > 1
        assert io["resident_passes"] == io["passes"] - 1
        assert was["resident_passes"] == 0
        assert io["blocks_read"] == blocks
        assert io["bytes_read"] == X.nbytes + y.nbytes
        # one pass of placed triples (int8 X, int8 y, bool validity),
        # the relevance vector and each folded redundancy term, and the
        # int32 ids of the q columns each later pass cuts
        cond = resolve_criterion(criterion).needs_conditional_redundancy
        terms = 1 + (self.SELECT - 1) * (2 if cond else 1)
        vectors = 4 * self.COLS * terms
        ids = 4 * q * io["resident_passes"]
        assert io["h2d_bytes"] == (
            blocks * self.BLOCK * (self.COLS + 2) + vectors + ids
        )
        assert io["host_syncs"] == was["host_syncs"]
        assert io["state_bytes"] == was["state_bytes"]

    def test_float_blocks_bitwise(self, monkeypatch):
        # A continuous score cuts float32 columns on the device.
        rng = np.random.default_rng(12)
        X = rng.normal(size=(700, 9)).astype(np.float32)
        y = (X[:, 0] + 0.5 * rng.normal(size=700)).astype(np.float32)
        kw = dict(num_select=4, score=PearsonMIScore(), block_obs=256)
        streamed = MRMRSelector(**kw).fit(ArraySource(X, y))
        self.budget(monkeypatch, 1 << 40)
        resident = MRMRSelector(**kw).fit(ArraySource(X, y))
        self.assert_same(resident, streamed)
        assert resident.result_.io["resident_passes"] == 3

    @pytest.mark.parametrize(
        "case", ["over_budget", "at_budget", "maxrel", "one_pick", "binned"]
    )
    def test_which_fits_stream(self, data, monkeypatch, case):
        from repro.dist.streaming import BlockPlacer

        kw, source = {}, None
        need = BlockPlacer(self.BLOCK, num_features=self.COLS).resident_bytes(
            self.ROWS, 1
        )
        budget = 1 << 40
        if case == "over_budget":
            budget = need - 1
        elif case == "at_budget":
            budget = need
        elif case == "maxrel":
            kw = dict(criterion="maxrel")
        elif case == "one_pick":
            kw = dict(num_select=1)
        else:  # the fused binned path: float blocks encoded on the device
            X, y = data
            source = ArraySource(X.astype(np.float32) + 0.5, y)
            kw = dict(bins=3)
        streamed = self.fit(data, source, **kw)
        self.budget(monkeypatch, budget)
        got = self.fit(data, source, **kw)
        self.assert_same(got, streamed)
        io, was = got.result_.io, streamed.result_.io
        if case in ("over_budget", "at_budget"):
            assert io["resident_need_bytes"] == need
            assert io["resident_budget_bytes"] == budget
        else:  # a fit that never weighs residency
            assert "resident_need_bytes" not in io
            assert "resident_budget_bytes" not in io
        if case == "at_budget":
            assert io["resident_passes"] == io["passes"] - 1 > 0
            return
        assert io["resident_passes"] == 0
        assert io["h2d_bytes"] == was["h2d_bytes"]
        assert io["bytes_read"] == was["bytes_read"]

    @pytest.mark.parametrize(
        "prefetch,readahead", [(0, 0), (2, 0), (0, 2)],
        ids=["sync", "prefetch2", "readahead2"],
    )
    def test_reads_the_source_once(self, data, monkeypatch, prefetch, readahead):
        reads = []

        class Counting(ArraySource):
            def iter_blocks(self, block_obs):
                for block in super().iter_blocks(block_obs):
                    reads.append(block[0].shape[0])
                    yield block

        self.budget(monkeypatch, 1 << 40)
        # a given score: the front door makes no stats scan of its own
        got = self.fit(
            data, Counting(*data), score=MIScore(3, 2), prefetch=prefetch,
            readahead=readahead,
        )
        assert got.result_.io["resident_passes"] == self.SELECT - 1
        # a read-ahead thread would have read into pass 2 by now
        assert sum(reads) == self.ROWS
        import threading

        assert not any(
            t.name in ("block-prefetch", "cross-pass-readahead")
            and t.is_alive()
            for t in threading.enumerate()
        )

    @pytest.mark.parametrize("ends", ["returns", "raises"])
    def test_no_device_array_outlives_the_fit(self, data, monkeypatch, ends):
        import gc

        import repro.dist.streaming as dist
        from repro.dist.streaming import ResidentBlocks

        self.budget(monkeypatch, 1 << 40)
        self.fit(data)  # compile outside the count
        kept, held = [], []
        triples = ResidentBlocks.triples

        def spy(resident, *a, **kw):
            kept.append(resident)
            held.extend(resident.blocks)
            if ends == "raises" and len(kept) == 3:
                raise RuntimeError("device lost")
            return triples(resident, *a, **kw)

        monkeypatch.setattr(ResidentBlocks, "triples", spy)
        gc.collect()
        before = {id(a) for a in jax.live_arrays()}
        if ends == "raises":
            with pytest.raises(RuntimeError, match="device lost"):
                self.fit(data)
            result = ()
        else:
            res = self.fit(data).result_
            result = {id(res.selected), id(res.gains), id(res.relevance)}
        gc.collect()
        left = [
            a for a in jax.live_arrays()
            if id(a) not in before and id(a) not in result
        ]
        assert kept and not left, [(a.shape, a.dtype) for a in left]
        # freed even where a reference outlives the fit (a traceback's),
        # and their bytes no longer promised
        assert held and all(a.is_deleted() for t in held for a in t)
        assert not dist._RESERVED

    @pytest.mark.parametrize("criterion", ["mid", "jmi"])
    def test_fit_above_its_budget_streams(self, data, monkeypatch, criterion):
        # A device that reports its memory, with half of it free one byte
        # short of the placed dataset: the fit streams every pass, the
        # partial last block included, and says how far above it sat.
        need = 4 * self.BLOCK * (self.COLS + 8 + 1)  # 4 blocks, the last of 232 rows
        assert BlockPlacer(self.BLOCK, num_features=self.COLS).resident_bytes(
            self.ROWS, 1
        ) == need
        X, y = data
        plain = self.fit(data, criterion=criterion).result_.io
        assert plain["resident_need_bytes"] == need
        assert "resident_budget_bytes" not in plain  # the CPU reports none

        monkeypatch.setattr(
            type(jax.devices()[0]), "memory_stats",
            lambda device: dict(bytes_limit=2 * (need - 1), bytes_in_use=0),
        )
        streamed = self.fit(data, criterion=criterion)
        io = streamed.result_.io
        assert io["resident_passes"] == 0
        assert io["blocks_read"] == io["passes"] * 4 and io["passes"] > 1
        assert io["bytes_read"] == io["passes"] * (X.nbytes + y.nbytes)
        assert io["resident_need_bytes"] == need
        assert io["resident_budget_bytes"] == need - 1

        monkeypatch.setattr(
            type(jax.devices()[0]), "memory_stats",
            lambda device: dict(bytes_limit=2 * need, bytes_in_use=0),
        )
        resident = self.fit(data, criterion=criterion)
        assert resident.result_.io["resident_passes"] == io["passes"] - 1
        assert resident.result_.io["resident_budget_bytes"] == need
        self.assert_same(streamed, resident)

        from repro.core.mrmr import mrmr_reference

        ref = mrmr_reference(
            jax.numpy.asarray(X.T), jax.numpy.asarray(y), self.SELECT,
            MIScore(3, 2), criterion=criterion,
        )
        np.testing.assert_array_equal(streamed.selected_, ref.selected)
        np.testing.assert_array_equal(streamed.scores_, ref.relevance)
        # the reference folds its redundancy sums in another order: a few
        # float32 roundings of gains up to 0.3
        np.testing.assert_allclose(streamed.gains_, ref.gains, rtol=0, atol=1e-7)

    def test_concurrent_fits_share_the_budget(self, data, monkeypatch):
        # A fit places its resident blocks over the whole of its first
        # pass.  A second fit that decides meanwhile must see them as
        # taken: with free memory for one dataset's blocks at the
        # resident fraction but not for two, it streams.
        import threading

        import repro.dist.streaming as dist
        from repro.dist.streaming import BlockPlacer

        need = BlockPlacer(self.BLOCK, num_features=self.COLS).resident_bytes(
            self.ROWS, 1
        )
        limit = 5 * need // 2  # half of it fits one; half of the rest not
        monkeypatch.setattr(
            type(jax.devices()[0]), "memory_stats",
            lambda device: dict(bytes_limit=limit, bytes_in_use=0),
        )
        decided, go = threading.Event(), threading.Event()

        class Paused(ArraySource):
            def iter_blocks(self, block_obs):
                decided.set()  # the first read comes after the decision
                assert go.wait(60)
                yield from super().iter_blocks(block_obs)

        kw = dict(score=MIScore(3, 2), prefetch=0)
        fits = {}
        first = threading.Thread(
            target=lambda: fits.update(first=self.fit(data, Paused(*data), **kw))
        )
        first.start()
        try:
            assert decided.wait(60)
            second = self.fit(data, **kw)
        finally:
            go.set()
            first.join(60)
        assert fits["first"].result_.io["resident_passes"] == self.SELECT - 1
        assert second.result_.io["resident_passes"] == 0
        self.assert_same(second, fits["first"])
        assert not dist._RESERVED
        # once the first fit has ended its bytes are free again
        again = self.fit(data, **kw)
        assert again.result_.io["resident_passes"] == self.SELECT - 1
