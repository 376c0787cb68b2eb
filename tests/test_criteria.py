"""Criterion layer: fold semantics, registry, engine x criterion
equivalence (the api_redesign acceptance bar), and the selector read side.
"""

import dataclasses

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro import (
    CMIMCriterion,
    Criterion,
    CustomScore,
    JMICriterion,
    MIDCriterion,
    MIQCriterion,
    MIScore,
    MRMRSelector,
    MaxRelCriterion,
    available_criteria,
    register_criterion,
)
from repro.core import mrmr_reference
from repro.core.criteria import (
    _CRITERIA,
    conditional_terms,
    marginal_terms,
    resolve_criterion,
)
from repro.core.mrmr import MRMRResult
from repro.core.selector import check_num_select, register_engine
from repro.data.sources import ArraySource
from repro.data.synthetic import corral_dataset
from repro.dist import make_mesh


@pytest.fixture(scope="module")
def corral():
    X, y = corral_dataset(2000, 32, seed=1, flip_prob=0.02)
    return np.asarray(X, np.int32), np.asarray(y)


ALL_ENCODINGS = ["reference", "conventional", "alternative", "grid"]


def fit(X, y, encoding, L=5, **kw):
    mesh = make_mesh((1, 1), ("data", "model")) if encoding == "grid" else None
    return MRMRSelector(num_select=L, encoding=encoding, mesh=mesh, **kw).fit(X, y)


class TestFoldSemantics:
    """The built-in folds compute exactly their documented formulas."""

    def test_mid_is_difference(self):
        crit = MIDCriterion()
        rel = jnp.asarray([1.0, 2.0, 3.0])
        st = crit.init_state(3)
        st = crit.update(st, jnp.asarray([0.5, 1.0, 0.0]), 0)
        st = crit.update(st, jnp.asarray([0.5, 1.0, 0.0]), 1)
        np.testing.assert_allclose(
            np.asarray(crit.objective(rel, st, 2)), [0.5, 1.0, 3.0]
        )
        # l=0: empty state, denominator clamps to 1 -> pure relevance
        np.testing.assert_allclose(
            np.asarray(crit.objective(rel, crit.init_state(3), 0)),
            np.asarray(rel),
        )

    def test_miq_is_quotient(self):
        crit = MIQCriterion()
        rel = jnp.asarray([1.0, 2.0])
        st = crit.update(crit.init_state(2), jnp.asarray([0.5, 4.0]), 0)
        np.testing.assert_allclose(
            np.asarray(crit.objective(rel, st, 1)), [2.0, 0.5]
        )

    def test_miq_first_pick_is_relevance_argmax(self, corral):
        X, y = corral
        miq = fit(X, y, "reference", criterion="miq")
        assert miq.selected_[0] == int(np.argmax(miq.scores_))

    def test_maxrel_needs_no_redundancy(self):
        crit = MaxRelCriterion()
        assert not crit.needs_redundancy
        rel = jnp.asarray([3.0, 1.0])
        st = crit.update(crit.init_state(2), jnp.asarray([9.0, 9.0]), 0)
        np.testing.assert_allclose(np.asarray(crit.objective(rel, st, 1)), rel)

    def test_maxrel_selects_top_relevance(self, corral):
        X, y = corral
        sel = fit(X, y, "reference", L=6, criterion="maxrel")
        # iterated masked argmax == stable descending relevance order
        want = np.argsort(-sel.scores_, kind="stable")[:6]
        np.testing.assert_array_equal(sel.selected_, want)


class TestRegistry:
    def test_builtins_registered(self):
        assert {"mid", "miq", "maxrel", "jmi", "cmim"} <= set(
            available_criteria()
        )

    def test_resolve(self):
        assert resolve_criterion("mid").name == "mid"
        inst = MIQCriterion()
        assert resolve_criterion(inst) is inst
        assert resolve_criterion(None).name == "mid"
        with pytest.raises(ValueError, match="unknown criterion"):
            resolve_criterion("nope")

    def test_unnamed_criterion_rejected(self):
        with pytest.raises(ValueError, match="no name"):
            register_criterion(Criterion())

    def test_name_alias_syncs_instance_name(self):
        # Registering under name= must keep provenance (.name) in sync
        # with the registry key, or result_.criterion could not be
        # round-tripped through resolve_criterion.
        try:
            register_criterion(MIQCriterion(), name="_test_alias")
            crit = resolve_criterion("_test_alias")
            assert crit.name == "_test_alias"
        finally:
            _CRITERIA.pop("_test_alias", None)

    def test_register_round_trip(self, corral):
        # The user-extensibility bar: a registered criterion is resolvable
        # by name and runs end-to-end through the front door.
        X, y = corral

        @register_criterion
        @dataclasses.dataclass(frozen=True)
        class DoublePenalty(MIDCriterion):
            name = "_test_mid2x"

            def objective(self, rel, state, l):
                denom = jnp.maximum(l, 1).astype(jnp.float32)
                return rel - 2.0 * state["red_sum"] / denom

        try:
            assert "_test_mid2x" in available_criteria()
            sel = MRMRSelector(num_select=4, criterion="_test_mid2x").fit(X, y)
            assert sel.result_.criterion == "_test_mid2x"
            assert len(set(sel.selected_.tolist())) == 4
            # doubling the penalty is not a no-op on this dataset's gains
            mid = MRMRSelector(num_select=4, criterion="mid").fit(X, y)
            assert not np.allclose(sel.gains_[1:], mid.gains_[1:])
        finally:
            _CRITERIA.pop("_test_mid2x", None)


class TestMidReproducesLegacy:
    """`mid` through the Criterion layer == the pre-criterion fold.

    The default path IS the criterion path now, so the strongest pin is
    (a) default == explicit mid == fresh MIDCriterion instance, bitwise,
    and (b) the objective trajectory equals an independently computed
    rel - red_sum/l fold from the raw score primitives.
    """

    @pytest.mark.parametrize("encoding", ALL_ENCODINGS)
    def test_default_is_mid_bitwise(self, corral, encoding):
        X, y = corral
        a = fit(X, y, encoding)
        b = fit(X, y, encoding, criterion="mid")
        c = fit(X, y, encoding, criterion=MIDCriterion())
        np.testing.assert_array_equal(a.selected_, b.selected_)
        np.testing.assert_array_equal(a.selected_, c.selected_)
        np.testing.assert_array_equal(a.gains_, b.gains_)   # bitwise
        np.testing.assert_array_equal(a.gains_, c.gains_)   # bitwise

    def test_trajectory_matches_manual_fold(self, corral):
        X, y = corral
        L = 5
        score = MIScore(2, 2)
        sel = fit(X, y, "reference", L=L)
        # independent numpy fold over the same score primitives
        Xr = jnp.asarray(X.T)
        rel = np.asarray(score.relevance(Xr, jnp.asarray(y)), np.float32)
        red_sum = np.zeros_like(rel)
        mask = np.zeros(rel.shape, bool)
        for l in range(L):
            g = rel - red_sum / np.float32(max(l, 1))
            g[mask] = -np.inf
            k = int(np.argmax(g))
            assert sel.selected_[l] == k
            # in-loop vs out-of-loop XLA fusion wiggles the last ulp or two
            np.testing.assert_allclose(sel.gains_[l], g[k], rtol=1e-5,
                                       atol=1e-6)
            mask[k] = True
            red_sum = red_sum + np.asarray(
                score.redundancy(Xr, Xr[k]), np.float32
            )

    @pytest.mark.parametrize("encoding", ["reference", "conventional"])
    def test_recompute_path_mid(self, corral, encoding):
        X, y = corral
        a = fit(X, y, encoding, L=6, incremental=True)
        b = fit(X, y, encoding, L=6, incremental=False)
        np.testing.assert_array_equal(a.selected_, b.selected_)
        np.testing.assert_allclose(a.gains_, b.gains_, rtol=1e-5, atol=1e-6)


class TestCriterionEngineAgreement:
    """Every criterion selects identically on every engine."""

    @pytest.mark.parametrize("criterion", ["miq", "maxrel", "jmi", "cmim"])
    def test_engines_agree(self, corral, criterion):
        X, y = corral
        ref = fit(X, y, "reference", criterion=criterion)
        for encoding in ALL_ENCODINGS[1:]:
            got = fit(X, y, encoding, criterion=criterion)
            np.testing.assert_array_equal(got.selected_, ref.selected_)
            # the quotient amplifies cross-engine MI ulp differences when
            # mean redundancy is tiny; selections are the acceptance bar
            np.testing.assert_allclose(got.gains_, ref.gains_,
                                       rtol=5e-3, atol=1e-5)

    @pytest.mark.parametrize("encoding", ["reference", "conventional",
                                          "alternative"])
    def test_miq_incremental_equals_recompute(self, corral, encoding):
        X, y = corral
        a = fit(X, y, encoding, L=6, criterion="miq", incremental=True)
        b = fit(X, y, encoding, L=6, criterion="miq", incremental=False)
        np.testing.assert_array_equal(a.selected_, b.selected_)

    def test_miq_differs_from_mid_somewhere(self, corral):
        # The knob must actually steer: on this seed dataset the quotient
        # form picks a different set than the difference form.
        X, y = corral
        mid = fit(X, y, "reference", criterion="mid")
        miq = fit(X, y, "reference", criterion="miq")
        assert mid.selected_.tolist() != miq.selected_.tolist()


class TestConditionalFoldSemantics:
    """JMI/CMIM folds compute exactly their documented formulas, and the
    terms helpers accept both the dict form and bare arrays."""

    def test_jmi_is_mean_gap(self):
        crit = JMICriterion()
        assert crit.needs_redundancy and crit.needs_conditional_redundancy
        rel = jnp.asarray([1.0, 2.0])
        st = crit.init_state(2)
        st = crit.update(st, dict(marginal=jnp.asarray([0.5, 1.0]),
                                  conditional=jnp.asarray([1.0, 0.5])), 0)
        st = crit.update(st, dict(marginal=jnp.asarray([0.0, 1.0]),
                                  conditional=jnp.asarray([0.5, 0.0])), 1)
        # gaps (cond - marg): [0.5, -0.5] then [0.5, -1.0]; mean over 2
        np.testing.assert_allclose(
            np.asarray(crit.objective(rel, st, 2)), [1.5, 1.25]
        )
        # l=0: empty state -> pure relevance
        np.testing.assert_allclose(
            np.asarray(crit.objective(rel, crit.init_state(2), 0)),
            np.asarray(rel),
        )

    def test_cmim_is_min_gap(self):
        crit = CMIMCriterion()
        assert crit.needs_conditional_redundancy
        rel = jnp.asarray([1.0, 2.0])
        st = crit.init_state(2)
        st = crit.update(st, dict(marginal=jnp.asarray([0.5, 1.0]),
                                  conditional=jnp.asarray([1.0, 0.5])), 0)
        st = crit.update(st, dict(marginal=jnp.asarray([0.0, 1.0]),
                                  conditional=jnp.asarray([0.5, 0.0])), 1)
        # min-fold keeps the WORST gap: [0.5, -1.0]
        np.testing.assert_allclose(
            np.asarray(crit.objective(rel, st, 2)), [1.5, 1.0]
        )

    def test_cmim_inf_identity_never_leaks(self):
        # The min-fold identity is +inf; at l=0 the objective must be pure
        # finite relevance (rel + inf would poison the argmax), and a
        # single fold must fully replace the identity.
        crit = CMIMCriterion()
        rel = jnp.asarray([3.0, 1.0])
        obj0 = np.asarray(crit.objective(rel, crit.init_state(2), 0))
        np.testing.assert_allclose(obj0, np.asarray(rel))
        assert np.isfinite(obj0).all()
        st = crit.update(crit.init_state(2),
                         dict(marginal=jnp.asarray([1.0, 1.0]),
                              conditional=jnp.asarray([1.5, 0.5])), 0)
        obj1 = np.asarray(crit.objective(rel, st, 1))
        np.testing.assert_allclose(obj1, [3.5, 0.5])
        assert np.isfinite(obj1).all()

    def test_terms_helpers(self):
        arr = jnp.asarray([1.0])
        assert marginal_terms(arr) is arr  # bare-array back-compat
        d = dict(marginal=arr, conditional=arr + 1.0)
        assert marginal_terms(d) is arr
        np.testing.assert_allclose(np.asarray(conditional_terms(d)), [2.0])
        for bad in (arr, dict(marginal=arr, conditional=None)):
            with pytest.raises(ValueError, match="conditional"):
                conditional_terms(bad)

    def test_marginal_criteria_declare_no_conditional(self):
        # The zero-cost contract hangs off this flag: if a marginal
        # criterion ever flips it, every engine starts counting 3-way
        # tables for it.
        for crit in (MIDCriterion(), MIQCriterion(), MaxRelCriterion()):
            assert not crit.needs_conditional_redundancy


class TestConditionalTrajectory:
    """Reference JMI/CMIM selections match an independent numpy fold over
    the raw score primitives (the manual-fold oracle pattern)."""

    @pytest.mark.parametrize("criterion", ["jmi", "cmim"])
    def test_trajectory_matches_manual_fold(self, corral, criterion):
        X, y = corral
        L = 5
        score = MIScore(2, 2)
        sel = fit(X, y, "reference", L=L, criterion=criterion)
        Xr = jnp.asarray(X.T)
        yj = jnp.asarray(y)
        rel = np.asarray(score.relevance(Xr, yj), np.float32)
        gap_acc = (np.zeros_like(rel) if criterion == "jmi"
                   else np.full_like(rel, np.inf))
        mask = np.zeros(rel.shape, bool)
        for l in range(L):
            if l == 0:
                g = rel.copy()
            elif criterion == "jmi":
                g = rel + gap_acc / np.float32(l)
            else:
                g = rel + gap_acc
            g[mask] = -np.inf
            k = int(np.argmax(g))
            assert sel.selected_[l] == k
            np.testing.assert_allclose(sel.gains_[l], g[k], rtol=1e-5,
                                       atol=1e-6)
            mask[k] = True
            terms = score.redundancy_terms(Xr, Xr[k], yj, conditional=True)
            gap = (np.asarray(terms["conditional"], np.float32)
                   - np.asarray(terms["marginal"], np.float32))
            gap_acc = (gap_acc + gap if criterion == "jmi"
                       else np.minimum(gap_acc, gap))

    @pytest.mark.parametrize("criterion", ["jmi", "cmim"])
    def test_incremental_equals_recompute(self, corral, criterion):
        X, y = corral
        a = fit(X, y, "reference", L=6, criterion=criterion,
                incremental=True)
        b = fit(X, y, "reference", L=6, criterion=criterion,
                incremental=False)
        np.testing.assert_array_equal(a.selected_, b.selected_)
        np.testing.assert_allclose(a.gains_, b.gains_, rtol=1e-5, atol=1e-6)

    def test_jmi_cmim_steer_differently(self, corral):
        # The conditional fold must actually change selections vs mid on
        # the seed dataset, and the mean/min folds must differ from each
        # other somewhere in the trajectory.
        X, y = corral
        mid = fit(X, y, "reference", L=6, criterion="mid")
        jmi = fit(X, y, "reference", L=6, criterion="jmi")
        cmim = fit(X, y, "reference", L=6, criterion="cmim")
        assert not np.array_equal(jmi.gains_, mid.gains_)
        assert not np.array_equal(jmi.gains_, cmim.gains_)

    def test_cmim_tie_break_lowest_id(self, corral):
        # Duplicate columns produce exactly tied objectives; the argmax
        # contract (toward the lowest id) must hold for the min-fold too,
        # on both the compiled and the host-driven fold.
        X, y = corral
        X = X.copy()
        X[:, 12] = X[:, 5]
        ref = fit(X, y, "reference", L=6, criterion="cmim")
        got = MRMRSelector(num_select=6, criterion="cmim",
                           block_obs=512).fit(ArraySource(X, y))
        np.testing.assert_array_equal(got.selected_, ref.selected_)
        picks = ref.selected_.tolist()
        if 12 in picks:
            assert 5 in picks and picks.index(5) < picks.index(12)


class TestConditionalStreaming:
    """Streaming JMI/CMIM == in-memory, at dividing / non-dividing /
    oversized block sizes, under candidate batching, and with bins=."""

    @pytest.mark.parametrize("criterion", ["jmi", "cmim"])
    @pytest.mark.parametrize("block_obs", [128, 999, 4096])
    def test_streaming_matches_reference(self, corral, criterion,
                                         block_obs):
        X, y = corral
        ref = fit(X, y, "reference", criterion=criterion)
        got = MRMRSelector(num_select=5, criterion=criterion,
                           block_obs=block_obs).fit(ArraySource(X, y))
        assert got.plan_.encoding == "streaming"
        np.testing.assert_array_equal(got.selected_, ref.selected_)
        np.testing.assert_allclose(got.gains_, ref.gains_, rtol=1e-4,
                                   atol=1e-5)

    @pytest.mark.parametrize("criterion", ["jmi", "cmim"])
    @pytest.mark.parametrize("q", [2, 4])
    def test_batched_candidates_bitwise(self, corral, criterion, q):
        X, y = corral
        plain = MRMRSelector(num_select=5, criterion=criterion,
                             block_obs=512).fit(ArraySource(X, y))
        batched = MRMRSelector(num_select=5, criterion=criterion,
                               block_obs=512,
                               batch_candidates=q).fit(ArraySource(X, y))
        np.testing.assert_array_equal(batched.selected_, plain.selected_)
        np.testing.assert_array_equal(batched.gains_, plain.gains_)
        assert batched.result_.io["passes"] <= plain.result_.io["passes"]

    def test_state_bytes_ledger(self, corral):
        # The zero-cost contract, asserted in bytes: a conditional
        # criterion's statistics state carries the class axis (d_c x the
        # pair state), a marginal criterion's does not.
        X, y = corral
        n, v, c = X.shape[1], 2, 2

        def io_of(criterion):
            sel = MRMRSelector(num_select=4, criterion=criterion,
                               block_obs=512).fit(ArraySource(X, y))
            return sel.result_.io

        mid, jmi, cmim = io_of("mid"), io_of("jmi"), io_of("cmim")
        # int32 counts: relevance (n, v, c), marginal pair (n, v, v),
        # conditional pair (n, v, v*c) -- peak is the redundancy state
        assert mid["state_bytes"] == n * v * max(v, c) * 4
        assert jmi["state_bytes"] == n * v * v * c * 4
        assert cmim["state_bytes"] == jmi["state_bytes"]
        # the class axis rides the SAME passes -- no extra I/O
        assert jmi["passes"] == mid["passes"]
        assert jmi["bytes_read"] == mid["bytes_read"]

    @pytest.mark.parametrize("criterion", ["jmi", "cmim"])
    def test_bins_composition(self, criterion):
        # Continuous data -> quantile bins -> conditional criterion: the
        # in-memory binned fit and the streamed fused-encode fit agree.
        rng = np.random.default_rng(7)
        X = rng.normal(size=(900, 16)).astype(np.float32)
        y = (X[:, 3] + 0.5 * X[:, 8] > 0).astype(np.int32)
        a = MRMRSelector(num_select=4, criterion=criterion, bins=8).fit(X, y)
        b = MRMRSelector(num_select=4, criterion=criterion, bins=8,
                         block_obs=256).fit(ArraySource(X, y))
        assert b.plan_.encoding == "streaming"
        np.testing.assert_array_equal(a.selected_, b.selected_)
        assert 3 in a.selected_.tolist()

    @pytest.mark.parametrize("criterion", ["jmi", "cmim"])
    def test_obs_sharded_mesh(self, corral, criterion):
        # Tall regime: blocks shard over the data axis (1 device locally,
        # 8 in CI); the psum'd 3-way state must match the reference.
        X, y = corral
        mesh = make_mesh((len(jax.devices()),), ("data",))
        got = MRMRSelector(num_select=4, criterion=criterion,
                           block_obs=512, mesh=mesh).fit(ArraySource(X, y))
        ref = fit(X, y, "reference", L=4, criterion=criterion)
        np.testing.assert_array_equal(got.selected_, ref.selected_)
        np.testing.assert_allclose(got.gains_, ref.gains_, rtol=1e-4,
                                   atol=1e-5)

    def test_feature_sharded_conditional_state(self):
        # Wide regime: the (n, v, v*c) conditional statistics state shards
        # over the feature axis like every other leaf.
        from repro.data.sources import CorralSource

        X, y = CorralSource(256, 1024, seed=5).materialize()
        want = MRMRSelector(num_select=4, criterion="jmi",
                            encoding="alternative").fit(X, y)
        mesh = make_mesh((len(jax.devices()),), ("model",))
        got = MRMRSelector(num_select=4, criterion="jmi", block_obs=100,
                           mesh=mesh).fit(ArraySource(X, y))
        assert got.plan_.feat_axes == ("model",)
        np.testing.assert_array_equal(got.selected_, want.selected_)
        np.testing.assert_allclose(got.gains_, want.gains_, rtol=1e-4,
                                   atol=1e-5)

    def test_grid_2d_mesh(self, corral):
        # 2-D obs x feat grid: conditional state pvaried over feat axes,
        # blocks split over both.
        X, y = corral
        mesh = make_mesh((1, len(jax.devices())), ("data", "model"))
        got = MRMRSelector(num_select=4, criterion="cmim", block_obs=512,
                           mesh=mesh).fit(ArraySource(X, y))
        ref = fit(X, y, "reference", L=4, criterion="cmim")
        np.testing.assert_array_equal(got.selected_, ref.selected_)


class TestConditionalGuards:
    def test_pearson_rejects_conditional_in_memory(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(200, 8)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        with pytest.raises(ValueError, match="class-conditioned"):
            MRMRSelector(num_select=2, criterion="jmi").fit(X, y)

    def test_pearson_rejects_conditional_streaming(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(200, 8)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        with pytest.raises(ValueError, match="bins="):
            MRMRSelector(num_select=2, criterion="cmim").fit(
                ArraySource(X, y)
            )

    def test_score_without_conditional_decomposition(self, corral):
        X, y = corral
        from repro.core.mrmr import check_conditional_support
        from repro.core.scores import PearsonMIScore

        check_conditional_support(MIScore(2, 2), resolve_criterion("jmi"))
        check_conditional_support(PearsonMIScore(),
                                  resolve_criterion("mid"))
        with pytest.raises(ValueError, match="conditional"):
            check_conditional_support(PearsonMIScore(),
                                      resolve_criterion("cmim"))


class TestGuards:
    def test_custom_score_rejects_non_mid(self, corral):
        X, y = corral
        score = CustomScore(get_result=lambda v, c, s, n: jnp.float32(0))
        with pytest.raises(ValueError, match="CustomScore"):
            MRMRSelector(num_select=2, score=score, criterion="miq").fit(X, y)

    def test_unknown_criterion_fails_at_fit(self, corral):
        X, y = corral
        with pytest.raises(ValueError, match="unknown criterion"):
            MRMRSelector(num_select=2, criterion="typo").fit(X, y)

    def test_check_num_select(self):
        check_num_select(1, 1)
        for bad in (0, -3, 5):
            with pytest.raises(ValueError, match="out of range"):
                check_num_select(bad, 4)


class TestResultReport:
    def test_rich_result_fields(self, corral):
        X, y = corral
        score = MIScore(2, 2)
        res = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), 4, score,
                             criterion="miq")
        assert res.criterion == "miq" and res.engine == "reference"
        assert res.relevance.shape == (X.shape[1],)
        np.testing.assert_allclose(
            np.asarray(res.relevance),
            np.asarray(score.relevance(jnp.asarray(X.T), jnp.asarray(y))),
            rtol=1e-6,
        )
        np.testing.assert_array_equal(
            np.asarray(res.objective_trajectory), np.asarray(res.gains)
        )

    def test_custom_score_nan_relevance(self, corral):
        from repro.core import mrmr_custom_score

        X, y = corral
        custom = mrmr_custom_score(MIScore(2, 2))
        sel = MRMRSelector(num_select=3, score=custom).fit(X, y)
        assert np.isnan(sel.scores_).all()
        assert sel.result_.engine == "alternative"  # custom -> alternative


class TestSelectorReadSide:
    @pytest.mark.parametrize("encoding", ALL_ENCODINGS)
    def test_in_memory_read_side(self, corral, encoding):
        X, y = corral
        L = 5
        sel = fit(X, y, encoding, L=L)
        n = X.shape[1]
        assert sel.n_features_in_ == n
        assert sel.scores_.shape == (n,) and sel.scores_.dtype == np.float32
        # relevance VALUES must survive sharded assembly (out_specs concat
        # order on feature-sharded engines under forced multi-device runs)
        want = np.asarray(
            MIScore(2, 2).relevance(jnp.asarray(X.T), jnp.asarray(y))
        )
        np.testing.assert_allclose(sel.scores_, want, rtol=1e-4, atol=1e-6)
        # ranking: selected get 1..L in pick order, the rest share L+1
        assert sel.ranking_.shape == (n,)
        for rank, feat in enumerate(sel.selected_, start=1):
            assert sel.ranking_[feat] == rank
        assert (sel.ranking_[sel.get_support() == False] == L + 1).all()  # noqa: E712
        # support: boolean mask <-> ascending indices
        mask = sel.get_support()
        assert mask.dtype == bool and mask.sum() == L
        np.testing.assert_array_equal(
            sel.get_support(indices=True), np.sort(sel.selected_)
        )

    def test_streaming_read_side(self, corral):
        from repro.data.sources import ArraySource

        X, y = corral
        sel = MRMRSelector(num_select=4, block_obs=300).fit(ArraySource(X, y))
        assert sel.plan_.encoding == "streaming"
        assert sel.scores_.shape == (X.shape[1],)
        assert sel.result_.engine == "streaming"
        assert sel.get_support().sum() == 4
        in_mem = MRMRSelector(num_select=4).fit(X, y)
        np.testing.assert_allclose(sel.scores_, in_mem.scores_,
                                   rtol=1e-5, atol=1e-6)

    def test_get_support_before_fit_raises(self):
        with pytest.raises(RuntimeError, match="fit"):
            MRMRSelector(num_select=2).get_support()

    def test_stub_engine_without_relevance(self, corral):
        # Engines predating the rich report return MRMRResult(sel, gains);
        # the selector must still populate ranking_/support and leave
        # scores_ None rather than crash.
        X, y = corral

        @register_engine("_test_stub_crit")
        def stub(X, y, *, num_select, plan, mesh):
            return MRMRResult(
                selected=jnp.arange(num_select, dtype=jnp.int32),
                gains=jnp.zeros((num_select,), jnp.float32),
            )

        try:
            sel = MRMRSelector(num_select=3, encoding="_test_stub_crit",
                               criterion="miq").fit(X, y)
            assert sel.scores_ is None
            assert sel.result_.engine == "_test_stub_crit"
            # criterion provenance backfills from the plan, not "mid"
            assert sel.result_.criterion == "miq"
            np.testing.assert_array_equal(sel.get_support(indices=True),
                                          [0, 1, 2])
        finally:
            from repro.core import selector as selector_mod

            selector_mod._ENGINES.pop("_test_stub_crit", None)


class TestSummedFoldCriteria:
    """MIFS/CIFE/ICAP: the un-normalised-sum family (Brown et al.'s
    unified frame at β=γ=1) — fold formulas, registry, and engine
    agreement including streaming."""

    def test_mifs_is_summed_redundancy(self):
        from repro import MIFSCriterion

        crit = MIFSCriterion()
        assert crit.needs_redundancy
        assert not crit.needs_conditional_redundancy
        rel = jnp.asarray([1.0, 2.0, 3.0])
        st = crit.init_state(3)
        st = crit.update(st, jnp.asarray([0.5, 1.0, 0.0]), 0)
        st = crit.update(st, jnp.asarray([0.5, 1.0, 0.0]), 1)
        # Sum, NOT mean: penalty 1.0 / 2.0 / 0.0 (mid would halve it).
        np.testing.assert_allclose(
            np.asarray(crit.objective(rel, st, 2)), [0.0, 0.0, 3.0]
        )
        np.testing.assert_allclose(
            np.asarray(crit.objective(rel, crit.init_state(3), 0)),
            np.asarray(rel),
        )

    def test_cife_is_summed_gap(self):
        from repro import CIFECriterion

        crit = CIFECriterion()
        assert crit.needs_conditional_redundancy
        rel = jnp.asarray([1.0, 2.0])
        st = crit.init_state(2)
        st = crit.update(st, dict(marginal=jnp.asarray([0.5, 1.0]),
                                  conditional=jnp.asarray([1.0, 0.5])), 0)
        st = crit.update(st, dict(marginal=jnp.asarray([0.0, 1.0]),
                                  conditional=jnp.asarray([0.5, 0.0])), 1)
        # gaps (cond - marg): [0.5, -0.5] + [0.5, -1.0], summed not meaned
        np.testing.assert_allclose(
            np.asarray(crit.objective(rel, st, 2)), [2.0, 0.5]
        )

    def test_icap_caps_at_zero(self):
        from repro import ICAPCriterion

        crit = ICAPCriterion()
        assert crit.needs_conditional_redundancy
        rel = jnp.asarray([1.0, 2.0])
        st = crit.init_state(2)
        # feature 0: class explains the dependence (cond > marg) -> no
        # penalty; feature 1: unexplained redundancy 0.5 -> penalised.
        st = crit.update(st, dict(marginal=jnp.asarray([0.5, 1.0]),
                                  conditional=jnp.asarray([1.0, 0.5])), 0)
        np.testing.assert_allclose(
            np.asarray(crit.objective(rel, st, 1)), [1.0, 1.5]
        )
        # Synergy never accumulates negative penalty across folds.
        st = crit.update(st, dict(marginal=jnp.asarray([0.0, 0.0]),
                                  conditional=jnp.asarray([2.0, 2.0])), 1)
        np.testing.assert_allclose(
            np.asarray(crit.objective(rel, st, 2)), [1.0, 1.5]
        )

    def test_registered(self):
        names = available_criteria()
        for name in ("mifs", "cife", "icap"):
            assert name in names
            assert resolve_criterion(name).name == name

    @pytest.mark.parametrize("criterion", ["mifs", "cife", "icap"])
    def test_engines_agree(self, corral, criterion):
        X, y = corral
        ref = fit(X, y, "reference", criterion=criterion)
        for encoding in ALL_ENCODINGS[1:]:
            got = fit(X, y, encoding, criterion=criterion)
            np.testing.assert_array_equal(got.selected_, ref.selected_)
            np.testing.assert_allclose(got.gains_, ref.gains_,
                                       rtol=5e-3, atol=1e-5)

    @pytest.mark.parametrize("criterion", ["mifs", "cife", "icap"])
    @pytest.mark.parametrize("block_obs", [128, 999, 4096])
    def test_streaming_matches_reference(self, corral, criterion,
                                         block_obs):
        X, y = corral
        ref = fit(X, y, "reference", criterion=criterion)
        got = MRMRSelector(num_select=5, criterion=criterion,
                           block_obs=block_obs).fit(ArraySource(X, y))
        assert got.plan_.encoding == "streaming"
        np.testing.assert_array_equal(got.selected_, ref.selected_)
        np.testing.assert_allclose(got.gains_, ref.gains_, rtol=1e-4,
                                   atol=1e-5)

    def test_mifs_diverges_from_mid_late(self, corral):
        # The growing un-normalised penalty must actually steer: on the
        # seed dataset MIFS and mid disagree somewhere in a longer fit.
        X, y = corral
        mid = fit(X, y, "reference", L=8, criterion="mid")
        mifs = fit(X, y, "reference", L=8, criterion="mifs")
        assert mid.selected_.tolist() != mifs.selected_.tolist()
