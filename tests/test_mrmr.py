"""mRMR driver tests: reference behaviour, encoding agreement, invariants."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    MIScore,
    PearsonMIScore,
    make_conventional_fn,
    mrmr_alternative,
    mrmr_conventional,
    mrmr_reference,
    mrmr_select,
    mrmr_custom_score,
)
from repro.data.synthetic import corral_dataset, continuous_wide_dataset


def brute_force_mrmr(X_cols: np.ndarray, y: np.ndarray, L: int, vx: int, vy: int):
    """Slow numpy mRMR for ground truth (conventional orientation input)."""
    from tests.test_scores import np_mi, np_pair_counts

    n = X_cols.shape[1]
    rel = np.array([np_mi(np_pair_counts(X_cols[:, k], y, vx, vy)) for k in range(n)])
    selected, cand = [], set(range(n))
    for l in range(L):
        best_k, best_g = None, -np.inf
        for k in sorted(cand):
            red = np.mean(
                [np_mi(np_pair_counts(X_cols[:, k], X_cols[:, j], vx, vx))
                 for j in selected]
            ) if selected else 0.0
            g = rel[k] - red
            if g > best_g + 1e-12:
                best_g, best_k = g, k
        selected.append(best_k)
        cand.remove(best_k)
    return selected


@pytest.fixture(scope="module")
def small_discrete():
    rng = np.random.default_rng(0)
    X = rng.integers(0, 2, size=(400, 12)).astype(np.int32)
    # Make col 0 highly class-informative, col 1 a near-copy of col 0
    # (redundant), col 2 moderately informative.
    y = (X[:, 0] ^ (rng.random(400) < 0.1)).astype(np.int32)
    X[:, 1] = X[:, 0] ^ (rng.random(400) < 0.05)
    X[:, 2] = y ^ (rng.random(400) < 0.3)
    return X, y


class TestReference:
    def test_matches_brute_force(self, small_discrete):
        X, y = small_discrete
        want = brute_force_mrmr(X, y, 4, 2, 2)
        score = MIScore(num_values=2, num_classes=2)
        got = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), 4, score)
        assert list(np.asarray(got.selected)) == want

    def test_incremental_equals_paper_faithful(self, small_discrete):
        X, y = small_discrete
        score = MIScore(num_values=2, num_classes=2)
        a = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), 6, score,
                           incremental=True)
        b = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), 6, score,
                           incremental=False)
        np.testing.assert_array_equal(a.selected, b.selected)
        np.testing.assert_allclose(a.gains, b.gains, rtol=1e-5, atol=1e-6)

    def test_redundant_feature_down_ranked(self, small_discrete):
        X, y = small_discrete
        score = MIScore(num_values=2, num_classes=2)
        res = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), 3, score)
        sel = list(np.asarray(res.selected))
        # col 0 first (max relevance); col 1 (its copy) must NOT be second.
        assert sel[0] == 0
        assert sel[1] != 1

    def test_unique_selection(self, small_discrete):
        X, y = small_discrete
        score = MIScore(num_values=2, num_classes=2)
        res = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), 10, score)
        sel = list(np.asarray(res.selected))
        assert len(set(sel)) == 10
        assert all(0 <= s < 12 for s in sel)


class TestEncodingAgreement:
    def test_conventional_equals_reference(self, small_discrete):
        X, y = small_discrete
        score = MIScore(num_values=2, num_classes=2)
        ref = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), 5, score)
        conv = mrmr_conventional(jnp.asarray(X), jnp.asarray(y), 5, score)
        np.testing.assert_array_equal(ref.selected, conv.selected)
        np.testing.assert_allclose(ref.gains, conv.gains, rtol=1e-4, atol=1e-5)

    def test_alternative_equals_reference(self, small_discrete):
        X, y = small_discrete
        score = MIScore(num_values=2, num_classes=2)
        ref = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), 5, score)
        alt = mrmr_alternative(jnp.asarray(X.T), jnp.asarray(y), 5, score)
        np.testing.assert_array_equal(ref.selected, alt.selected)

    def test_custom_score_path_agrees(self, small_discrete):
        X, y = small_discrete
        score = MIScore(num_values=2, num_classes=2)
        custom = mrmr_custom_score(score)
        ref = mrmr_reference(jnp.asarray(X.T), jnp.asarray(y), 4, score)
        cus = mrmr_alternative(jnp.asarray(X.T), jnp.asarray(y), 4, custom)
        np.testing.assert_array_equal(ref.selected, cus.selected)

    def test_bf16_onehot_counts_exact(self):
        # 0/1 one-hot entries are exact in bfloat16: the conventional
        # engine's default one-hot dtype counts what float32 counts.
        X, y = corral_dataset(4096, 16, seed=1)
        X, y = jnp.asarray(X, jnp.int32), jnp.asarray(y)
        score = MIScore(num_values=2, num_classes=2)
        bf = make_conventional_fn(6, score, onehot_dtype=jnp.bfloat16)(X, y)
        f32 = make_conventional_fn(6, score, onehot_dtype=jnp.float32)(X, y)
        np.testing.assert_array_equal(np.asarray(bf[0]), np.asarray(f32[0]))
        np.testing.assert_allclose(
            np.asarray(bf[1]), np.asarray(f32[1]), rtol=1e-6, atol=1e-7
        )


class TestCorral:
    def test_recovers_relevant_features(self):
        X, y = corral_dataset(4000, 32, seed=1, flip_prob=0.02)
        res = mrmr_select(np.asarray(X), np.asarray(y), 8, layout="conventional")
        sel = set(np.asarray(res.selected).tolist())
        # The 8 Eq.-3 features plus the correlated col 8 dominate; require
        # most of the true 8 in the top-8 picks.
        assert len(sel & set(range(8))) >= 6

    def test_pearson_wide_dataset(self):
        X, y = continuous_wide_dataset(300, 64, seed=2)
        res = mrmr_select(
            np.asarray(X), np.asarray(y), 4,
            score=PearsonMIScore(), layout="alternative",
        )
        sel = list(np.asarray(res.selected))
        assert sel[0] == 0  # strongest signal column first
        assert 8 not in sel[:2]  # redundant shadow of col 0 not picked next


class TestSelectorAPI:
    def test_auto_layout(self):
        assert_sel = lambda X, y: mrmr_select(X, y, 2)
        rng = np.random.default_rng(3)
        X = rng.integers(0, 2, (64, 9)).astype(np.int32)
        y = rng.integers(0, 2, 64).astype(np.int32)
        res = assert_sel(X, y)
        assert res.selected.shape == (2,)

    def test_transform(self):
        from repro.core import FeatureSelector

        rng = np.random.default_rng(4)
        X = rng.integers(0, 2, (64, 9)).astype(np.int32)
        y = (X[:, 3] ^ (rng.random(64) < 0.1)).astype(np.int32)
        fs = FeatureSelector(num_select=3).fit(X, y)
        Xt = fs.transform(X)
        assert Xt.shape == (64, 3)
        assert fs.selected_[0] == 3
