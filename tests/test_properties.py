"""Property-based tests (hypothesis) for mRMR system invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st, assume, HealthCheck  # noqa: E402

from repro import MRMRSelector  # noqa: E402
from repro.core import MIScore, mrmr_reference, mi_from_counts  # noqa: E402
from repro.core.criteria import resolve_criterion  # noqa: E402
from repro.core.scores import cmi_from_counts  # noqa: E402
from repro.data.sources import ArraySource  # noqa: E402

_SETTINGS = dict(
    max_examples=10,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)


def _dataset(draw, max_n=10, max_m=96, num_values=2):
    n = draw(st.integers(4, max_n))
    m = draw(st.integers(16, max_m))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    X = rng.integers(0, num_values, (n, m)).astype(np.int32)  # feature-major
    y = rng.integers(0, 2, m).astype(np.int32)
    return X, y


@st.composite
def datasets(draw):
    return _dataset(draw)


@st.composite
def datasets_v3(draw):
    return _dataset(draw, num_values=3)


@given(datasets())
@settings(**_SETTINGS)
def test_selection_unique_and_in_range(data):
    X, y = data
    n = X.shape[0]
    L = min(4, n)
    res = mrmr_reference(jnp.asarray(X), jnp.asarray(y), L, MIScore(2, 2))
    sel = np.asarray(res.selected)
    assert len(np.unique(sel)) == L
    assert sel.min() >= 0 and sel.max() < n


@given(datasets())
@settings(**_SETTINGS)
def test_incremental_equals_faithful(data):
    X, y = data
    L = min(5, X.shape[0])
    a = mrmr_reference(jnp.asarray(X), jnp.asarray(y), L, MIScore(2, 2),
                       incremental=True)
    b = mrmr_reference(jnp.asarray(X), jnp.asarray(y), L, MIScore(2, 2),
                       incremental=False)
    np.testing.assert_array_equal(np.asarray(a.selected), np.asarray(b.selected))
    np.testing.assert_allclose(a.gains, b.gains, rtol=1e-4, atol=1e-5)


def _np_mrmr_with_gaps(X, y, L, v=2):
    """Numpy mRMR returning (selection, min top-2 score gap across steps)."""
    from tests.test_scores import np_mi, np_pair_counts

    n = X.shape[0]
    rel = np.array([np_mi(np_pair_counts(X[k], y, v, 2)) for k in range(n)])
    pair = np.array(
        [[np_mi(np_pair_counts(X[k], X[j], v, v)) for j in range(n)]
         for k in range(n)]
    )
    selected, min_gap = [], np.inf
    for l in range(L):
        red = (pair[:, selected].mean(axis=1) if selected else np.zeros(n))
        g = rel - red
        g[selected] = -np.inf
        order = np.argsort(g)[::-1]
        gap = g[order[0]] - g[order[1]] if n - len(selected) > 1 else np.inf
        min_gap = min(min_gap, gap)
        selected.append(int(order[0]))
    return selected, min_gap


@given(datasets(), st.integers(0, 2**31 - 1))
@settings(**_SETTINGS)
def test_selection_permutation_equivariant(data, perm_seed):
    """With no score ties at any greedy step, permuting feature order maps
    the selection exactly through the permutation (ties legitimately fork
    the greedy trajectory, so tied examples are discarded)."""
    X, y = data
    n = X.shape[0]
    L = min(4, n)
    sel_np, gap = _np_mrmr_with_gaps(X, y, L)
    assume(gap > 1e-4)
    score = MIScore(2, 2)
    res = mrmr_reference(jnp.asarray(X), jnp.asarray(y), L, score)
    np.testing.assert_array_equal(np.asarray(res.selected), sel_np)
    perm = np.random.default_rng(perm_seed).permutation(n)
    res_p = mrmr_reference(jnp.asarray(X[perm]), jnp.asarray(y), L, score)
    np.testing.assert_array_equal(
        perm[np.asarray(res_p.selected)], np.asarray(res.selected)
    )


@given(datasets_v3(), st.integers(0, 2**31 - 1))
@settings(**_SETTINGS)
def test_selection_invariant_to_category_relabeling(data, seed):
    """MI is invariant under per-feature category permutation, so (absent
    score ties, which float32 row-order effects can flip) the whole greedy
    trajectory must be identical."""
    X, y = data
    n = X.shape[0]
    L = min(4, n)
    _, gap = _np_mrmr_with_gaps(X, y, L, v=3)
    assume(gap > 1e-4)
    score = MIScore(3, 2)
    relabel = np.random.default_rng(seed).permutation(3)
    X2 = relabel[X]
    a = mrmr_reference(jnp.asarray(X), jnp.asarray(y), L, score)
    b = mrmr_reference(jnp.asarray(X2), jnp.asarray(y), L, score)
    np.testing.assert_array_equal(np.asarray(a.selected), np.asarray(b.selected))
    np.testing.assert_allclose(a.gains, b.gains, rtol=1e-4, atol=1e-5)


@given(st.integers(0, 2**31 - 1), st.integers(2, 6), st.integers(2, 6))
@settings(**_SETTINGS)
def test_mi_nonnegative_symmetric(seed, v, c):
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 40, (v, c)).astype(np.float32)
    assume(counts.sum() > 0)
    a = float(mi_from_counts(jnp.asarray(counts)))
    b = float(mi_from_counts(jnp.asarray(counts.T)))
    assert a >= -1e-6
    assert abs(a - b) < 1e-5


@given(st.integers(0, 2**31 - 1), st.integers(16, 200))
@settings(**_SETTINGS)
def test_mi_data_processing(seed, m):
    """I(x; y) <= H(x): MI bounded by the entropy of either variable."""
    from repro.core import entropy_from_counts
    from repro.core.contingency import pair_counts

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 4, m))
    y = jnp.asarray(rng.integers(0, 3, m))
    counts = pair_counts(x, y, 4, 3)
    mi = float(mi_from_counts(counts))
    hx = float(entropy_from_counts(counts.sum(axis=1)))
    hy = float(entropy_from_counts(counts.sum(axis=0)))
    assert mi <= min(hx, hy) + 1e-5



_CRITERIA = ("mid", "miq", "maxrel", "jmi", "cmim", "mifs", "cife", "icap")


@st.composite
def streamed_datasets(draw):
    """(X (obs, feat), y, V, C, block_obs): V 2-4 categories, C 2-3
    classes, and a block size that leaves the last block short."""
    v = draw(st.integers(2, 4))
    c = draw(st.integers(2, 3))
    m = draw(st.sampled_from([40, 97]))
    block_obs = draw(st.sampled_from([7, 30]))  # divides neither m
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    X = rng.integers(0, v, (m, 6)).astype(np.int8)
    y = rng.integers(0, c, m).astype(np.int8)
    return X, y, v, c, block_obs


def _min_gap(X, y, L, v, c, criterion):
    """Smallest top-2 objective gap along the greedy trajectory, from
    exact counts folded by the criterion's own hooks: a near-zero gap is
    a score tie, which float32 summation order may break either way."""
    crit = resolve_criterion(criterion)
    n = X.shape[1]

    def counts(b, vb):  # (n, v, vb) tables of every column against b
        t = np.zeros((n, v, vb))
        for k in range(n):
            np.add.at(t[k], (X[:, k], b), 1)
        return t

    rel = mi_from_counts(jnp.asarray(counts(y, c)))
    state, selected, gap = crit.init_state(n), [], np.inf
    for l in range(L):
        g = np.asarray(crit.objective(rel, state, l), np.float64)
        g[selected] = -np.inf
        top = np.sort(g)[::-1]
        gap = min(gap, top[0] - top[1]) if l < n - 1 else gap
        k = int(np.argmax(g))
        selected.append(k)
        if crit.needs_redundancy:
            fused = X[:, k].astype(np.int64) * c + y
            cnt = counts(fused, v * c).reshape(n, v, v, c)
            terms = dict(marginal=mi_from_counts(jnp.asarray(cnt.sum(-1))),
                         conditional=cmi_from_counts(jnp.asarray(cnt)))
            if not crit.needs_conditional_redundancy:
                terms = terms["marginal"]
            state = crit.update(state, terms, l)
    return gap


@pytest.mark.parametrize("criterion", _CRITERIA)
@given(streamed_datasets())
@settings(**_SETTINGS)
def test_streaming_matches_reference(criterion, data):
    """A streamed fit over ragged blocks selects what the in-memory
    reference selects, for every registered criterion (absent score
    ties)."""
    X, y, v, c, block_obs = data
    L = 4
    assume(_min_gap(X, y, L, v, c, criterion) > 1e-4)
    score = MIScore(v, c)
    ref = mrmr_reference(jnp.asarray(X.T.astype(np.int32)),
                         jnp.asarray(y.astype(np.int32)), L, score,
                         criterion=criterion)
    got = MRMRSelector(num_select=L, score=score, criterion=criterion,
                       block_obs=block_obs).fit(ArraySource(X, y))
    assert got.plan_.encoding == "streaming"
    np.testing.assert_array_equal(got.selected_, np.asarray(ref.selected))
