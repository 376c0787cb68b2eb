"""Pallas kernel tests: shape/dtype sweeps vs the pure-jnp oracles.

All kernels run in ``interpret=True`` mode on CPU (the kernel body executes
in Python), which validates the BlockSpec tiling, accumulation-across-grid
logic and padding behaviour against ``repro.kernels.ref``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.binning import bin_codes_pallas
from repro.kernels.contingency import (
    conditional_tables_pallas,
    contingency_tables_pallas,
)
from repro.kernels.mi_score import mi_scores_pallas
from repro.kernels.pearson import pearson_corr_pallas


class TestContingencyKernel:
    @pytest.mark.parametrize(
        "m,f,v,c",
        [
            (16, 4, 2, 2),
            (100, 7, 3, 2),     # non-divisible M and F
            (512, 8, 4, 3),
            (1030, 33, 5, 4),   # padding on both axes
            (64, 1, 2, 2),      # single feature
        ],
    )
    def test_matches_oracle(self, m, f, v, c):
        rng = np.random.default_rng(hash((m, f, v, c)) % 2**31)
        X = jnp.asarray(rng.integers(0, v, (m, f)), jnp.int32)
        y = jnp.asarray(rng.integers(0, c, m), jnp.int32)
        got = contingency_tables_pallas(X, y, v, c, interpret=True)
        want = ref.contingency_tables(X, y, v, c)
        np.testing.assert_allclose(got, want, atol=0)

    @pytest.mark.parametrize("tile_m,tile_f", [(8, 2), (32, 8), (512, 64)])
    def test_tile_sweep(self, tile_m, tile_f):
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.integers(0, 3, (130, 21)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 2, 130), jnp.int32)
        got = contingency_tables_pallas(
            X, y, 3, 2, tile_m=tile_m, tile_f=tile_f, interpret=True
        )
        want = ref.contingency_tables(X, y, 3, 2)
        np.testing.assert_allclose(got, want, atol=0)

    def test_out_of_range_padding_rows(self):
        X = jnp.asarray([[0], [1], [2**31 - 1]], jnp.int32)
        y = jnp.asarray([0, 1, 2**31 - 1], jnp.int32)
        got = contingency_tables_pallas(X, y, 2, 2, interpret=True)
        np.testing.assert_allclose(got[0], np.array([[1, 0], [0, 1]]))

    @pytest.mark.parametrize(
        "dtype,m,f,v,conditional",
        [
            pytest.param(jnp.int8, 40, 5, 2, False, id="int8"),
            pytest.param(jnp.int16, 40, 5, 2, False, id="int16"),
            pytest.param(jnp.int32, 40, 5, 2, False, id="int32"),
            # X counted as it arrives, unpadded: ragged last feature tile
            # (300 and 1000 at the default 256) and ragged last row tile.
            pytest.param(jnp.int8, 1030, 300, 3, False, id="int8-1030x300"),
            pytest.param(jnp.int16, 1030, 300, 3, False, id="int16-1030x300"),
            pytest.param(jnp.int32, 1030, 300, 3, False, id="int32-1030x300"),
            pytest.param(jnp.int8, 2100, 1000, 2, False, id="int8-2100x1000"),
            pytest.param(jnp.int8, 1030, 300, 3, True, id="cond-int8-1030x300"),
            pytest.param(jnp.int16, 1030, 300, 3, True, id="cond-int16-1030x300"),
            pytest.param(jnp.int32, 1030, 300, 3, True, id="cond-int32-1030x300"),
            pytest.param(jnp.int8, 1100, 1000, 2, True, id="cond-int8-1100x1000"),
        ],
    )
    def test_dtypes(self, dtype, m, f, v, conditional):
        rng = np.random.default_rng(1)
        X = jnp.asarray(rng.integers(0, v, (m, f)), dtype)
        y = jnp.asarray(rng.integers(0, 2, m), dtype)
        X32, y32 = X.astype(jnp.int32), y.astype(jnp.int32)
        if conditional:
            xj = jnp.asarray(rng.integers(0, v, m), dtype)
            got = conditional_tables_pallas(X, xj, y, v, 2, interpret=True)
            want = ref.conditional_tables(X32, xj.astype(jnp.int32), y32, v, 2)
        else:
            got = contingency_tables_pallas(X, y, v, 2, interpret=True)
            want = ref.contingency_tables(X32, y32, v, 2)
        assert got.shape == want.shape
        np.testing.assert_array_equal(got, want)


class TestPearsonKernel:
    @pytest.mark.parametrize(
        "f,t,m",
        [
            (4, 1, 64),
            (7, 3, 100),     # non-divisible everywhere
            (128, 128, 512),
            (130, 5, 1030),  # padding on every axis
        ],
    )
    def test_matches_oracle(self, f, t, m):
        rng = np.random.default_rng(hash((f, t, m)) % 2**31)
        X = jnp.asarray(rng.normal(size=(f, m)), jnp.float32)
        Y = jnp.asarray(rng.normal(size=(t, m)), jnp.float32)
        got = pearson_corr_pallas(X, Y, interpret=True)
        want = ref.pearson_corr(X, Y)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("tile", [(8, 8, 16), (64, 32, 128)])
    def test_tile_sweep(self, tile):
        tf, tt, tm = tile
        rng = np.random.default_rng(2)
        X = jnp.asarray(rng.normal(size=(33, 200)), jnp.float32)
        Y = jnp.asarray(rng.normal(size=(9, 200)), jnp.float32)
        got = pearson_corr_pallas(
            X, Y, tile_f=tf, tile_t=tt, tile_m=tm, interpret=True
        )
        want = ref.pearson_corr(X, Y)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_bf16_input(self):
        rng = np.random.default_rng(3)
        X = jnp.asarray(rng.normal(size=(8, 128)), jnp.bfloat16)
        Y = jnp.asarray(rng.normal(size=(2, 128)), jnp.bfloat16)
        got = pearson_corr_pallas(X, Y, interpret=True)
        want = ref.pearson_corr(X.astype(jnp.float32), Y.astype(jnp.float32))
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)

    def test_self_correlation_diagonal(self):
        rng = np.random.default_rng(4)
        X = jnp.asarray(rng.normal(size=(6, 300)), jnp.float32)
        got = pearson_corr_pallas(X, X, interpret=True)
        np.testing.assert_allclose(np.diag(got), np.ones(6), rtol=1e-4)


class TestMIScoreKernel:
    @pytest.mark.parametrize(
        "f,v,c", [(1, 2, 2), (10, 3, 2), (300, 4, 4), (257, 5, 3)]
    )
    def test_matches_oracle(self, f, v, c):
        rng = np.random.default_rng(hash((f, v, c)) % 2**31)
        counts = jnp.asarray(rng.integers(0, 50, (f, v, c)), jnp.float32)
        got = mi_scores_pallas(counts, interpret=True)
        want = ref.mi_scores(counts)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_zero_rows(self):
        counts = jnp.zeros((4, 3, 3), jnp.float32)
        got = mi_scores_pallas(counts, interpret=True)
        np.testing.assert_allclose(got, np.zeros(4), atol=1e-6)


class TestBinCodesKernel:
    @pytest.mark.parametrize(
        "b,n,e",
        [
            (16, 4, 3),
            (100, 7, 15),     # non-divisible B and N
            (300, 130, 7),    # feature padding past one lane tile
            (64, 1, 31),      # single feature
            (1, 5, 1),        # single row, single edge
        ],
    )
    def test_matches_oracle_bitwise(self, b, n, e):
        rng = np.random.default_rng(hash((b, n, e)) % 2**31)
        X = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
        edges = jnp.asarray(np.sort(rng.normal(size=(n, e)), axis=1), jnp.float32)
        got = np.asarray(bin_codes_pallas(X, edges, interpret=True))
        want = np.asarray(ref.bin_codes(X, edges))
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("tile_b,tile_n", [(8, 2), (64, 8), (512, 256)])
    def test_tile_sweep(self, tile_b, tile_n):
        rng = np.random.default_rng(11)
        X = jnp.asarray(rng.normal(size=(130, 21)), jnp.float32)
        edges = jnp.asarray(np.sort(rng.normal(size=(21, 9)), axis=1), jnp.float32)
        got = bin_codes_pallas(
            X, edges, tile_b=tile_b, tile_n=tile_n, interpret=True
        )
        np.testing.assert_array_equal(got, ref.bin_codes(X, edges))

    def test_ties_go_to_upper_bin(self):
        # side="right" semantics: a value exactly on an edge counts that
        # edge, landing in the bin ABOVE it — both paths must agree.
        edges = jnp.asarray([[0.0, 1.0, 2.0]], jnp.float32).T.reshape(1, 3)
        X = jnp.asarray([[-1.0], [0.0], [0.5], [1.0], [2.0], [3.0]], jnp.float32)
        got = np.asarray(bin_codes_pallas(X, edges, interpret=True))[:, 0]
        np.testing.assert_array_equal(got, [0, 1, 1, 2, 3, 3])
        np.testing.assert_array_equal(
            got, np.asarray(ref.bin_codes(X, edges))[:, 0]
        )

    def test_duplicate_edges_skip_bins(self):
        # Heavy-tie features fit duplicate edges; codes jump past the
        # empty bins identically in kernel and oracle.
        edges = jnp.asarray([[1.0, 1.0, 1.0, 5.0]], jnp.float32)
        X = jnp.asarray([[0.0], [1.0], [4.0], [5.0]], jnp.float32)
        got = np.asarray(bin_codes_pallas(X, edges, interpret=True))[:, 0]
        np.testing.assert_array_equal(got, [0, 3, 3, 4])
        np.testing.assert_array_equal(
            got, np.asarray(ref.bin_codes(X, edges))[:, 0]
        )

    def test_ops_dispatch_agrees(self):
        rng = np.random.default_rng(12)
        X = jnp.asarray(rng.normal(size=(77, 13)), jnp.float32)
        edges = jnp.asarray(np.sort(rng.normal(size=(13, 7)), axis=1), jnp.float32)
        auto = np.asarray(ops.bin_codes(X, edges))
        forced = np.asarray(ops.bin_codes(X, edges, use_pallas=True))
        oracle = np.asarray(ref.bin_codes(X, edges))
        np.testing.assert_array_equal(auto, oracle)
        np.testing.assert_array_equal(forced, oracle)


class TestOpsDispatch:
    def test_ops_cpu_uses_oracle(self):
        rng = np.random.default_rng(5)
        X = jnp.asarray(rng.integers(0, 2, (50, 6)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 2, 50), jnp.int32)
        auto = ops.contingency_tables(X, y, 2, 2)
        oracle = ref.contingency_tables(X, y, 2, 2)
        np.testing.assert_allclose(auto, oracle)

    def test_ops_forced_pallas_interpret(self):
        rng = np.random.default_rng(6)
        X = jnp.asarray(rng.integers(0, 3, (64, 8)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 2, 64), jnp.int32)
        forced = ops.contingency_tables(X, y, 3, 2, use_pallas=True)
        oracle = ref.contingency_tables(X, y, 3, 2)
        np.testing.assert_allclose(forced, oracle)

    def test_mi_tables_end_to_end(self):
        rng = np.random.default_rng(7)
        X = jnp.asarray(rng.integers(0, 2, (200, 10)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 2, 200), jnp.int32)
        got = ops.mi_tables(X, y, 2, 2, use_pallas=True)
        from repro.core import mi_from_counts

        want = mi_from_counts(ref.contingency_tables(X, y, 2, 2))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
