"""Tests for the normalized-LP scoring (eqs. 10-12) and Spinner scoring (eq. 3)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.lp import (
    MAX_PAIR_K,
    edge_histogram_jnp,
    gather_pair,
    normalized_penalty,
    revolver_scores,
    sorted_segment_histogram,
    sorted_segment_sum,
    spinner_scores,
    tau_term,
)


def _hist_oracle(rows, slots, vals, n_rows, k):
    h = np.zeros((n_rows, k), dtype=np.float64)
    for r, s, v in zip(rows, slots, vals):
        h[r, s] += v
    return h


class TestEdgeHistogram:
    def test_matches_oracle(self):
        rng = np.random.default_rng(0)
        E, n, k = 500, 32, 8
        rows = rng.integers(0, n, size=E)
        slots = rng.integers(0, k, size=E)
        vals = rng.uniform(0, 2, size=E).astype(np.float32)
        out = edge_histogram_jnp(jnp.asarray(rows), jnp.asarray(slots),
                                 jnp.asarray(vals), n, k)
        np.testing.assert_allclose(np.asarray(out), _hist_oracle(rows, slots, vals, n, k),
                                   rtol=1e-5)

    def test_padding_zero_vals_ignored(self):
        rows = jnp.array([0, 0, 1])
        slots = jnp.array([1, 1, 0])
        vals = jnp.array([1.0, 0.0, 2.0])
        out = edge_histogram_jnp(rows, slots, vals, 2, 2)
        np.testing.assert_allclose(np.asarray(out), [[0, 1], [2, 0]])


# (row degrees, weights, padded tail, k, form): the slab of
# `blocking.fill_block_slab`, rows in order and padding (row 0, weight 0)
# after the last real slot
_SORTED_SLABS = {
    "empty-rows": ([0, 3, 0, 0, 5, 1, 0, 0], "eq4", 5, 8, "hist"),
    "hub": ([2, 900, 1, 0, 3], "eq4", 0, 8, "hist"),
    "padded-tail": ([1, 4, 0, 2, 6] * 8, "eq4", 300, 2, "hist"),
    "coarse": ([0, 7, 120, 3, 0, 40, 1] * 3, "coarse", 64, 64, "hist"),
    "pair-self-lambda": ([0, 6, 1, 0, 400, 2, 9, 0], "eq4", 100, 8, "pair"),
    "pair-coarse": ([5, 0, 70, 1, 33], "coarse", 7, 64, "pair"),
}


@pytest.mark.parametrize("case", sorted(_SORTED_SLABS))
def test_sorted_segment_histogram_equals_scatter(case):
    """The prefix-sum histogram of a slab whose rows are stored in order
    equals `edge_histogram_jnp` bit for bit, for eq.-4 weights {1, 2} and
    coarse integer sums, empty rows (row 0 among them), a hub row, and a
    padded tail; "pair" is the eq.-13 (A, N) form against the k-slot
    `self_lambda` scatter."""
    degrees, weights, pad, k, form = _SORTED_SLABS[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    n_rows, real = len(degrees), sum(degrees)
    ptr = np.concatenate([[0], np.cumsum(degrees)]).astype(np.int32)
    rows = np.concatenate([np.repeat(np.arange(n_rows), degrees),
                           np.zeros(pad, np.int64)]).astype(np.int32)
    hi = 3 if weights == "eq4" else 5000
    w = rng.integers(1, hi, size=real + pad).astype(np.float32)
    w[real:] = 0.0
    slots = rng.integers(0, k, size=real + pad).astype(np.int32)
    if form == "hist":
        want = edge_histogram_jnp(jnp.asarray(rows), jnp.asarray(slots),
                                  jnp.asarray(w), n_rows, k)
        got = sorted_segment_histogram(
            jnp.asarray(ptr), jnp.asarray(slots),
            jnp.asarray(w.astype(np.int32)), k).astype(jnp.float32)
    else:
        # slots are the neighbours' lambda; each row has its action, its
        # lambda and a per-slot feasibility as in revolver's eq. 13
        action = rng.integers(0, k, size=n_rows).astype(np.int32)
        lam = rng.integers(0, k, size=n_rows).astype(np.int32)
        feasible = rng.random(k) < 0.6
        agree = action[rows] == slots
        val = np.where(agree, w, np.where(feasible[lam[rows]], 1.0, 0.0))
        val = np.where(w > 0, val, 0.0).astype(np.float32)
        want = edge_histogram_jnp(jnp.asarray(rows), jnp.asarray(lam[rows]),
                                  jnp.asarray(val), n_rows, k)
        a = sorted_segment_sum(jnp.asarray(ptr),
                               jnp.asarray(np.where(agree, w, 0).astype(np.int32)))
        n = sorted_segment_sum(jnp.asarray(ptr),
                               jnp.asarray((~agree).astype(np.int32)))
        contrib = np.asarray(a) + np.where(feasible[lam], np.asarray(n), 0)
        got = (np.eye(k, dtype=np.float32)[lam]
               * contrib.astype(np.float32)[:, None])
    assert np.asarray(want).dtype == np.asarray(got).dtype == np.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(want).any()


class TestGatherPair:
    @pytest.mark.parametrize("k", [2, 8, 127, 256, MAX_PAIR_K])
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16), n=st.integers(1, 300),
           e=st.integers(1, 500))
    def test_equals_two_gathers(self, k, seed, n, e):
        """Both halves come back as the plain gathers read them, for label
        values up to k - 1 and with padding indices (0) among the reads."""
        rng = np.random.default_rng(seed)
        a = rng.integers(0, k, size=n, dtype=np.int32)
        b = rng.integers(0, k, size=n, dtype=np.int32)
        a[0], b[-1] = k - 1, k - 1                 # the largest value is read
        idx = rng.integers(0, n, size=e, dtype=np.int32)
        idx[rng.random(e) < 0.3] = 0               # padding slots read row 0
        got_a, got_b = gather_pair(jnp.asarray(a), jnp.asarray(b),
                                   jnp.asarray(idx))
        assert got_a.dtype == got_b.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got_a), a[idx])
        np.testing.assert_array_equal(np.asarray(got_b), b[idx])

    def test_block_shaped_indices(self):
        """[nb, e_max] indices (the Pallas wrapper's slabs) keep their shape."""
        a = jnp.arange(6, dtype=jnp.int32)
        b = 5 - a
        idx = jnp.array([[0, 5, 2], [3, 0, 0]], jnp.int32)
        got_a, got_b = gather_pair(a, b, idx)
        np.testing.assert_array_equal(np.asarray(got_a), np.asarray(a[idx]))
        np.testing.assert_array_equal(np.asarray(got_b), np.asarray(b[idx]))


class TestNormalizedPenalty:
    def test_sums_to_one(self):
        loads = jnp.array([10.0, 20.0, 5.0, 1.0])
        pi = normalized_penalty(loads, capacity=30.0)
        np.testing.assert_allclose(float(jnp.sum(pi)), 1.0, rtol=1e-6)

    def test_less_loaded_gets_higher_penalty_score(self):
        loads = jnp.array([10.0, 20.0])
        pi = normalized_penalty(loads, capacity=30.0)
        assert float(pi[0]) > float(pi[1])

    def test_negative_shift_footnote(self):
        """Over-capacity partitions make (1 - b/C) negative; footnote 1 shifts."""
        loads = jnp.array([40.0, 10.0])  # first partition over capacity 30
        pi = normalized_penalty(loads, capacity=30.0)
        assert float(jnp.min(pi)) >= 0.0
        np.testing.assert_allclose(float(jnp.sum(pi)), 1.0, rtol=1e-6)

    def test_paper_capacity_mode_all_negative(self):
        """With C = eps|E|/k every term is negative; still a distribution."""
        loads = jnp.array([100.0, 120.0, 90.0])
        pi = normalized_penalty(loads, capacity=5.0)
        assert float(jnp.min(pi)) >= 0.0
        np.testing.assert_allclose(float(jnp.sum(pi)), 1.0, rtol=1e-6)

    @settings(max_examples=30, deadline=None)
    @given(k=st.integers(2, 64), seed=st.integers(0, 2**16),
           cap=st.floats(1.0, 1e4))
    def test_property_distribution(self, k, seed, cap):
        rng = np.random.default_rng(seed)
        loads = jnp.asarray(rng.uniform(0, 2 * cap, size=k).astype(np.float32))
        pi = np.asarray(normalized_penalty(loads, cap))
        assert np.all(pi >= 0)
        np.testing.assert_allclose(pi.sum(), 1.0, atol=1e-4)


class TestScores:
    def test_revolver_score_bounds(self):
        """tau in [0,1], pi in [0,1] => score in [0,1]."""
        rng = np.random.default_rng(1)
        n, k = 16, 4
        hist = rng.uniform(0, 3, size=(n, k)).astype(np.float32)
        wsum = hist.sum(-1) + 1e-6
        inv = (1.0 / wsum).astype(np.float32)
        loads = jnp.asarray(rng.uniform(0, 50, size=k).astype(np.float32))
        s = np.asarray(revolver_scores(jnp.asarray(hist), jnp.asarray(inv), loads, 40.0))
        assert np.all(s >= 0) and np.all(s <= 1.0 + 1e-5)

    def test_spinner_score_matches_eq3(self):
        hist = jnp.array([[2.0, 1.0]])
        inv = jnp.array([1.0 / 3.0])
        loads = jnp.array([30.0, 60.0])
        s = np.asarray(spinner_scores(hist, inv, loads, capacity=60.0))
        np.testing.assert_allclose(s, [[2 / 3 - 0.5, 1 / 3 - 1.0]], rtol=1e-5)

    def test_tau_prefers_majority_label(self):
        hist = jnp.array([[5.0, 1.0, 0.0]])
        inv = jnp.array([1.0 / 6.0])
        tau = np.asarray(tau_term(hist, inv))
        assert tau[0, 0] > tau[0, 1] > tau[0, 2]
        np.testing.assert_allclose(tau.sum(), 1.0, rtol=1e-5)
