"""Integration tests for the Revolver partitioner and its baselines."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.device_graph import capacity, capacity_device, prepare_device_graph
from repro.core.lp import MAX_PAIR_K
from repro.core.metrics import local_edges, max_normalized_load, partition_loads
from repro.core.revolver import RevolverConfig, revolver_init, revolver_superstep
from repro.core.runner import run_partitioner
from repro.core.spinner import SpinnerConfig, spinner_init, spinner_superstep
from repro.graphs.generators import dc_sbm, ring_of_cliques


@pytest.fixture(scope="module")
def clique_graph():
    return ring_of_cliques(8, 16)


@pytest.fixture(scope="module")
def sbm_graph():
    return dc_sbm(1024, 8192, n_comm=16, mixing=0.25, degree_exponent=0.5, seed=3)


class TestRevolverInvariants:
    def test_loads_match_labels_every_step(self, sbm_graph):
        """Invariant: state.loads == recomputed b(l) after async chunk updates."""
        dg = prepare_device_graph(sbm_graph, n_blocks=4)
        cfg = RevolverConfig(k=4, max_steps=10)
        st = revolver_init(dg, cfg, jax.random.PRNGKey(0))
        for _ in range(5):
            st = revolver_superstep(dg, cfg, st)
            expect = partition_loads(st.labels, dg.deg_out, 4)
            np.testing.assert_allclose(np.asarray(st.loads), np.asarray(expect), rtol=1e-5)

    def test_labels_in_range(self, sbm_graph):
        dg = prepare_device_graph(sbm_graph, n_blocks=4)
        cfg = RevolverConfig(k=6)
        st = revolver_init(dg, cfg, jax.random.PRNGKey(1))
        for _ in range(3):
            st = revolver_superstep(dg, cfg, st)
        lab = np.asarray(st.labels)
        assert lab.min() >= 0 and lab.max() < 6

    def test_probs_remain_simplex(self, sbm_graph):
        dg = prepare_device_graph(sbm_graph, n_blocks=4)
        cfg = RevolverConfig(k=4)
        st = revolver_init(dg, cfg, jax.random.PRNGKey(2))
        for _ in range(5):
            st = revolver_superstep(dg, cfg, st)
        sums = np.asarray(jnp.sum(st.probs, axis=-1))
        np.testing.assert_allclose(sums, 1.0, atol=1e-4)

    def test_deterministic_given_seed(self, clique_graph):
        r1 = run_partitioner("revolver", clique_graph, 4, max_steps=15, seed=7,
                             track_history=False)
        r2 = run_partitioner("revolver", clique_graph, 4, max_steps=15, seed=7,
                             track_history=False)
        np.testing.assert_array_equal(r1.labels, r2.labels)

    def test_sync_mode_single_block(self, sbm_graph):
        """n_blocks=1 (synchronous degenerate case) still works."""
        r = run_partitioner("revolver", sbm_graph, 4, max_steps=20, seed=0,
                            n_blocks=1, track_history=False)
        assert r.local_edges > 0


class TestRevolverQuality:
    def test_recovers_planted_cliques(self, clique_graph):
        r = run_partitioner("revolver", clique_graph, 8, max_steps=290, seed=0,
                            track_history=False)
        assert r.local_edges > 0.9          # near-perfect: one clique per part
        assert r.max_norm_load < 1.10

    def test_beats_hash_on_communities(self, sbm_graph):
        rh = run_partitioner("hash", sbm_graph, 8)
        rr = run_partitioner("revolver", sbm_graph, 8, max_steps=150, seed=0,
                             track_history=False)
        assert rr.local_edges > rh.local_edges + 0.1

    def test_balance_within_epsilon_slack(self, sbm_graph):
        """Paper claim: Revolver stays within the 5% imbalance budget."""
        r = run_partitioner("revolver", sbm_graph, 8, max_steps=150, seed=0,
                            track_history=False)
        assert r.max_norm_load <= 1.10  # 1+eps (+ small sampling noise)

    def test_paper_capacity_mode_runs(self, sbm_graph):
        r = run_partitioner("revolver", sbm_graph, 4, max_steps=20, seed=0,
                            capacity_mode="paper", track_history=False)
        assert 0.0 <= r.local_edges <= 1.0


class TestSpinner:
    def test_spinner_improves_over_random(self, sbm_graph):
        rh = run_partitioner("hash", sbm_graph, 8)
        rs = run_partitioner("spinner", sbm_graph, 8, max_steps=150, seed=0,
                             track_history=False)
        assert rs.local_edges > rh.local_edges + 0.1

    def test_spinner_loads_consistent(self, sbm_graph):
        dg = prepare_device_graph(sbm_graph, n_blocks=1)
        cfg = SpinnerConfig(k=4)
        st = spinner_init(dg, cfg, jax.random.PRNGKey(0))
        for _ in range(5):
            st = spinner_superstep(dg, cfg, st)
            expect = partition_loads(st.labels, dg.deg_out, 4)
            np.testing.assert_allclose(np.asarray(st.loads), np.asarray(expect), rtol=1e-5)


class TestStaticPartitioners:
    def test_hash_balanced_on_uniform_ids(self):
        g = dc_sbm(1024, 4096, n_comm=8, seed=0)
        r = run_partitioner("hash", g, 8)
        assert r.max_norm_load < 1.5

    def test_range_contiguous(self):
        g = ring_of_cliques(4, 8)
        r = run_partitioner("range", g, 4)
        # range partitioning on community-sorted ids == planted partition
        assert r.local_edges > 0.9


class TestCapacity:
    def test_capacity_modes(self):
        assert capacity(1000, 10, 0.05, "spinner") == pytest.approx(105.0)
        assert capacity(1000, 10, 0.05, "paper") == pytest.approx(5.0)
        with pytest.raises(ValueError):
            capacity(1000, 10, 0.05, "bogus")

    def test_capacity_device_cached(self):
        """The superstep-side capacity is hoisted: same (m, cfg) inputs hit
        one committed device buffer instead of a per-step recompute."""
        a = capacity_device(1000, 10, 0.05, "spinner")
        b = capacity_device(1000, 10, 0.05, "spinner")
        assert a is b
        assert float(a) == pytest.approx(105.0)
        assert capacity_device(1000, 10, 0.05, "paper") is not a


class TestConfigValidation:
    """Impl/mode knobs reject typos at construction instead of silently
    falling back to the jnp path."""

    @pytest.mark.parametrize("field,bad", [
        ("la_impl", "palas"),
        ("hist_impl", "cuda"),
        ("weight_mode", "self_lamda"),
        ("capacity_mode", "bogus"),
    ])
    def test_revolver_bad_choice_raises(self, field, bad):
        with pytest.raises(ValueError, match=field):
            RevolverConfig(k=4, **{field: bad})

    def test_revolver_k_beyond_packed_pairs_raises(self):
        """The edge phase packs label pairs into 16-bit halves of one word."""
        assert RevolverConfig(k=MAX_PAIR_K).k == MAX_PAIR_K
        with pytest.raises(ValueError, match="k=32768"):
            RevolverConfig(k=MAX_PAIR_K + 1)

    def test_revolver_valid_choices_accepted(self):
        cfg = RevolverConfig(k=4, la_impl="pallas", hist_impl="pallas",
                             weight_mode="neighbor_lambda",
                             capacity_mode="paper")
        assert cfg.hist_impl == "pallas"

    def test_spinner_bad_capacity_mode_raises(self):
        with pytest.raises(ValueError, match="capacity_mode"):
            SpinnerConfig(k=4, capacity_mode="bogus")


class TestFusedHistParity:
    """hist_impl="pallas" routes the superstep through the fused
    dual-histogram edge-phase kernel; at fixed seed it must reproduce the
    jnp scatter-add partition (acceptance: 1e-5 score tolerance)."""

    @pytest.mark.parametrize("weight_mode", ["self_lambda", "neighbor_lambda"])
    def test_superstep_trajectory_matches_jnp(self, sbm_graph, weight_mode):
        dg = prepare_device_graph(sbm_graph, n_blocks=4)
        finals = {}
        for impl in ("jnp", "pallas"):
            cfg = RevolverConfig(k=4, hist_impl=impl, weight_mode=weight_mode)
            st = revolver_init(dg, cfg, jax.random.PRNGKey(0))
            for _ in range(6):
                st = revolver_superstep(dg, cfg, st)
            finals[impl] = st
        assert abs(float(finals["jnp"].score)
                   - float(finals["pallas"].score)) <= 1e-5
        np.testing.assert_allclose(np.asarray(finals["jnp"].probs),
                                   np.asarray(finals["pallas"].probs),
                                   atol=1e-5, rtol=1e-5)
        # bit-exact labels only hold where both paths accumulate f32 the
        # same way (CPU interpret mode); a compiled MXU reduction may flip
        # ULP-level argmax ties, which the score tolerance above absorbs
        if jax.default_backend() == "cpu":
            np.testing.assert_array_equal(np.asarray(finals["jnp"].labels),
                                          np.asarray(finals["pallas"].labels))

    def test_end_to_end_partition_matches_jnp(self, clique_graph):
        rj = run_partitioner("revolver", clique_graph, 4, max_steps=15, seed=7,
                             track_history=False, hist_impl="jnp")
        rp = run_partitioner("revolver", clique_graph, 4, max_steps=15, seed=7,
                             track_history=False, hist_impl="pallas")
        assert rp.local_edges == pytest.approx(rj.local_edges, abs=1e-5)
        assert rp.max_norm_load == pytest.approx(rj.max_norm_load, abs=1e-5)
        if jax.default_backend() == "cpu":  # see trajectory test above
            assert rp.steps == rj.steps
            np.testing.assert_array_equal(rj.labels, rp.labels)

    def test_pallas_hist_with_pallas_la(self, clique_graph):
        """Both kernel knobs on at once (the full-TPU configuration)."""
        r = run_partitioner("revolver", clique_graph, 4, max_steps=10, seed=0,
                            track_history=False, hist_impl="pallas",
                            la_impl="pallas")
        assert 0.0 <= r.local_edges <= 1.0
        assert r.max_norm_load > 0.0


class TestPaperClaims:
    """The paper's two headline claims, validated on the DC-SBM suite
    (EXPERIMENTS.md §Reproduction reports the full sweep)."""

    def test_revolver_balance_beats_spinner(self, sbm_graph):
        rr = run_partitioner("revolver", sbm_graph, 8, max_steps=200, seed=0,
                             track_history=False)
        rs = run_partitioner("spinner", sbm_graph, 8, max_steps=200, seed=0,
                             track_history=False)
        assert rr.max_norm_load <= rs.max_norm_load + 0.02

    def test_revolver_local_edges_comparable_to_spinner(self, sbm_graph):
        rr = run_partitioner("revolver", sbm_graph, 8, max_steps=200, seed=0,
                             track_history=False)
        rs = run_partitioner("spinner", sbm_graph, 8, max_steps=200, seed=0,
                             track_history=False)
        assert rr.local_edges >= rs.local_edges - 0.05
