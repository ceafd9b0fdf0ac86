"""The benchmark's device generator builds exactly the `Graph` that
`repro.graphs.csr.build_graph` builds from the same drawn edges, and its
per-seed relabeling keeps every size the program's layout depends on."""
import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchlib import graphgen  # noqa: E402
from repro.graphs.csr import build_graph  # noqa: E402

FIELDS = ("row_ptr", "col_idx", "adj_ptr", "adj_idx", "adj_w", "deg_out")
ROAD = dict(family="grid_road", graph_seed=4, n=10001, drop_frac=0.39)


def assert_same_graph(got, want):
    assert (got.n, got.m) == (want.n, want.m)
    for f in FIELDS:
        a, b = getattr(got, f), getattr(want, f)
        assert a.dtype == b.dtype, f
        np.testing.assert_array_equal(a, b, err_msg=f)


def _road_pairs(present, side):
    """The directed edge list a lattice mask stands for."""
    v, j = np.nonzero(present)
    offs = np.array([-side, -1, 1, side], dtype=np.int64)
    return v.astype(np.int64), v + offs[j]


def _relabeled_pairs(cfg, seed):
    key = jax.random.fold_in(jax.random.PRNGKey(seed), graphgen.RELABEL_STREAM)
    side, n = graphgen.road_sizes(cfg)
    src, dst = _road_pairs(np.asarray(graphgen.road_present(cfg)), side)
    perm = np.asarray(graphgen.relabeling(key, n))
    return perm[src], perm[dst], n


def test_generator_matches_build_graph():
    src, dst, n = _relabeled_pairs(ROAD, seed=2**31 + 17)
    assert_same_graph(graphgen.generate(ROAD, 2**31 + 17), build_graph(src, dst, n))


def test_relabeling_permutes_within_groups_of_eight():
    n = 8 * 37 + 5
    perm = np.asarray(graphgen.relabeling(jax.random.PRNGKey(1), n))
    np.testing.assert_array_equal(np.sort(perm), np.arange(n))
    np.testing.assert_array_equal(perm // 8, np.arange(n) // 8)
    assert not np.array_equal(perm, np.arange(n))


def test_seeds_share_sizes_and_differ_in_order():
    a, b = graphgen.generate(ROAD, 1), graphgen.generate(ROAD, 2)
    assert (a.n, a.m, a.num_sym_edges) == (b.n, b.m, b.num_sym_edges)
    # every 8-aligned vertex group keeps its edge count, so does every block
    np.testing.assert_array_equal(a.adj_ptr[::8], b.adj_ptr[::8])
    np.testing.assert_array_equal(a.row_ptr[::8], b.row_ptr[::8])
    assert not np.array_equal(a.col_idx, b.col_idx)

