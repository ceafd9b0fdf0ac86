"""The benchmark's device generator builds exactly the `Graph` that
`repro.graphs.csr.build_graph` builds from the same drawn edges, and its
per-seed relabeling keeps every size the program's layout depends on. A
graph family is the file `bench/families/<family>.py`."""
import gc
import hashlib
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchlib import graphgen, spec  # noqa: E402
from repro.graphs.csr import build_graph  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(HERE))
FIELDS = ("row_ptr", "col_idx", "adj_ptr", "adj_idx", "adj_w", "deg_out")
ROAD = dict(family="grid_road", graph_seed=4, n=10001, drop_frac=0.39)
grid_road = spec.load_module(ROOT, "families", "grid_road")


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
    side, n = grid_road.road_sizes(cfg)
    src, dst = _road_pairs(np.asarray(grid_road.road_present(cfg)), side)
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


def _digest(g):
    """SHA-256 over |V|, |E| and every CSR array with its dtype and shape."""
    h = hashlib.sha256(f"{g.n} {g.m}".encode())
    for f in FIELDS:
        a = getattr(g, f)
        h.update(f"{f} {a.dtype} {a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed, digest", [
    (7, "48bcf39ee8325ae2a4590f9031f8742248818dac667fa2073038b0648babde62"),
    (2**31 + 17,
     "189493c6fb5462fbc12e4c42e7b3e2b3ab3aa916328841178edca82c3bc2d3b4"),
])
def test_grid_road_graph_is_pinned(seed, digest):
    """The lattice, array for array, as the generator drew it before the
    family moved into a file of its own."""
    assert _digest(graphgen.generate(ROAD, seed)) == digest


def test_unknown_family_names_the_file_it_looked_for():
    with pytest.raises(ValueError, match=r"families/no_such_family\.py"):
        graphgen.generate(dict(ROAD, family="no_such_family"), 1)


def test_family_module_stays_loaded_across_generate_calls():
    """The family's module, and the programs it compiled, outlive each call,
    as an imported module's do: the device peak of the run's jobs moves
    when they are freed (PERF.md, section 2)."""
    graphgen.generate(ROAD, 1)
    gc.collect()
    family = spec.load_module(ROOT, "families", "grid_road")
    assert family is grid_road
    compiled = family._road_rows._cache_size()
    assert compiled >= 1
    graphgen.generate(ROAD, 2)
    gc.collect()
    assert spec.load_module(ROOT, "families", "grid_road") is family
    assert family._road_rows._cache_size() == compiled
