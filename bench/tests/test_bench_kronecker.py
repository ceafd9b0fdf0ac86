"""The Graph500 Kronecker family (`bench/families/kronecker.py`): its tuples
are those of the spec's `kronecker_generator.m` from the same uniforms, its
`Graph` is the one `build_graph` builds from them taken both ways, every
`--seed` has the same sizes, and a small Kronecker cell under
`rmat-s22-k8`'s traffic and limits comes out correct, and its control
not."""
import json
import os
import shutil
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchlib import graphgen, harness, spec  # noqa: E402
from repro.graphs.csr import build_graph  # noqa: E402
from test_bench_generator import FIELDS, assert_same_graph  # noqa: E402
from test_bench_run import _run, restore_jax_config  # noqa: E402,F401

ROOT = os.path.dirname(os.path.dirname(HERE))
kronecker = spec.load_module(ROOT, "families", "kronecker")
CONFIG = json.load(open(os.path.join(ROOT, "bench", "configs", "rmat-s22.json")))
SEED = 2**31 + 29


def small(scale):
    return dict(CONFIG, scale=scale)


def spec_tuples(u, perm, thr):
    """`kronecker_generator.m` in numpy, from its uniforms (`u[ib - 1]` is
    level ib's pair of `rand (1, M)`) and its `randperm (N)` (`perm`, 0-based);
    ids are 1-based until the last line, as there."""
    scale, _, m = u.shape
    ab, c_norm, a_norm = thr
    ij = np.ones((2, m), np.int64)
    for ib in range(1, scale + 1):
        ii_bit = u[ib - 1, 0] > ab
        jj_bit = u[ib - 1, 1] > (c_norm * ii_bit + a_norm * np.logical_not(ii_bit))
        ij = ij + 2 ** (ib - 1) * np.stack([ii_bit, jj_bit])
    p = perm + 1
    ij = p[ij - 1]
    return ij - 1


@pytest.mark.parametrize("scale", [8, 9, 10])
def test_tuples_are_the_spec_generators(scale):
    cfg = small(scale)
    n, m = kronecker.sizes(cfg)
    assert (n, m) == (2**scale, 16 * 2**scale)
    k_bits, k_perm = kronecker.graph_keys(cfg)
    u = np.stack([np.asarray(kronecker.level_uniforms(k_bits, lv, m))
                  for lv in range(scale)])
    perm = np.asarray(jax.random.permutation(k_perm, n))
    want = spec_tuples(u, perm, kronecker.thresholds(cfg))
    got = np.stack([np.asarray(x) for x in kronecker.tuples(cfg)])
    np.testing.assert_array_equal(got, want)
    # the initiator shows: id 0's quadrant (both bits clear) is drawn at A
    # per level, so the spec permutation's image of 0 is the largest hub
    assert np.bincount(want.reshape(-1), minlength=n).argmax() == perm[0]


def test_level_bits_at_the_thresholds():
    """A uniform equal to its threshold sets no bit, one just above sets it,
    as `rand > x` does."""
    thr = kronecker.thresholds(CONFIG)
    u = np.array([[thr[0], np.nextafter(thr[0], 1), thr[0], np.float32(1)],
                  [thr[2], thr[1], np.nextafter(thr[2], 1),
                   np.nextafter(thr[1], 1)]], np.float32)
    want = spec_tuples(u[None], np.arange(2), thr)
    got = np.stack([np.asarray(x) for x in kronecker.level_bits(u, thr)])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(want, [[0, 1, 0, 1], [0, 0, 1, 1]])


def test_graph_is_build_graph_of_the_tuples_both_ways():
    cfg = small(10)
    g = graphgen.generate(cfg, SEED)
    key = jax.random.fold_in(jax.random.PRNGKey(SEED), graphgen.RELABEL_STREAM)
    relabel = np.asarray(graphgen.relabeling(key, g.n))
    i, j = (relabel[np.asarray(x)] for x in kronecker.tuples(cfg))
    assert_same_graph(g, build_graph(np.concatenate([i, j]),
                                     np.concatenate([j, i]), g.n))
    assert g.n == 2**10
    src = np.repeat(np.arange(g.n), np.diff(g.row_ptr))
    assert not np.any(src == g.col_idx)
    keys = src * g.n + g.col_idx
    assert np.all(np.diff(keys) > 0)            # rows ascending, no repeat
    np.testing.assert_array_equal(np.sort(g.col_idx * g.n + src), keys)
    np.testing.assert_array_equal(g.adj_w, np.full(g.m, 2.0, np.float32))


@pytest.mark.parametrize("block_v", [8, 24, 128])
def test_seeds_share_edges_per_block(block_v):
    a, b = graphgen.generate(small(10), 1), graphgen.generate(small(10), SEED)
    assert (a.n, a.m, a.num_sym_edges) == (b.n, b.m, b.num_sym_edges)
    for f in ("row_ptr", "adj_ptr"):
        np.testing.assert_array_equal(getattr(a, f)[::block_v],
                                      getattr(b, f)[::block_v])
    assert not np.array_equal(a.col_idx, b.col_idx)
    assert all(getattr(a, f).dtype == getattr(b, f).dtype for f in FIELDS)


@pytest.fixture(scope="module")
def kron_root(tmp_path_factory):
    """The benchmark's tree with a SCALE-11 Kronecker cell held to
    `rmat-s22-k8`'s traffic and limits."""
    root = str(tmp_path_factory.mktemp("kron_root"))
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = {w["name"]: w for w in bench["workloads"]}["rmat-s22-k8"]
    with open(os.path.join(root, "bench", "configs", "toy-kron.json"), "w") as f:
        json.dump(dict(small(11), name="toy-kron"), f)
    shutil.copy(os.path.join(ROOT, "bench", "limits", "rmat-s22-k8.json"),
                os.path.join(root, "bench", "limits", "toy-k8.json"))
    bench["configs"] = [{"name": "toy-kron", "source": "x", "why": "x",
                         "file": "bench/configs/toy-kron.json",
                         "reduced": []}]
    bench["workloads"] = [dict(cell, name="toy-k8", config="toy-kron")]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_kronecker_cell_is_correct(kron_root, capsys, restore_jax_config):
    r = _run(kron_root, capsys)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert r["checks"]["labels_differ"]["value"] == 0.0
    assert {"edges_per_s", "local_edges", "max_norm_load", "setup_s"} <= set(
        r["metrics"])


def test_kronecker_control_is_not_correct(kron_root, capsys,
                                          restore_jax_config):
    r = _run(kron_root, capsys, job_fn=harness.control_job)
    assert r["correct"] is False and r["failed"] == r["attempted"]
    assert any(v["value"] > v["limit"] for v in r["checks"].values())
