"""The Graph500 Kronecker graph (Graph500 specification v3.0, section 3, and
its reference `kronecker_generator.m`): N = 2^scale vertices and M =
edgefactor * N tuples. Each tuple's two ids are drawn one bit per level
from the initiator A/B/C/D, level `l` setting bit `l`:

    ii_bit = rand > A + B
    jj_bit = rand > (C / (1 - A - B) if ii_bit else A / (A + B))

and the ids are then put through a random permutation of the vertices. The
graph is undirected, and the directed model takes every tuple both ways, so
every arc has its reverse and carries eq.-4 weight 2. Self-loops and
duplicates are dropped, as `build_graph` drops them. The spec's shuffle of
the tuples is skipped: the arcs are sorted, so it changes nothing.

Everything up to the sorted, deduplicated arcs runs on the device; the host
only cuts the kept arcs out and writes the CSR pointers. The uniforms and
the thresholds they are compared with are float32.

Configuration keys: `scale`, `edgefactor`, `a`, `b`, `c` and `graph_seed`,
from which the tuples and the vertex permutation are drawn.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import graphgen


def sizes(cfg: dict) -> tuple[int, int]:
    """(N vertices, M tuples)."""
    n = 1 << int(cfg["scale"])
    return n, int(cfg["edgefactor"]) * n


def thresholds(cfg: dict) -> np.ndarray:
    """float32 [A + B, C / (1 - A - B), A / (A + B)]: what a level's ii
    uniform, then its jj uniform (by ii_bit), must exceed to set the bit."""
    a, b, c = (float(cfg[x]) for x in ("a", "b", "c"))
    return np.array([a + b, c / (1.0 - (a + b)), a / (a + b)], np.float32)


def graph_keys(cfg: dict):
    """(key of the tuples' uniforms, key of the vertex permutation)."""
    return jax.random.split(jax.random.PRNGKey(cfg["graph_seed"]))


@partial(jax.jit, static_argnames=("m",))
def level_uniforms(key, level, m):
    """[2, m] float32: the ii and the jj uniforms of one level."""
    return jax.random.uniform(jax.random.fold_in(key, level), (2, m))


def level_bits(u, thr):
    """(ii_bit, jj_bit) of one level from its [2, m] uniforms."""
    ii = u[0] > thr[0]
    return ii, u[1] > jnp.where(ii, thr[1], thr[2])


@partial(jax.jit, static_argnames=("scale", "m"))
def _tuples(key, thr, perm, scale, m):
    def level(lv, ij):
        ii, jj = level_bits(level_uniforms(key, lv, m), thr)
        return (ij[0] | (ii.astype(jnp.int32) << lv),
                ij[1] | (jj.astype(jnp.int32) << lv))

    zero = jnp.zeros((m,), jnp.int32)
    i, j = jax.lax.fori_loop(0, scale, level, (zero, zero))
    return perm[i], perm[j]


def tuples(cfg: dict):
    """The spec's [M] int32 (i, j) tuples, after the vertex permutation, on
    the device."""
    n, m = sizes(cfg)
    k_bits, k_perm = graph_keys(cfg)
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    return _tuples(k_bits, jnp.asarray(thresholds(cfg)), perm,
                   int(cfg["scale"]), m)


@partial(jax.jit, static_argnames=("n",))
def _arcs(i, j, relabel, n):
    """The tuples relabeled and taken both ways, sorted by (src, dst): dst,
    whether each arc is kept (not a self-loop, not a repeat of the arc before
    it), and the CSR row pointers [n + 1] of the kept arcs."""
    a, b = relabel[i], relabel[j]
    src, dst = jax.lax.sort((jnp.concatenate([a, b]), jnp.concatenate([b, a])),
                            num_keys=2)
    new = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
    keep = jnp.concatenate([jnp.ones((1,), bool), new]) & (src != dst)
    kept_before = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(keep, dtype=jnp.int32)])
    rows = jnp.searchsorted(src, jnp.arange(n + 1, dtype=jnp.int32))
    return dst, keep, kept_before[rows]


def generate(cfg: dict, relabel_key, log=None):
    from repro.graphs.csr import Graph

    t = time.perf_counter()
    n, _ = sizes(cfg)
    i, j = tuples(cfg)
    dst, keep, ptr = _arcs(i, j, graphgen.relabeling(relabel_key, n), n)
    del i, j
    dst, keep, ptr = jax.device_get((dst, keep, ptr))
    graphgen._log(log, f"  kronecker: tuples drawn, sorted and deduplicated "
                       f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    col = dst[keep]
    ptr = ptr.astype(np.int64)
    deg = np.diff(ptr).astype(np.int32)
    g = Graph(n=n, m=int(col.shape[0]), row_ptr=ptr, col_idx=col,
              adj_ptr=ptr.copy(), adj_idx=col.copy(),
              adj_w=np.full(col.shape[0], 2.0, np.float32), deg_out=deg)
    graphgen._log(log, f"  kronecker: host CSR {time.perf_counter() - t:.3f} s; "
                       f"{keep.shape[0]} arcs drawn, {g.m} kept, isolated "
                       f"{np.mean(deg == 0):.4f}, max degree {deg.max()}")
    return g
