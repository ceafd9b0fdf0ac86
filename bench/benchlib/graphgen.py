"""Graph families drawn on the device from a seed.

The program's own generators (`repro.graphs.generators`) draw and build a
graph on the host in numpy: 89.7 s for a 31M-edge graph on the chip's host,
which every run of the benchmark would pay. This module draws its families
with `jax.random` on the device and hands back the host `Graph` (directed
CSR plus the eq.-4 symmetrized adjacency) exactly as
`repro.graphs.csr.build_graph` would build it from the same edges:

* ``grid_road``: a square lattice whose road segments are dropped with
  probability ``drop_frac`` and the rest made two-way, the stand-in for road
  networks (left-skewed, average degree below the mode).

Each configuration's structure is drawn from its own fixed `graph_seed`;
the run's `--seed` then relabels the vertices by a random permutation within
each aligned group of 8 ids. Every seed so gets an isomorphic graph with the
same sizes everywhere: the same |E|, the same edges per vertex block under
any block size that is a multiple of 8 (the program's layout pads blocks to
such a size), so the same compiled programs and the same amount of work,
with the vertices in another order. The same seed gives the same graph on
the same platform.
"""
from __future__ import annotations

import math
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

RELABEL_STREAM = 1  # fold_in tag: the relabeling's key is apart from the
                    # partition's, which the program derives from the seed
GROUP = 8          # relabeling permutes ids within aligned groups of this size


def _log(log, msg):
    if log is not None:
        log(msg)


def _ptr(deg: np.ndarray) -> np.ndarray:
    ptr = np.zeros(deg.shape[0] + 1, dtype=np.int64)
    np.cumsum(deg, out=ptr[1:])
    return ptr


# ---------------------------------------------------------------------------
# grid_road: the lattice is built in CSR order directly (no sort needed)
# ---------------------------------------------------------------------------
def road_sizes(cfg: dict) -> tuple[int, int]:
    side = math.isqrt(int(cfg["n"]))
    return side, side * side


@partial(jax.jit, static_argnames=("side",))
def _road_present(key, drop_frac, side):
    """[n, 4] bool: whether each vertex's (up, left, right, down) road
    segment exists; each segment is kept with probability 1 - drop_frac and
    is two-way, so up[v] == down[v - side] and left[v] == right[v - 1]."""
    n = side * side
    k_r, k_d = jax.random.split(key)
    v = jnp.arange(n, dtype=jnp.int32)
    x, y = v % side, v // side
    right = (x < side - 1) & (jax.random.uniform(k_r, (n,)) >= drop_frac)
    down = (y < side - 1) & (jax.random.uniform(k_d, (n,)) >= drop_frac)
    left = jnp.concatenate([jnp.zeros((1,), bool), right[:-1]])
    up = jnp.concatenate([jnp.zeros((side,), bool), down[:-side]])
    return jnp.stack([up, left, right, down], axis=1)


def road_present(cfg: dict):
    side, _ = road_sizes(cfg)
    return _road_present(jax.random.PRNGKey(cfg["graph_seed"]),
                         jnp.float32(cfg["drop_frac"]), side)


@partial(jax.jit, static_argnames=("side",))
def _road_rows(present, perm, side):
    """[n, 4] neighbor ids of each relabeled vertex, ascending, with n in
    the slots of absent segments (they sort last)."""
    n = side * side
    v = jnp.arange(n, dtype=jnp.int32)[:, None]
    offs = jnp.array([-side, -1, 1, side], jnp.int32)
    nbr = jnp.where(present, perm[jnp.clip(v + offs, 0, n - 1)], n)
    inv = jnp.zeros((n,), jnp.int32).at[perm].set(v[:, 0])
    return jnp.sort(nbr[inv], axis=1)


def build_road(present, perm, side: int, log=None):
    """The `Graph` of a lattice mask with its vertices relabeled by `perm`
    (old id -> new id): CSR rows in ascending neighbor order, and every edge
    two-way (weight 2)."""
    from repro.graphs.csr import Graph

    t = time.perf_counter()
    n = side * side
    nbr = np.asarray(jax.device_get(_road_rows(present, perm, side)))
    real = nbr < n
    deg = real.sum(axis=1, dtype=np.int32)
    col = nbr[real]
    ptr = _ptr(deg)
    g = Graph(n=side * side, m=int(col.shape[0]), row_ptr=ptr, col_idx=col,
              adj_ptr=ptr.copy(), adj_idx=col.copy(),
              adj_w=np.full(col.shape[0], 2.0, np.float32), deg_out=deg)
    _log(log, f"  lattice fetch + host CSR {time.perf_counter() - t:.3f} s")
    return g


# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n",))
def relabeling(key, n):
    """[n] int32 old id -> new id: a random permutation within each aligned
    group of GROUP ids (the last group holds only the ids below n)."""
    groups = -(-n // GROUP)
    keys = jax.random.uniform(key, (groups, GROUP))
    keys = jnp.where(jnp.arange(groups * GROUP).reshape(groups, GROUP) < n,
                     keys, 2.0)
    rank = jnp.argsort(jnp.argsort(keys, axis=1), axis=1).astype(jnp.int32)
    base = (jnp.arange(groups, dtype=jnp.int32) * GROUP)[:, None]
    return (base + rank).reshape(-1)[:n]


def generate(cfg: dict, seed: int, log=None):
    """The configuration's graph, relabeled from `seed`, as a host `Graph`."""
    family = cfg["family"]
    key = jax.random.fold_in(jax.random.PRNGKey(seed), RELABEL_STREAM)
    if family == "grid_road":
        side, n = road_sizes(cfg)
        return build_road(road_present(cfg), relabeling(key, n), side, log)
    raise ValueError(f"unknown graph family {family!r}")
