"""A square lattice whose road segments are dropped with probability
`drop_frac` and the rest made two-way: the stand-in for road networks
(left-skewed, average degree below the mode). Built in CSR order directly,
with no sort of the edge list.

Configuration keys: `n` (the lattice side is floor(sqrt(n))), `drop_frac`
and `graph_seed`, from which the structure is drawn.
"""
from __future__ import annotations

import math
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import graphgen


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
    ptr = graphgen._ptr(deg)
    g = Graph(n=side * side, m=int(col.shape[0]), row_ptr=ptr, col_idx=col,
              adj_ptr=ptr.copy(), adj_idx=col.copy(),
              adj_w=np.full(col.shape[0], 2.0, np.float32), deg_out=deg)
    graphgen._log(log, f"  lattice fetch + host CSR {time.perf_counter() - t:.3f} s")
    return g


def generate(cfg: dict, relabel_key, log=None):
    side, n = road_sizes(cfg)
    return build_road(road_present(cfg), graphgen.relabeling(relabel_key, n),
                      side, log)
