"""Graph families drawn on the device from a seed.

The program's own generators (`repro.graphs.generators`) draw and build a
graph on the host in numpy: 89.7 s for a 31M-edge graph on the chip's host,
which every run of the benchmark would pay. The benchmark draws its
families with `jax.random` on the device and hands back the host `Graph`
(directed CSR plus the eq.-4 symmetrized adjacency) exactly as
`repro.graphs.csr.build_graph` would build it from the same edges.

A family is a file of its own, `families/<family>.py` under the benchmark's
tree, found by the configuration's `family` key. Its
`generate(cfg, relabel_key, log)` returns the `Graph` with its vertices
relabeled by `relabeling(relabel_key, n)`; this module holds what families
share and picks the file.

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

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import spec

RELABEL_STREAM = 1  # fold_in tag: the relabeling's key is apart from the
                    # partition's, which the program derives from the seed
GROUP = 8          # relabeling permutes ids within aligned groups of this size
# the checkout this module belongs to, whose families `generate` reads
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _log(log, msg):
    if log is not None:
        log(msg)


def _ptr(deg: np.ndarray) -> np.ndarray:
    ptr = np.zeros(deg.shape[0] + 1, dtype=np.int64)
    np.cumsum(deg, out=ptr[1:])
    return ptr


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


def generate(cfg: dict, seed: int, log=None, root: str = ROOT):
    """The configuration's graph, relabeled from `seed`, as a host `Graph`:
    `generate` of `<root>/bench/families/<family>.py`."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), RELABEL_STREAM)
    family = spec.load_module(root, "families", cfg["family"])
    return family.generate(cfg, key, log)
