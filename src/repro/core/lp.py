"""Label-propagation scoring: the paper's normalized LP (eqs. 10-12) and the
Spinner baseline scoring (eqs. 3-5).

Both scorers share one primitive — the *edge label histogram*: for every
vertex v accumulate, per partition l, the eq.-(4)-weighted count of neighbors
currently labeled l. `edge_histogram_jnp` is the XLA scatter-add reference;
`repro.kernels.edge_histogram` is the Pallas TPU kernel (one-hot matmul on
the MXU) with identical semantics. `gather_pair` is how the edge phase
reads two label-valued vertex vectors at the same edge indices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# `gather_pair` packs two values into the halves of one int32 word; the high
# half keeps the sign bit clear, so each value has 15 bits and label-valued
# pairs need k <= MAX_PAIR_K
MAX_PAIR_K = (1 << 15) - 1


def gather_pair(a: jax.Array, b: jax.Array,
                idx: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(a[idx], b[idx])`` through one indexed read.

    A TPU gather costs per index, not per byte, so two int32 vectors read
    at the same indices are packed into one word (``a | b << 16``),
    gathered once and unpacked with a mask and a shift. Both must be int32
    with values in ``[0, MAX_PAIR_K]``. The TPU compiler materialises the
    packed word as the gather's operand (``tests/test_tpu_compile.py``
    counts the superstep's gathers); fused into the gather, the packing
    would read ``a`` and ``b`` at every index again.
    """
    word = (a | (b << 16))[idx]
    return word & 0xFFFF, word >> 16


def edge_histogram_jnp(
    rows: jax.Array,
    slots: jax.Array,
    vals: jax.Array,
    n_rows: int,
    k: int,
) -> jax.Array:
    """hist[r, s] = sum of vals[e] over edges with rows[e]==r, slots[e]==s.

    Args:
      rows: [E] int32 destination row per edge (local vertex index).
      slots: [E] int32 partition slot per edge (e.g. neighbor's label).
      vals: [E] float values (0.0 for padding edges).
      n_rows, k: histogram shape.
    """
    hist = jnp.zeros((n_rows, k), dtype=vals.dtype)
    return hist.at[rows, slots].add(vals)


def tau_term(hist: jax.Array, inv_wsum: jax.Array) -> jax.Array:
    """Eq. (11): neighborhood affinity normalized by the total edge weight."""
    return hist * inv_wsum[:, None]


def normalized_penalty(loads: jax.Array, capacity: float) -> jax.Array:
    """Eq. (12) with the footnote-1 negative shift.

    pi(l) = (1 - b(l)/C) normalized over partitions; if any term is negative
    (partition over capacity), shift by the minimum before normalizing.
    """
    pen = 1.0 - loads / capacity
    mn = jnp.min(pen)
    pen = jnp.where(mn < 0, pen - mn, pen)
    total = jnp.sum(pen)
    k = loads.shape[0]
    return jnp.where(total > 0, pen / jnp.where(total > 0, total, 1.0),
                     jnp.full_like(pen, 1.0 / k))


def revolver_scores(hist: jax.Array, inv_wsum: jax.Array, loads: jax.Array,
                    capacity: float) -> jax.Array:
    """Eq. (10): score(v,l) = (tau(v,l) + pi(l)) / 2."""
    tau = tau_term(hist, inv_wsum)
    pi = normalized_penalty(loads, capacity)
    return 0.5 * (tau + pi[None, :])


def spinner_penalty(loads: jax.Array, capacity: float) -> jax.Array:
    """Eq. (5): pi_hat(l) = b(l)/C (unnormalized; the term Spinner subtracts)."""
    return loads / capacity


def spinner_scores(hist: jax.Array, inv_wsum: jax.Array, loads: jax.Array,
                   capacity: float) -> jax.Array:
    """Eq. (3): score_hat(v,l) = tau_hat(v,l) - pi_hat(l)."""
    tau = tau_term(hist, inv_wsum)
    return tau - spinner_penalty(loads, capacity)[None, :]
