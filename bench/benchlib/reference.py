"""Plain reference for Revolver partition jobs, with no code of the program.

`revolver_labels` replays the paper's superstep (Section IV-D, steps 1-8)
from the seed, in jax.numpy, straight from the CSR arrays of the host graph.
It follows the semantics the program documents for its sequential
schedule: vertices in `n_blocks` blocks of `block_v` storage ids, visited in
storage order within a superstep, each block seeing the labels, lambdas and loads
that earlier blocks left (the paper's asynchrony); eq.-4 weights;
Spinner capacity (1 + epsilon)|E|/k; the weighted LA update of eqs. 8/9 run
penalty passes first, then projected back onto the simplex; one PRNG chain
split three ways per block, in the order the program draws them (init
labels over all storage ids, then per block the action's categorical draw
and the migration's uniform draw). The layout (`n_blocks`, `block_v`, and
the map of vertices to storage ids) is the program's to choose, so the
caller passes what the program reports.

`dtype` is the precision of every fractional quantity (probabilities,
logits, scores, migration probabilities, LA weights). The configuration
states float32; the control computes the same in bfloat16.

`quality` recomputes `local_edges` and `max_norm_load` of a labelling from
the host graph in float64.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ALPHA = 1.0        # LA reward rate (paper, Section V-F)
BETA = 0.1         # LA penalty rate
EPSILON = 0.05     # imbalance ratio
_P_FLOOR = 1e-12   # simplex projection floor


def quality(g, labels: np.ndarray, k: int) -> tuple[float, float]:
    """(local_edges, max_norm_load) in float64: the share of directed edges
    inside one part, and the largest out-degree load over |E|/k."""
    src = np.repeat(np.arange(g.n), np.diff(g.row_ptr))
    local = float(np.mean(labels[src] == labels[g.col_idx]))
    loads = np.bincount(labels, weights=g.deg_out.astype(np.float64),
                        minlength=k)
    return local, float(loads.max() / (g.m / k))


def _storage_rows(g, n_pad: int, o2s):
    """The symmetrized adjacency in the program's storage order: per
    storage id s (of n_pad) its neighbors' storage ids and eq.-4 weights,
    rows in storage order, plus the out-degree and the real-vertex mask per
    storage id. `o2s` maps each original vertex to its storage id; None is
    the identity."""
    n = g.n
    lens = np.diff(g.adj_ptr)
    if o2s is None:
        deg = np.zeros(n_pad, np.float32)
        deg[:n] = g.deg_out
        mask = np.zeros(n_pad, bool)
        mask[:n] = True
        return g.adj_ptr, g.adj_idx, g.adj_w, deg, mask
    o2s = np.asarray(o2s, np.int64)[:n]
    s2o = np.full(n_pad, -1, np.int64)
    s2o[o2s] = np.arange(n)
    mask = s2o >= 0
    slen = np.zeros(n_pad, np.int64)
    slen[o2s] = lens
    ptr = np.zeros(n_pad + 1, np.int64)
    np.cumsum(slen, out=ptr[1:])
    # slot e of storage row r is slot (e - ptr[r]) of original row s2o[r]
    rows = np.repeat(np.arange(n_pad), slen)
    take = g.adj_ptr[s2o[rows]] + np.arange(ptr[-1]) - ptr[rows]
    deg = np.zeros(n_pad, np.float32)
    deg[o2s] = g.deg_out
    return (ptr, o2s[g.adj_idx[take]].astype(np.int32), g.adj_w[take], deg,
            mask)


class _Blocks:
    """The graph in storage order cut into the schedule's vertex blocks:
    per block the symmetrized edges (neighbor, local row, eq.-4 weight)
    padded to the longest block with weight-0 slots, plus per-vertex
    degree, 1/sum(w) and the real-vertex mask over n_blocks * block_v."""

    def __init__(self, g, n_blocks: int, block_v: int, o2s=None):
        n_pad = n_blocks * block_v
        ptr, idx, adj_w, deg, mask = _storage_rows(g, n_pad, o2s)
        n_rows = ptr.shape[0] - 1
        lo = np.minimum(np.arange(n_blocks, dtype=np.int64) * block_v, n_rows)
        hi = np.minimum(lo + block_v, n_rows)
        e_lo, e_hi = ptr[lo], ptr[hi]
        e_pad = max(int((e_hi - e_lo).max()), 1)
        dst = np.zeros((n_blocks, e_pad), np.int32)
        row = np.zeros((n_blocks, e_pad), np.int32)
        w = np.zeros((n_blocks, e_pad), np.float32)
        rows_all = np.repeat(np.arange(n_rows, dtype=np.int64), np.diff(ptr))
        for b in range(n_blocks):
            c = int(e_hi[b] - e_lo[b])
            dst[b, :c] = idx[e_lo[b]:e_hi[b]]
            row[b, :c] = rows_all[e_lo[b]:e_hi[b]] - lo[b]
            w[b, :c] = adj_w[e_lo[b]:e_hi[b]]
        wsum = np.zeros(n_pad, np.float64)
        wsum[:n_rows] = np.bincount(rows_all, weights=adj_w.astype(np.float64),
                                    minlength=n_rows)
        wsum32 = wsum.astype(np.float32)
        inv = np.zeros(n_pad, np.float32)
        np.divide(np.float32(1.0), wsum32, out=inv, where=wsum32 > 0)
        self.n, self.m, self.n_pad = g.n, g.m, n_pad
        self.n_blocks, self.block_v = n_blocks, block_v
        self.dst, self.row, self.w = (jnp.asarray(x) for x in (dst, row, w))
        self.deg, self.inv_wsum, self.mask = (
            jnp.asarray(x) for x in (deg, inv, mask))


def _penalty(loads, cap):
    """Eq. 12 with the footnote-1 shift: pi(l) = (1 - b(l)/C), shifted up
    by its minimum when some part is over capacity, normalized to sum 1."""
    pen = 1.0 - loads / cap
    mn = jnp.min(pen)
    pen = jnp.where(mn < 0, pen - mn, pen)
    total = jnp.sum(pen)
    k = loads.shape[0]
    return jnp.where(total > 0, pen / jnp.where(total > 0, total, 1.0),
                     jnp.full_like(pen, 1.0 / k))


def _la_update(p, w, r):
    """Eqs. 8/9 as k passes over each row, penalty actions first (stable
    within each class); a slot with zero weight carries no signal; then the
    rows are projected back onto the simplex."""
    k = p.shape[-1]
    iota = jnp.arange(k)
    order = jnp.argsort(-r, axis=-1, stable=True)

    def one_pass(t, p):
        i = jnp.take(order, t, axis=-1)
        mask = iota == i[..., None]
        w_i = jnp.sum(jnp.where(mask, w, 0.0), axis=-1, keepdims=True)
        p_rew = jnp.where(mask, p + ALPHA * w * (1.0 - p), p * (1.0 - ALPHA * w))
        floor = BETA * w / (k - 1)
        p_pen = jnp.where(mask, p * (1.0 - BETA * w),
                          p * (1.0 - BETA * w) + floor)
        is_pen = jnp.sum(jnp.where(mask, r, 0.0), axis=-1, keepdims=True) > 0
        return jnp.where(w_i > 0, jnp.where(is_pen, p_pen, p_rew), p)

    p = jax.lax.fori_loop(0, k, one_pass, p)
    p = jnp.clip(p, _P_FLOOR, 1.0)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def _signals(w_raw):
    """Step 6: r = 1 (penalty) where w <= mean(w), each half normalized to
    sum 1 (a half with no weight stays 0)."""
    mean = jnp.mean(w_raw, axis=-1, keepdims=True)
    r = (w_raw <= mean).astype(w_raw.dtype)
    rew_sum = jnp.sum(w_raw * (1.0 - r), axis=-1, keepdims=True)
    pen_sum = jnp.sum(w_raw * r, axis=-1, keepdims=True)
    w_rew = jnp.where(rew_sum > 0, w_raw / jnp.where(rew_sum > 0, rew_sum, 1.0), 0.0)
    w_pen = jnp.where(pen_sum > 0, w_raw / jnp.where(pen_sum > 0, pen_sum, 1.0), 0.0)
    return jnp.where(r > 0, w_pen, w_rew), r


@partial(jax.jit, static_argnames=("k", "block_v", "dtype"),
         donate_argnames=("labels", "lam", "probs"))
def _block_step(labels, lam, probs, loads, key, b, blk_dst, blk_row, blk_w,
                deg_all, inv_all, mask_all, cap, *, k, block_v, dtype):
    """One block of one superstep (steps 1-8 of Section IV-D)."""
    v0 = b * block_v
    dst, row, w = blk_dst[b], blk_row[b], blk_w[b]
    deg, inv_wsum, mask = (jax.lax.dynamic_slice(x, (v0,), (block_v,))
                           for x in (deg_all, inv_all, mask_all))
    key, k_act, k_mig = jax.random.split(key, 3)
    cur = jax.lax.dynamic_slice(labels, (v0,), (block_v,))
    pb = jax.lax.dynamic_slice(probs, (v0, 0), (block_v, k))
    # 1. roulette-wheel action
    logits = jnp.log(jnp.clip(pb, 1e-30, 1.0))
    action = jax.random.categorical(k_act, logits, axis=-1).astype(jnp.int32)
    action = jnp.where(mask, action, cur)
    # 2. migration probability per part
    wants = (action != cur) & mask
    demand = jnp.zeros((k,), jnp.float32).at[action].add(deg * wants)
    remaining = (cap - loads).astype(dtype)
    p_mig = jnp.where(demand > 0,
                      jnp.clip(remaining / jnp.maximum(demand.astype(dtype), 1e-9),
                               0.0, 1.0),
                      1.0).astype(dtype)
    # 3. LP scores (eqs. 10-12) and lambda
    hist = jnp.zeros((block_v, k), jnp.float32).at[row, labels[dst]].add(w)
    tau = hist.astype(dtype) * inv_wsum.astype(dtype)[:, None]
    scores = 0.5 * (tau + _penalty(loads.astype(dtype), cap.astype(dtype))[None, :])
    lam_b = jnp.argmax(scores, axis=-1).astype(jnp.int32)
    # 4. gated migration, 8. load update
    u = jax.random.uniform(k_mig, (block_v,), dtype)
    migrate = wants & (u < p_mig[action])
    new = jnp.where(migrate, action, cur)
    moved = deg * migrate
    loads = loads.at[cur].add(-moved).at[action].add(moved)
    # 5. eq.-13 weights into slot lambda(v): w_hat on agreement of the
    # chosen action with the neighbor's lambda, else 1 where that slot may
    # take migrations
    lam_nbr = lam[dst]
    slot = lam_b[row]
    val = jnp.where(action[row] == lam_nbr, w,
                    jnp.where(p_mig[slot] > 0, 1.0, 0.0))
    val = jnp.where(w > 0, val, 0.0)
    w_raw = jnp.zeros((block_v, k), jnp.float32).at[row, slot].add(val)
    # 6./7. signals and the weighted LA update
    w_norm, r = _signals(w_raw.astype(dtype))
    pb = _la_update(pb, w_norm, r)
    labels = jax.lax.dynamic_update_slice(labels, new, (v0,))
    lam = jax.lax.dynamic_update_slice(lam, lam_b, (v0,))
    probs = jax.lax.dynamic_update_slice(probs, pb.astype(probs.dtype), (v0, 0))
    return labels, lam, probs, loads, key


@partial(jax.jit, static_argnames=("k", "n_pad"))
def _init(seed_key, mask, deg, *, k, n_pad):
    k_lab, key = jax.random.split(seed_key)
    labels = jax.random.randint(k_lab, (n_pad,), 0, k, dtype=jnp.int32)
    labels = jnp.where(mask, labels, 0)
    loads = jnp.zeros((k,), jnp.float32).at[labels].add(deg)
    return labels, loads, key


def revolver_labels(g, k: int, seed: int, steps: int, n_blocks: int,
                    block_v: int, o2s=None, dtype=jnp.float32,
                    log=None) -> np.ndarray:
    """Labels after `steps` supersteps from `seed`, in vertex order [n].
    `o2s` is the layout's map of each original vertex to its storage id
    (None: the identity); blocks are visited in storage order."""
    t = time.perf_counter()
    blk = _Blocks(g, n_blocks, block_v, o2s)
    jax.block_until_ready(blk.dst)
    t_host = time.perf_counter() - t
    t = time.perf_counter()
    labels, loads, key = _init(jax.random.PRNGKey(seed), blk.mask, blk.deg,
                               k=k, n_pad=blk.n_pad)
    lam = jnp.copy(labels)
    probs = jnp.full((blk.n_pad, k), 1.0 / k, dtype)
    cap = jnp.float32((1.0 + EPSILON) * g.m / k)
    for _ in range(steps):
        for b in range(n_blocks):
            labels, lam, probs, loads, key = _block_step(
                labels, lam, probs, loads, key, jnp.int32(b), blk.dst,
                blk.row, blk.w, blk.deg, blk.inv_wsum, blk.mask, cap, k=k,
                block_v=block_v, dtype=dtype)
    labels = np.asarray(labels)
    out = labels[:g.n] if o2s is None else labels[np.asarray(o2s)[:g.n]]
    if log is not None:
        log(f"  reference ({jnp.dtype(dtype).name}): blocks built and placed "
            f"{t_host:.3f} s, {steps} supersteps replayed {time.perf_counter() - t:.3f} s")
    return out


def control_metrics(g, labels: np.ndarray, k: int, dtype) -> tuple[float, float]:
    """(local_edges, max_norm_load) as the control reports them: computed on
    the device and rounded to `dtype`."""
    src = jnp.asarray(np.repeat(np.arange(g.n, dtype=np.int32), np.diff(g.row_ptr)))
    lab = jnp.asarray(labels)
    local = jnp.mean((lab[src] == lab[jnp.asarray(g.col_idx)]).astype(jnp.float32))
    loads = jnp.zeros((k,), jnp.float32).at[lab].add(jnp.asarray(g.deg_out, jnp.float32))
    mnl = jnp.max(loads) / (jnp.sum(loads) / k)
    return float(local.astype(dtype)), float(mnl.astype(dtype))
