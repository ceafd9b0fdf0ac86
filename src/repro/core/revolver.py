"""Revolver: the paper's partitioning superstep (Section IV-D, steps 1-9).

This module is a **rule module**: it contributes Revolver's per-block local
rule (the nine steps below), its config/state, and its warm-start path; the
execution schedules — the sequential asynchronous scan, the ``shard_map``
Jacobi superstep, buffer donation, state placement — live in
``repro.core.engine`` and are shared with every other registered algorithm
(see ``core/README.md``).

Execution model — TPU adaptation of the paper's asynchrony (DESIGN.md §3):
vertices are processed in `n_blocks` chunks via the engine's `lax.scan`.
Label migrations, load updates and freshly-computed argmax labels (lambda)
from chunk i are visible to chunk i+1 *within the same superstep* — exactly
the incremental visibility the paper credits for its balanced partitions.
`n_blocks=1` degenerates to a synchronous (Spinner-like BSP) schedule; the
async-vs-sync ablation in benchmarks/fig4_convergence.py sweeps this knob.

Per chunk, the nine steps of Section IV-D:
  1. LA action selection (roulette wheel == Gumbel-max categorical sampling)
  2. migration probability  p_mig(l) = clip((C - b(l)) / m(l), 0, 1)
  3. normalized LP scores (eq. 10) and lambda(v) = argmax_l score(v,l)
  4. gated migration (action != label and U(0,1) < p_mig(action))
  5. weight accumulation from neighbors' lambda (eq. 13)
  6. mean-split reinforcement signals + per-half normalization
  7. weighted-LA probability update (eqs. 8/9)
  8. exact load update (the chunk's migrations are applied immediately)
  9. convergence score accumulation (mean best LP score)
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import engine
from repro.core.device_graph import CAPACITY_MODES, DeviceGraph, ShardedDeviceGraph  # noqa: F401  (re-exported API)
from repro.core.la import split_weights_and_signals, weighted_la_update
from repro.core.lp import (MAX_PAIR_K, edge_histogram_jnp, gather_pair,
                           prefix_histogram_pays, revolver_scores,
                           sorted_segment_histogram, sorted_segment_sum)
from repro.core.registry import register

# valid values per config knob; typos used to silently fall back to the jnp
# path (e.g. la_impl="palas"), now they raise at construction
_VALID_CHOICES = {
    "la_impl": ("jnp", "pallas"),
    "hist_impl": ("jnp", "pallas"),
    "weight_mode": ("self_lambda", "neighbor_lambda"),
    "capacity_mode": CAPACITY_MODES,
    "chunk_schedule": ("sequential", "sharded", "halo", "async"),
}


@dataclasses.dataclass(frozen=True)
class RevolverConfig:
    """Hyper-parameters; defaults match Section V-F of the paper."""

    k: int
    alpha: float = 1.0            # LA reward rate
    beta: float = 0.1             # LA penalty rate
    epsilon: float = 0.05         # imbalance ratio
    max_steps: int = 290
    patience: int = 5             # consecutive non-improving steps to halt
    theta: float = 0.001          # min score improvement
    capacity_mode: str = "spinner"  # see device_graph.capacity
    renorm: bool = True           # simplex re-projection after eqs. (8)/(9)
    la_impl: str = "jnp"          # "jnp" | "pallas"
    hist_impl: str = "jnp"        # "jnp" (scatter-add) | "pallas" (fused
                                  # dual-histogram edge-phase kernel)
    # eq. (13) ambiguity (DESIGN.md §10): which W slot a neighbor u reinforces.
    #   "self_lambda":     the literal LHS w(v, lambda(v)) — each neighbor
    #                      contributes to v's own argmax-score slot.
    #   "neighbor_lambda": slot lambda(u) — v accumulates a histogram of its
    #                      neighbors' argmax labels.
    weight_mode: str = "self_lambda"
    # superstep execution schedule (owned by the engine):
    #   "sequential": one device, lax.scan over all vertex blocks — the PR-2
    #                 async semantics, bit-identical at fixed seed.
    #   "sharded":    shard_map over a 1-D ("blocks",) mesh — each device
    #                 scans only its own blocks (async within the shard),
    #                 labels are all-gathered and load deltas psum-merged
    #                 once per superstep (Jacobi sync across shards).
    #   "halo":       the sharded schedule with the full label all-gather
    #                 replaced by a precomputed boundary-block exchange
    #                 (O(halo) traffic; exact — see repro.core.halo).
    #   "async":      the halo schedule with the exchange overlapped onto
    #                 the interior block scan; staleness_bound=0 is
    #                 bit-identical to "halo" (see docs/async-superstep.md).
    chunk_schedule: str = "sequential"
    # how many supersteps a shard may run against a stale halo tail before
    # the runner forces a refresh ("async" schedule only). 0 = refresh every
    # superstep, which keeps the bit-identity contract with "halo"; s >= 1
    # trades exactness for overlap and is gated on converged quality in the
    # scaling bench.
    staleness_bound: int = 0

    def __post_init__(self):
        for name, valid in _VALID_CHOICES.items():
            value = getattr(self, name)
            if value not in valid:
                raise ValueError(
                    f"RevolverConfig.{name}={value!r} is not one of {valid}")
        if not isinstance(self.staleness_bound, int) or \
                self.staleness_bound < 0:
            raise ValueError(
                f"RevolverConfig.staleness_bound={self.staleness_bound!r} "
                "must be an int >= 0")
        if self.staleness_bound > 0 and self.chunk_schedule != "async":
            raise ValueError(
                "staleness_bound > 0 only applies to chunk_schedule='async' "
                f"(got chunk_schedule={self.chunk_schedule!r})")
        if self.k > MAX_PAIR_K:
            # the edge phase reads label pairs packed into one int32 word
            raise ValueError(
                f"RevolverConfig.k={self.k} exceeds {MAX_PAIR_K}, the most "
                "the edge phase's packed label pairs hold")


class RevolverState(NamedTuple):
    labels: jnp.ndarray    # [n_pad] int32 current partition per vertex
    lam: jnp.ndarray       # [n_pad] int32 latest argmax-score label (lambda)
    probs: jnp.ndarray     # [n_blocks, block_v, k] f32 LA probability vectors
    loads: jnp.ndarray     # [k] f32 b(l)
    key: jax.Array
    step: jnp.ndarray      # int32
    score: jnp.ndarray     # f32 mean best LP score (convergence metric)


def revolver_init(dg: DeviceGraph, cfg: RevolverConfig, key: jax.Array) -> RevolverState:
    """Random initial labels; uniform 1/k LA probabilities (Section IV-C)."""
    k_lab, key = jax.random.split(key)
    labels = jax.random.randint(k_lab, (dg.n_pad,), 0, cfg.k, dtype=jnp.int32)
    labels = jnp.where(dg.vmask, labels, 0)
    loads = engine.loads_from_labels(dg, cfg.k, labels)
    probs = jnp.full((dg.n_blocks, dg.block_v, cfg.k), 1.0 / cfg.k, jnp.float32)
    # lam is a *copy*: labels and lam are separately donated superstep
    # buffers, so the initial state must not alias them to one buffer
    return RevolverState(
        labels=labels,
        lam=jnp.copy(labels),
        probs=probs,
        loads=loads,
        key=key,
        step=jnp.zeros((), jnp.int32),
        score=jnp.zeros((), jnp.float32),
    )


def revolver_init_from_labels(
    dg: DeviceGraph,
    cfg: RevolverConfig,
    key: jax.Array,
    labels: jnp.ndarray,
    probs: jnp.ndarray | None = None,
    prob_sharpen: float = 0.0,
) -> RevolverState:
    """Warm-start state from a previous assignment (streaming repartitioning).

    `labels` carries the partition of up to `len(labels)` surviving vertices
    (clipped to [0, k)); vertices beyond it — newly arrived in the stream —
    draw a random label, exactly like a cold `revolver_init` would. `probs`
    optionally carries the LA probability tensor of a previous state
    ([n_blocks', block_v', k]); surviving vertices keep their learned
    automata, new vertices start at the uniform 1/k of Section IV-C. Loads
    are recomputed from the (possibly changed) degree vector, so the
    invariant b(l) == sum deg over labels==l holds from step 0.

    Both `labels` and `probs` are indexed by **original vertex id** (row v =
    vertex v); on a locality-permuted layout they are scattered to each
    vertex's storage position, mirroring how `run_partitioner` /
    `StreamRunner` return them in original order.

    `prob_sharpen` in [0, 1) blends every automaton toward a one-hot on its
    carried label: p <- (1-s) p + s onehot(label). Carried probabilities
    from a refinement that halted early are still diffuse, which makes the
    roulette wheel re-explore settled vertices; sharpening converts the
    carried assignment into LA confidence so refinement spends its steps on
    genuinely contested vertices. s=0 (default) carries state untouched.
    """
    if not 0.0 <= prob_sharpen < 1.0:
        raise ValueError(f"prob_sharpen must be in [0, 1), got {prob_sharpen}")
    k_lab, key = jax.random.split(key)
    lab = engine.warm_labels(dg, cfg.k, k_lab, labels)
    loads = engine.loads_from_labels(dg, cfg.k, lab)

    flat = jnp.full((dg.n_pad, cfg.k), 1.0 / cfg.k, jnp.float32)
    if probs is not None:
        p = jnp.asarray(probs, jnp.float32)
        if p.shape[-1] != cfg.k:
            raise ValueError(
                f"carried probs have k={p.shape[-1]}, config expects k={cfg.k}")
        p = p.reshape(-1, cfg.k)
        p_keep = min(int(p.shape[0]), dg.n_pad)
        o2s = getattr(dg, "o2s", None)
        if o2s is None:
            flat = jax.lax.dynamic_update_slice(flat, p[:p_keep], (0, 0))
        else:  # carried rows are original-order; scatter to storage slots
            flat = flat.at[jnp.asarray(o2s[:p_keep])].set(p[:p_keep])
    if prob_sharpen > 0.0:
        onehot = jax.nn.one_hot(lab, cfg.k, dtype=jnp.float32)
        flat = (1.0 - prob_sharpen) * flat + prob_sharpen * onehot
    return RevolverState(
        labels=lab,
        lam=jnp.copy(lab),   # no aliasing: both buffers are donated
        probs=flat.reshape(dg.n_blocks, dg.block_v, cfg.k),
        loads=loads,
        key=key,
        step=jnp.zeros((), jnp.int32),
        score=jnp.zeros((), jnp.float32),
    )


def _revolver_chunk_rule(cfg: RevolverConfig, ctx: engine.ChunkContext,
                         vert, block, loads, cap, key) -> engine.ChunkUpdate:
    """The nine steps of Section IV-D for one asynchronous chunk.

    `vert` is the engine's drifting per-vertex view (labels + lambda, fresh
    with every earlier chunk's updates); `block` carries this chunk's LA
    probability tile. The rule returns the chunk's new label/lambda slices —
    the engine splices them into the drifting view — plus the updated loads,
    PRNG chain, and score contribution.
    """
    labels, lam = vert["labels"], vert["lam"]
    probs = block["probs"]
    bv, k = probs.shape
    if (cfg.hist_impl, cfg.la_impl) != ("jnp", "jnp"):
        from repro.kernels.ops import superstep_kernels

        fused_op, la_op = superstep_kernels(cfg.hist_impl, cfg.la_impl)
    else:  # pure-XLA lowering stays importable without the kernel package
        fused_op, la_op = None, None

    # Every operation of the rule sits in one phase scope (`la-select`,
    # `edge-phase`, `migrate`, `la-update`), so device profiles split the
    # superstep's time by phase (docs/observability.md).
    with obs.annotate("la-select"):
        key, k_act, k_mig = jax.random.split(key, 3)
        cur = jax.lax.dynamic_slice(labels, (ctx.v0,), (bv,))

        # -- 1. LA action selection (roulette wheel) -------------------------
        logits = jnp.log(jnp.clip(probs, 1e-30, 1.0))
        action = jax.random.categorical(k_act, logits,
                                        axis=-1).astype(jnp.int32)
        action = jnp.where(ctx.vmask, action, cur)

        # -- 2. migration probability per partition --------------------------
        wants = (action != cur) & ctx.vmask
        demand = jnp.zeros((k,), jnp.float32).at[action].add(
            ctx.deg * wants)                                           # m(l)
        remaining = cap - loads                                        # r(l)
        p_mig = jnp.where(
            demand > 0,
            jnp.clip(remaining / jnp.maximum(demand, 1e-9), 0.0, 1.0),
            1.0,
        )

    # -- 3. + 5. edge phase: LP-score histogram + eq.-13 accumulation --------
    # Both histograms read the same edge slab. Every input they need
    # (labels, lam, action, p_mig) exists *before* the edge phase, so the
    # pallas path computes both in one fused slab pass (see
    # kernels/edge_phase.py; for weight_mode="self_lambda" the kernel
    # returns the per-row (A, N) factorization and the lambda(v) one-hot
    # scatter is finished below once scores exist). The jnp path is the
    # two-scatter-add reference with identical semantics; where the layout
    # passes the slab's row pointers and rows are long enough to pay for
    # them, it builds the same histograms from int32 prefix sums read at
    # the row ends (`lp.sorted_segment_histogram`; exact, since eq.-4
    # weights are integers). Vertex values read at the same edge indices
    # go through one packed gather (`gather_pair`).
    prefix = (fused_op is None and ctx.e_ptr is not None
              and prefix_histogram_pays(bv, ctx.e_w.shape[0], k))
    hist_path = ("fused" if fused_op is not None
                 else "prefix" if prefix else "scatter")
    with obs.annotate("edge-phase", impl=cfg.hist_impl, gather="packed",
                      hist=hist_path):
        if fused_op is not None:
            feasible_f = (p_mig > 0).astype(jnp.float32)
            hist, w_acc = fused_op(
                ctx.e_dst[None], ctx.e_row[None], ctx.e_w[None], labels, lam,
                action[None], feasible_f[None],
                block_v=bv, k=k, weight_mode=cfg.weight_mode)
            hist, w_acc = hist[0], w_acc[0]
        else:
            # async: freshest labels; lam_nbr feeds eq. 13 below
            nbr_labels, lam_nbr = gather_pair(labels, lam, ctx.e_dst)
            if prefix:
                e_wi = ctx.e_w.astype(jnp.int32)
                hist = sorted_segment_histogram(
                    ctx.e_ptr, nbr_labels, e_wi, k).astype(jnp.float32)
            else:
                hist = edge_histogram_jnp(ctx.e_row, nbr_labels, ctx.e_w,
                                          bv, k)
            w_acc = None

        scores = revolver_scores(hist, ctx.inv_wsum, loads, cap)
        lam_chunk = jnp.argmax(scores, axis=-1).astype(jnp.int32)
        best = jnp.max(scores, axis=-1)
        score = jnp.sum(jnp.where(ctx.vmask, best, 0.0))

    with obs.annotate("migrate"):
        # -- 4. gated migration -----------------------------------------------
        u = jax.random.uniform(k_mig, (bv,))
        migrate = wants & (u < p_mig[action])
        new_lbl = jnp.where(migrate, action, cur)

        # -- 8. exact load update (visible to the next chunk) ----------------
        dmig = ctx.deg * migrate
        loads = loads.at[cur].add(-dmig).at[action].add(dmig)

    # -- 5. eq. (13) weight accumulation --------------------------------------
    # Each neighbor u of v contributes
    #   w_hat(u,v)           if psi(v) == lambda(u)      (agreement)
    #   1                    else if the slot is feasible (p_mig > 0)
    # psi(v) is the label assigned by the LA — the *selected action* (the
    # paper defines psi: A -> L), so a capacity-denied migration still
    # counts as agreement for the reinforcement signal.
    # The slot written depends on cfg.weight_mode (eq. 13 ambiguity):
    #   self_lambda     -> slot lambda(v) (the literal LHS w(v, lambda(v)))
    #   neighbor_lambda -> slot lambda(u)
    with obs.annotate("edge-phase", impl=cfg.hist_impl, gather="packed",
                      hist=hist_path, part="weights"):
        if w_acc is not None:
            if cfg.weight_mode == "self_lambda":
                # finish the kernel's (A, N) packing: every edge of row v
                # lands in slot lambda(v), feasibility is a per-row scalar
                contrib = w_acc[:, 0] + jnp.where(
                    p_mig[lam_chunk] > 0, w_acc[:, 1], 0.0)
                w_raw = jax.nn.one_hot(
                    lam_chunk, k, dtype=jnp.float32) * contrib[:, None]
            else:
                w_raw = w_acc                        # finished in-kernel
        elif prefix and cfg.weight_mode == "self_lambda":
            # the (A, N) factorization of the fused kernel, from two prefix
            # sums: A sums agreeing weights, N counts the other real slots
            agree = action[ctx.e_row] == lam_nbr
            # (padding slots lie past the last row end and are never read)
            a = sorted_segment_sum(ctx.e_ptr, jnp.where(agree, e_wi, 0))
            n = sorted_segment_sum(ctx.e_ptr, (~agree).astype(jnp.int32))
            contrib = a + jnp.where(p_mig[lam_chunk] > 0, n, 0)
            w_raw = jax.nn.one_hot(lam_chunk, k, dtype=jnp.float32) * \
                contrib.astype(jnp.float32)[:, None]
        else:
            if cfg.weight_mode == "self_lambda":
                row_action, slot = gather_pair(action, lam_chunk, ctx.e_row)
            else:
                row_action, slot = action[ctx.e_row], lam_nbr
            agree = (row_action == lam_nbr)
            feasible = p_mig[slot] > 0
            if prefix:
                val = jnp.where(agree, e_wi, feasible.astype(jnp.int32))
                w_raw = sorted_segment_histogram(
                    ctx.e_ptr, slot, val, k).astype(jnp.float32)
            else:
                val = jnp.where(agree, ctx.e_w,
                                jnp.where(feasible, 1.0, 0.0))
                val = jnp.where(ctx.e_w > 0, val, 0.0)  # kill padding slots
                w_raw = edge_histogram_jnp(ctx.e_row, slot, val, bv, k)

    # -- 6./7. reinforcement signals + weighted LA update ---------------------
    with obs.annotate("la-update", impl=cfg.la_impl):
        w_norm, r = split_weights_and_signals(w_raw)
        if la_op is not None:
            new_probs = la_op(probs, w_norm, r, cfg.alpha, cfg.beta,
                              renorm=cfg.renorm)
        else:
            new_probs = weighted_la_update(probs, w_norm, r, cfg.alpha,
                                           cfg.beta, renorm=cfg.renorm)

    return engine.ChunkUpdate(
        vert={"labels": new_lbl, "lam": lam_chunk},
        block={"probs": new_probs},
        loads=loads,
        key=key,
        score=score,
    )


def _revolver_run_counters(cfg: RevolverConfig, dg) -> dict:
    """`edge_hist_prefix`: 1 where the rule builds its histograms from the
    layout's row pointers (the sequential jnp edge phase on a layout that
    has them, at rows long enough to pay), else 0."""
    prefix = (cfg.hist_impl == "jnp" and cfg.chunk_schedule == "sequential"
              and getattr(dg, "blk_ptr", None) is not None
              and prefix_histogram_pays(dg.block_v, dg.e_max, cfg.k))
    return {"edge_hist_prefix": float(prefix)}


REVOLVER = register(engine.Algorithm(
    name="revolver",
    config_cls=RevolverConfig,
    state_cls=RevolverState,
    kind="chunk",
    vertex_fields=("labels", "lam"),
    wire_int8_fields=("labels", "lam"),   # both in [0, k)
    block_fields=("probs",),
    donate=("labels", "lam", "probs", "loads"),
    init=revolver_init,
    init_from_labels=revolver_init_from_labels,
    supports_probs=True,
    chunk_rule=_revolver_chunk_rule,
    run_counters=_revolver_run_counters,
))


def place_revolver_state(state: RevolverState, sdg: ShardedDeviceGraph) -> RevolverState:
    """Commit a freshly-initialized state to the sharded layout (see
    ``engine.place_state``)."""
    return engine.place_state(REVOLVER, state, sdg)


def revolver_superstep(dg, cfg: RevolverConfig, state: RevolverState) -> RevolverState:
    """One full superstep over all chunks (see ``engine.superstep``).

    `cfg.chunk_schedule` selects the execution plan: "sequential" scans all
    blocks on one device (`dg` is a plain DeviceGraph); "sharded" runs the
    per-shard scans data-parallel under shard_map (`dg` must be a
    ShardedDeviceGraph, see `prepare_sharded_device_graph`).

    The state's labels / lam / probs / loads buffers are **donated** under
    either schedule; the passed-in `state` must not be reused after this
    call (every caller in the repo rebinds,
    ``state = revolver_superstep(...)``).
    """
    return engine.superstep(REVOLVER, dg, cfg, state)
