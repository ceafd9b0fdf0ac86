"""Schedule-agnostic partitioner engine: one superstep core, pluggable rules.

Revolver's LA+LP superstep, the Spinner baseline, and prioritized
restreaming are all instances of one family: a **local rule** (how a vertex
scores partitions and decides to migrate) driven by a **global schedule**
(in what order vertices see each other's decisions, and where the work
runs). This module owns everything schedule-shaped, so an algorithm module
contributes only its rule:

  rule      (algorithm module, e.g. core/revolver.py)
      a config dataclass, a state NamedTuple, ``init`` /
      ``init_from_labels``, and either a per-block ``chunk_rule`` or a
      per-shard ``shard_rule``;
  schedule  (this module)
      the sequential asynchronous ``lax.scan`` over vertex blocks, the
      ``shard_map`` Jacobi superstep on a 1-D ``("blocks",)`` mesh (label
      all-gather, psum load-delta merge, per-shard PRNG chains), the
      ``"halo"`` variant of the Jacobi superstep that syncs only the
      precomputed boundary blocks (``repro.core.halo``; an exact,
      traffic-proportional-to-edge-cut optimization of the full gather),
      the ``"async"`` variant that splits each shard's scan into interior
      blocks (no remote/hub references — scanned while the halo exchange
      is still in flight) and boundary blocks (scanned after the sync),
      with a bounded-staleness halo cache (``async_superstep``),
      buffer donation, and sharded state placement;
  kernel    (repro/kernels, routed via ``ops.superstep_kernels``)
      the fused Pallas edge phase and LA update behind the ``hist_impl`` /
      ``la_impl`` config knobs; the jnp scatter-add reference lives in
      core/lp.py.

See ``src/repro/core/README.md`` for the full contract an algorithm
implements and what it inherits.

Rule kinds
----------
``kind="chunk"`` (Revolver, restream): the rule processes one vertex block
at a time inside a scan; migrations and per-vertex updates from block i are
visible to block i+1 within the same superstep (the paper's asynchrony,
DESIGN.md §3). Under the sharded schedule each device scans only its own
blocks (async within the shard, Jacobi across shards) and the engine
all-gathers the declared ``vertex_fields`` once per superstep, psum-merges
the ``[k]`` load delta, and re-replicates shard 0's PRNG chain.

``kind="shard"`` (Spinner): the rule processes its whole shard in one BSP
step against the previous superstep's configuration, calling the context's
collectives (``gather`` / ``psum``) where cross-shard reductions are
needed. The sequential schedule runs the same rule with identity
collectives on a single shard spanning the whole graph — one rule, both
schedules.

Load-delta accounting lives here too: rules mutate their drifting ``loads``
view freely; the engine recovers the shard's superstep delta as
``loads_end - loads_start`` (exact while every part's load is below 2^24
= 16,777,216: loads are sums of integer-valued degrees in f32, and f32
holds every integer only up to there) and psum-merges it at the superstep
boundary. The sequential path simply keeps ``loads_end``, so sequential
rules no longer carry sharded-only accumulator slots (the dead ``delta``
chain the PR-3 scan threaded through every chunk is gone).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.device_graph import (
    DeviceGraph,
    ShardedDeviceGraph,
    capacity_device,
)
from repro.parallel.collectives import (
    gather_shards,
    hub_gather,
    psum_delta_merge,
    replicated_chain_key,
    shard_chain_key,
    vertex_halo_exchange,
)

AXIS = "blocks"   # the 1-D mesh axis every sharded superstep runs over


# ---------------------------------------------------------------------------
# algorithm protocol
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True, eq=False)
class Algorithm:
    """A partitioning algorithm as the engine sees it.

    Frozen with identity hashing (``eq=False``): instances are module-level
    singletons and serve as jit static arguments.

    Attributes:
      name: registry key ("revolver", "spinner", ...).
      config_cls: frozen config dataclass. The engine reads ``k``,
        ``epsilon``, ``capacity_mode``, ``chunk_schedule``, ``max_steps``,
        ``patience``, ``theta``; everything else is rule-private.
      state_cls: state NamedTuple. Must carry ``labels`` ([n_pad] int32),
        ``loads`` ([k] f32), ``key``, ``step``, ``score``; may add more.
      kind: "chunk" or "shard" (see module docstring).
      vertex_fields: state fields holding per-vertex [n_pad] arrays that the
        schedule synchronizes (all-gathered each sharded superstep, updated
        by the rule per block/shard). Must include "labels".
      block_fields: state fields holding per-block [n_blocks, ...] tensors
        (e.g. Revolver's LA probabilities) scanned alongside the edge slabs;
        chunk-kind only.
      replicated_fields: state fields the schedule passes through replicated
        and untouched (per-superstep constants, e.g. restream's degree
        ranks). Available to rules via the context.
      wire_int8_fields: vertex_fields whose values always fit int8 (label-
        valued, i.e. in [0, k)): when ``cfg.k <= 127`` the per-vertex halo
        exchange moves them on an int8 wire — an exact round trip, 4x fewer
        bytes. Fields not listed ride the wire at their storage width.
      donate: state fields whose buffers the jitted superstep donates
        (updated in place; callers must rebind ``state = superstep(...)``).
      init: ``(dg, cfg, key) -> state`` cold start.
      init_from_labels: ``(dg, cfg, key, labels, probs=None,
        prob_sharpen=0.0) -> state`` warm start, or None if unsupported.
      supports_probs: whether the algorithm carries an LA probability tensor
        (enables ``keep_probs`` / ``init_probs`` / ``init_sharpen`` in the
        runner and probability carrying in the streaming path).
      chunk_rule / shard_rule: the local rule (exactly one, per ``kind``).
      run_counters: ``(cfg, dg) -> {name: value}`` static per-run gauges an
        enabled tracer records once per ``run_partitioner`` call (e.g.
        which edge-phase path the layout selects), or None.
    """

    name: str
    config_cls: type
    state_cls: type
    kind: str
    init: Callable
    vertex_fields: Tuple[str, ...] = ("labels",)
    block_fields: Tuple[str, ...] = ()
    replicated_fields: Tuple[str, ...] = ()
    wire_int8_fields: Tuple[str, ...] = ()
    donate: Tuple[str, ...] = ("labels", "loads")
    init_from_labels: Optional[Callable] = None
    supports_probs: bool = False
    chunk_rule: Optional[Callable] = None
    shard_rule: Optional[Callable] = None
    run_counters: Optional[Callable] = None

    def __post_init__(self):
        if self.kind not in ("chunk", "shard"):
            raise ValueError(f"Algorithm.kind={self.kind!r}")
        if "labels" not in self.vertex_fields:
            raise ValueError(f"{self.name}: vertex_fields must include 'labels'")
        if (self.chunk_rule is None) == (self.kind == "chunk"):
            raise ValueError(f"{self.name}: kind={self.kind!r} needs exactly "
                             "the matching rule callable")
        if (self.shard_rule is None) == (self.kind == "shard"):
            raise ValueError(f"{self.name}: kind={self.kind!r} needs exactly "
                             "the matching rule callable")
        required = {"labels", "loads", "key", "step", "score"}
        missing = required - set(self.state_cls._fields)
        if missing:
            raise ValueError(f"{self.name}: state_cls lacks {sorted(missing)}")
        stray = set(self.wire_int8_fields) - set(self.vertex_fields)
        if stray:
            raise ValueError(
                f"{self.name}: wire_int8_fields {sorted(stray)} are not "
                "vertex_fields")


class ChunkContext(NamedTuple):
    """What a chunk rule sees for one vertex block.

    ``repl`` carries the full replicated_fields arrays; per-vertex slices of
    the block are taken with ``v0``. ``step`` is the 0-based superstep index
    (rules may schedule on it, e.g. restream's priority ramp).

    ``v0`` addresses the *drifting per-vertex view* the rule slices and the
    engine splices (the full ``[n_pad]`` vector under the sequential and
    full-gather schedules; the shard's ``local + halo`` buffer under
    ``chunk_schedule="halo"``, where the block's edge slab ids are likewise
    pre-rewritten into buffer space). ``gv0`` is the block's *global* vertex
    offset, for slicing replicated ``[n_pad]`` arrays in ``repl`` (restream's
    degree ranks); the two coincide except under the halo schedule.

    ``n_shards`` tells the rule how many shards are drifting this superstep
    concurrently (1 under the sequential schedule). A rule that rations
    shared capacity against its drifting ``loads`` view must divide the
    remaining headroom by it: under the Jacobi schedule every shard sees
    the same start-of-superstep loads, so an un-rationed greedy rule lets
    each shard independently spend the *whole* remaining capacity of a
    popular partition — n_shards-fold overshoot and oscillation (restream
    collapsed to max_norm_load ~6 at 8 shards before this).
    """

    blk_idx: jnp.ndarray    # scalar int32 global block index
    v0: jnp.ndarray         # scalar int32 block offset into the drifting view
    gv0: jnp.ndarray        # scalar int32 global vertex offset of the block
    e_dst: jnp.ndarray      # [e_max] int32 neighbor ids (0 pad)
    e_row: jnp.ndarray      # [e_max] int32 local row in the block (0 pad)
    e_w: jnp.ndarray        # [e_max] f32 eq.(4) weights (0.0 pad)
    deg: jnp.ndarray        # [block_v] f32 outdegrees
    inv_wsum: jnp.ndarray   # [block_v] f32 1/sum w_hat
    vmask: jnp.ndarray      # [block_v] bool real-vertex mask
    step: jnp.ndarray       # scalar int32 superstep index
    n_shards: int           # static: concurrent Jacobi shards (1 sequential)
    loads0: jnp.ndarray     # [k] start-of-superstep loads (the Jacobi base
                            # every shard drifts from; == the drifting loads
                            # arg at the first chunk of a sequential scan)
    repl: Dict[str, jnp.ndarray]
    # [block_v + 1] int32 row pointers into the slab (rows stored in order),
    # or None: only the sequential schedule passes the layout's `blk_ptr`
    e_ptr: Optional[jnp.ndarray] = None

    def shared_headroom(self, cap, loads) -> jnp.ndarray:
        """Per-partition capacity this block may spend without cross-shard
        overshoot: the shard's 1/n_shards share of the start-of-superstep
        global headroom, plus whatever capacity the shard itself freed
        since (its outflows are in its drifting ``loads`` view; remote
        shards' are not until the Jacobi merge). Degenerates to the plain
        ``cap - loads`` under the sequential schedule."""
        if self.n_shards == 1:
            return cap - loads
        return (cap - self.loads0) / self.n_shards + (self.loads0 - loads)


class ChunkUpdate(NamedTuple):
    """A chunk rule's output: the engine applies ``vert`` slices to the
    drifting per-vertex arrays (visible to later blocks in the superstep),
    stacks ``block`` as the scan output, and threads loads/key/score."""

    vert: Dict[str, jnp.ndarray]    # vertex_field -> [block_v] new values
    block: Dict[str, jnp.ndarray]   # block_field -> updated block tensor
    loads: jnp.ndarray              # [k] updated drifting load view
    key: jnp.ndarray                # chained PRNG key
    score: jnp.ndarray              # scalar score sum over the block


@dataclasses.dataclass(frozen=True)
class ShardContext:
    """What a shard rule sees: its slice of the blocked layout plus
    collectives that degenerate to identities on the sequential schedule.

    Under ``chunk_schedule="halo"`` the slab neighbor ids in ``blk_dst`` are
    pre-rewritten into the shard's ``local + halo`` buffer space and
    ``gather`` returns that buffer (own slice first, then the exchanged
    boundary slabs) instead of the full ``[n_pad]`` vector — rules that only
    index the gather result through ``blk_dst`` (the contract) run unchanged
    under all three schedules.
    """

    axis: Optional[str]     # mesh axis name, or None (sequential)
    idx: jnp.ndarray        # scalar int32 shard index (0 when sequential)
    n: int                  # real vertex count
    n_pad: int              # global padded vertex count
    local_n: int            # vertices owned by this shard
    block_v: int
    blocks: int             # blocks owned by this shard
    v0: jnp.ndarray         # scalar int32 global offset of the local range
    blk_dst: jnp.ndarray    # [blocks, e_max] local edge slabs
    blk_row: jnp.ndarray
    blk_w: jnp.ndarray
    deg: jnp.ndarray        # [local_n]
    inv_wsum: jnp.ndarray   # [local_n]
    vmask: jnp.ndarray      # [local_n]
    step: jnp.ndarray
    repl: Dict[str, jnp.ndarray]
    halo_rows: Optional[jnp.ndarray] = None   # [S, b_max] boundary plan
    send_ids: Optional[jnp.ndarray] = None    # [S, S, h_max] per-vertex plan
    hub_owner: Optional[jnp.ndarray] = None   # [hub_pad] hub replication plan
    hub_local: Optional[jnp.ndarray] = None
    wire_int8: bool = False    # label-valued gathers may ride an int8 wire

    def gather(self, x):
        """Make every vertex id in ``blk_dst`` resolvable: the full
        all-gather, the boundary-block halo exchange, or the per-vertex
        all-to-all when the layout carries the matching plan (identity on
        the sequential schedule), plus the replicated hub region when hub
        replication is on. Rules gather label-valued fields only (the
        contract), so ``wire_int8`` applies to every per-vertex gather."""
        if self.halo_rows is not None:
            with obs.annotate("halo-exchange", kind="halo"):
                y = halo_exchange(x, self.halo_rows, self.idx, self.blocks,
                                  self.block_v, self.axis)
        elif self.send_ids is not None:
            with obs.annotate("halo-exchange", kind="per-vertex"):
                wire = jnp.int8 if (self.wire_int8
                                    and x.dtype == jnp.int32) else None
                tail = vertex_halo_exchange(x, self.send_ids, self.axis,
                                            wire_dtype=wire)
                y = jnp.concatenate([x, tail]) if tail.shape[0] else x
        elif self.axis:
            with obs.annotate("halo-exchange", kind="full-gather"):
                y = gather_shards(x, self.axis)
        else:
            y = x
        if self.hub_owner is not None:
            with obs.annotate("halo-exchange", kind="hub-assemble"):
                y = jnp.concatenate(
                    [y, hub_gather(x, self.hub_owner, self.hub_local,
                                   self.axis)])
        return y

    def psum(self, x):
        """Sum a shard-local reduction across shards."""
        return jax.lax.psum(x, self.axis) if self.axis else x

    def local_rows(self) -> jnp.ndarray:
        """[blocks * e_max] local row ids for a flat slab histogram."""
        base = jnp.arange(self.blocks, dtype=jnp.int32)[:, None] * self.block_v
        return (base + self.blk_row).reshape(-1)


class ShardUpdate(NamedTuple):
    vert: Dict[str, jnp.ndarray]    # vertex_field -> [local_n] new values
    loads_delta: jnp.ndarray        # [k] this shard's load delta
    key: jnp.ndarray                # chained PRNG key (replicated semantics)
    score: jnp.ndarray              # scalar score sum over the shard


class _Layout(NamedTuple):
    """Static shape info (hashable jit key)."""

    n: int
    n_pad: int
    n_blocks: int
    block_v: int
    blocks_per_shard: int


def _graph_arrays(dg: DeviceGraph) -> Dict[str, jnp.ndarray]:
    return {
        "blk_dst": dg.blk_dst, "blk_row": dg.blk_row, "blk_w": dg.blk_w,
        "deg": dg.deg_out, "inv_wsum": dg.inv_wsum, "vmask": dg.vmask,
    }


_GRAPH_SPECS = {
    "blk_dst": P(AXIS, None), "blk_row": P(AXIS, None), "blk_w": P(AXIS, None),
    "deg": P(AXIS), "inv_wsum": P(AXIS), "vmask": P(AXIS),
    "halo_rows": P(),   # replicated boundary plan (block-halo schedule)
    "send_ids": P(),    # replicated per-vertex exchange plan
    # hub replication: the plan vectors are replicated, the per-shard vote
    # slabs are sharded like the edge slabs they were cut from
    "hub_owner": P(), "hub_local": P(), "hub_deg": P(),
    "hub_src": P(AXIS, None), "hub_slot": P(AXIS, None),
    "hub_w": P(AXIS, None),
}


def _state_spec(algo: Algorithm, name: str, value) -> P:
    """Sharding spec for one state field (block axis leads block tensors)."""
    if name in algo.vertex_fields:
        return P(AXIS)
    if name in algo.block_fields:
        return P(AXIS, *([None] * (value.ndim - 1)))
    return P()


# ---------------------------------------------------------------------------
# the superstep body (shared by the schedules; axis=None == sequential)
# ---------------------------------------------------------------------------
def halo_exchange(x, halo_rows, idx, bps, block_v, axis):
    """Boundary-only label sync: each shard contributes the `[b_max]`
    blocks of its slice that remote slabs reference (`halo_rows[idx]`,
    precomputed — see `repro.core.halo`), one all-gather moves them, and
    the result is appended to the shard's own slice. Cross-device traffic
    is O(b_max * block_v) per field instead of O(n_pad); the remote slabs
    received are the same start-of-superstep snapshots the full gather
    would deliver, so the halo schedule is an *exact* optimization of the
    full-gather Jacobi sync."""
    if halo_rows.shape[1] == 0:        # no cross-shard references at all
        return x
    rows = jnp.take(halo_rows, idx, axis=0)                   # [b_max]
    contrib = jnp.take(x.reshape(bps, block_v), rows, axis=0)
    gathered = jax.lax.all_gather(contrib, axis)              # [S, b_max, bv]
    return jnp.concatenate([x, gathered.reshape(-1)])


def _hub_reconcile(graph, k, cap, axis, idx, labels, loads, local_n):
    """Per-superstep hub vote reconciliation — O(hub_pad * k), never O(E).

    Hubs are frozen during the scan (`vmask_nonhub`), so at this point every
    shard holds the same start-of-superstep hub labels. Each shard
    accumulates weighted one-hot votes from its local slab slots that point
    at hubs (`hub_src` / `hub_slot` / `hub_w`, precomputed host-side), one
    psum merges the `[hub_pad, k]` vote table, and an identical
    deterministic capacity-gated scan runs on every shard: per slot, the
    argmax label wins (ties break to the lowest partition index), gated on
    the merged global loads so hub migrations never breach capacity. All
    inputs are replicated, so every shard computes the same winners and the
    same updated loads — each owner then scatters its hubs' winners into
    its local slice. With ``axis=None`` the psums are identities and the
    same arithmetic runs on the single shard (the sequential hub schedule),
    which is why 1-shard hub runs match the sequential reference
    bit-for-bit.
    """
    owner = graph["hub_owner"]               # [hub_pad] replicated
    local = graph["hub_local"]
    hdeg = graph["hub_deg"]
    src = graph["hub_src"][0]                # this shard's vote slab
    slot = graph["hub_slot"][0]
    w = graph["hub_w"][0]
    hub_pad = owner.shape[0]

    # current hub labels: exactly one owner contributes per slot
    cur = jnp.where(owner == idx, jnp.take(labels, local), 0)
    lab_src = jnp.take(labels, src)
    votes = jnp.zeros((hub_pad, k), jnp.float32).at[slot, lab_src].add(w)
    if axis:
        with obs.annotate("halo-exchange", kind="hub-votes"):
            cur = jax.lax.psum(cur, axis)
            votes = jax.lax.psum(votes, axis)
    valid = owner >= 0
    total = votes.sum(axis=1)
    cand = jnp.argmax(votes, axis=1).astype(labels.dtype)

    def decide(carry_loads, j):
        c, p, d = cand[j], cur[j], hdeg[j]
        ok = valid[j] & (total[j] > 0) & (c != p) & (carry_loads[c] + d <= cap)
        new = jnp.where(ok, c, p)
        delta = jnp.where(ok, d, 0.0)
        carry_loads = carry_loads.at[p].add(-delta).at[new].add(delta)
        return carry_loads, new

    loads, winners = jax.lax.scan(decide, loads,
                                  jnp.arange(hub_pad, dtype=jnp.int32))
    # scatter winners into the owner's slice (non-owned slots hit a dummy
    # extension row that is trimmed right back off)
    safe = jnp.where(owner == idx, local, local_n)
    ext = jnp.concatenate([labels, jnp.zeros((1,), labels.dtype)])
    return ext.at[safe].set(winners)[:local_n], loads


def _expand_vertex_field(x, graph, idx, bps, block_v, axis, wire_dtype=None):
    """Build one field's drifting view: the (local) slice, then the halo
    tail the layout's plan exchanges, then the replicated hub region."""
    if "halo_rows" in graph:
        y = halo_exchange(x, graph["halo_rows"], idx, bps, block_v, axis)
    elif "send_ids" in graph:
        tail = vertex_halo_exchange(x, graph["send_ids"], axis,
                                    wire_dtype=wire_dtype)
        y = jnp.concatenate([x, tail]) if tail.shape[0] else x
    elif axis:
        y = gather_shards(x, axis)
    else:
        y = x
    if "hub_owner" in graph:
        y = jnp.concatenate(
            [y, hub_gather(x, graph["hub_owner"], graph["hub_local"], axis)])
    return y


def _exchange_tail(x, graph, idx, bps, block_v, axis, wire_dtype=None):
    """The exchanged part of one field's drifting view — everything past the
    shard's own slice: the halo tail the layout's plan moves, then the
    replicated hub region. ``_expand_vertex_field(x, ...)`` equals
    ``concat([x, _exchange_tail(x, ...)])`` whenever a plan is attached
    (the async schedule assembles the two halves at different times)."""
    parts = []
    if "halo_rows" in graph:
        halo_rows = graph["halo_rows"]
        if halo_rows.shape[1]:
            rows = jnp.take(halo_rows, idx, axis=0)
            contrib = jnp.take(x.reshape(bps, block_v), rows, axis=0)
            parts.append(jax.lax.all_gather(contrib, axis).reshape(-1))
    elif "send_ids" in graph:
        tail = vertex_halo_exchange(x, graph["send_ids"], axis,
                                    wire_dtype=wire_dtype)
        if tail.shape[0]:
            parts.append(tail)
    if "hub_owner" in graph:
        parts.append(
            hub_gather(x, graph["hub_owner"], graph["hub_local"], axis))
    if not parts:
        return jnp.zeros((0,), x.dtype)
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _chunk_superstep(algo, cfg, layout, axis, graph, cap, state, step):
    """Scan the (local) blocks with the algorithm's chunk rule.

    Sequential: one shard spanning every block, identity collectives, the
    state key used directly — the PR-2 semantics. Sharded: Jacobi across
    shards (gather once, scan local blocks, slice back, merge the exact
    load delta, re-replicate shard 0's chained key). Halo: the Jacobi
    schedule with the full label gather replaced by the boundary-block or
    per-vertex exchange — the drifting view is the shard's `local + halo`
    buffer (own slice first, so intra-shard asynchrony is untouched) and
    the slab ids in `graph["blk_dst"]` are pre-rewritten into buffer
    space. Hub replication appends the psum-assembled hub region to the
    buffer, freezes hubs during the scan (the layout swapped `vmask` for
    `vmask_nonhub`), and reconciles their labels by weighted votes after
    the load merge (`_hub_reconcile`) — also runnable with `axis=None`,
    where every collective degenerates to the identity (the sequential hub
    schedule, the 1-shard bit-identity oracle).
    """
    idx = jax.lax.axis_index(axis) if axis else jnp.zeros((), jnp.int32)
    bps = layout.blocks_per_shard if axis else layout.n_blocks
    n_shards = layout.n_blocks // layout.blocks_per_shard if axis else 1
    block_v = layout.block_v
    halo = "halo_rows" in graph or "send_ids" in graph
    hub_on = "hub_owner" in graph
    kind = ("halo" if "halo_rows" in graph
            else "per-vertex" if "send_ids" in graph
            else "full-gather" if axis else "local")
    wire_ok = cfg.k <= 127
    if axis or halo or hub_on:
        with obs.annotate("halo-exchange", kind=kind, hubs=int(hub_on),
                          fields=len(algo.vertex_fields)):
            vert = {f: _expand_vertex_field(
                        state[f], graph, idx, bps, block_v, axis,
                        wire_dtype=(jnp.int8 if wire_ok and
                                    f in algo.wire_int8_fields else None))
                    for f in algo.vertex_fields}
    else:
        vert = {f: state[f] for f in algo.vertex_fields}
    key = shard_chain_key(state["key"], axis) if axis else state["key"]
    repl = {f: state[f] for f in algo.replicated_fields}
    loads0 = state["loads"]

    xs = (
        idx * bps + jnp.arange(bps, dtype=jnp.int32),
        graph["blk_dst"], graph["blk_row"], graph["blk_w"],
        {f: state[f] for f in algo.block_fields},
        graph["deg"].reshape(bps, block_v),
        graph["inv_wsum"].reshape(bps, block_v),
        graph["vmask"].reshape(bps, block_v),
        graph.get("blk_ptr"),
    )

    def scan_step(carry, x):
        vert, loads, key, score_sum = carry
        blk_idx, e_dst, e_row, e_w, block, deg, inv_wsum, vmask, e_ptr = x
        gv0 = blk_idx * block_v
        v0 = (blk_idx - idx * bps) * block_v if halo else gv0
        ctx = ChunkContext(
            blk_idx=blk_idx, v0=v0, gv0=gv0, e_dst=e_dst, e_row=e_row,
            e_w=e_w, deg=deg, inv_wsum=inv_wsum, vmask=vmask, step=step,
            n_shards=n_shards, loads0=loads0, repl=repl, e_ptr=e_ptr)
        upd = algo.chunk_rule(cfg, ctx, vert, block, loads, cap, key)
        vert = {f: jax.lax.dynamic_update_slice(vert[f], upd.vert[f], (ctx.v0,))
                for f in vert}
        return (vert, upd.loads, upd.key, score_sum + upd.score), upd.block

    carry = (vert, loads0, key, jnp.zeros((), jnp.float32))
    (vert, loads_end, key_end, score_sum), block_out = \
        jax.lax.scan(scan_step, carry, xs)

    local_n = bps * block_v
    if halo or hub_on:
        # the (local) slice leads its buffer; the halo tail and hub region
        # are read-only within the scan
        vert = {f: v[:local_n] for f, v in vert.items()}
    elif axis:
        v0 = idx * local_n
        vert = {f: jax.lax.dynamic_slice(v, (v0,), (local_n,))
                for f, v in vert.items()}
    if axis:
        # the shard's migrations, recovered exactly (integer-valued f32)
        loads_end = psum_delta_merge(loads0, loads_end - loads0, axis)
        score_sum = jax.lax.psum(score_sum, axis)
        key_end = replicated_chain_key(key_end, axis)
    if hub_on:
        vert["labels"], loads_end = _hub_reconcile(
            graph, cfg.k, cap, axis, idx, vert["labels"], loads_end, local_n)
    return {**vert, **block_out, "loads": loads_end, "key": key_end,
            "score": score_sum}


def _async_chunk_superstep(algo, cfg, layout, split, refresh, axis,
                           graph, cap, state, cache, step):
    """The halo chunk superstep with the scan split at ``split``: interior
    blocks first, carrying only the shard's own slice, then the boundary
    blocks against the full ``local + halo + hub`` buffer.

    Interior blocks reference no exchanged and no hub-replicated vertex
    (their rewritten slab ids are all ``< local_n`` — the classification in
    `repro.core.halo.build_halo_spec`), so the phase-1 scan has no data
    dependency on the exchange; XLA is free to overlap the collective with
    the interior compute. The tail is assembled from the start-of-superstep
    state either way, and the scan processes the blocks in the same order
    with the same loads/key/score chaining as `_chunk_superstep`, so a
    refreshing async superstep is **bit-identical** to the halo schedule.

    ``refresh`` (static) selects the tail source: True assembles it with the
    plan's collectives; False reuses ``cache`` — the tail of an earlier
    superstep, up to ``staleness_bound`` steps old (the refresh policy lives
    in the caller; the engine only distinguishes fresh from cached). The
    tail actually read is returned as the new cache either way.
    """
    idx = jax.lax.axis_index(axis)
    bps = layout.blocks_per_shard
    n_shards = layout.n_blocks // layout.blocks_per_shard
    block_v = layout.block_v
    local_n = bps * block_v
    hub_on = "hub_owner" in graph
    kind = ("halo" if "halo_rows" in graph
            else "per-vertex" if "send_ids" in graph else "hub-only")
    wire_ok = cfg.k <= 127

    key = shard_chain_key(state["key"], axis)
    repl = {f: state[f] for f in algo.replicated_fields}
    loads0 = state["loads"]

    xs = (
        idx * bps + jnp.arange(bps, dtype=jnp.int32),
        graph["blk_dst"], graph["blk_row"], graph["blk_w"],
        {f: state[f] for f in algo.block_fields},
        graph["deg"].reshape(bps, block_v),
        graph["inv_wsum"].reshape(bps, block_v),
        graph["vmask"].reshape(bps, block_v),
    )
    head_xs = jax.tree_util.tree_map(lambda a: a[:split], xs)
    tail_xs = jax.tree_util.tree_map(lambda a: a[split:], xs)

    def scan_step(carry, x):
        vert, loads, key, score_sum = carry
        blk_idx, e_dst, e_row, e_w, block, deg, inv_wsum, vmask = x
        gv0 = blk_idx * block_v
        v0 = (blk_idx - idx * bps) * block_v
        ctx = ChunkContext(
            blk_idx=blk_idx, v0=v0, gv0=gv0, e_dst=e_dst, e_row=e_row,
            e_w=e_w, deg=deg, inv_wsum=inv_wsum, vmask=vmask, step=step,
            n_shards=n_shards, loads0=loads0, repl=repl)
        upd = algo.chunk_rule(cfg, ctx, vert, block, loads, cap, key)
        vert = {f: jax.lax.dynamic_update_slice(vert[f], upd.vert[f], (ctx.v0,))
                for f in vert}
        return (vert, upd.loads, upd.key, score_sum + upd.score), upd.block

    # phase 1: interior blocks drift on the shard's own slice while the
    # exchange is in flight (the nested spans are the overlap contract the
    # trace validator checks — see tools/trace_report.py --validate)
    local = {f: state[f] for f in algo.vertex_fields}
    with obs.annotate("interior-scan", schedule="async", blocks=split,
                      refresh=int(refresh)):
        if refresh:
            with obs.annotate("halo-exchange", kind=kind, hubs=int(hub_on),
                              fields=len(algo.vertex_fields), overlap=1):
                halo_tail = {
                    f: _exchange_tail(
                        state[f], graph, idx, bps, block_v, axis,
                        wire_dtype=(jnp.int8 if wire_ok and
                                    f in algo.wire_int8_fields else None))
                    for f in algo.vertex_fields}
        else:
            halo_tail = {f: cache[f] for f in algo.vertex_fields}
        carry = (local, loads0, key, jnp.zeros((), jnp.float32))
        (local, loads_mid, key_mid, score_mid), block_head = \
            jax.lax.scan(scan_step, carry, head_xs)

    # phase 2: boundary blocks see the synced (or cached) tail; intra-shard
    # drift continues — phase 1's updates lead the buffer
    vert = {f: jnp.concatenate([local[f], halo_tail[f]])
            if halo_tail[f].shape[0] else local[f]
            for f in algo.vertex_fields}
    carry = (vert, loads_mid, key_mid, score_mid)
    (vert, loads_end, key_end, score_sum), block_tail = \
        jax.lax.scan(scan_step, carry, tail_xs)
    block_out = {f: jnp.concatenate([block_head[f], block_tail[f]], axis=0)
                 for f in algo.block_fields}

    vert = {f: v[:local_n] for f, v in vert.items()}
    loads_end = psum_delta_merge(loads0, loads_end - loads0, axis)
    score_sum = jax.lax.psum(score_sum, axis)
    key_end = replicated_chain_key(key_end, axis)
    if hub_on:
        vert["labels"], loads_end = _hub_reconcile(
            graph, cfg.k, cap, axis, idx, vert["labels"], loads_end, local_n)
    out = {**vert, **block_out, "loads": loads_end, "key": key_end,
           "score": score_sum}
    return out, halo_tail


def _shard_superstep(algo, cfg, layout, axis, graph, cap, state, step):
    """Run the algorithm's BSP shard rule once over the (local) slabs."""
    idx = jax.lax.axis_index(axis) if axis else jnp.zeros((), jnp.int32)
    bps = layout.blocks_per_shard if axis else layout.n_blocks
    local_n = bps * layout.block_v
    ctx = ShardContext(
        axis=axis, idx=idx, n=layout.n, n_pad=layout.n_pad, local_n=local_n,
        block_v=layout.block_v, blocks=bps, v0=idx * local_n,
        blk_dst=graph["blk_dst"], blk_row=graph["blk_row"],
        blk_w=graph["blk_w"], deg=graph["deg"], inv_wsum=graph["inv_wsum"],
        vmask=graph["vmask"], step=step,
        repl={f: state[f] for f in algo.replicated_fields},
        halo_rows=graph.get("halo_rows"), send_ids=graph.get("send_ids"),
        hub_owner=graph.get("hub_owner"), hub_local=graph.get("hub_local"),
        wire_int8=bool(algo.wire_int8_fields) and cfg.k <= 127)
    local = {f: state[f] for f in algo.vertex_fields}
    upd = algo.shard_rule(cfg, ctx, local, state["loads"], cap, state["key"])
    loads = psum_delta_merge(state["loads"], upd.loads_delta, axis) if axis \
        else state["loads"] + upd.loads_delta
    score = jax.lax.psum(upd.score, axis) if axis else upd.score
    vert = dict(upd.vert)
    if "hub_owner" in graph:
        vert["labels"], loads = _hub_reconcile(
            graph, cfg.k, cap, axis, idx, vert["labels"], loads, local_n)
    return {**vert, "loads": loads, "key": upd.key, "score": score}


_BODIES = {"chunk": _chunk_superstep, "shard": _shard_superstep}


def _finish(algo, layout, state_in, out, step):
    out = dict(out)
    score_sum = out.pop("score")
    return algo.state_cls(
        **out,
        **{f: state_in[f] for f in algo.replicated_fields},
        step=step + 1,
        score=score_sum / layout.n,
    )


@partial(jax.jit, static_argnames=("algo", "cfg", "layout"),
         donate_argnames=("donated",))
def _sequential_superstep(algo, cfg, layout, graph, cap, donated, kept):
    # this body runs only while XLA traces it — i.e. exactly once per
    # jit-cache miss — so this records every (re)compile, with its static
    # shape signature for cause attribution (no-op when tracing is off)
    obs.record_compile(
        "superstep", algo=algo.name, schedule="sequential",
        n_blocks=layout.n_blocks, block_v=layout.block_v,
        e_max=int(graph["blk_dst"].shape[-1]),
        hub_pad=(int(graph["hub_owner"].shape[0])
                 if "hub_owner" in graph else None))
    state = {**donated, **kept}
    step = state.pop("step")
    state.pop("score")
    out = _BODIES[algo.kind](algo, cfg, layout, None, graph, cap, state, step)
    return _finish(algo, layout, state, out, step)


@partial(jax.jit, static_argnames=("algo", "cfg", "mesh", "layout"),
         donate_argnames=("donated",))
def _sharded_superstep(algo, cfg, mesh, layout, graph, cap, donated, kept):
    obs.record_compile(
        "superstep", algo=algo.name, schedule=cfg.chunk_schedule,
        n_shards=layout.n_blocks // layout.blocks_per_shard,
        n_blocks=layout.n_blocks, block_v=layout.block_v,
        e_max=int(graph["blk_dst"].shape[-1]),
        b_max=(int(graph["halo_rows"].shape[-1])
               if "halo_rows" in graph else None),
        h_max=(int(graph["send_ids"].shape[-1])
               if "send_ids" in graph else None),
        hub_pad=(int(graph["hub_owner"].shape[0])
                 if "hub_owner" in graph else None))
    state = {**donated, **kept}
    step = state.pop("step")
    state.pop("score")
    state_specs = {f: _state_spec(algo, f, v) for f, v in state.items()}
    out_specs = {f: state_specs[f] for f in state
                 if f not in algo.replicated_fields}
    out_specs["score"] = P()
    body = partial(_BODIES[algo.kind], algo, cfg, layout, AXIS)
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=({k: _GRAPH_SPECS[k] for k in graph}, P(), state_specs, P()),
        out_specs=out_specs,
        check_vma=False,
    )
    out = sharded(graph, cap, state, step)
    return _finish(algo, layout, state, out, step)


@partial(jax.jit,
         static_argnames=("algo", "cfg", "mesh", "layout", "split", "refresh"),
         donate_argnames=("donated",))
def _async_sharded_superstep(algo, cfg, mesh, layout, split, refresh,
                             graph, cap, donated, kept, cache):
    obs.record_compile(
        "superstep", algo=algo.name, schedule="async", refresh=bool(refresh),
        split=split,
        n_shards=layout.n_blocks // layout.blocks_per_shard,
        n_blocks=layout.n_blocks, block_v=layout.block_v,
        e_max=int(graph["blk_dst"].shape[-1]),
        b_max=(int(graph["halo_rows"].shape[-1])
               if "halo_rows" in graph else None),
        h_max=(int(graph["send_ids"].shape[-1])
               if "send_ids" in graph else None),
        hub_pad=(int(graph["hub_owner"].shape[0])
                 if "hub_owner" in graph else None))
    state = {**donated, **kept}
    step = state.pop("step")
    state.pop("score")
    state_specs = {f: _state_spec(algo, f, v) for f, v in state.items()}
    out_specs = {f: state_specs[f] for f in state
                 if f not in algo.replicated_fields}
    out_specs["score"] = P()
    # the cache is the per-shard exchanged tail: sharded over the mesh like
    # every other per-shard buffer, empty under refresh (it is rebuilt)
    cache_specs = {f: P(AXIS) for f in cache}
    tail_specs = {f: P(AXIS) for f in algo.vertex_fields}
    body = partial(_async_chunk_superstep, algo, cfg, layout, split, refresh,
                   AXIS)
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=({k: _GRAPH_SPECS[k] for k in graph}, P(), state_specs,
                  cache_specs, P()),
        out_specs=(out_specs, tail_specs),
        check_vma=False,
    )
    out, new_cache = sharded(graph, cap, state, cache, step)
    return _finish(algo, layout, state, out, step), new_cache


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
def _apply_halo_plan(graph: Dict[str, jnp.ndarray], spec) -> None:
    """Swap the layout's plan arrays into the superstep's graph dict: the
    rewritten slabs, the chosen exchange plan, and — when the plan carries
    hubs — the vote slabs plus the hub-frozen vertex mask."""
    graph["blk_dst"] = spec.blk_dst_halo
    if spec.granularity == "vertex":
        graph["send_ids"] = spec.send_ids
    else:
        graph["halo_rows"] = spec.boundary_rows
    if spec.hub_owner is not None:
        graph["vmask"] = spec.vmask_nonhub
        graph["hub_owner"] = spec.hub_owner
        graph["hub_local"] = spec.hub_local
        graph["hub_deg"] = spec.hub_deg
        graph["hub_src"] = spec.hub_src
        graph["hub_slot"] = spec.hub_slot
        graph["hub_w"] = spec.hub_w


def superstep(algo: Algorithm, dg, cfg, state, halo=None):
    """One full superstep of ``algo`` under ``cfg.chunk_schedule``.

    "sequential" runs on one device (``dg`` is a plain DeviceGraph, or a
    ShardedDeviceGraph whose arrays are consumed directly); "sharded" runs
    under shard_map on the graph's ``("blocks",)`` mesh (``dg`` must be a
    ShardedDeviceGraph, see ``prepare_sharded_device_graph``); "halo" is the
    sharded schedule with the full label all-gather replaced by the
    precomputed exchange plan in ``dg.halo`` — boundary-block slabs or
    per-vertex rows per the plan's granularity, plus hub replication when
    the plan carries a hub set (``shard_device_graph(..., halo=True,
    hubs=...)``); a plan whose coverage exceeded its threshold runs the
    full gather, bit-identically.

    ``halo`` passes a 1-shard `HaloSpec` to the *sequential* schedule — the
    hub-replication oracle: the sequential scan then runs on the same
    rewritten slabs, frozen hubs, and vote reconciliation as a 1-shard halo
    run, bit-for-bit (`run_partitioner(hub_replication=True)` builds it).

    The state fields named in ``algo.donate`` are **donated** under every
    schedule (buffers updated in place); the passed-in state must not be
    reused after this call — every caller rebinds
    ``state = superstep(...)``. Small undonated leaves (key/step/score and
    any replicated fields) stay valid, so the convergence loop's windowed
    score buffering is unaffected.
    """
    if cfg.chunk_schedule == "async":
        # the always-refresh call: every superstep rebuilds its halo tail,
        # which is exactly the staleness_bound=0 (bit-identical-to-halo)
        # semantics; callers that exploit the staleness bound thread the
        # cache through async_superstep themselves (core/runner.py)
        return async_superstep(algo, dg, cfg, state)[0]
    cap = capacity_device(dg.m, cfg.k, cfg.epsilon, cfg.capacity_mode)
    sd = state._asdict()
    donated = {f: sd.pop(f) for f in algo.donate}
    if cfg.chunk_schedule in ("sharded", "halo"):
        if not isinstance(dg, ShardedDeviceGraph):
            raise TypeError(
                f"chunk_schedule={cfg.chunk_schedule!r} needs a "
                "ShardedDeviceGraph (see prepare_sharded_device_graph); got "
                "a plain DeviceGraph")
        layout = _Layout(dg.n, dg.n_pad, dg.n_blocks, dg.block_v,
                         dg.blocks_per_shard)
        graph = _graph_arrays(dg.dg)
        if cfg.chunk_schedule == "halo":
            spec = dg.halo
            if spec is None:
                raise ValueError(
                    "chunk_schedule='halo' needs a halo-enabled layout: "
                    "build it with shard_device_graph(..., halo=True) / "
                    "attach_halo, or let run_partitioner build it")
            if not spec.fallback:
                _apply_halo_plan(graph, spec)
            # fallback: coverage too high for the exchange to win — run the
            # full-gather Jacobi schedule (same trajectory, bit-for-bit;
            # hub replication is off under fallback, there is no halo left)
        return _sharded_superstep(algo, cfg, dg.mesh, layout, graph, cap,
                                  donated, sd)
    if isinstance(dg, ShardedDeviceGraph):
        dg = dg.dg
    layout = _Layout(dg.n, dg.n_pad, dg.n_blocks, dg.block_v, dg.n_blocks)
    graph = _graph_arrays(dg)
    if dg.blk_ptr is not None:
        # rows in slab order: chunk rules may build segment sums from them
        graph["blk_ptr"] = dg.blk_ptr
    if halo is not None and halo.hub_owner is not None and not halo.fallback:
        if halo.n_shards != 1:
            raise ValueError(
                "the sequential schedule takes a 1-shard halo plan; got "
                f"n_shards={halo.n_shards}")
        _apply_halo_plan(graph, halo)
        # a 1-shard plan has no exchange tail (b_max == h_max == 0); drop
        # the empty plan arrays so only the hub machinery engages
        graph.pop("halo_rows", None)
        graph.pop("send_ids", None)
    return _sequential_superstep(algo, cfg, layout, graph, cap,
                                 donated, sd)


def async_superstep(algo: Algorithm, dg, cfg, state, cache=None):
    """One ``chunk_schedule="async"`` superstep; returns ``(state, cache)``.

    The async schedule is the halo schedule with the per-shard block scan
    split in two: the leading **interior** blocks (no remote and no
    hub-replicated references — ``dg.halo.interior_split`` of them, see
    `repro.core.halo`) scan against the shard's own slice while the halo
    exchange is still in flight; the **boundary** blocks scan after the
    sync, against the full ``local + halo + hub`` buffer. The exchanged
    tail is built from the same start-of-superstep snapshot the halo
    schedule would move, and the blocks run in the same order with the same
    loads/key/score chaining — a refreshing async superstep is
    **bit-identical** to ``chunk_schedule="halo"`` on the same layout.

    ``cache`` is the bounded-staleness knob: ``None`` (the default) forces
    a refresh — the tail is rebuilt with the plan's collectives; passing
    the cache returned by an earlier call reuses that superstep's tail
    verbatim, skipping the exchange entirely. The *policy* (how many
    supersteps a tail may be reused — ``cfg.staleness_bound``) lives in the
    caller (`core/runner.py`'s refresh closure, the streaming runner); the
    engine only distinguishes fresh from cached, so the jit cache holds
    exactly two entries per layout. Under a fallback plan (coverage too
    high) the full-gather schedule runs instead, bit-identical to the halo
    fallback, and the returned cache is ``None`` — staleness is vacuous
    when every superstep already moves everything.

    Donation matches `superstep`: the fields in ``algo.donate`` are updated
    in place; rebind both results. The cache buffers are *not* donated — a
    stale superstep returns its input cache unchanged.
    """
    if algo.kind != "chunk":
        raise ValueError(
            f"chunk_schedule='async' overlaps the interior *block scan* "
            f"with the halo exchange; {algo.name} is kind={algo.kind!r} "
            "and has no block scan (use 'sharded' or 'halo')")
    if not isinstance(dg, ShardedDeviceGraph):
        raise TypeError(
            "chunk_schedule='async' needs a ShardedDeviceGraph (see "
            "prepare_sharded_device_graph); got a plain DeviceGraph")
    spec = dg.halo
    if spec is None:
        raise ValueError(
            "chunk_schedule='async' needs a halo-enabled layout: build it "
            "with shard_device_graph(..., halo=True) / attach_halo, or let "
            "run_partitioner build it")
    cap = capacity_device(dg.m, cfg.k, cfg.epsilon, cfg.capacity_mode)
    sd = state._asdict()
    donated = {f: sd.pop(f) for f in algo.donate}
    layout = _Layout(dg.n, dg.n_pad, dg.n_blocks, dg.block_v,
                     dg.blocks_per_shard)
    graph = _graph_arrays(dg.dg)
    if spec.fallback:
        # coverage too high for any exchange to win: run the full-gather
        # Jacobi schedule, exactly like the halo schedule's fallback
        return (_sharded_superstep(algo, cfg, dg.mesh, layout, graph, cap,
                                   donated, sd), None)
    _apply_halo_plan(graph, spec)
    refresh = cache is None
    return _async_sharded_superstep(
        algo, cfg, dg.mesh, layout, spec.interior_split, refresh,
        graph, cap, donated, sd, {} if refresh else cache)


def place_state(algo: Algorithm, state, sdg: ShardedDeviceGraph):
    """Commit a freshly-initialized state to the sharded layout per the
    algorithm's declared specs: vertex fields sliced onto their owning
    device, block tensors likewise, everything else replicated — so the
    donated superstep buffers are reused in place from step one."""
    mesh = sdg.mesh
    placed = {
        name: jax.device_put(
            value, NamedSharding(mesh, _state_spec(algo, name, value)))
        for name, value in state._asdict().items()
    }
    return algo.state_cls(**placed)


def state_shardings(algo: Algorithm, state, mesh):
    """`NamedSharding`s for every state field per the algorithm's declared
    specs — the elastic-restore companion of `place_state`: hand them to
    `repro.checkpoint.restore_checkpoint(shardings=)` and a checkpoint
    lands directly on the current mesh, whatever mesh wrote it. Accepts a
    state NamedTuple (or pytree dict) of arrays or ShapeDtypeStructs and
    returns the matching structure of shardings."""
    items = (state._asdict() if hasattr(state, "_asdict") else state).items()
    made = {name: NamedSharding(mesh, _state_spec(algo, name, value))
            for name, value in items}
    return algo.state_cls(**made) if hasattr(state, "_asdict") else made


# ---------------------------------------------------------------------------
# shared warm-start helpers (every rule's init_from_labels uses these)
# ---------------------------------------------------------------------------
def warm_labels(dg, k: int, key: jax.Array, labels) -> jnp.ndarray:
    """Carried labels for surviving vertices, random draws for new ones.

    ``labels`` covers up to ``len(labels)`` surviving vertices **in original
    vertex order** (clipped to [0, k)); vertices beyond it — newly arrived
    in a stream — draw a random label exactly like a cold init would. On a
    locality-permuted layout the carried slice is scattered to each
    vertex's storage position (``dg.o2s``); the unpermuted path is the
    original contiguous splice, bit-for-bit.
    """
    lab = jax.random.randint(key, (dg.n_pad,), 0, k, dtype=jnp.int32)
    carried = jnp.clip(jnp.asarray(labels, jnp.int32), 0, k - 1)
    m_keep = min(int(carried.shape[0]), dg.n_pad)
    o2s = getattr(dg, "o2s", None)
    if o2s is None:
        lab = jax.lax.dynamic_update_slice(lab, carried[:m_keep], (0,))
    else:
        lab = lab.at[jnp.asarray(o2s[:m_keep])].set(carried[:m_keep])
    return jnp.where(dg.vmask, lab, 0)


def loads_from_labels(dg, k: int, labels) -> jnp.ndarray:
    """Recompute b(l) from the degree vector so the invariant
    b(l) == sum deg over labels==l holds from step 0."""
    return jnp.zeros((k,), jnp.float32).at[labels].add(dg.deg_out)


__all__ = [
    "AXIS",
    "Algorithm",
    "ChunkContext",
    "ChunkUpdate",
    "ShardContext",
    "ShardUpdate",
    "halo_exchange",
    "superstep",
    "async_superstep",
    "place_state",
    "state_shardings",
    "warm_labels",
    "loads_from_labels",
]
