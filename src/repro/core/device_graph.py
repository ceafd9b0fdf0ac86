"""Device-resident graph layout consumed by the partitioning supersteps.

Two layouts are kept:

  * **flat** edge arrays `[M]` (src row, dst, eq.-4 weight) — used by the
    synchronous Spinner baseline and by the quality metrics;
  * **blocked** per-chunk slabs `[n_blocks, e_max]` — used by Revolver's
    chunked semi-asynchronous superstep (the TPU adaptation of the paper's
    per-thread asynchrony; see DESIGN.md §3) and by the Pallas kernels.

All per-vertex arrays are padded to `n_pad = n_blocks * block_v`; `vmask`
marks real vertices. Padding vertices carry zero degree and no edges so they
never influence loads or scores.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.halo import (
    DEFAULT_HALO_THRESHOLD,
    HaloSpec,
    HubConfig,
    build_halo_spec,
)
from repro.graphs.blocking import (
    block_adjacency,
    block_edges,
    block_row_ptr,
    locality_block_order,
    vcycle_block_order,
)
from repro.graphs.csr import Graph


class DeviceGraph(NamedTuple):
    """Static-shape device arrays for one graph. Ints are python (static)."""

    n: int
    n_pad: int
    m: int               # |E| directed edges
    n_blocks: int
    block_v: int
    e_max: int
    # flat symmetrized adjacency (for sync algorithms / metrics)
    edge_src: jnp.ndarray     # [Ms] int32 owning vertex
    edge_dst: jnp.ndarray     # [Ms] int32 neighbor
    edge_w: jnp.ndarray       # [Ms] f32 eq.(4) weight
    # flat *directed* edges (for the local-edges metric)
    dir_src: jnp.ndarray      # [M] int32
    dir_dst: jnp.ndarray      # [M] int32
    # blocked symmetrized adjacency (for async chunks / Pallas kernels)
    blk_dst: jnp.ndarray      # [n_blocks, e_max] int32 (0 pad)
    blk_row: jnp.ndarray      # [n_blocks, e_max] int32 local row (0 pad)
    blk_w: jnp.ndarray        # [n_blocks, e_max] f32 (0.0 pad)
    # per-vertex
    deg_out: jnp.ndarray      # [n_pad] f32 outdegree (load contribution)
    inv_wsum: jnp.ndarray     # [n_pad] f32 1/sum_u w_hat(u,v) (0 if isolated)
    vmask: jnp.ndarray        # [n_pad] bool real-vertex mask
    # [n_blocks, block_v + 1] int32 row pointers into each slab, for the
    # edge phase's prefix-sum histograms; None where they cannot pay or be
    # exact (`graphs.blocking.block_row_ptr`) and on sharded layouts
    blk_ptr: Optional[jnp.ndarray] = None


def prepare_device_graph(g: Graph, n_blocks: int = 8, block_multiple: int = 8) -> DeviceGraph:
    """Build the DeviceGraph with `n_blocks` asynchronous chunks."""
    n_blocks = max(1, min(n_blocks, g.n))
    block_v = -(-g.n // n_blocks)
    block_v = -(-block_v // block_multiple) * block_multiple
    blocked = block_edges(g, block_v=block_v)
    n_blocks = blocked.n_blocks
    n_pad = blocked.n_pad
    blk_ptr = block_row_ptr(g, blocked.block_v, n_blocks, blocked.e_max)

    deg_out = np.zeros(n_pad, dtype=np.float32)
    deg_out[: g.n] = g.deg_out.astype(np.float32)

    wsum = np.zeros(n_pad, dtype=np.float32)
    np.add.at(wsum, np.repeat(np.arange(g.n), np.diff(g.adj_ptr).astype(np.int64)), g.adj_w)
    inv_wsum = np.where(wsum > 0, 1.0 / np.maximum(wsum, 1e-30), 0.0).astype(np.float32)

    vmask = np.zeros(n_pad, dtype=bool)
    vmask[: g.n] = True

    src_flat = np.repeat(np.arange(g.n, dtype=np.int32), np.diff(g.adj_ptr).astype(np.int64))
    dir_src = np.repeat(np.arange(g.n, dtype=np.int32), np.diff(g.row_ptr).astype(np.int64))

    return DeviceGraph(
        n=g.n,
        n_pad=n_pad,
        m=g.m,
        n_blocks=n_blocks,
        block_v=blocked.block_v,
        e_max=blocked.e_max,
        edge_src=jnp.asarray(src_flat),
        edge_dst=jnp.asarray(g.adj_idx),
        edge_w=jnp.asarray(g.adj_w),
        dir_src=jnp.asarray(dir_src),
        dir_dst=jnp.asarray(g.col_idx),
        blk_dst=jnp.asarray(blocked.edge_dst),
        blk_row=jnp.asarray(blocked.edge_row),
        blk_w=jnp.asarray(blocked.edge_w),
        deg_out=jnp.asarray(deg_out),
        inv_wsum=jnp.asarray(inv_wsum),
        vmask=jnp.asarray(vmask),
        blk_ptr=None if blk_ptr is None else jnp.asarray(blk_ptr),
    )


# ---------------------------------------------------------------------------
# sharded (multi-device) layout: chunk_schedule="sharded"
# ---------------------------------------------------------------------------
# arrays indexed by block (or by block-major vertex) shard over "blocks";
# the flat metric arrays are replicated so eager metrics stay SPMD-legal
_BLOCKED_FIELDS = ("blk_dst", "blk_row", "blk_w")
_VERTEX_FIELDS = ("deg_out", "inv_wsum", "vmask")


@dataclasses.dataclass(frozen=True)
class ShardedDeviceGraph:
    """A `DeviceGraph` placed on a 1-D ``("blocks",)`` mesh.

    The block axis (and the block-major padded vertex axis) is sharded:
    device d owns `blocks_per_shard` contiguous blocks and the matching
    `[n_pad / n_shards]` slices of every per-vertex array, so the sharded
    superstep's edge phase reads only device-local slabs. Flat metric
    arrays are replicated. Attribute access falls through to the wrapped
    `DeviceGraph`, so metric/runner code consumes either layout unchanged.

    `n_blocks` is always a multiple of `n_shards` (see `align_blocks`):
    alignment pads with empty blocks (zero slabs, masked vertices) rather
    than resizing `block_v`, keeping per-shard shapes static and identical.

    **Locality-aware assignment** (`assignment="locality"`, or an explicit
    permutation): the stored block order is permuted so each shard's
    contiguous slice is a cluster of densely connected blocks
    (`locality_block_order`), and every vertex id in the wrapped arrays is
    rewritten into the permuted space (`permute_blocks`). `block_perm` /
    `o2s` / `s2o` record the mapping; labels and probabilities cross the
    public API boundary in *original* vertex order (`vertices_to_original`
    and the warm-start helpers convert).

    **Halo exchange** (`halo=True`): `halo` carries the precomputed
    boundary-exchange plan for `chunk_schedule="halo"`
    (see `repro.core.halo`); `None` means only the full-gather schedules
    are runnable.
    """

    dg: DeviceGraph
    mesh: jax.sharding.Mesh
    n_shards: int
    blocks_per_shard: int
    block_perm: Optional[Tuple[int, ...]] = None  # storage slot -> orig block
    o2s: Optional[np.ndarray] = None   # [n_pad] original vertex -> storage id
    s2o: Optional[np.ndarray] = None   # [n_pad] storage id -> original vertex
    halo: Optional[HaloSpec] = None
    # [n_blocks, n_blocks] block edge-cut matrix in *storage* order, filled
    # once (by the layout prep that already needed it, or lazily by
    # `block_adj_matrix`) and reused by every later consumer — locality
    # re-preps, the V-cycle, the scaling bench's traffic model
    block_adj: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False)

    def __getattr__(self, name):
        return getattr(self.dg, name)

    def block_adj_matrix(self) -> np.ndarray:
        """The block-level edge-cut matrix of the stored layout, computed at
        most once per layout (cached on the instance — an `np.add.at` over
        every slab edge is an O(E) host pass worth not repeating)."""
        if self.block_adj is None:
            adj = block_adjacency(np.asarray(self.blk_dst),
                                  np.asarray(self.blk_w), self.block_v)
            object.__setattr__(self, "block_adj", adj)
        return self.block_adj


def align_blocks(dg: DeviceGraph, multiple: int) -> DeviceGraph:
    """Pad `dg` with empty blocks until `n_blocks % multiple == 0`.

    Padding blocks carry all-zero slabs (dst=0, row=0, w=0.0) and masked-out
    vertices with zero degree, exactly like the in-block padding the kernels
    already ignore, so they change no score, load, or migration.
    """
    if multiple <= 0:
        raise ValueError(f"multiple must be positive, got {multiple}")
    pad_blocks = (-dg.n_blocks) % multiple
    if pad_blocks == 0:
        return dg
    nb = dg.n_blocks + pad_blocks
    n_pad = nb * dg.block_v
    pad_v = n_pad - dg.n_pad

    def pad_rows(a, fill):
        return jnp.concatenate(
            [a, jnp.full((pad_blocks, a.shape[1]), fill, a.dtype)], axis=0)

    return dg._replace(
        n_pad=n_pad,
        n_blocks=nb,
        blk_dst=pad_rows(dg.blk_dst, 0),
        blk_row=pad_rows(dg.blk_row, 0),
        blk_w=pad_rows(dg.blk_w, 0.0),
        blk_ptr=None if dg.blk_ptr is None else pad_rows(dg.blk_ptr, 0),
        deg_out=jnp.pad(dg.deg_out, (0, pad_v)),
        inv_wsum=jnp.pad(dg.inv_wsum, (0, pad_v)),
        vmask=jnp.pad(dg.vmask, (0, pad_v)),
    )


def block_vertex_perms(perm: np.ndarray, block_v: int) -> Tuple[np.ndarray, np.ndarray]:
    """Vertex-id maps induced by a block permutation.

    Returns `(o2s, s2o)` int32 `[n_blocks * block_v]` arrays: `o2s[v]` is
    the storage position of original vertex `v` (its block moved, its row
    within the block did not), `s2o` the inverse.
    """
    perm = np.asarray(perm, dtype=np.int64)
    nb = perm.size
    pos = np.empty(nb, dtype=np.int64)
    pos[perm] = np.arange(nb)
    v = np.arange(nb * block_v, dtype=np.int64)
    o2s = pos[v // block_v] * block_v + v % block_v
    s2o = np.empty_like(o2s)
    s2o[o2s] = v
    return o2s.astype(np.int32), s2o.astype(np.int32)


def permute_blocks(dg: DeviceGraph, perm: np.ndarray) -> DeviceGraph:
    """Reorder the blocked layout so storage slot i holds block `perm[i]`.

    Every vertex id in the returned graph — slab neighbor ids and the flat
    metric arrays included — is rewritten into the permuted space, so the
    result is a self-consistent `DeviceGraph`: the engine, the kernels, and
    the metrics consume it exactly like an unpermuted one. Only the *meaning*
    of index v changes (storage slot, not original vertex id); callers that
    cross the boundary convert with `block_vertex_perms` /
    `vertices_to_original`.

    The streaming layer maintains the same permuted layout incrementally
    (`repro.streaming.delta_graph.IncrementalDeviceGraph._to_device`); a
    field added to one rewrite must be added to the other —
    `tests/test_halo.py` pins the two layouts equal.
    """
    perm = np.asarray(perm, dtype=np.int64)
    if perm.shape != (dg.n_blocks,) or not np.array_equal(
            np.sort(perm), np.arange(dg.n_blocks)):
        raise ValueError(
            f"perm must be a permutation of range({dg.n_blocks})")
    if np.array_equal(perm, np.arange(dg.n_blocks)):
        return dg
    o2s, _ = block_vertex_perms(perm, dg.block_v)

    def per_vertex(a):
        return jnp.asarray(
            np.asarray(a).reshape(dg.n_blocks, dg.block_v)[perm].reshape(-1))

    def ids(a):
        return jnp.asarray(o2s[np.asarray(a)])

    return dg._replace(
        edge_src=ids(dg.edge_src),
        edge_dst=ids(dg.edge_dst),
        dir_src=ids(dg.dir_src),
        dir_dst=ids(dg.dir_dst),
        blk_dst=jnp.asarray(o2s[np.asarray(dg.blk_dst)[perm]]),
        blk_row=jnp.asarray(np.asarray(dg.blk_row)[perm]),
        blk_w=jnp.asarray(np.asarray(dg.blk_w)[perm]),
        blk_ptr=(None if dg.blk_ptr is None
                 else jnp.asarray(np.asarray(dg.blk_ptr)[perm])),
        deg_out=per_vertex(dg.deg_out),
        inv_wsum=per_vertex(dg.inv_wsum),
        vmask=per_vertex(dg.vmask),
    )


def vertices_to_original(sdg, x: jax.Array) -> jax.Array:
    """Reindex a storage-order per-vertex array into original vertex order
    (identity for unpermuted layouts and plain `DeviceGraph`s); the first
    `n` entries then correspond to real vertices 0..n-1 again."""
    o2s = getattr(sdg, "o2s", None)
    if o2s is None:
        return x
    return jnp.take(x, jnp.asarray(o2s), axis=0)


def resolve_assignment(
    dg: DeviceGraph,
    n_shards: int,
    assignment: Union[str, np.ndarray, None],
    adj: Optional[np.ndarray] = None,
) -> Optional[np.ndarray]:
    """Turn an `assignment=` argument into a block permutation (or None).

    "contiguous" / None keep the natural block striping; "locality" runs
    the greedy co-location pass over the block-level edge-cut matrix;
    "vcycle" runs the one-level-up multilevel solve of the same problem
    (`vcycle_block_order` — never worse than "locality" by construction);
    an explicit array is validated and used as-is. Identity permutations
    collapse to None so the unpermuted fast paths stay in force. `adj`
    hands in a precomputed edge-cut matrix so callers that already hold
    one (`ShardedDeviceGraph.block_adj_matrix`) skip the O(E) rebuild.
    """
    if assignment is None or (isinstance(assignment, str)
                              and assignment == "contiguous"):
        return None
    if isinstance(assignment, str):
        if assignment not in ("locality", "vcycle"):
            raise ValueError(
                f"unknown assignment {assignment!r}; expected 'contiguous', "
                "'locality', 'vcycle', or an explicit block permutation")
        if adj is None:
            adj = block_adjacency(np.asarray(dg.blk_dst),
                                  np.asarray(dg.blk_w), dg.block_v)
        order_fn = (locality_block_order if assignment == "locality"
                    else vcycle_block_order)
        perm = order_fn(adj, n_shards)
    else:
        perm = np.asarray(assignment, dtype=np.int64)
    if np.array_equal(perm, np.arange(dg.n_blocks)):
        return None
    return perm


def shard_device_graph(
    dg: DeviceGraph,
    mesh: jax.sharding.Mesh,
    *,
    assignment: Union[str, np.ndarray, None] = "contiguous",
    halo: bool = False,
    halo_threshold: float = DEFAULT_HALO_THRESHOLD,
    halo_granularity: str = "auto",
    hubs: Optional[HubConfig] = None,
) -> ShardedDeviceGraph:
    """Align `dg` to the mesh and place every array with a `NamedSharding`.

    Blocked slabs and per-vertex arrays land sliced on their owning device
    (`P("blocks", ...)`), flat metric arrays replicated (`P()`), so the
    sharded superstep starts from committed, correctly-placed buffers and
    donation can reuse them in place.

    `assignment` selects the block->shard mapping: "contiguous" (default)
    keeps the natural striping, "locality" greedily co-locates densely
    connected blocks (`locality_block_order`), an explicit `[n_blocks]`
    permutation is used verbatim. `halo=True` additionally precomputes the
    halo-exchange plan consumed by `chunk_schedule="halo"`; see
    `repro.core.halo` for the traffic model, the `halo_threshold`
    full-gather fallback, the `halo_granularity` knob ("auto" | "block" |
    "vertex" exchange plan), and `hubs` (a `HubConfig` enabling Spinner-
    style hub replication with per-superstep vote reconciliation).
    """
    if "blocks" not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no 'blocks' axis")
    n_shards = int(mesh.shape["blocks"])
    # the sharded schedules build their histograms by scatter-add
    dg = align_blocks(dg._replace(blk_ptr=None), n_shards)
    adj = None
    if isinstance(assignment, str) and assignment in ("locality", "vcycle"):
        # computed once here, seeded onto the returned layout's cache so
        # the V-cycle / traffic model never rebuild it for this layout
        adj = block_adjacency(np.asarray(dg.blk_dst), np.asarray(dg.blk_w),
                              dg.block_v)
    perm = resolve_assignment(dg, n_shards, assignment, adj=adj)
    o2s = s2o = None
    if perm is not None:
        dg = permute_blocks(dg, perm)
        o2s, s2o = block_vertex_perms(perm, dg.block_v)
        if adj is not None:
            # re-expressed in storage order: slot i holds original block
            # perm[i], so the cached matrix matches the stored layout
            adj = np.ascontiguousarray(adj[np.ix_(perm, perm)])
    placed = {}
    for name in dg._fields:
        value = getattr(dg, name)
        if not isinstance(value, jnp.ndarray):
            placed[name] = value
            continue
        if name in _BLOCKED_FIELDS:
            spec = P("blocks", None)
        elif name in _VERTEX_FIELDS:
            spec = P("blocks")
        else:
            spec = P()
        placed[name] = jax.device_put(value, NamedSharding(mesh, spec))
    spec = None
    if halo:
        spec = build_halo_spec(
            np.asarray(dg.blk_dst), np.asarray(dg.blk_w), n_shards,
            dg.block_v, threshold=halo_threshold,
            granularity=halo_granularity, hubs=hubs,
            deg=np.asarray(dg.deg_out), vmask=np.asarray(dg.vmask),
            blk_row=np.asarray(dg.blk_row), mesh=mesh)
    return ShardedDeviceGraph(
        dg=DeviceGraph(**placed),
        mesh=mesh,
        n_shards=n_shards,
        blocks_per_shard=dg.n_blocks // n_shards,
        block_perm=tuple(int(b) for b in perm) if perm is not None else None,
        o2s=o2s,
        s2o=s2o,
        halo=spec,
        block_adj=adj,
    )


def attach_halo(
    sdg: ShardedDeviceGraph,
    halo_threshold: float = DEFAULT_HALO_THRESHOLD,
    *,
    halo_granularity: str = "auto",
    hubs: Optional[HubConfig] = None,
) -> ShardedDeviceGraph:
    """Build (or rebuild) the halo-exchange plan for an already-placed
    sharded layout — the path `run_partitioner(chunk_schedule="halo")`
    takes when handed a pre-built `ShardedDeviceGraph` without one (or
    with one built under different granularity/hub knobs)."""
    spec = build_halo_spec(
        np.asarray(sdg.blk_dst), np.asarray(sdg.blk_w), sdg.n_shards,
        sdg.block_v, threshold=halo_threshold,
        granularity=halo_granularity, hubs=hubs,
        deg=np.asarray(sdg.deg_out), vmask=np.asarray(sdg.vmask),
        blk_row=np.asarray(sdg.blk_row), mesh=sdg.mesh)
    return dataclasses.replace(sdg, halo=spec)


def prepare_sharded_device_graph(
    g: Graph,
    mesh: jax.sharding.Mesh,
    n_blocks: int = 8,
    block_multiple: int = 8,
    *,
    assignment: Union[str, np.ndarray, None] = "contiguous",
    halo: bool = False,
    halo_threshold: float = DEFAULT_HALO_THRESHOLD,
    halo_granularity: str = "auto",
    hubs: Optional[HubConfig] = None,
) -> ShardedDeviceGraph:
    """`prepare_device_graph` + device-aligned blocking + NamedSharding placement.

    Requests at least one block per shard; whatever block count the blocking
    pass settles on is then padded up to a multiple of the mesh size. See
    `shard_device_graph` for `assignment` / `halo` / `halo_granularity` /
    `hubs`.
    """
    n_shards = int(mesh.shape["blocks"])
    dg = prepare_device_graph(
        g, n_blocks=max(n_blocks, n_shards), block_multiple=block_multiple)
    return shard_device_graph(dg, mesh, assignment=assignment, halo=halo,
                              halo_threshold=halo_threshold,
                              halo_granularity=halo_granularity, hubs=hubs)


CAPACITY_MODES = ("spinner", "paper")


def capacity(m: int, k: int, epsilon: float, mode: str) -> float:
    """Partition capacity C.

    mode="spinner": C = (1+eps)|E|/k — Spinner's definition, the default.
    mode="paper":   C = eps|E|/k     — the literal Section III-A text (makes
                    every partition over-capacity; kept for faithfulness,
                    the footnote-1 shift in eq. (12) keeps it well-defined).
    """
    if mode == "spinner":
        return (1.0 + epsilon) * m / k
    if mode == "paper":
        return epsilon * m / k
    raise ValueError(f"unknown capacity mode {mode!r}")


@functools.lru_cache(maxsize=512)
def capacity_device(m: int, k: int, epsilon: float, mode: str) -> jnp.ndarray:
    """`capacity(...)` as a device-resident f32 scalar, cached on its inputs.

    Capacity depends only on (|E|, cfg); the supersteps call this instead of
    recomputing + re-`asarray`-ing it every step, so the same graph/config
    pair reuses one committed device buffer across the whole convergence
    loop (and across warm restarts in the streaming runner).
    """
    return jnp.asarray(capacity(m, k, epsilon, mode), jnp.float32)
