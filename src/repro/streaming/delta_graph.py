"""Incremental graph state: merge edge deltas without a full host rebuild.

Batch mode pays O(m log m) in `build_graph` (sort + dedup of the whole edge
list) and a full re-block per graph. Streaming cannot afford that per delta.
This module maintains three sorted structures across deltas:

  * `dir_keys`  — sorted int64 keys of the directed edge set;
  * `sym_keys`, `sym_w` — sorted keys + eq.-(4) weights of the symmetrized
    adjacency (weight 1 = one direction present, 2 = both);
  * the padded block slabs of the `DeviceGraph` (blk_dst / blk_row / blk_w).

A delta of d events merges in O(m + d log m): canonicalize the delta (sort +
dedup of d keys only), splice it into the maintained arrays with
searchsorted-based inserts/deletes, recompute the eq.-(4) weights for the
touched vertex *pairs* only, and rewrite only the block slabs owning a
touched vertex. The device-side block layout (n_pad, block_v, e_max) is
reused across deltas, so the jitted Revolver superstep never recompiles —
until a block overflows `e_max`, at which point the slabs are re-padded with
headroom (`e_headroom`) and one recompile is paid.

The vertex space is declared up front (`n`): cloud deployments know their id
space (or reserve headroom); vertices materialize implicitly as edges touch
them and contribute nothing while isolated.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.device_graph import (
    DeviceGraph,
    ShardedDeviceGraph,
    block_vertex_perms,
)
from repro.core.halo import DEFAULT_HALO_THRESHOLD, HubConfig, build_halo_spec
from repro.graphs.blocking import (
    block_adjacency,
    block_row_ptr,
    block_slab_sizes,
    fill_block_slab,
    locality_block_order,
)
from repro.graphs.csr import (
    Graph,
    canonicalize_edges,
    decode_edge_keys,
    graph_from_sorted_state,
    merge_sorted_keys,
    remove_sorted_keys,
    sorted_isin,
)
from repro.streaming.stream import EdgeDelta


@dataclasses.dataclass
class MergeInfo:
    """What one delta merge did (diagnostics + StreamRunner reporting)."""

    added: int = 0              # directed edges actually inserted
    deleted: int = 0            # directed edges actually removed
    dup_dropped: int = 0        # insertions already present (or in-delta dups)
    missing_dropped: int = 0    # deletions of absent edges
    touched_vertices: Optional[np.ndarray] = None   # endpoints of changed pairs
    dirty_blocks: int = 0       # block slabs rewritten (device layer)
    repadded: bool = False      # e_max overflow forced a full re-pad
    m: int = 0                  # |E| after the merge


class IncrementalGraph:
    """Host-side CSR state maintained across deltas (see module docstring)."""

    def __init__(self, n: int):
        if n <= 0:
            raise ValueError(f"vertex space must be positive, got {n}")
        self.n = n
        self.dir_keys = np.empty(0, dtype=np.int64)
        self.sym_keys = np.empty(0, dtype=np.int64)
        self.sym_w = np.empty(0, dtype=np.float32)
        self.deltas_applied = 0

    @property
    def m(self) -> int:
        return int(self.dir_keys.size)

    def _check_delta(self, delta: EdgeDelta):
        """Reject malformed deltas before any state is touched, naming the
        delta so a bad producer in a long stream is attributable."""
        idx = self.deltas_applied
        pairs = (("add_src", delta.add_src, "add_dst", delta.add_dst),
                 ("del_src", delta.del_src, "del_dst", delta.del_dst))
        for sname, s, dname, d in pairs:
            s, d = np.asarray(s), np.asarray(d)
            if s.shape != d.shape:
                raise ValueError(
                    f"delta {idx}: {sname}/{dname} shape mismatch "
                    f"{s.shape} vs {d.shape}")
            for name, a in ((sname, s), (dname, d)):
                if a.dtype.kind == "f" and not np.isfinite(a).all():
                    raise ValueError(
                        f"delta {idx}: {name} contains NaN/inf edge data")
                if a.dtype.kind not in "iu" and not (
                        a.dtype.kind == "f"
                        and (not a.size or (a == np.floor(a)).all())):
                    raise ValueError(
                        f"delta {idx}: {name} dtype {a.dtype} is not a "
                        "vertex-id array")
                if a.size and int(a.min()) < 0:
                    raise ValueError(
                        f"delta {idx}: {name} contains negative vertex ids "
                        f"(min {int(a.min())})")
                if a.size and int(a.max()) >= self.n:
                    raise ValueError(
                        f"delta {idx}: {name} contains vertex ids >= "
                        f"n={self.n} (max {int(a.max())})")

    def apply(self, delta: EdgeDelta) -> MergeInfo:
        """Merge one delta. Deletions apply before insertions, so an edge
        deleted and re-added within the same delta ends up present.
        Malformed deltas (id out of [0, n), NaN/inf data, shape-mismatched
        src/dst) raise ValueError naming the delta index, before any state
        is modified."""
        self._check_delta(delta)
        n = self.n
        info = MergeInfo()

        dels = canonicalize_edges(delta.del_src, delta.del_dst, n)
        dels = dels[sorted_isin(self.dir_keys, dels)]
        info.missing_dropped = delta.n_del - int(dels.size)
        dir_mid = remove_sorted_keys(self.dir_keys, dels)

        adds = canonicalize_edges(delta.add_src, delta.add_dst, n)
        adds = adds[~sorted_isin(dir_mid, adds)]
        info.dup_dropped = delta.n_add - int(adds.size)
        self.dir_keys = merge_sorted_keys(dir_mid, adds)
        info.added, info.deleted = int(adds.size), int(dels.size)
        info.m = self.m

        # ---- eq.-(4) weight maintenance for the touched pairs only --------
        changed = np.concatenate([dels, adds])
        if changed.size:
            u, v = decode_edge_keys(changed, n)
            pu, pv = np.minimum(u, v).astype(np.int64), np.maximum(u, v).astype(np.int64)
            pairs = np.unique(pu * n + pv)
            pu, pv = decode_edge_keys(pairs, n)
            pu, pv = pu.astype(np.int64), pv.astype(np.int64)
            fwd, rev = pu * n + pv, pv * n + pu
            cnt = (
                sorted_isin(self.dir_keys, fwd).astype(np.int8)
                + sorted_isin(self.dir_keys, rev).astype(np.int8)
            )
            present = sorted_isin(self.sym_keys, fwd)

            # slots to drop: pair lost its last direction
            gone = present & (cnt == 0)
            if gone.any():
                drop = np.sort(np.concatenate([fwd[gone], rev[gone]]))
                idx = np.searchsorted(self.sym_keys, drop)
                self.sym_keys = np.delete(self.sym_keys, idx)
                self.sym_w = np.delete(self.sym_w, idx)

            # weight rewrites: pair survives with a (possibly) new direction count
            upd = present & (cnt > 0)
            if upd.any():
                keys = np.concatenate([fwd[upd], rev[upd]])
                w = np.concatenate([cnt[upd], cnt[upd]]).astype(np.float32)
                self.sym_w[np.searchsorted(self.sym_keys, keys)] = w

            # fresh slots: pair gained its first direction
            new = (~present) & (cnt > 0)
            if new.any():
                keys = np.concatenate([fwd[new], rev[new]])
                w = np.concatenate([cnt[new], cnt[new]]).astype(np.float32)
                order = np.argsort(keys)
                keys, w = keys[order], w[order]
                idx = np.searchsorted(self.sym_keys, keys)
                self.sym_keys = np.insert(self.sym_keys, idx, keys)
                self.sym_w = np.insert(self.sym_w, idx, w)

            info.touched_vertices = np.unique(np.concatenate([pu, pv])).astype(np.int64)
        else:
            info.touched_vertices = np.empty(0, dtype=np.int64)
        self.deltas_applied += 1
        return info

    def to_graph(self) -> Graph:
        """O(m) materialization of the standard `Graph` container."""
        return graph_from_sorted_state(self.n, self.dir_keys, self.sym_keys, self.sym_w)


class IncrementalDeviceGraph:
    """Pads an evolving graph into a shape-stable `DeviceGraph`.

    `apply(delta)` returns a fresh `DeviceGraph` whose blocked arrays keep
    their shapes across deltas (jit-cache friendly); only slabs of blocks
    owning a touched vertex are rewritten. The flat metric arrays
    (dir_src/dir_dst, edge_*) track the true edge count and therefore change
    length — they feed cheap eager metrics, not the jitted superstep.

    **Locality-aware assignment** (`assignment="locality"` or an explicit
    block permutation; requires `mesh`): the maintained slabs live in
    permuted *storage* order with neighbor ids rewritten into the permuted
    space, so a rewritten dirty slab still transfers straight to the shard
    that owns the block under the permuted assignment. A "locality"
    permutation is decided once, from the block-level edge-cut matrix of the
    first merged delta (typically the bulk load), and then held fixed for
    the whole stream — the carried labels/probabilities and the jit cache
    depend on a stable layout; a drifting graph that outgrows its
    assignment is a re-shard event, not a per-delta adjustment.

    **Halo** (`as_sharded(halo=True)`): the boundary-exchange plan is
    rebuilt per delta from the current slabs (same O(n_blocks * e_max) host
    cost class as the per-delta array uploads), with `b_max` only ever
    growing (monotonic floor) so the jitted halo superstep keeps its shapes
    until the halo genuinely widens — the same recompile discipline as an
    `e_max` re-pad.
    """

    def __init__(
        self,
        n: int,
        *,
        n_blocks: int = 8,
        block_multiple: int = 8,
        edge_chunk: int = 256,
        e_headroom: float = 1.5,
        mesh=None,
        assignment: Union[str, np.ndarray, None] = "contiguous",
    ):
        self.inc = IncrementalGraph(n)
        n_blocks = max(1, min(n_blocks, n))
        block_v = -(-n // n_blocks)
        block_v = -(-block_v // block_multiple) * block_multiple
        self.block_v = block_v
        self.n_blocks = -(-n // block_v)
        # blocks that can ever hold a real vertex (slab rewrites stop here;
        # alignment blocks beyond stay all-zero for the whole stream)
        self._real_blocks = self.n_blocks
        # mesh-aligned streaming (sharded chunk_schedule): pad to a multiple
        # of the mesh size with empty blocks up front, so every delta's
        # device layout is already device-aligned and each rewritten dirty
        # slab transfers straight to its owning device
        self.mesh = mesh
        if mesh is not None:
            n_shards = int(mesh.shape["blocks"])
            self.n_blocks += (-self.n_blocks) % n_shards
        self.n_pad = self.n_blocks * block_v
        self.edge_chunk = edge_chunk
        self.e_headroom = float(e_headroom)
        self.e_max = 0
        self._blk_dst = np.zeros((self.n_blocks, 0), dtype=np.int32)
        self._blk_row = np.zeros((self.n_blocks, 0), dtype=np.int32)
        self._blk_w = np.zeros((self.n_blocks, 0), dtype=np.float32)
        self.graph: Optional[Graph] = None
        self.device_graph: Optional[DeviceGraph] = None
        # block->shard assignment state (storage permutation)
        if isinstance(assignment, str) and assignment not in (
                "contiguous", "locality"):
            raise ValueError(
                f"unknown assignment {assignment!r}; expected 'contiguous', "
                "'locality', or an explicit block permutation")
        if not isinstance(assignment, str) and assignment is not None:
            assignment = np.asarray(assignment, dtype=np.int64)
        if mesh is None and (
                (isinstance(assignment, str) and assignment == "locality")
                or isinstance(assignment, np.ndarray)):
            raise ValueError("a block->shard assignment needs a mesh")
        self._assignment = assignment
        self.block_perm: Optional[np.ndarray] = None  # storage -> orig block
        self._pos: Optional[np.ndarray] = None        # orig block -> storage
        self.o2s: Optional[np.ndarray] = None
        self.s2o: Optional[np.ndarray] = None
        # "locality" is decided once, from the first non-empty merge; the
        # flag (not `block_perm is None` — the decision may legitimately be
        # the identity) keeps it from being re-litigated every delta
        self._perm_decided = not (isinstance(assignment, str)
                                  and assignment == "locality")
        if isinstance(assignment, np.ndarray):
            self._set_perm(assignment)
        self._b_max_floor = 0
        # per-vertex / hub plan floors — same monotonic discipline as
        # _b_max_floor: the jitted superstep's shapes only change when an
        # exchange genuinely outgrows its padding or the hub set grows
        self._h_max_floor = 0
        self._hub_pad_floor = 0
        self._he_max_floor = 0
        self._hub_ids: Tuple[int, ...] = ()
        # host copies of the per-vertex arrays (storage order), kept for the
        # hub-selection pass in as_sharded
        self._deg_host: Optional[np.ndarray] = None
        self._vmask_host: Optional[np.ndarray] = None

    def _set_perm(self, perm: np.ndarray):
        perm = np.asarray(perm, dtype=np.int64)
        if perm.shape != (self.n_blocks,) or not np.array_equal(
                np.sort(perm), np.arange(self.n_blocks)):
            raise ValueError(
                f"perm must be a permutation of range({self.n_blocks})")
        if np.array_equal(perm, np.arange(self.n_blocks)):
            return
        self.block_perm = perm
        self._pos = np.empty(self.n_blocks, dtype=np.int64)
        self._pos[perm] = np.arange(self.n_blocks)
        self.o2s, self.s2o = block_vertex_perms(perm, self.block_v)

    def _storage_row(self, blk: int) -> int:
        return int(self._pos[blk]) if self._pos is not None else int(blk)

    @property
    def n(self) -> int:
        return self.inc.n

    @property
    def b_max_floor(self) -> int:
        """Monotonic halo width (padded boundary blocks per shard) the jitted
        halo superstep is compiled for; growth means a recompile
        (`StreamRunner` attributes it as a "halo-widen" event)."""
        return self._b_max_floor

    @property
    def h_max_floor(self) -> int:
        """Monotonic per-vertex need-list padding (per shard pair) — the
        vertex-granularity analogue of `b_max_floor`."""
        return self._h_max_floor

    @property
    def hub_pad_floor(self) -> int:
        """Monotonic replicated-hub-region length; growth means the hub set
        was promoted (`StreamRunner` attributes it as a "hub-promote"
        event)."""
        return self._hub_pad_floor

    @property
    def hub_ids(self) -> Tuple[int, ...]:
        """The replicated hub set (monotonic across deltas — once a vertex
        is mirrored everywhere, demoting it would reshuffle every shard's
        buffer layout for no traffic win)."""
        return self._hub_ids

    def _round_e(self, need: int) -> int:
        return -(-max(need, 1) // self.edge_chunk) * self.edge_chunk

    def _fill(self, g: Graph, blk: int):
        fill_block_slab(g, blk, self.block_v, self._blk_dst, self._blk_row,
                        self._blk_w, out_blk=self._storage_row(blk),
                        dst_map=self.o2s)

    def apply(self, delta: EdgeDelta) -> Tuple[DeviceGraph, MergeInfo]:
        info = self.inc.apply(delta)
        g = self.inc.to_graph()
        self.graph = g

        sizes = block_slab_sizes(g.adj_ptr, g.n, self.block_v, self._real_blocks)
        need = int(sizes.max()) if sizes.size else 0
        if need > self.e_max or self.e_max == 0:
            # overflow: re-pad every slab with headroom (one jit recompile)
            self.e_max = self._round_e(int(need * self.e_headroom))
            self._blk_dst = np.zeros((self.n_blocks, self.e_max), dtype=np.int32)
            self._blk_row = np.zeros((self.n_blocks, self.e_max), dtype=np.int32)
            self._blk_w = np.zeros((self.n_blocks, self.e_max), dtype=np.float32)
            dirty = np.arange(self._real_blocks)
            info.repadded = True
        else:
            touched = info.touched_vertices
            dirty = np.unique(touched // self.block_v) if touched.size else np.empty(0, np.int64)
        for blk in dirty:
            self._fill(g, int(blk))
        info.dirty_blocks = int(len(dirty))

        if not self._perm_decided and g.m > 0:
            # decide the stream's assignment from the first non-empty merge
            # (slabs are still in natural order at this point), then rebuild
            # every slab into permuted storage — a one-time full rewrite,
            # same cost class as the initial fill
            adj = block_adjacency(self._blk_dst, self._blk_w, self.block_v)
            perm = locality_block_order(adj, int(self.mesh.shape["blocks"]))
            self._perm_decided = True
            self._set_perm(perm)
            if self.block_perm is not None:
                self._blk_dst[:] = 0
                self._blk_row[:] = 0
                self._blk_w[:] = 0.0
                for blk in range(self._real_blocks):
                    self._fill(g, blk)

        self.device_graph = self._to_device(g)
        return self.device_graph, info

    def as_sharded(
        self,
        *,
        halo: bool = False,
        halo_threshold: float = DEFAULT_HALO_THRESHOLD,
        halo_granularity: str = "auto",
        hubs: Optional[HubConfig] = None,
    ) -> ShardedDeviceGraph:
        """Wrap the latest device layout for the sharded/halo schedules.

        The arrays are already mesh-aligned, permuted, and placed; this
        attaches the assignment metadata (so carried labels/probs convert
        at the API boundary) and, for `halo=True`, the exchange plan
        rebuilt against the current slabs (`halo_granularity` / `hubs` as
        in `build_halo_spec`). Every exchange shape is floored at its
        historical maximum — `b_max`, the per-vertex `h_max`, the hub
        region `hub_pad`, and the vote-table `he_max` — so the jitted
        superstep keeps its shapes while the halo only drifts; growth past
        a floor recompiles (a "halo-widen" or, for the hub region, a
        "hub-promote" event). The hub set itself is monotonic: hubs
        promoted by an earlier delta stay replicated.
        """
        if self.mesh is None:
            raise ValueError("as_sharded needs a mesh-aligned layout")
        if self.device_graph is None:
            raise ValueError("no device layout yet; apply a delta first")
        n_shards = int(self.mesh.shape["blocks"])
        spec = None
        if halo:
            spec = build_halo_spec(
                self._blk_dst, self._blk_w, n_shards, self.block_v,
                threshold=halo_threshold, granularity=halo_granularity,
                b_max_floor=self._b_max_floor,
                h_max_floor=self._h_max_floor,
                hubs=hubs,
                deg=self._deg_host, vmask=self._vmask_host,
                blk_row=self._blk_row,
                hub_ids_floor=self._hub_ids,
                hub_pad_floor=self._hub_pad_floor,
                he_max_floor=self._he_max_floor,
                mesh=self.mesh)
            self._b_max_floor = spec.b_max
            self._h_max_floor = spec.h_max
            if not spec.fallback:
                self._hub_ids = tuple(int(h) for h in spec.hub_ids)
                self._hub_pad_floor = max(self._hub_pad_floor, spec.hub_pad)
                self._he_max_floor = max(self._he_max_floor, spec.he_max)
        return ShardedDeviceGraph(
            dg=self.device_graph,
            mesh=self.mesh,
            n_shards=n_shards,
            blocks_per_shard=self.n_blocks // n_shards,
            block_perm=(tuple(int(b) for b in self.block_perm)
                        if self.block_perm is not None else None),
            o2s=self.o2s,
            s2o=self.s2o,
            halo=spec,
        )

    def _to_device(self, g: Graph) -> DeviceGraph:
        n_pad = self.n_pad
        deg_out = np.zeros(n_pad, dtype=np.float32)
        deg_out[: g.n] = g.deg_out.astype(np.float32)
        wsum = np.zeros(n_pad, dtype=np.float32)
        np.add.at(
            wsum,
            np.repeat(np.arange(g.n), np.diff(g.adj_ptr).astype(np.int64)),
            g.adj_w,
        )
        inv_wsum = np.where(wsum > 0, 1.0 / np.maximum(wsum, 1e-30), 0.0).astype(np.float32)
        vmask = np.zeros(n_pad, dtype=bool)
        vmask[: g.n] = True
        src_flat = np.repeat(np.arange(g.n, dtype=np.int32), np.diff(g.adj_ptr).astype(np.int64))
        dir_src = np.repeat(np.arange(g.n, dtype=np.int32), np.diff(g.row_ptr).astype(np.int64))
        edge_dst, dir_dst = g.adj_idx, g.col_idx
        if self.block_perm is not None:
            # storage-permuted layout: per-vertex arrays follow their block,
            # flat metric ids are rewritten so metrics read the same space
            # the (permuted) labels live in. This mirrors
            # device_graph.permute_blocks field-for-field (the incremental
            # path permutes incrementally instead of wholesale); a
            # DeviceGraph field added to one site must be added to the
            # other — tests/test_halo.py pins the two layouts equal.
            perm = self.block_perm

            def pv(a):
                return a.reshape(self.n_blocks, self.block_v)[perm].reshape(-1)

            deg_out, inv_wsum, vmask = pv(deg_out), pv(inv_wsum), pv(vmask)
            src_flat = self.o2s[src_flat]
            edge_dst = self.o2s[edge_dst]
            dir_src = self.o2s[dir_src]
            dir_dst = self.o2s[dir_dst]
        # storage-order host copies feed the hub-selection pass (as_sharded)
        self._deg_host, self._vmask_host = deg_out, vmask
        if self.mesh is not None:
            # device-aligned placement: each slab row / per-vertex slice goes
            # straight from host to its owning device; flat metric arrays
            # are replicated so eager metrics stay SPMD-legal
            def put_blocked(a):
                return jax.device_put(a, NamedSharding(self.mesh, P("blocks", None)))

            def put_vertex(a):
                return jax.device_put(a, NamedSharding(self.mesh, P("blocks")))

            def put_flat(a):
                return jax.device_put(np.asarray(a), NamedSharding(self.mesh, P()))
        else:
            put_blocked = put_vertex = put_flat = jnp.asarray
        # row pointers of the slabs as this delta left them, rebuilt from
        # the merged CSR (natural-order layouts only, as the sequential
        # schedule reads them)
        blk_ptr = None
        if self.mesh is None:
            blk_ptr = block_row_ptr(g, self.block_v, self.n_blocks, self.e_max)
        return DeviceGraph(
            n=g.n,
            n_pad=n_pad,
            m=g.m,
            n_blocks=self.n_blocks,
            block_v=self.block_v,
            e_max=self.e_max,
            edge_src=put_flat(src_flat),
            edge_dst=put_flat(edge_dst),
            edge_w=put_flat(g.adj_w),
            dir_src=put_flat(dir_src),
            dir_dst=put_flat(dir_dst),
            blk_dst=put_blocked(self._blk_dst),
            blk_row=put_blocked(self._blk_row),
            blk_w=put_blocked(self._blk_w),
            deg_out=put_vertex(deg_out),
            inv_wsum=put_vertex(inv_wsum),
            vmask=put_vertex(vmask),
            blk_ptr=None if blk_ptr is None else jnp.asarray(blk_ptr),
        )
