"""Host-side convergence loop (Section IV-D step 9) shared by all algorithms.

Runs jitted supersteps, tracks the paper's quality metrics each step, and
halts when the LP score fails to improve by `theta` for `patience`
consecutive steps (paper settings: theta=0.001, patience=5, max 290 steps).

Algorithm dispatch goes through the string-keyed registry
(`repro.core.registry`): any registered `engine.Algorithm` — revolver,
spinner, restream, or an out-of-tree rule — runs through the same
convergence loop, warm-start plumbing, schedule selection, and metric
fetching; `StaticAlgorithm` entries (hash, range) take the closed-form fast
path.

Host/device synchronization: materializing `state.score` as a python float
blocks on the device every superstep, serializing dispatch. The loop instead
buffers the per-step score arrays and fetches them with a single
`jax.device_get` every `sync_every` supersteps, letting XLA pipeline the
window; with `track_history=True` the per-step `local_edges` /
`max_norm_load` arrays are buffered and drained on the same window (no
per-step host sync there either). Convergence is then detected up to
`sync_every - 1` steps late (the extra steps are still valid partitioning
steps and are reflected in `PartitionResult.steps` and the history lists);
`sync_every=1` (the default) reproduces the fully synchronous behavior
exactly.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults, obs
from repro.checkpoint import store as ckpt_store
from repro.core import engine
from repro.core.device_graph import (
    DeviceGraph,
    ShardedDeviceGraph,
    attach_halo,
    prepare_device_graph,
    prepare_sharded_device_graph,
    shard_device_graph,
    vertices_to_original,
)
from repro.core.halo import (
    DEFAULT_HALO_THRESHOLD,
    HubConfig,
    build_halo_spec,
    interior_first_order,
)
from repro.core.metrics import local_edges, max_normalized_load
from repro.core.registry import StaticAlgorithm, get_algorithm
from repro.graphs.csr import Graph

_log = logging.getLogger("repro.core.runner")


class PartitionStateError(RuntimeError):
    """The drain-window state guard found corrupt partitioner state
    (non-finite LA probabilities or out-of-range labels) under the
    ``guard="raise"`` policy, or a recovery policy could not be applied
    (e.g. rollback with no usable checkpoint)."""


@dataclasses.dataclass
class PartitionResult:
    algo: str
    k: int
    labels: np.ndarray                 # [n] final partition per vertex
    steps: int
    converged: bool
    local_edges: float
    max_norm_load: float
    history: Dict[str, List[float]]
    wall_s: float
    probs: Optional[np.ndarray] = None  # [n_blocks, block_v, k] final LA state
                                        # (probs-carrying algorithms with
                                        # keep_probs=True only; feeds warm
                                        # restarts)
    resumed_from: int = 0               # global superstep of the checkpoint
                                        # this run resumed from (0 = fresh);
                                        # `steps` counts from superstep 0
                                        # either way


def run_convergence_loop(
    step_fn,
    state,
    *,
    max_steps: int,
    patience: int,
    theta: float,
    sync_every: int = 1,
    on_step=None,
    on_score=None,
    on_drain=None,
    tracer=None,
    step0: int = 0,
    prev_score: float = -np.inf,
    stall: int = 0,
):
    """Drive `step_fn` with the paper's score-stall halting (Section IV-D
    step 9): stop after `patience` consecutive steps whose score improves by
    less than `theta`. Scores are fetched from device in `sync_every`-sized
    windows (see module docstring); convergence is then detected up to
    `sync_every - 1` steps late. Shared by `run_partitioner` and the
    streaming `StreamRunner` so the halting semantics cannot drift.

    `on_step(state)` fires after every superstep (history tracking);
    `on_score(float)` fires for every drained score, in step order — every
    *executed* step's score is drained, including the up-to-`sync_every - 1`
    steps past the detected convergence point, so history lists stay aligned
    with `steps_executed`. `on_drain(state, steps, prev_score, stall)` fires
    once per fetched window, after its scores; callers buffering their own
    per-step device arrays (e.g. `run_partitioner`'s history metrics) drain
    them there, on the same cadence as the score fetch. It receives the
    loop's halting state so a checkpoint written at the drain can resume
    exactly; it may return a dict with any of ``state`` / ``prev_score`` /
    ``stall`` to *replace* the loop's state (the guard's rollback/reinit
    recovery path — a replacement also clears a convergence detected in the
    corrupted window).

    `prev_score` / `stall` seed the halting state — a resumed run passes the
    values its checkpoint recorded so the stall counter picks up exactly
    where the killed run left it; `step0` likewise offsets the superstep
    numbering (spans, fault-injection points) to the global step index.

    Fault injection (`repro.faults`): after each dispatched superstep the
    loop checks the ``superstep`` point with the global step index — a kill
    plan SIGKILLs here, a poison plan corrupts the state device-side (for
    guard testing). No-ops (one early-returning call) when no plan is
    active.

    `tracer` (a `repro.obs.Tracer`; default no-op) records one "superstep"
    span per executed step — the *dispatch* cost; the device time of a
    window accrues to its blocking "device-sync" span — numbered from
    `step0` (streaming passes a global step offset so spans stay monotonic
    across deltas). Tracing changes no fetch cadence: the only blocking
    calls are the same windowed `device_get`s the untraced loop makes.

    Returns (state, steps_executed, converged).
    """
    tracer = tracer if tracer is not None else obs.NULL_TRACER
    converged = False
    steps = 0
    pending: list = []
    for step in range(max_steps):
        with tracer.span("superstep", step=step0 + step):
            state = step_fn(state)
        act = faults.fire("superstep", step0 + step)
        if act is not None:
            state = faults.poison(state, act)
        steps = step + 1
        pending.append(state.score)
        if on_step is not None:
            on_step(state)
        if len(pending) < sync_every and steps < max_steps:
            continue
        with tracer.span("device-sync", steps=len(pending), what="scores"):
            scores = jax.device_get(pending)
        for score in (float(s) for s in scores):
            if on_score is not None:
                on_score(score)
            if converged:
                continue  # window tail past the detection point
            if score - prev_score < theta:
                stall += 1
                if stall >= patience:
                    converged = True
            else:
                stall = 0
            prev_score = score
        pending = []
        if on_drain is not None:
            replace = on_drain(state, steps, prev_score, stall)
            if replace is not None:
                state = replace.get("state", state)
                prev_score = replace.get("prev_score", prev_score)
                stall = replace.get("stall", stall)
                converged = False   # scores from corrupt state don't count
        if converged:
            break
    return state, steps, converged


def _make_cfg(cls, k: int, max_steps: Optional[int], cfg_kwargs: dict):
    """Build an algorithm config, rejecting unknown keys loudly.

    The spinner branch used to silently drop revolver-only kwargs, which
    turned typos (e.g. `capacty_mode=`) into no-ops; every registered
    algorithm now raises TypeError on anything its config dataclass doesn't
    define.
    """
    valid = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(cfg_kwargs) - valid)
    if unknown:
        raise TypeError(
            f"unknown config kwargs for {cls.__name__}: {unknown}; "
            f"valid keys: {sorted(valid - {'k'})}"
        )
    cfg = cls(k=k, **cfg_kwargs)
    if max_steps is not None:
        cfg = dataclasses.replace(cfg, max_steps=max_steps)
    return cfg


# ---------------------------------------------------------------------------
# crash safety: checkpointed resume (see docs/fault-tolerance.md)
# ---------------------------------------------------------------------------
def _is_vertex_field(algo, dg, name, value) -> bool:
    return ((name in algo.vertex_fields or name in algo.replicated_fields)
            and getattr(value, "ndim", 0) >= 1
            and value.shape[0] == dg.n_pad)


def _state_to_original(algo, state, dg) -> dict:
    """Checkpoint view of a state: every per-vertex / per-block field
    gathered into original vertex order (identity on unpermuted layouts,
    a device-side gather otherwise — enqueued at the drain so the fetch
    bundles with the window's metrics). A checkpoint is therefore
    layout-independent: restorable onto a different device count or
    block->shard assignment of the same graph."""
    if getattr(dg, "o2s", None) is None:
        # unpermuted layout: every conversion below is an identity
        # reshape/gather round-trip — skip the dispatch overhead entirely
        return dict(state._asdict())
    out = {}
    for name, v in state._asdict().items():
        if name in algo.block_fields:
            flat = v.reshape((dg.n_pad,) + v.shape[2:])
            out[name] = vertices_to_original(dg, flat).reshape(v.shape)
        elif _is_vertex_field(algo, dg, name, v):
            out[name] = vertices_to_original(dg, v)
        else:
            out[name] = v
    return out


def _state_from_original(algo, tree: dict, dg):
    """Inverse of `_state_to_original`: arrays in original vertex order ->
    a state NamedTuple in the layout's storage order (scatter via ``s2o``;
    identity on unpermuted layouts)."""
    s2o = getattr(dg, "s2o", None)
    out = {}
    for name, v in tree.items():
        if s2o is not None and name in algo.block_fields:
            flat = np.asarray(v).reshape((dg.n_pad,) + tuple(v.shape[2:]))
            out[name] = jnp.asarray(flat[np.asarray(s2o)]).reshape(v.shape)
        elif s2o is not None and _is_vertex_field(algo, dg, name, v):
            out[name] = jnp.asarray(np.asarray(v)[np.asarray(s2o)])
        else:
            out[name] = v
    return algo.state_cls(**out)


class _CheckpointManager:
    """Drain-window checkpointing for `run_partitioner`.

    Saves ride the existing ``sync_every`` drain windows: the state's
    original-order view is enqueued device-side and fetched **in the same
    bundled `jax.device_get`** as the window's metrics (zero additional
    blocking device fetches — the PR-6 sync-count contract), then written
    by an async writer thread while the loop keeps dispatching. One writer
    is in flight at a time; waiting on the previous handle before the next
    save (and at run end) both orders the atomic renames and re-raises
    write failures instead of swallowing them.
    """

    def __init__(self, ckpt_dir, every, keep, algorithm, dg, sharded,
                 meta, tracer):
        self.dir = ckpt_dir
        self.every = every
        self.keep = keep
        self.algorithm = algorithm
        self.dg = dg
        self.sharded = sharded
        self.meta = meta
        self.tracer = tracer
        self.last_saved = 0
        self.saved = 0
        self._handles: list = []

    def _reap(self, block: bool = False):
        """Collect finished writer threads, re-raising any write failure
        (the satellite contract: a swallowed ENOSPC is a checkpoint that
        does not exist when the resume needs it). Non-blocking unless
        `block` — the convergence loop must never stall on an fsync."""
        alive = []
        for h in self._handles:
            if block or h._thread is None or not h._thread.is_alive():
                h.wait()
            else:
                alive.append(h)
        self._handles = alive

    def busy(self) -> bool:
        """True when the disk is falling behind (two writes already in
        flight); the due save is skipped rather than blocking the loop —
        the next drain window picks it up."""
        self._reap()
        return len(self._handles) >= 2

    def due(self, global_steps: int) -> bool:
        return self.every > 0 and global_steps - self.last_saved >= self.every

    def device_tree(self, state) -> dict:
        return _state_to_original(self.algorithm, state, self.dg)

    def save(self, global_steps: int, host_tree: dict, prev_score, stall):
        meta = dict(self.meta, steps=global_steps,
                    prev_score=float(prev_score), stall=int(stall),
                    converged=bool(stall >= self.meta.get("patience", 1 << 30)))
        with self.tracer.span("checkpoint-save", step=global_steps):
            self._handles.append(ckpt_store.save_checkpoint(
                self.dir, global_steps, host_tree, async_save=True,
                meta=meta, keep=self.keep))
        self.last_saved = global_steps
        self.saved += 1
        if self.tracer.enabled:
            self.tracer.counter("checkpoints_saved", float(self.saved),
                                step=global_steps)

    def finish(self):
        self._reap(block=True)

    # -- restore ---------------------------------------------------------- #

    def restore_latest(self, like_state):
        """Restore the newest usable checkpoint, falling back past corrupt
        or incompatible ones. Returns ``(state, steps, prev_score, stall,
        converged)`` or None when no checkpoint is usable."""
        for step in reversed(ckpt_store.all_steps(self.dir)):
            try:
                return self._restore(step, like_state)
            except (ckpt_store.CheckpointError, ValueError, KeyError) as e:
                _log.warning(
                    "checkpoint step %d in %s unusable (%s); trying the "
                    "previous one", step, self.dir, e)
        return None

    def _restore(self, step, like_state):
        manifest = ckpt_store.load_manifest(self.dir, step)
        meta = manifest.get("meta", {})
        for field in ("algo", "k", "n", "m"):
            if field in meta and field in self.meta \
                    and meta[field] != self.meta[field]:
                raise ValueError(
                    f"checkpoint step {step} was written by a different run: "
                    f"{field}={meta[field]!r} vs this run's "
                    f"{self.meta[field]!r}")
        algo, dg = self.algorithm, self.dg
        like = {name: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for name, v in like_state._asdict().items()}
        shardings = None
        if self.sharded and getattr(dg, "o2s", None) is None:
            # unpermuted layout: original order == storage order, so the
            # checkpoint lands directly on the mesh — the store's elastic
            # re-shard path, whatever device count wrote it
            shardings = engine.state_shardings(algo, like, dg.mesh)
        with self.tracer.span("checkpoint-restore", step=step):
            tree = ckpt_store.restore_checkpoint(self.dir, step, like,
                                                 shardings=shardings)
            if shardings is not None:
                state = algo.state_cls(**tree)
            else:
                state = _state_from_original(algo, tree, dg)
                if self.sharded:
                    state = engine.place_state(algo, state, dg)
        if self.tracer.enabled:
            self.tracer.instant("resumed", step=step)
        return (state, int(meta.get("steps", step)),
                float(meta.get("prev_score", -np.inf)),
                int(meta.get("stall", 0)), bool(meta.get("converged", False)))


_GUARD_POLICIES = ("off", "raise", "rollback", "reinit")
_GUARD_ALIASES = {"rollback-to-last-checkpoint": "rollback",
                  "reinit-affected-vertices": "reinit"}


def run_partitioner(
    algo: str,
    graph: Graph,
    k: int,
    *,
    seed: int = 0,
    n_blocks: int = 8,
    max_steps: Optional[int] = None,
    track_history: bool = True,
    dg: Optional[DeviceGraph] = None,
    mesh=None,
    assignment="contiguous",
    halo_threshold: float = DEFAULT_HALO_THRESHOLD,
    halo_granularity: str = "auto",
    hub_replication: bool = False,
    hub_quantile: float = 0.0,
    hub_target_coverage: Optional[float] = None,
    sync_every: int = 1,
    init_labels: Optional[np.ndarray] = None,
    init_probs: Optional[np.ndarray] = None,
    init_sharpen: float = 0.0,
    keep_probs: bool = False,
    trace=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    resume: bool = False,
    keep_checkpoints: int = 2,
    guard: str = "off",
    mode: str = "flat",
    coarse_n: Optional[int] = None,
    level_decay: Optional[float] = None,
    vcycle_sharpen: Optional[float] = None,
    **cfg_kwargs,
) -> PartitionResult:
    """Partition `graph` into `k` parts with the named algorithm.

    algo: any key in the algorithm registry — "revolver" | "spinner" |
    "restream" | "hash" | "range" out of the box (see
    `repro.core.registry.available_algorithms`). Extra kwargs flow into the
    algorithm's config dataclass (unknown keys raise TypeError).
    `sync_every` batches device->host score fetches (see module docstring).
    `init_labels` (and, for probs-carrying algorithms, `init_probs` /
    `init_sharpen`) warm-start the state from a previous assignment — the
    streaming subsystem's incremental repartitioning path. Carrying labels
    without LA state leaves the automata uniform, whose first exploration
    steps can wreck the carried assignment; `init_sharpen > 0` blends the
    automata toward the carried labels to prevent that (see
    `revolver_init_from_labels`). `keep_probs=True` returns the final LA
    probability tensor in `PartitionResult.probs` (needed to chain warm
    restarts); it is off by default because fetching [n_pad, k] floats to
    host is a real cost at production scale.

    `chunk_schedule="sharded"` (a config knob on every superstep algorithm)
    runs the superstep data-parallel over a 1-D ``("blocks",)`` mesh —
    `mesh` selects it (default: all visible devices, see `make_blocks_mesh`);
    a passed `dg` is aligned and placed onto the mesh if it is not already a
    `ShardedDeviceGraph`. `chunk_schedule="halo"` is the sharded schedule
    with the full label all-gather replaced by the precomputed
    boundary-block exchange (`repro.core.halo`; `halo_threshold` sets the
    coverage above which it falls back to the full gather). `assignment`
    selects the block->shard mapping ("contiguous" | "locality" | explicit
    permutation, see `shard_device_graph`) — locality co-location shrinks
    the halo, making the exchanged traffic proportional to partition
    quality. Returned labels (and probs) are always in original vertex
    order, whatever the assignment. `chunk_schedule="async"` is the halo
    schedule with the exchange overlapped onto each shard's interior block
    scan (the runner reorders blocks interior-first to widen the overlap
    window); `staleness_bound=0` (config default) refreshes the halo every
    superstep and stays bit-identical to "halo", while `staleness_bound=s`
    lets shards reuse a stale tail for up to `s` supersteps between
    refreshes — see `docs/async-superstep.md`.

    `mode="vcycle"` runs the METIS-style multilevel V-cycle
    (`repro.core.multilevel`): coarsen by heavy-edge matching down to
    `coarse_n` vertices, partition the coarsest graph to score-stall
    convergence, then uncoarsen level by level with `init_from_labels`
    warm starts under shrinking per-level superstep budgets (the finest
    level is capped at `level_decay * max_steps`; probs-carrying rules
    sharpen the projected labels by `vcycle_sharpen`). The schedule/mesh/assignment knobs apply to the
    finest level only; the V-cycle builds its own per-level layouts, so it
    is incompatible with a passed `dg`, warm-start args, checkpointing, and
    the state guard. See `docs/multilevel.md`.

    `halo_granularity` ("auto" | "block" | "vertex") picks the halo
    exchange unit: whole boundary blocks, or the exact per-vertex need
    lists moved by all-to-all with label-valued fields on an int8 wire
    (`repro.core.halo`; "auto" takes whichever moves fewer elements).
    `hub_replication=True` mirrors the top-degree vertices into every
    shard's replicated buffer region and reconciles their labels each
    superstep by a global weighted vote (`hub_quantile` /
    `hub_target_coverage` size the hub set, see `HubConfig`). On the
    sequential schedule hub replication runs the same plan on a 1-shard
    spec — the oracle trajectory the sharded hub mode is checked against;
    it is incompatible with `chunk_schedule="sharded"` (the full gather
    already replicates everything).

    `trace` (a `repro.obs.Tracer`; default off) records the run into a
    perfetto-exportable trace: a "run-partitioner" root span, layout build,
    one span per superstep, the windowed device syncs, recompile events,
    and per-superstep counter series (`local_edges`, `max_norm_load`,
    `migrations`) that ride the existing `sync_every` drain windows — the
    traced loop issues exactly the same blocking device fetches as the
    untraced one, and with tracing off results are bit-identical (see
    `docs/observability.md`).

    Crash safety (see `docs/fault-tolerance.md`): `checkpoint_dir` +
    `checkpoint_every=N` snapshot the full algorithm state (every state
    field, in original vertex order, plus the host-side score-stall
    counters) at the first drain window N or more supersteps after the last
    save — the state fetch rides the window's existing bundled
    `jax.device_get` (zero additional blocking fetches) and the disk write
    is async. `resume=True` restores the newest usable checkpoint (corrupt
    ones are skipped) and continues; a killed-and-resumed run is
    bit-identical to an uninterrupted one at the same arguments, including
    resuming on a different device count (sequential schedule; the sharded
    trajectory is device-count-specific, so its kill-resume exactness holds
    at an unchanged count and a count change matches a planned
    save/restore/continue migration). `keep_checkpoints` bounds the
    checkpoints kept on disk. `guard` checks state sanity (finite probs,
    in-range labels) at each drain window: "off" (default) | "raise" |
    "rollback"/"rollback-to-last-checkpoint" | "reinit"/
    "reinit-affected-vertices".
    """
    t0 = time.time()
    if sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {sync_every}")
    guard = _GUARD_ALIASES.get(guard, guard)
    if guard not in _GUARD_POLICIES:
        raise ValueError(
            f"unknown guard policy {guard!r}; expected one of "
            f"{_GUARD_POLICIES} (or a long alias "
            f"{tuple(_GUARD_ALIASES)})")
    if checkpoint_every < 0:
        raise ValueError(
            f"checkpoint_every must be >= 0, got {checkpoint_every}")
    if checkpoint_dir is None and (checkpoint_every > 0 or resume):
        raise ValueError(
            "checkpoint_every/resume need a checkpoint_dir")
    if guard == "rollback" and checkpoint_dir is None:
        raise ValueError("guard='rollback' needs a checkpoint_dir")
    algorithm = get_algorithm(algo)
    static = isinstance(algorithm, StaticAlgorithm)
    schedule = cfg_kwargs.get("chunk_schedule")
    sharded = schedule in ("sharded", "halo", "async")
    if mesh is not None and not sharded:
        raise ValueError(
            "mesh is only meaningful with chunk_schedule='sharded'/'halo'/"
            "'async'")
    if not sharded and not (isinstance(assignment, str)
                            and assignment == "contiguous"):
        raise ValueError(
            "assignment is only meaningful with chunk_schedule="
            "'sharded'/'halo'/'async'")
    if halo_granularity not in ("auto", "block", "vertex"):
        raise ValueError(
            f"halo_granularity={halo_granularity!r} is not one of "
            "('auto', 'block', 'vertex')")
    if halo_granularity != "auto" and schedule not in ("halo", "async"):
        raise ValueError(
            "halo_granularity is only meaningful with chunk_schedule="
            "'halo'/'async'")
    if not hub_replication and (hub_quantile or hub_target_coverage is not None):
        raise ValueError(
            "hub_quantile/hub_target_coverage need hub_replication=True")
    if hub_replication and schedule == "sharded":
        raise ValueError(
            "hub_replication is incompatible with chunk_schedule='sharded' "
            "(the full gather already replicates every vertex); use "
            "chunk_schedule='halo' or the sequential schedule")
    hubs = (HubConfig(quantile=hub_quantile,
                      target_coverage=hub_target_coverage)
            if hub_replication else None)
    if static and cfg_kwargs:
        raise TypeError(f"{algo!r} runs no supersteps; it takes no config kwargs")
    if static and (checkpoint_dir is not None or guard != "off"):
        raise TypeError(
            f"{algo!r} runs no supersteps; checkpointing and the state guard "
            "are meaningless")
    if mode not in ("flat", "vcycle"):
        raise ValueError(f"mode={mode!r} is not one of ('flat', 'vcycle')")
    if mode != "vcycle" and (coarse_n is not None or level_decay is not None
                             or vcycle_sharpen is not None):
        raise ValueError(
            "coarse_n/level_decay/vcycle_sharpen are only meaningful with "
            "mode='vcycle'")
    if mode == "vcycle":
        if static:
            raise TypeError(
                f"{algo!r} runs no supersteps; mode='vcycle' refines through "
                "warm starts")
        if checkpoint_dir is not None or resume or guard != "off":
            raise ValueError(
                "mode='vcycle' is incompatible with checkpointing/resume/"
                "guard; its per-level runs are short — checkpoint a flat "
                "refinement from init_labels instead")
        if init_labels is not None or init_probs is not None or init_sharpen:
            raise ValueError(
                "mode='vcycle' derives its warm starts from the coarse "
                "levels; init_labels/init_probs/init_sharpen cannot be "
                "passed in")
        if dg is not None:
            raise ValueError(
                "mode='vcycle' builds its own per-level device layouts; "
                "dg= cannot be passed in")
        from repro.core import multilevel

        return multilevel.run_vcycle(
            algo, graph, k, seed=seed, n_blocks=n_blocks,
            max_steps=max_steps, track_history=track_history, mesh=mesh,
            assignment=assignment, halo_threshold=halo_threshold,
            halo_granularity=halo_granularity,
            hub_replication=hub_replication, hub_quantile=hub_quantile,
            hub_target_coverage=hub_target_coverage, sync_every=sync_every,
            keep_probs=keep_probs, trace=trace, coarse_n=coarse_n,
            level_decay=level_decay, vcycle_sharpen=vcycle_sharpen,
            cfg_kwargs=cfg_kwargs)
    tracer = trace if trace is not None else obs.NULL_TRACER
    with obs.use(tracer), \
            tracer.span("run-partitioner", algo=algo, k=k,
                        schedule=schedule or "sequential",
                        n=graph.n, m=graph.m):
        result = _run_partitioner_traced(
            tracer, algorithm, static, schedule, sharded,
            algo, graph, k, t0,
            seed=seed, n_blocks=n_blocks, max_steps=max_steps,
            track_history=track_history, dg=dg, mesh=mesh,
            assignment=assignment, halo_threshold=halo_threshold,
            halo_granularity=halo_granularity, hubs=hubs,
            sync_every=sync_every, init_labels=init_labels,
            init_probs=init_probs, init_sharpen=init_sharpen,
            keep_probs=keep_probs, checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, resume=resume,
            keep_checkpoints=keep_checkpoints, guard=guard,
            cfg_kwargs=cfg_kwargs)
    if tracer.enabled:
        # run manifest: trace_report --validate checks one superstep span
        # per executed step against this (resumed steps ran in an earlier
        # process — only the steps executed here have spans)
        tracer.meta.setdefault("runs", []).append({
            "algo": algo, "k": k, "schedule": schedule or "sequential",
            "steps": result.steps - result.resumed_from})
    return result


def _run_partitioner_traced(
    tracer, algorithm, static, schedule, sharded,
    algo: str, graph: Graph, k: int, t0: float, *,
    seed, n_blocks, max_steps, track_history, dg, mesh, assignment,
    halo_threshold, halo_granularity, hubs,
    sync_every, init_labels, init_probs, init_sharpen,
    keep_probs, checkpoint_dir, checkpoint_every, resume, keep_checkpoints,
    guard, cfg_kwargs,
) -> PartitionResult:
    """Body of `run_partitioner`, running under `obs.use(tracer)` inside the
    root span (split out so the traced scope covers every early return)."""
    with tracer.span("prepare-layout", schedule=schedule or "sequential"):
        if sharded:
            halo = schedule in ("halo", "async")
            if mesh is None and isinstance(dg, ShardedDeviceGraph):
                mesh = dg.mesh
            if mesh is None:
                from repro.launch.mesh import make_blocks_mesh

                mesh = make_blocks_mesh()
            if dg is None:
                dg = prepare_sharded_device_graph(
                    graph, mesh, n_blocks=n_blocks, assignment=assignment,
                    halo=halo, halo_threshold=halo_threshold,
                    halo_granularity=halo_granularity, hubs=hubs)
                if schedule == "async":
                    # interior-first storage order: pull each shard's
                    # interior blocks to the front so the phase-1 overlap
                    # window (interior_split) reaches min(interior_counts);
                    # boundary-ness only depends on ownership + hub set, so
                    # one rebuild with the composed permutation converges
                    order = interior_first_order(dg.halo)
                    if order is not None:
                        perm = (np.asarray(dg.block_perm)[order]
                                if dg.block_perm is not None else order)
                        dg = prepare_sharded_device_graph(
                            graph, mesh, n_blocks=n_blocks, assignment=perm,
                            halo=True, halo_threshold=halo_threshold,
                            halo_granularity=halo_granularity, hubs=hubs)
            elif not isinstance(dg, ShardedDeviceGraph):
                plain = dg
                dg = shard_device_graph(dg, mesh, assignment=assignment,
                                        halo=halo, halo_threshold=halo_threshold,
                                        halo_granularity=halo_granularity,
                                        hubs=hubs)
                if schedule == "async":
                    order = interior_first_order(dg.halo)
                    if order is not None:
                        perm = (np.asarray(dg.block_perm)[order]
                                if dg.block_perm is not None else order)
                        dg = shard_device_graph(
                            plain, mesh, assignment=perm, halo=True,
                            halo_threshold=halo_threshold,
                            halo_granularity=halo_granularity, hubs=hubs)
            else:
                if not (isinstance(assignment, str)
                        and assignment == "contiguous"):
                    # a placed layout's assignment is baked into its storage
                    # order — silently running the contiguous layout here would
                    # fake locality measurements
                    raise ValueError(
                        "assignment cannot be applied to a pre-built "
                        "ShardedDeviceGraph; pass assignment= to "
                        "shard_device_graph / prepare_sharded_device_graph "
                        "when building the layout")
                if halo and dg.halo is None:
                    dg = attach_halo(dg, halo_threshold,
                                     halo_granularity=halo_granularity,
                                     hubs=hubs)
        elif dg is None:
            dg = prepare_device_graph(graph, n_blocks=n_blocks)
    if tracer.enabled and sharded:
        # static per-run exchange gauges from the precomputed plan — what
        # each superstep's gather moves, without touching the device
        n_fields = 1 if static else len(algorithm.vertex_fields)
        if dg.halo is not None:
            spec = dg.halo
            # per-field wire width: label-valued fields ride the int8 wire
            # on the per-vertex exchange (exact for k <= 127), everything
            # else moves at storage width
            if static:
                wire_sum = 4 * n_fields
            else:
                wire_sum = sum(
                    spec.wire_bytes_per_elem(
                        k, f in algorithm.wire_int8_fields)
                    for f in algorithm.vertex_fields)
            tracer.counter("halo_b_max", spec.b_max)
            tracer.counter("halo_h_max", spec.h_max)
            tracer.counter("halo_coverage", spec.coverage)
            if schedule == "async":
                # trace_report --validate requires the overlap span pair
                # for async runs unless the plan fell back to the full
                # gather (no interior scan exists to overlap with)
                if spec.fallback:
                    tracer.meta["async_fallback"] = True
                tracer.counter("interior_split", spec.interior_split)
            tracer.counter(
                "gathered_bytes_halo",
                spec.gathered_elems_per_device() * wire_sum)
            tracer.counter(
                "gathered_bytes_full",
                spec.full_gather_elems_per_device() * 4 * n_fields)
            if spec.granularity == "vertex" and not spec.fallback:
                tracer.counter(
                    "pervertex_halo_bytes",
                    spec.gathered_elems_per_device() * wire_sum)
            tracer.counter("hub_count", spec.n_hubs)
            if spec.n_hubs:
                tracer.counter(
                    "replica_vote_bytes",
                    spec.hub_sync_elems_per_device(k, n_fields) * 4)
        else:
            n_shards = int(dg.mesh.devices.size)
            per_dev = (n_shards - 1) * (dg.n_blocks // n_shards) * dg.block_v
            tracer.counter("gathered_bytes_full", per_dev * 4 * n_fields)
    key = jax.random.PRNGKey(seed)

    if static:
        if init_labels is not None or init_probs is not None or init_sharpen:
            raise TypeError(f"{algo!r} is stateless; warm-start args are meaningless")
        labels = jax.numpy.pad(algorithm.partition(graph.n, k),
                               (0, dg.n_pad - graph.n))
        le = float(local_edges(labels, dg.dir_src, dg.dir_dst))
        ml = float(max_normalized_load(labels[: graph.n], dg.deg_out[: graph.n], k))
        if tracer.enabled:
            tracer.counter("local_edges", le, step=0)
            tracer.counter("max_norm_load", ml, step=0)
        return PartitionResult(
            algo=algo, k=k, labels=np.asarray(labels[: graph.n]), steps=0,
            converged=True, local_edges=le, max_norm_load=ml,
            history={"local_edges": [le], "max_norm_load": [ml], "score": [0.0]},
            wall_s=time.time() - t0,
        )

    cfg = _make_cfg(algorithm.config_cls, k, max_steps, cfg_kwargs)
    if tracer.enabled and algorithm.run_counters is not None:
        for name, value in algorithm.run_counters(cfg, dg).items():
            tracer.counter(name, value)
    if not algorithm.supports_probs:
        if init_probs is not None:
            raise TypeError(
                f"{algo!r} has no LA state; init_probs/init_sharpen are meaningless")
        if init_sharpen:
            raise TypeError(
                f"{algo!r} has no LA state; init_probs/init_sharpen are meaningless")
    if init_labels is not None:
        if algorithm.init_from_labels is None:
            raise TypeError(f"{algo!r} does not support warm starts")
        if algorithm.supports_probs:
            state = algorithm.init_from_labels(dg, cfg, key, init_labels,
                                               probs=init_probs,
                                               prob_sharpen=init_sharpen)
        else:
            state = algorithm.init_from_labels(dg, cfg, key, init_labels)
    else:
        if init_probs is not None:
            raise TypeError("init_probs requires init_labels")
        if init_sharpen:
            raise TypeError("init_sharpen requires init_labels")
        state = algorithm.init(dg, cfg, key)
    if sharded:
        state = engine.place_state(algorithm, state, dg)
    seq_halo = None
    if hubs is not None and not sharded:
        # sequential hub oracle: run the same hub plan on a 1-shard spec —
        # the reference trajectory the sharded hub mode is checked against
        # bit-exactly (quantile hub selection is shard-count independent)
        seq_halo = build_halo_spec(
            np.asarray(dg.blk_dst), np.asarray(dg.blk_w), 1, dg.block_v,
            threshold=halo_threshold, hubs=hubs,
            deg=np.asarray(dg.deg_out), vmask=np.asarray(dg.vmask),
            blk_row=np.asarray(dg.blk_row))
    # async staleness driver: the engine only distinguishes fresh (cache is
    # None) from stale (reuse the returned tail); the *policy* lives here.
    # Refresh when the bound expires (g % (s+1) == 0 keeps any tail at most
    # staleness_bound supersteps old) and on every checkpoint window (g %
    # sync_every == 0), so a snapshot is always taken downstream of a fresh
    # exchange and kill-and-resume replays bit-identically even at s >= 1
    # (a resumed run starts with cache=None — the same forced refresh).
    async_box = {"cache": None, "g": None, "last_refresh": 0}
    if schedule == "async":
        staleness = getattr(cfg, "staleness_bound", 0)
        ckpt_windows = checkpoint_dir is not None and checkpoint_every > 0

        def base_step(s):
            if async_box["g"] is None:   # first call: resume-aware origin
                async_box["g"] = start_step
                async_box["last_refresh"] = start_step
            g = async_box["g"]
            refresh = (async_box["cache"] is None
                       or staleness == 0
                       or g % (staleness + 1) == 0
                       or (ckpt_windows and g % sync_every == 0))
            if refresh:
                async_box["cache"] = None
                async_box["last_refresh"] = g
            s2, async_box["cache"] = engine.async_superstep(
                algorithm, dg, cfg, s, cache=async_box["cache"])
            if tracer.enabled:
                tracer.counter("halo_staleness",
                               float(g - async_box["last_refresh"]), step=g)
            async_box["g"] = g + 1
            return s2
    else:
        base_step = lambda s: engine.superstep(algorithm, dg, cfg, s,
                                               halo=seq_halo)

    # ---- crash safety: checkpoint manager + resume -----------------------
    ckpt = None
    if checkpoint_dir is not None:
        run_meta = {"kind": "partition", "algo": algo, "k": k, "n": graph.n,
                    "m": graph.m, "schedule": schedule or "sequential",
                    "seed": seed, "sync_every": sync_every,
                    "patience": cfg.patience}
        ckpt = _CheckpointManager(checkpoint_dir, checkpoint_every,
                                  keep_checkpoints, algorithm, dg, sharded,
                                  run_meta, tracer)
    start_step, start_prev_score, start_stall = 0, -np.inf, 0
    resumed_converged = False
    if resume:
        restored = ckpt.restore_latest(state)
        if restored is not None:
            (state, start_step, start_prev_score, start_stall,
             resumed_converged) = restored
            ckpt.last_saved = start_step
        # no checkpoint yet -> a fresh run (so the same command line works
        # for the first launch and every relaunch)

    history: Dict[str, List[float]] = {"local_edges": [], "max_norm_load": [], "score": []}
    # per-step metric arrays stay on device and are drained on the same
    # sync_every window as the scores — neither history tracking nor tracing
    # forces a host sync per superstep
    pending_le: List[jax.Array] = []
    pending_ml: List[jax.Array] = []
    pending_mig: List[jax.Array] = []
    step_ts: List[float] = []    # dispatch timestamp per buffered step, so
                                 # drained counters are back-dated to the
                                 # superstep that produced them
    drained = [0]                # global index of the next drained step

    if tracer.enabled:
        def step_fn(s):
            # labels are donated into the superstep — copy *before* dispatch
            # (the copy is enqueued ahead of the overwrite) to count
            # migrations as a device-side reduction drained with the window
            prev = jnp.copy(s.labels)
            s2 = base_step(s)
            pending_mig.append(jnp.sum((s2.labels != prev) & dg.vmask))
            return s2
    else:
        step_fn = base_step

    collect = track_history or tracer.enabled

    def on_step(s):
        # labels and the dir_*/deg arrays live in the same (possibly
        # locality-permuted) index space; the load metric uses the full
        # padded arrays because real vertices are not a prefix under a
        # permuted assignment (padding carries zero degree, so the value is
        # unchanged on contiguous layouts). Dispatch allocates the metrics'
        # device buffers, which can wait for the superstep to free its own:
        # the span shows that wait.
        with tracer.span("dispatch", what="metrics"):
            pending_le.append(local_edges(s.labels, dg.dir_src, dg.dir_dst))
            pending_ml.append(max_normalized_load(s.labels, dg.deg_out, k))
        if tracer.enabled:
            step_ts.append(tracer.now_us())

    def drain_metrics(dstate, loop_steps, prev_score, stall):
        # one bundled fetch per window, traced or not — the sync-count
        # contract pinned by tests/test_obs.py. Guard predicates and the
        # checkpoint snapshot ride the *same* device_get, so crash safety
        # adds zero blocking fetches.
        gsteps = start_step + loop_steps
        bundle = {"le": pending_le, "ml": pending_ml, "mig": pending_mig}
        if guard != "off":
            checks = {"labels": jnp.all(jnp.where(
                dg.vmask, (dstate.labels >= 0) & (dstate.labels < cfg.k), True))}
            if algorithm.supports_probs:
                checks["probs"] = jnp.all(jnp.isfinite(dstate.probs))
            bundle["guard"] = checks
        save_due = ckpt is not None and ckpt.due(gsteps) and not ckpt.busy()
        if save_due:
            bundle["ckpt"] = ckpt.device_tree(dstate)
        with tracer.span("device-sync", steps=len(pending_le), what="metrics"):
            fetched = jax.device_get(bundle)
        le_v, ml_v, mig_v = fetched["le"], fetched["ml"], fetched["mig"]
        if track_history:
            history["local_edges"].extend(float(x) for x in le_v)
            history["max_norm_load"].extend(float(x) for x in ml_v)
        if tracer.enabled:
            for i in range(len(le_v)):
                step = drained[0] + i
                ts = step_ts[i] if i < len(step_ts) else None
                tracer.counter("local_edges", float(le_v[i]), step=step, ts=ts)
                tracer.counter("max_norm_load", float(ml_v[i]), step=step, ts=ts)
                if i < len(mig_v):
                    tracer.counter("migrations", float(mig_v[i]), step=step, ts=ts)
        drained[0] += len(le_v)
        pending_le.clear()
        pending_ml.clear()
        pending_mig.clear()
        step_ts.clear()

        bad = [name for name, ok in fetched.get("guard", {}).items()
               if not bool(ok)]
        if bad:
            return _handle_guard_violation(bad, gsteps)
        if save_due:
            ckpt.save(gsteps, fetched["ckpt"], prev_score, stall)
        return None

    def _handle_guard_violation(bad, gsteps):
        # never checkpoint a corrupt state — the save for this window is
        # skipped no matter which recovery policy runs
        desc = ("non-finite probs" if "probs" in bad
                else "out-of-range labels")
        tracer.instant("guard-violation", step=gsteps, checks=",".join(bad))
        tracer.counter("guard_violations", 1)
        _log.warning("state guard tripped at step %d: %s", gsteps, desc)
        if guard == "raise":
            raise PartitionStateError(
                f"state guard tripped at step {gsteps}: {desc}")
        if guard == "rollback":
            restored = ckpt.restore_latest(state)
            if restored is None:
                raise PartitionStateError(
                    f"state guard tripped at step {gsteps} ({desc}) and no "
                    f"usable checkpoint exists in {checkpoint_dir} to roll "
                    f"back to")
            r_state, r_step, r_prev, r_stall, _ = restored
            tracer.instant("rollback", from_step=gsteps, to_step=r_step)
            _log.warning("rolled back to checkpoint step %d", r_step)
            # a cached halo tail was built from the now-discarded trajectory
            async_box["cache"] = None
            # loop step counting continues forward; only the halting state
            # and device state rewind
            return {"state": r_state, "prev_score": r_prev, "stall": r_stall}
        # reinit-affected-vertices: repair device-side — clamp labels into
        # range, rebuild loads from the repaired labels, and reset any
        # non-finite prob rows to uniform
        s = state_box[0]
        labels = jnp.clip(s.labels, 0, cfg.k - 1).astype(s.labels.dtype)
        fix = {"labels": labels}
        if hasattr(s, "loads"):
            fix["loads"] = engine.loads_from_labels(dg, cfg.k, labels)
        if algorithm.supports_probs:
            flat = s.probs.reshape(dg.n_pad, cfg.k)
            row_ok = jnp.all(jnp.isfinite(flat), axis=1, keepdims=True)
            uniform = jnp.full_like(flat, 1.0 / cfg.k)
            fix["probs"] = jnp.where(row_ok, flat, uniform).reshape(
                s.probs.shape)
        tracer.instant("reinit", step=gsteps)
        _log.warning("reinitialized affected vertices at step %d", gsteps)
        async_box["cache"] = None   # tail may carry the corrupt labels
        return {"state": s._replace(**fix), "prev_score": -np.inf, "stall": 0}

    # the reinit path needs the loop's current state object (drain_metrics
    # receives it); a one-slot box keeps the closure simple
    state_box = [state]

    def on_drain(dstate, loop_steps, prev_score, stall):
        state_box[0] = dstate
        return drain_metrics(dstate, loop_steps, prev_score, stall)

    need_drain = collect or ckpt is not None or guard != "off"
    remaining = cfg.max_steps - start_step
    if resumed_converged or remaining <= 0:
        # nothing left to run: the checkpoint already recorded the outcome
        # (hitting max_steps without a stall is converged=False, same as an
        # uninterrupted run)
        loop_steps, converged = 0, resumed_converged
    else:
        state, loop_steps, converged = run_convergence_loop(
            step_fn, state,
            max_steps=remaining, patience=cfg.patience, theta=cfg.theta,
            sync_every=sync_every,
            on_step=on_step if collect else None,
            on_score=history["score"].append if track_history else None,
            on_drain=on_drain if need_drain else None,
            tracer=tracer,
            step0=start_step, prev_score=start_prev_score, stall=start_stall,
        )
    if ckpt is not None:
        ckpt.finish()
    steps = start_step + loop_steps

    # final fetch: one device_get for everything still needed. With history
    # tracking on, the final step's local_edges/max_norm_load already came
    # back through the windowed drain — reuse them instead of issuing two
    # extra blocking float(...) syncs after convergence. Labels/probs cross
    # the API boundary in original vertex order (identity gather on
    # unpermuted layouts).
    fetch = {"labels": vertices_to_original(dg, state.labels)[: graph.n]}
    if track_history and history["local_edges"]:
        le, ml = history["local_edges"][-1], history["max_norm_load"][-1]
    elif tracer.enabled and tracer.series.get("local_edges"):
        le = tracer.series["local_edges"][-1][1]
        ml = tracer.series["max_norm_load"][-1][1]
    else:
        fetch["le"] = local_edges(state.labels, dg.dir_src, dg.dir_dst)
        fetch["ml"] = max_normalized_load(state.labels, dg.deg_out, k)
    if keep_probs and algorithm.supports_probs:
        flat = state.probs.reshape(dg.n_pad, cfg.k)
        fetch["probs"] = vertices_to_original(dg, flat).reshape(
            dg.n_blocks, dg.block_v, cfg.k)
    with tracer.span("device-sync", what="result"):
        fetched = jax.device_get(fetch)
    if "le" in fetched:
        le, ml = float(fetched["le"]), float(fetched["ml"])
    return PartitionResult(
        algo=algo, k=k, labels=np.asarray(fetched["labels"]), steps=steps,
        converged=converged, local_edges=le, max_norm_load=ml, history=history,
        wall_s=time.time() - t0,
        probs=np.asarray(fetched["probs"]) if "probs" in fetched else None,
        resumed_from=start_step,
    )
