"""Chip smoke test: the Revolver partitioner end to end on a TPU.

  python chip_smoke.py             # one chip: WIKI main path + kernel leg
  python chip_smoke.py --chips 4   # four chips: sharded vs halo schedules

Runs in one process and refuses anything but a TPU backend: there is no CPU
path. It builds the paper's WIKI graph at Table-I size (scale 1.0) from a
seed, partitions it through `repro.core.run_partitioner`, and checks every
result against plain numpy recomputations and the repo's own references.

* one chip: the default sequential schedule with the jnp kernels for 20
  supersteps, checked against numpy metrics and the hash baseline; then the
  Pallas kernels (`hist_impl`/`la_impl="pallas"`) against `kernels/ref.py`
  on one real edge chunk, and against the jnp path over 10 supersteps;
* four chips (`--chips 4`): the `"sharded"` (full label all-gather) and
  `"halo"` (per-shard exchange plan) schedules on a 4-device blocks mesh,
  whose labels must be bit-identical, with every edge slab spread over all
  four devices.

Every line but the last is a log; the times it prints are smoke timings of
one run, not benchmark numbers. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed;
any failed check raises, so the process exits nonzero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

K = 8
SEED = 0
MAIN_STEPS = 20
KERNEL_STEPS = 10
KERNEL_BLOCK_V = 2048     # the widest vertex block the edge kernel holds
MESH_CHIPS = 4
MESH_STEPS = 10
MESH_BLOCKS = 64
PARITY_TOL = 1e-5         # kernel vs reference histograms (superstep_bench)
QUALITY_TOL = 0.01        # pallas vs jnp end-to-end quality, relative
METRIC_TOL = 1e-5         # device f32 metric vs numpy float64 recomputation
TABLE1_WIKI = (1_790_000, 28_510_000)   # |V|, |E| (graphs/datasets.py)


def log(msg: str = "") -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    """Raise unless `ok`; log the passed check."""
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")
    log(f"  ok: {what}")


def require_tpu(devices) -> None:
    """Exit nonzero unless JAX's first device is a TPU."""
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU backend; JAX found platform "
            f"{platform!r} ({len(devices)} device(s)). There is no CPU path.")


def log_memory(devices) -> None:
    for d in devices:
        log(f"  {d}: memory_stats={d.memory_stats()}")


def numpy_quality(g, labels, k: int) -> tuple[float, float]:
    """(local_edges, max_norm_load) of `labels` recomputed from the host
    graph in float64: the share of directed edges inside one part, and the
    largest out-degree load over the ideal |E|/k."""
    import numpy as np

    src = np.repeat(np.arange(g.n), np.diff(g.row_ptr))
    local = float(np.mean(labels[src] == labels[g.col_idx]))
    loads = np.bincount(labels, weights=g.deg_out.astype(np.float64),
                        minlength=k)
    return local, float(loads.max() / (g.m / k))


def check_result(g, res, k: int, what: str) -> None:
    """Labels in range; the run's metrics equal the numpy recomputation."""
    labels = res.labels
    check(labels.shape == (g.n,) and labels.min() >= 0 and labels.max() < k,
          f"{what}: all {g.n} labels in [0, {k})")
    le, ml = numpy_quality(g, labels, k)
    log(f"  {what}: local_edges={res.local_edges!r} (numpy {le!r}) "
        f"max_norm_load={res.max_norm_load!r} (numpy {ml!r})")
    check(abs(res.local_edges - le) <= METRIC_TOL
          and abs(res.max_norm_load - ml) <= METRIC_TOL,
          f"{what}: local_edges/max_norm_load match numpy within "
          f"{METRIC_TOL}")


def superstep_times(tracer) -> tuple[float, float]:
    """(first superstep dispatch, mean start-to-start interval of the
    steady supersteps) in seconds, from the tracer's superstep spans. The
    first dispatch traces and compiles the jitted superstep; the intervals
    from the second step on cover dispatch, device time and the per-step
    metric drain."""
    spans = sorted((e for e in tracer.events
                    if e.get("ph") == "X" and e["name"] == "superstep"),
                   key=lambda e: e["ts"])
    starts = [e["ts"] for e in spans[1:]]
    steady = ((starts[-1] - starts[0]) / (len(starts) - 1) / 1e6
              if len(starts) > 1 else float("nan"))
    return spans[0]["dur"] / 1e6, steady


def build_graph():
    from repro.graphs import load_dataset

    t = time.perf_counter()
    g = load_dataset("WIKI", scale=1.0, seed=SEED)
    gen_s = time.perf_counter() - t
    log(f"WIKI scale=1.0 seed={SEED}: |V|={g.n} |E|={g.m} "
        f"symmetrized slots={g.num_sym_edges} "
        f"(Table I: {TABLE1_WIKI[0]} / {TABLE1_WIKI[1]}); "
        f"host generation {gen_s:.1f} s (smoke timing)")
    check(abs(g.n / TABLE1_WIKI[0] - 1) < 0.01
          and abs(g.m / TABLE1_WIKI[1] - 1) < 0.10,
          "graph is at Table-I WIKI size (|V| within 1%, |E| within 10%)")
    return g


def main_path(g) -> None:
    """Default sequential schedule, jnp kernels, 20 supersteps."""
    from repro import obs
    from repro.core import run_partitioner

    log(f"\n== main path: run_partitioner('revolver', k={K}, seed={SEED}, "
        f"max_steps={MAIN_STEPS}) ==")
    tracer = obs.Tracer()
    # patience=MAIN_STEPS: score-stall halting cannot stop the run before
    # the step budget, so the smoke always runs the same supersteps
    res = run_partitioner("revolver", g, K, seed=SEED, max_steps=MAIN_STEPS,
                          patience=MAIN_STEPS, trace=tracer)
    layout_s = sum(e["dur"] for e in tracer.events
                   if e.get("ph") == "X" and e["name"] == "prepare-layout")
    first_s, step_s = superstep_times(tracer)
    log(f"  smoke timings: layout build {layout_s / 1e6:.2f} s, first "
        f"superstep (trace+compile+dispatch) {first_s:.2f} s, steady "
        f"superstep {step_s * 1e3:.1f} ms, run wall {res.wall_s:.1f} s, "
        f"superstep compiles {len(tracer.recompiles)}")
    check(res.steps == MAIN_STEPS, f"{MAIN_STEPS} supersteps run")
    check_result(g, res, K, "revolver")
    hashed = run_partitioner("hash", g, K)
    log(f"  hash baseline: local_edges={hashed.local_edges!r} "
        f"max_norm_load={hashed.max_norm_load!r}")
    check(res.local_edges > hashed.local_edges,
          "revolver local_edges beats the hash baseline")


def kernel_leg(g) -> None:
    """Pallas kernels vs references: one real chunk, then 10 supersteps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (RevolverConfig, edge_histogram_jnp, local_edges,
                            max_normalized_load, prepare_device_graph,
                            revolver_init, revolver_superstep,
                            weighted_la_update)
    from repro.core.la import split_weights_and_signals
    from repro.kernels import ops, ref
    from repro.kernels.edge_phase import fused_edge_phase_pallas

    n_blocks = -(-g.n // KERNEL_BLOCK_V)
    t = time.perf_counter()
    dg = prepare_device_graph(g, n_blocks=n_blocks)
    jax.block_until_ready(dg.blk_dst)
    log(f"\n== kernel leg: n_blocks={dg.n_blocks} block_v={dg.block_v} "
        f"e_max={dg.e_max} (layout build {time.perf_counter() - t:.2f} s, "
        f"smoke timing) ==")
    check(dg.block_v <= KERNEL_BLOCK_V, f"block_v <= {KERNEL_BLOCK_V}")

    # -- direct: one real chunk, the fullest block -------------------------
    b = int(np.argmax(np.asarray(jnp.sum(dg.blk_w > 0, axis=1))))
    key = jax.random.PRNGKey(SEED)
    kl, km, ka, kf, kp = jax.random.split(key, 5)
    labels = jax.random.randint(kl, (dg.n_pad,), 0, K, dtype=jnp.int32)
    lam = jax.random.randint(km, (dg.n_pad,), 0, K, dtype=jnp.int32)
    actions = jax.random.randint(ka, (1, dg.block_v), 0, K, dtype=jnp.int32)
    feasible = (jax.random.uniform(kf, (1, K)) > 0.25).astype(jnp.float32)
    dst, row, w = (x[b:b + 1] for x in (dg.blk_dst, dg.blk_row, dg.blk_w))
    host = [np.asarray(x) for x in (dst, row, w, labels, lam, actions,
                                    feasible)]
    hist_jnp = jax.jit(edge_histogram_jnp, static_argnums=(3, 4))(
        row[0], labels[dst[0]], w[0], dg.block_v, K)
    for mode in ("self_lambda", "neighbor_lambda"):
        args = (dst, row, w, labels, lam, actions, feasible)
        hlo = fused_edge_phase_pallas.lower(
            *args, block_v=dg.block_v, k=K, weight_mode=mode).as_text()
        check("tpu_custom_call" in hlo,
              f"edge phase ({mode}) lowers to a compiled TPU kernel")
        hist, wacc = ops.fused_edge_phase(*args, block_v=dg.block_v, k=K,
                                          weight_mode=mode)
        hist_ref, wacc_ref = ref.fused_edge_phase_ref(
            *host, block_v=dg.block_v, k=K, weight_mode=mode)
        errs = (float(np.abs(np.asarray(hist[0]) - hist_ref[0]).max()),
                float(np.abs(np.asarray(wacc[0]) - wacc_ref[0]).max()),
                float(jnp.abs(hist[0] - hist_jnp).max()))
        log(f"  block {b} ({int((host[2] > 0).sum())} edges) {mode}: "
            f"max |hist - ref|={errs[0]!r} |w_acc - ref|={errs[1]!r} "
            f"|hist - jnp|={errs[2]!r}")
        check(max(errs) <= PARITY_TOL,
              f"fused edge phase ({mode}) == references within {PARITY_TOL}")

    probs = jax.random.dirichlet(kp, jnp.ones(K), (dg.block_v,))
    w_norm, r = split_weights_and_signals(wacc[0])
    hlo = jax.jit(ops.la_update, static_argnums=(3, 4)).lower(
        probs, w_norm, r, 1.0, 0.1).as_text()
    check("tpu_custom_call" in hlo, "LA update lowers to a compiled TPU kernel")
    la_err = float(jnp.abs(ops.la_update(probs, w_norm, r, 1.0, 0.1)
                           - weighted_la_update(probs, w_norm, r, 1.0,
                                                0.1)).max())
    log(f"  LA update on {dg.block_v} rows: max |pallas - jnp|={la_err!r}")
    check(la_err <= PARITY_TOL, f"LA update == jnp within {PARITY_TOL}")

    # -- end to end: jnp/jnp and pallas/pallas in lockstep from one seed ---
    cfgs = {impl: RevolverConfig(k=K, hist_impl=impl, la_impl=impl)
            for impl in ("jnp", "pallas")}
    states = {impl: revolver_init(dg, cfg, key) for impl, cfg in cfgs.items()}
    times = {impl: [] for impl in cfgs}
    first_diverged = None
    for step in range(1, KERNEL_STEPS + 1):
        for impl, cfg in cfgs.items():
            t = time.perf_counter()
            states[impl] = revolver_superstep(dg, cfg, states[impl])
            jax.block_until_ready(states[impl].labels)
            times[impl].append(time.perf_counter() - t)
        n_diff = int(jnp.sum(states["jnp"].labels != states["pallas"].labels))
        if n_diff and first_diverged is None:
            first_diverged = step
    for impl in cfgs:
        log(f"  {impl:6s} smoke timings: first superstep (compile+run) "
            f"{times[impl][0]:.2f} s, steady superstep "
            f"{np.mean(times[impl][1:]) * 1e3:.1f} ms")
    quality = {}
    for impl, st in states.items():
        lbl = np.asarray(st.labels)[:g.n]
        check(lbl.min() >= 0 and lbl.max() < K,
              f"{impl} leg: all labels in [0, {K})")
        le = float(local_edges(st.labels, dg.dir_src, dg.dir_dst))
        ml = float(max_normalized_load(st.labels, dg.deg_out, K))
        le_np, ml_np = numpy_quality(g, lbl, K)
        log(f"  {impl} after {KERNEL_STEPS} supersteps: local_edges={le!r} "
            f"(numpy {le_np!r}) max_norm_load={ml!r} (numpy {ml_np!r})")
        check(abs(le - le_np) <= METRIC_TOL and abs(ml - ml_np) <= METRIC_TOL,
              f"{impl} leg metrics match numpy within {METRIC_TOL}")
        quality[impl] = (le, ml)
    n_diff = int(np.sum(np.asarray(states["jnp"].labels)[:g.n]
                        != np.asarray(states["pallas"].labels)[:g.n]))
    log(f"  labels differing after {KERNEL_STEPS} supersteps: {n_diff} of "
        f"{g.n}; first superstep with a difference: {first_diverged}")
    (le_j, ml_j), (le_p, ml_p) = quality["jnp"], quality["pallas"]
    check(abs(le_p - le_j) <= QUALITY_TOL * le_j
          and abs(ml_p - ml_j) <= QUALITY_TOL * ml_j,
          f"pallas quality within {QUALITY_TOL:.0%} of jnp")


def mesh_leg(g, devices) -> None:
    """Sharded vs halo schedules on a 4-device blocks mesh."""
    import jax
    import numpy as np

    from repro.core import prepare_sharded_device_graph, run_partitioner
    from repro.launch.mesh import make_blocks_mesh

    mesh = make_blocks_mesh(MESH_CHIPS)
    t = time.perf_counter()
    # halo_threshold=2.0: WIKI's 30% global edges put its exchange plan
    # above the default fallback coverage, which would run the full gather
    # under "halo" too; the smoke needs the exchange itself on the wire
    sdg = prepare_sharded_device_graph(
        g, mesh, n_blocks=MESH_BLOCKS, assignment="locality", halo=True,
        halo_threshold=2.0)
    jax.block_until_ready(sdg.blk_dst)
    spec = sdg.halo
    log(f"\n== mesh leg: {MESH_CHIPS} devices, n_blocks={sdg.n_blocks} "
        f"block_v={sdg.block_v} e_max={sdg.e_max}; halo plan "
        f"granularity={spec.granularity} coverage={spec.coverage!r} "
        f"fallback={spec.fallback} b_max={spec.b_max} h_max={spec.h_max}; "
        f"block permutation {'on' if sdg.block_perm else 'identity'} "
        f"(layout build {time.perf_counter() - t:.1f} s, smoke timing) ==")
    check(not spec.fallback, "halo plan exchanges (no full-gather fallback)")
    mesh_devices = set(mesh.devices.flat)
    for name in ("blk_dst", "blk_row", "blk_w"):
        arr = getattr(sdg, name)
        shard_devs = [s.device for s in arr.addressable_shards]
        check(arr.sharding.device_set == mesh_devices
              and sorted(d.id for d in shard_devs)
              == sorted(d.id for d in mesh_devices),
              f"{name} has one shard on each of the {MESH_CHIPS} devices")

    results = {}
    for schedule in ("sharded", "halo"):
        t = time.perf_counter()
        res = run_partitioner("revolver", g, K, seed=SEED,
                              max_steps=MESH_STEPS, patience=MESH_STEPS,
                              dg=sdg, chunk_schedule=schedule)
        log(f"  {schedule}: {res.steps} supersteps in "
            f"{time.perf_counter() - t:.1f} s incl. compile (smoke timing)")
        check(res.steps == MESH_STEPS, f"{schedule}: {MESH_STEPS} supersteps")
        check_result(g, res, K, schedule)
        results[schedule] = res
    log("  device memory after both schedules:")
    log_memory(devices[:MESH_CHIPS])
    n_diff = int(np.sum(results["halo"].labels != results["sharded"].labels))
    log(f"  labels differing halo vs sharded: {n_diff}")
    check(n_diff == 0, "halo labels bit-identical to sharded")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, MESH_CHIPS), default=1,
                    help=f"1: main path + kernel leg on one chip; "
                         f"{MESH_CHIPS}: only the sharded/halo mesh path")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    require_tpu(devices)
    from repro.utils.compile_cache import configure_compile_cache

    dev = devices[0]
    log(f"jax {jax.__version__}; platform={dev.platform} "
        f"kind={dev.device_kind!r} count={len(devices)}; compile cache "
        f"{configure_compile_cache()}")
    log_memory(devices)
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {len(devices)}")
    g = build_graph()
    if args.chips == 1:
        main_path(g)
        kernel_leg(g)
    else:
        mesh_leg(g, devices)
    log("\nall checks passed")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
