"""Superstep throughput baseline: the repo's first perf-trajectory artifact.

Times Revolver supersteps-per-second and edges-per-second for every
``{hist_impl} x {la_impl}`` combination on Table-I generator datasets, plus
a kernel-level comparison of the fused dual-histogram edge phase against two
independent ``edge_histogram`` launches, and writes everything to
``BENCH_superstep.json`` so later PRs have a measured baseline to hold
against.

Five hard gates (process exits nonzero on failure — the CI regression check):
  * superstep parity — ``hist_impl="pallas"`` must reproduce the
    ``"jnp"`` partition at fixed seed within the score tolerance;
  * kernel parity — the fused kernel's histograms must match the two-call
    path within float tolerance;
  * algorithm quality — every engine algorithm in the registry is run at a
    fixed step budget against the hash baseline, and the restream rule's
    edge locality must stay within ``RESTREAM_GATE`` (0.90) of revolver's
    (the third-partitioner acceptance bar; see core/README.md);
  * checkpoint overhead — drain-window checkpointing must keep
    ``CHECKPOINT_GATE`` (0.95) of the plain steps/s and leave the final
    labels bit-identical (docs/fault-tolerance.md);
  * V-cycle — ``mode="vcycle"`` must reach ``VCYCLE_QUALITY_GATE`` (0.97)
    of flat refinement's edge locality at the same score-stall halting
    while spending at most ``VCYCLE_STEPS_GATE`` (0.5) of flat's
    supersteps at the fine level (docs/multilevel.md).

On the CPU backend the Pallas paths execute in interpret mode, so their
wall-clock is a harness/correctness sanity check, not TPU perf (see
kernel_bench.py); the numbers that matter for the trajectory are the XLA-path
throughputs and the fused-vs-two-call ratio measured under the same mode.

  PYTHONPATH=src python benchmarks/superstep_bench.py            # full
  PYTHONPATH=src python benchmarks/superstep_bench.py --quick    # CI smoke
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device_graph import prepare_device_graph
from repro.core.revolver import RevolverConfig, revolver_init, revolver_superstep
from repro.graphs import load_dataset
from repro.utils.provenance import bench_provenance

IMPLS = ("jnp", "pallas")
PARITY_TOL = 1e-5
RESTREAM_GATE = 0.90   # restream edge locality vs revolver, fixed budget
CHECKPOINT_GATE = 0.95  # steps/s with checkpointing on vs off (<=5% overhead)
VCYCLE_QUALITY_GATE = 0.97  # vcycle local_edges vs flat at score-stall
VCYCLE_STEPS_GATE = 0.5     # vcycle fine-level supersteps vs flat's total


def _algo_quality(g, dg, k: int, *, steps: int, seed: int) -> list[dict]:
    """Fixed-budget quality sweep across the algorithm registry.

    Every engine-driven algorithm runs `steps` supersteps (halting
    disabled) on the shared device graph; the static hash baseline anchors
    the no-learning floor. Rows feed BENCH_superstep.json so the
    cross-algorithm trajectory is versioned alongside the kernel numbers.
    """
    from repro.core.registry import superstep_algorithms
    from repro.core.runner import run_partitioner

    rh = run_partitioner("hash", g, k)
    rows = [{"algo": "hash", "steps": 0, "local_edges": rh.local_edges,
             "max_norm_load": rh.max_norm_load}]
    for name in superstep_algorithms():
        r = run_partitioner(name, g, k, seed=seed, max_steps=steps,
                            patience=10_000, track_history=False, dg=dg)
        rows.append({"algo": name, "steps": r.steps,
                     "local_edges": r.local_edges,
                     "max_norm_load": r.max_norm_load})
    by_algo = {row["algo"]: row for row in rows}
    ratio = (by_algo["restream"]["local_edges"]
             / max(by_algo["revolver"]["local_edges"], 1e-9))
    for row in rows:
        row["restream_vs_revolver"] = ratio
        row["pass"] = bool(ratio >= RESTREAM_GATE)
    return rows


def _vcycle_compare(g, k: int, *, seed: int) -> dict:
    """Flat refinement vs the multilevel V-cycle at the same score-stall
    halting (docs/multilevel.md). Both runs use the paper's convergence
    settings; the V-cycle must land within ``VCYCLE_QUALITY_GATE`` of the
    flat run's edge locality while spending at most ``VCYCLE_STEPS_GATE``
    of its supersteps at the fine level — the full-resolution steps that
    dominate wall-clock at production scale."""
    from repro.core.runner import run_partitioner

    flat = run_partitioner("revolver", g, k, seed=seed, track_history=False)
    vc = run_partitioner("revolver", g, k, seed=seed, mode="vcycle",
                         track_history=False)
    quality_ratio = vc.local_edges / max(flat.local_edges, 1e-9)
    steps_ratio = vc.steps / max(flat.steps, 1)
    return {
        "n": g.n,
        "m": g.m,
        "flat_local_edges": flat.local_edges,
        "flat_steps": flat.steps,
        "flat_supersteps_per_s": flat.steps / max(flat.wall_s, 1e-9),
        "vcycle_local_edges": vc.local_edges,
        "vcycle_fine_steps": vc.steps,
        "vcycle_supersteps_per_s": vc.steps / max(vc.wall_s, 1e-9),
        "quality_ratio": quality_ratio,
        "fine_steps_ratio": steps_ratio,
        "quality_gate": VCYCLE_QUALITY_GATE,
        "steps_gate": VCYCLE_STEPS_GATE,
        "pass": bool(quality_ratio >= VCYCLE_QUALITY_GATE
                     and steps_ratio <= VCYCLE_STEPS_GATE),
    }


def _checkpoint_overhead(k: int, *, steps: int, seed: int,
                         scale: float = 4e-3, trials: int = 4) -> dict:
    """Steps/s with drain-window checkpointing on vs off (the crash-safety
    cost; see docs/fault-tolerance.md). The snapshot rides the existing
    sync_every fetch and the disk write is async, so the gate is tight:
    checkpointing must keep >= CHECKPOINT_GATE of the plain throughput.
    Also asserts the two runs' labels are bit-identical — checkpointing
    must observe the trajectory, never perturb it.

    Measured on a dedicated graph large enough that supersteps are
    compute-bound (the fixed per-save host cost is meaningless against a
    dispatch-bound toy loop), best-of-N interleaved trials to shrug off
    scheduler noise on shared CI machines."""
    from repro.core.device_graph import prepare_device_graph
    from repro.core.runner import run_partitioner

    g = load_dataset("WIKI", scale=scale, seed=seed)
    dg = prepare_device_graph(g, n_blocks=8)
    common = dict(seed=seed, max_steps=steps, patience=10_000, dg=dg,
                  track_history=False, sync_every=4)
    run_partitioner("revolver", g, k, **common)              # compile + warm
    sps_off = sps_on = 0.0
    off = on = None
    n_ckpts = 0
    for _ in range(trials):
        off = run_partitioner("revolver", g, k, **common)
        td = tempfile.mkdtemp(prefix="bench_ckpt_")
        try:
            on = run_partitioner("revolver", g, k, checkpoint_dir=td,
                                 checkpoint_every=4, **common)
            n_ckpts = len([d for d in os.listdir(td)
                           if d.startswith("step_") and not d.endswith(".tmp")])
        finally:
            shutil.rmtree(td, ignore_errors=True)
        sps_off = max(sps_off, off.steps / max(off.wall_s, 1e-9))
        sps_on = max(sps_on, on.steps / max(on.wall_s, 1e-9))
    labels_eq = bool(np.array_equal(off.labels, on.labels))
    ratio = sps_on / max(sps_off, 1e-9)
    return {
        "n": g.n,
        "m": g.m,
        "steps": steps,
        "trials": trials,
        "checkpoint_every": 4,
        "checkpoints_written": n_ckpts,
        "supersteps_per_s_off": sps_off,
        "supersteps_per_s_on": sps_on,
        "overhead_ratio": ratio,
        "labels_bit_identical": labels_eq,
        "gate": CHECKPOINT_GATE,
        "pass": bool(ratio >= CHECKPOINT_GATE and labels_eq),
    }


def _time_supersteps(dg, cfg, *, steps: int, seed: int = 0) -> float:
    """Supersteps/second after a compile+warmup step (block on completion)."""
    st = revolver_init(dg, cfg, jax.random.PRNGKey(seed))
    st = revolver_superstep(dg, cfg, st)           # compile + warm
    jax.block_until_ready(st.labels)
    t0 = time.perf_counter()
    for _ in range(steps):
        st = revolver_superstep(dg, cfg, st)
    jax.block_until_ready(st.labels)
    return steps / (time.perf_counter() - t0)


def _superstep_parity(dg, k: int, *, steps: int, seed: int,
                      weight_mode: str) -> dict:
    """Fixed-seed jnp-vs-pallas superstep trajectory comparison."""
    finals = {}
    for impl in IMPLS:
        cfg = RevolverConfig(k=k, hist_impl=impl, weight_mode=weight_mode)
        st = revolver_init(dg, cfg, jax.random.PRNGKey(seed))
        for _ in range(steps):
            st = revolver_superstep(dg, cfg, st)
        finals[impl] = (float(st.score), np.asarray(st.labels))
    score_diff = abs(finals["jnp"][0] - finals["pallas"][0])
    labels_eq = float((finals["jnp"][1] == finals["pallas"][1]).mean())
    return {
        "weight_mode": weight_mode,
        "steps": steps,
        "score_diff": score_diff,
        "labels_equal_frac": labels_eq,
        "tol": PARITY_TOL,
        "pass": bool(score_diff <= PARITY_TOL),
    }


def _kernel_compare(dg, k: int, *, iters: int, seed: int) -> dict:
    """Fused single-pass kernel vs two independent edge_histogram launches.

    Both paths run in the same (interpret-on-CPU / compiled-on-TPU) mode and
    compute the same pair of [nb, block_v, k] histograms with
    weight_mode="neighbor_lambda" semantics, so the comparison isolates the
    fusion win: one slab read + one shared row-indicator instead of two.
    The two-call dispatch path is retired from the superstep; the
    single-histogram kernel survives only as this oracle, imported from its
    kernel module directly (no ops.py wrapper).
    """
    from repro.kernels.edge_histogram import edge_histogram_pallas
    from repro.kernels.ops import fused_edge_phase

    def edge_histogram(slots, rows, vals, *, block_v, k):
        return edge_histogram_pallas(slots, rows, vals, block_v=block_v, k=k)

    key = jax.random.PRNGKey(seed)
    nb, bv = dg.n_blocks, dg.block_v
    labels = jax.random.randint(key, (dg.n_pad,), 0, k, dtype=jnp.int32)
    lam = jax.random.randint(jax.random.fold_in(key, 1), (dg.n_pad,), 0, k,
                             dtype=jnp.int32)
    actions = jax.random.randint(jax.random.fold_in(key, 2), (nb, bv), 0, k,
                                 dtype=jnp.int32)
    feasible = (jax.random.uniform(jax.random.fold_in(key, 3), (nb, k))
                > 0.3).astype(jnp.float32)

    @jax.jit
    def fused(labels, lam, actions, feasible):
        return fused_edge_phase(
            dg.blk_dst, dg.blk_row, dg.blk_w, labels, lam, actions, feasible,
            block_v=bv, k=k, weight_mode="neighbor_lambda")

    @jax.jit
    def two_call(labels, lam, actions, feasible):
        nbr_lbl = labels[dg.blk_dst]
        lam_nbr = lam[dg.blk_dst]
        live = (dg.blk_w > 0).astype(jnp.float32)
        agree = jnp.take_along_axis(actions, dg.blk_row, axis=1) == lam_nbr
        val = jnp.where(agree, dg.blk_w,
                        jnp.take_along_axis(feasible, lam_nbr, axis=1)) * live
        h1 = edge_histogram(nbr_lbl, dg.blk_row, dg.blk_w, block_v=bv, k=k)
        h2 = edge_histogram(lam_nbr, dg.blk_row, val, block_v=bv, k=k)
        return h1, h2

    def timeit(fn):
        jax.block_until_ready(fn(labels, lam, actions, feasible))  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(labels, lam, actions, feasible)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e6            # us

    f_out = fused(labels, lam, actions, feasible)
    t_out = two_call(labels, lam, actions, feasible)
    err = max(float(jnp.abs(f_out[0] - t_out[0]).max()),
              float(jnp.abs(f_out[1] - t_out[1]).max()))
    us_fused = timeit(fused)
    us_two = timeit(two_call)
    return {
        "fused_us": us_fused,
        "two_call_us": us_two,
        "fused_speedup": us_two / max(us_fused, 1e-9),
        "max_abs_err": err,
        "pass": bool(err <= 1e-3),
    }


def run(*, quick: bool = False, out: str = "BENCH_superstep.json",
        datasets=None, scale: float | None = None, k: int = 8,
        n_blocks: int = 8, steps: int | None = None, seed: int = 0) -> dict:
    if datasets is None:
        datasets = ("WIKI",) if quick else ("WIKI", "LJ")
    if not datasets:
        raise ValueError("need at least one dataset (parity would be vacuous)")
    if scale is None:
        scale = 3e-4 if quick else 1e-3
    if steps is None:
        steps = 3 if quick else 8
    quality_steps = 20 if quick else 60

    results = {
        "meta": {
            "provenance": bench_provenance(),
            "quick": quick,
            "k": k,
            "n_blocks": n_blocks,
            "scale": scale,
            "steps_timed": steps,
            "quality_steps": quality_steps,
            "restream_gate": RESTREAM_GATE,
            "checkpoint_gate": CHECKPOINT_GATE,
            "vcycle_quality_gate": VCYCLE_QUALITY_GATE,
            "vcycle_steps_gate": VCYCLE_STEPS_GATE,
        },
        "superstep": [],
        "kernel": None,
        "parity": [],
        "algos": [],
        "vcycle": [],
        "checkpoint": None,
    }

    print(f"{'dataset':8s} {'hist':7s} {'la':7s} {'supersteps/s':>12s} "
          f"{'edges/s':>12s}")
    dg = None
    for name in datasets:
        g = load_dataset(name, scale=scale, seed=seed)
        dg = prepare_device_graph(g, n_blocks=n_blocks)
        for hist_impl in IMPLS:
            for la_impl in IMPLS:
                cfg = RevolverConfig(k=k, hist_impl=hist_impl, la_impl=la_impl)
                sps = _time_supersteps(dg, cfg, steps=steps, seed=seed)
                row = {
                    "dataset": name,
                    "n": g.n,
                    "m": g.m,
                    "hist_impl": hist_impl,
                    "la_impl": la_impl,
                    "supersteps_per_s": sps,
                    "edges_per_s": sps * g.m,
                    "sym_slab_edges": dg.n_blocks * dg.e_max,
                }
                results["superstep"].append(row)
                print(f"{name:8s} {hist_impl:7s} {la_impl:7s} {sps:12.2f} "
                      f"{sps * g.m:12.0f}")
        for weight_mode in ("self_lambda", "neighbor_lambda"):
            par = _superstep_parity(dg, k, steps=steps, seed=seed,
                                    weight_mode=weight_mode)
            par["dataset"] = name
            results["parity"].append(par)
            print(f"parity  {name}/{weight_mode}: score_diff="
                  f"{par['score_diff']:.2e} labels_eq="
                  f"{par['labels_equal_frac']:.4f} "
                  f"{'PASS' if par['pass'] else 'FAIL'}")
        for row in _algo_quality(g, dg, k, steps=quality_steps, seed=seed):
            row["dataset"] = name
            results["algos"].append(row)
            print(f"quality {name}/{row['algo']:9s}: "
                  f"local_edges={row['local_edges']:.4f} "
                  f"max_norm_load={row['max_norm_load']:.4f} "
                  f"steps={row['steps']}")
        ratio = results["algos"][-1]["restream_vs_revolver"]
        print(f"quality {name}: restream/revolver = {ratio:.3f} "
              f"(gate {RESTREAM_GATE}) "
              f"{'PASS' if ratio >= RESTREAM_GATE else 'FAIL'}")
        vc = _vcycle_compare(g, k, seed=seed)
        vc["dataset"] = name
        results["vcycle"].append(vc)
        print(f"vcycle  {name}: quality={vc['quality_ratio']:.3f} "
              f"(gate >={VCYCLE_QUALITY_GATE}) fine_steps="
              f"{vc['vcycle_fine_steps']}/{vc['flat_steps']} "
              f"ratio={vc['fine_steps_ratio']:.2f} "
              f"(gate <={VCYCLE_STEPS_GATE}) "
              f"{'PASS' if vc['pass'] else 'FAIL'}")

    # observability: a short traced run on the last dataset — the phase /
    # counter aggregates (superstep spans, migrations, recompiles) ride the
    # artifact so perf baselines carry their measurement context
    from repro import obs
    from repro.core.runner import run_partitioner

    tracer = obs.Tracer()
    run_partitioner("revolver", g, k, seed=seed, max_steps=steps,
                    patience=10_000, dg=dg, track_history=False, trace=tracer)
    results["obs"] = tracer.summary()

    results["kernel"] = _kernel_compare(dg, k, iters=3 if quick else 5,
                                        seed=seed)
    kc = results["kernel"]
    print(f"kernel  fused={kc['fused_us']:.0f}us two_call="
          f"{kc['two_call_us']:.0f}us speedup={kc['fused_speedup']:.2f}x "
          f"err={kc['max_abs_err']:.1e} "
          f"{'PASS' if kc['pass'] else 'FAIL'}")

    results["checkpoint"] = _checkpoint_overhead(
        k, steps=12 if quick else 24, seed=seed)
    ck = results["checkpoint"]
    print(f"ckpt    off={ck['supersteps_per_s_off']:.2f}/s "
          f"on={ck['supersteps_per_s_on']:.2f}/s "
          f"ratio={ck['overhead_ratio']:.3f} (gate {CHECKPOINT_GATE}) "
          f"bit_identical={ck['labels_bit_identical']} "
          f"{'PASS' if ck['pass'] else 'FAIL'}")

    parity_ok = (all(p["pass"] for p in results["parity"])
                 and results["kernel"]["pass"])
    quality_ok = bool(results["algos"]) and all(
        row["pass"] for row in results["algos"])
    checkpoint_ok = results["checkpoint"]["pass"]
    vcycle_ok = bool(results["vcycle"]) and all(
        row["pass"] for row in results["vcycle"])
    results["meta"]["parity_ok"] = parity_ok
    results["meta"]["quality_ok"] = quality_ok
    results["meta"]["checkpoint_ok"] = checkpoint_ok
    results["meta"]["vcycle_ok"] = vcycle_ok
    ok = parity_ok and quality_ok and checkpoint_ok and vcycle_ok
    if out:
        with open(out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {out}")
    if not parity_ok:
        print("KERNEL PARITY REGRESSION", file=sys.stderr)
    if not quality_ok:
        print(f"RESTREAM QUALITY REGRESSION (gate {RESTREAM_GATE})",
              file=sys.stderr)
    if not checkpoint_ok:
        print(f"CHECKPOINT OVERHEAD REGRESSION (gate {CHECKPOINT_GATE})",
              file=sys.stderr)
    if not vcycle_ok:
        print(f"VCYCLE REGRESSION (quality gate {VCYCLE_QUALITY_GATE}, "
              f"fine-steps gate {VCYCLE_STEPS_GATE})", file=sys.stderr)
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_superstep.json")
    ap.add_argument("--datasets", nargs="*", default=None)
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--n-blocks", type=int, default=8)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    results = run(quick=args.quick, out=args.out, datasets=args.datasets,
                  scale=args.scale, k=args.k, n_blocks=args.n_blocks,
                  steps=args.steps, seed=args.seed)
    return 0 if (results["meta"]["parity_ok"]
                 and results["meta"]["quality_ok"]
                 and results["meta"]["checkpoint_ok"]
                 and results["meta"]["vcycle_ok"]) else 1


if __name__ == "__main__":
    sys.exit(main())
