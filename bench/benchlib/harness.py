"""One run of one cell: set-up, the measured window, the correctness check,
the metrics, and the result line.

Set-up (timed as `setup_s`): the configuration's graph drawn on the device
from the seed (`graphgen`), the program's layout at its defaults
(`prepare_device_graph`, timed as `layout_s`), and one warm-up job like the
window's, which compiles every program the window runs (the program's
compiled superstep is keyed on the whole config, superstep budget and
patience included, so a shorter warm-up would leave it to the window).

The window drives the program's real entry: back-to-back partition jobs,
each `run_partitioner("revolver", g, k, seed=<run seed>, dg=dg,
max_steps=S, patience=S)` at the program's defaults otherwise, from init to
labels on the host in original vertex order. Jobs start while the elapsed
time is under `--seconds`; the last one finishes. Every job uses the run's
seed, so each is the same work and has the same answer. A traced run
(`--trace 1`) hands every job, the warm-up's too, the program's own
`obs.Tracer`, profiles the window, and reduces the profile by program
(`tracing`) and by phase scope and host span (`phases`) into the record's
`trace`.

`correct` compares every job of the window with the plain reference
(`reference.revolver_labels`, replayed once after the window from the same
seed, in the block order and vertex map of the layout the program chose):
the share of vertices whose label differs, and the gaps of the
`local_edges` / `max_norm_load` the job reports from the reference
labelling's float64 values. Each has a limit of its own in
`limits/<cell>.json`. The replay draws its random numbers in the program's
order, so the check pins that order too.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, List, Optional

import numpy as np

from benchlib import spec

CHECKS = ("labels_differ", "local_edges_gap", "max_norm_load_gap")


def log(msg: str = "") -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class Job:
    start: float
    end: float
    labels: np.ndarray
    local_edges: float
    max_norm_load: float
    steps: int


def program_job(g, dg, k: int, seed: int, steps: int, tracer) -> tuple:
    """One partition job through the program's entry."""
    from repro.core import run_partitioner

    res = run_partitioner("revolver", g, k, seed=seed, dg=dg, max_steps=steps,
                          patience=steps, trace=tracer)
    return res.labels, res.local_edges, res.max_norm_load, res.steps


def control_job(g, dg, k: int, seed: int, steps: int, tracer) -> tuple:
    """The reference in bfloat16, put in the program's place."""
    import jax.numpy as jnp

    from benchlib import reference

    labels = reference.revolver_labels(g, k, seed, steps, *layout_of(dg),
                                       dtype=jnp.bfloat16, log=log)
    le, ml = reference.control_metrics(g, labels, k, jnp.bfloat16)
    return labels, le, ml, steps


class CompileCounter:
    """Counts programs lowered for compilation (in-process jit cache misses,
    whether or not the persistent cache then holds them) from JAX's
    monitoring events; installed once per process."""

    _installed: Optional["CompileCounter"] = None

    def __init__(self):
        self.count = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax

            counter = cls()

            def on_event(event, duration, **kwargs):
                if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                    counter.count += 1

            jax.monitoring.register_event_duration_secs_listener(on_event)
            cls._installed = counter
        return cls._installed


def configure_cache() -> str:
    """The program's persistent compile cache (the operator's
    JAX_COMPILATION_CACHE_DIR where set, else `<checkout>/.jax_cache`), with
    every program cached, however quickly it compiled."""
    import jax

    from repro.utils.compile_cache import configure_compile_cache

    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def layout_of(dg) -> tuple:
    """(n_blocks, block_v, o2s) of the program's layout: the reference
    replays the blocks in the program's storage order."""
    return dg.n_blocks, dg.block_v, getattr(dg, "o2s", None)


def require_chip(chips: int) -> None:
    """Exit nonzero unless JAX finds a TPU with at least `chips` devices."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench: needs a TPU; JAX found platform {devices[0].platform!r} "
            f"({len(devices)} device(s)). There is no CPU path.")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")


def _peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def compare(g, k: int, steps: int, ref_labels: np.ndarray, jobs: List[Job],
            limits: dict) -> tuple:
    """(numbers, failed jobs): each compared number's worst value over the
    jobs, and how many jobs broke a limit. A job with labels of the wrong
    shape or out of range, or with another superstep count, differs
    everywhere."""
    from benchlib import reference

    ref_le, ref_ml = reference.quality(g, ref_labels, k)
    worst = {c: 0.0 for c in CHECKS}
    failed = 0
    for job in jobs:
        lab = np.asarray(job.labels)
        valid = (lab.shape == (g.n,) and job.steps == steps
                 and (g.n == 0 or (lab.min() >= 0 and lab.max() < k)))
        got = {"labels_differ": float(np.mean(lab != ref_labels)) if valid else 1.0,
               "local_edges_gap": abs(float(job.local_edges) - ref_le),
               "max_norm_load_gap": abs(float(job.max_norm_load) - ref_ml)}
        # a NaN would compare false both ways: it reads as infinite
        got = {c: v if v == v else math.inf for c, v in got.items()}
        failed += any(got[c] > limits[c] for c in CHECKS)
        worst = {c: max(worst[c], got[c]) for c in CHECKS}
    return worst, failed


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             job_fn: Callable = program_job) -> dict:
    """Set-up, window and check of one run; returns the run record."""
    import jax

    from benchlib import graphgen, phases, reference, tracing
    from repro import obs
    from repro.core import prepare_device_graph

    cfg, k, steps = cell.config, int(cell.traffic["k"]), int(cell.traffic["supersteps"])
    device = jax.devices()[0]
    tracer = obs.Tracer() if trace else None
    compiles = CompileCounter.get()

    # ---- set-up -----------------------------------------------------------
    t_setup = time.perf_counter()
    g = graphgen.generate(cfg, seed, log, root=cell.root)
    gen_s = time.perf_counter() - t_setup
    gen_peak = _peak_bytes(device)
    log(f"graph {cfg['name']} seed={seed}: |V|={g.n} |E|={g.m} "
        f"symmetrized slots={g.num_sym_edges}; generation {gen_s:.3f} s, "
        f"device peak after generation {gen_peak} B")
    t = time.perf_counter()
    dg = prepare_device_graph(g)
    jax.block_until_ready((dg.blk_dst, dg.edge_dst, dg.dir_dst))
    layout_s = time.perf_counter() - t
    layout = layout_of(dg)
    log(f"layout: n_blocks={layout[0]} block_v={layout[1]} e_max={dg.e_max} "
        f"vertex map={'identity' if layout[2] is None else 'permuted'}; "
        f"{layout_s:.3f} s")
    t = time.perf_counter()
    job_fn(g, dg, k, seed, steps, tracer)
    warmup_s = time.perf_counter() - t
    setup_s = time.perf_counter() - t_setup
    log(f"warm-up job {warmup_s:.3f} s; set-up {setup_s:.3f} s")

    # ---- window -------------------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=tracing.profile_options())
    compiles_before = compiles.count
    jobs: List[Job] = []
    try:
        with jax.profiler.TraceAnnotation("bench-window"):
            t0 = time.perf_counter()
            while not jobs or time.perf_counter() - t0 < seconds:
                with jax.profiler.TraceAnnotation("bench-job"):
                    s = time.perf_counter()
                    labels, le, ml, n_steps = job_fn(g, dg, k, seed, steps, tracer)
                    jobs.append(Job(s, time.perf_counter(), labels, le, ml,
                                    n_steps))
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_compiles = compiles.count - compiles_before
    peak = _peak_bytes(device)
    for i, j in enumerate(jobs):
        log(f"job {i}: {j.end - j.start:.3f} s, {j.steps} supersteps, "
            f"local_edges={j.local_edges!r} max_norm_load={j.max_norm_load!r}")
    log(f"window: {len(jobs)} jobs in {jobs[-1].end - jobs[0].start:.3f} s; "
        f"programs compiled inside the window: {window_compiles}; device peak "
        f"{peak} B (generation peak {gen_peak} B, below it: "
        f"{gen_peak is not None and peak is not None and gen_peak < peak})")

    # ---- check, after the program's state is freed ---------------------------
    del dg
    gc.collect()
    t = time.perf_counter()
    ref_labels = reference.revolver_labels(g, k, seed, steps, *layout,
                                           log=log)
    numbers, failed = compare(g, k, steps, ref_labels, jobs, cell.limits)
    log(f"reference replay and comparison {time.perf_counter() - t:.3f} s")

    reduced = None
    if trace:
        t = time.perf_counter()
        raw = phases.extract(trace_dir)
        reduced = {**tracing.reduce_trace(raw), **phases.reduce_phases(raw)}
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduction {time.perf_counter() - t:.3f} s: "
            f"{json.dumps(reduced)}")
    return {
        "cell": cell.name, "seed": seed, "n": g.n, "m": g.m,
        "ms": g.num_sym_edges, "k": k, "supersteps": steps,
        "setup": {"generate_s": gen_s, "layout_s": layout_s,
                  "warmup_s": warmup_s, "setup_s": setup_s},
        "jobs": [{"start": j.start, "end": j.end, "steps": j.steps,
                  "local_edges": float(j.local_edges),
                  "max_norm_load": float(j.max_norm_load)} for j in jobs],
        "window_compiles": window_compiles,
        "peak_bytes": peak, "generation_peak_bytes": gen_peak,
        "device_kind": device.device_kind, "platform": device.platform,
        "device_count": len(jax.devices()),
        "checks": numbers, "failed": failed,
        "trace": reduced,
    }


def result_line(root: str, cell: spec.Cell, rec: dict, trace: bool) -> dict:
    metrics = cell.per_layer if trace else cell.end_to_end
    out = {
        "correct": rec["failed"] == 0 and bool(rec["jobs"]),
        "attempted": len(rec["jobs"]),
        "failed": rec["failed"],
        "metrics": spec.read_metrics(root, metrics, rec),
        "device": {"platform": rec["platform"], "kind": rec["device_kind"],
                   "count": rec["device_count"],
                   "memory_peak_bytes": rec["peak_bytes"]},
    }
    if trace:
        out["device"]["busy_s"] = rec["trace"]["busy_s"]
        out["device"]["window_s"] = rec["trace"]["window_s"]
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    out["checks"] = {c: {"value": rec["checks"][c], "limit": cell.limits[c]}
                     for c in CHECKS}
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: str, chip: bool = True,
         job_fn: Callable = program_job) -> int:
    args = parse_args(argv)
    cell = spec.load_cell(root, args.workload)
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    cache = configure_cache()
    if chip:
        require_chip(cell.chips)
    d = jax.devices()[0]
    log(f"jax {jax.__version__}; platform={d.platform} kind={d.device_kind!r} "
        f"count={len(jax.devices())}; compile cache {cache}; cell {cell.name} "
        f"k={cell.traffic['k']} supersteps={cell.traffic['supersteps']}")
    rec = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   job_fn=job_fn)
    out = result_line(root, cell, rec, bool(args.trace))
    for c, v in out["checks"].items():
        print(f"check {c}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
