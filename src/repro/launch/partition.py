"""Graph-partitioning launcher — the paper's workload as a CLI.

  PYTHONPATH=src python -m repro.launch.partition --dataset LJ --scale 0.002 \
      --k 8 --algo revolver --algo spinner --algo restream --algo hash

`--algo` accepts any key in the algorithm registry (`repro.core.registry`),
so out-of-tree rules registered before `main()` are launchable without
touching this file. Superstep-only knobs (--epsilon, --max-steps,
--chunk-schedule) are passed only to engine-driven algorithms; the static
baselines (hash/range) take none.
"""
from __future__ import annotations

import argparse
import json
import os

from repro.core import run_partitioner
from repro.core.registry import (
    StaticAlgorithm,
    available_algorithms,
    get_algorithm,
)
from repro.graphs import load_dataset
from repro.utils.compile_cache import configure_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="LJ",
                    help="Table-I dataset key (WIKI/UK/USA/SO/LJ/EN/OK/HLWD/EU)")
    ap.add_argument("--scale", type=float, default=0.002)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--algo", action="append", default=None,
                    choices=list(available_algorithms()))
    ap.add_argument("--max-steps", type=int, default=290)
    ap.add_argument("--epsilon", type=float, default=0.05)
    ap.add_argument("--n-blocks", type=int, default=8)
    ap.add_argument("--chunk-schedule", default="sequential",
                    choices=["sequential", "sharded", "halo", "async"])
    ap.add_argument("--assignment", default="contiguous",
                    choices=["contiguous", "locality", "vcycle"],
                    help="block->shard mapping for sharded/halo schedules "
                         "(vcycle = locality seed + pairwise-swap "
                         "refinement, never worse than locality)")
    ap.add_argument("--mode", default="flat", choices=["flat", "vcycle"],
                    help="flat = refine at full resolution from superstep 0; "
                         "vcycle = coarsen, partition the coarsest graph, "
                         "uncoarsen with warm-started refinement (see "
                         "docs/multilevel.md)")
    ap.add_argument("--coarse-n", type=int, default=None,
                    help="coarsest-level vertex target for --mode vcycle "
                         "(default 512)")
    ap.add_argument("--level-decay", type=float, default=None,
                    help="per-level superstep budget decay for --mode vcycle "
                         "(default 0.5)")
    ap.add_argument("--halo-granularity", default="auto",
                    choices=["auto", "block", "vertex"],
                    help="halo exchange unit (halo/async schedules): whole "
                         "boundary blocks or per-vertex need lists on an "
                         "int8 wire; auto takes whichever moves fewer "
                         "elements")
    ap.add_argument("--staleness-bound", type=int, default=0,
                    help="async schedule: supersteps a shard may run against "
                         "a stale halo before a forced refresh (0 = refresh "
                         "every superstep, bit-identical to the halo "
                         "schedule on the same layout; see "
                         "docs/async-superstep.md)")
    ap.add_argument("--hub-replication", action="store_true",
                    help="mirror top-degree vertices into every shard and "
                         "reconcile their labels by a per-superstep global "
                         "vote (halo schedule; see repro.core.halo)")
    ap.add_argument("--hub-quantile", type=float, default=0.0,
                    help="degree quantile above which vertices are hubs "
                         "(0 = auto-size the hub set from halo coverage)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sync-every", type=int, default=1,
                    help="device->host score fetch window (supersteps); "
                         "checkpoints and state guards ride these windows")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="root directory for crash-safe checkpoints; each "
                         "algorithm saves under <dir>/<algo> (see "
                         "docs/fault-tolerance.md)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="snapshot the partitioner state every N supersteps "
                         "(0 = off; needs --checkpoint-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume each algorithm from its newest usable "
                         "checkpoint under --checkpoint-dir (fresh run if "
                         "none exists) — a killed run relaunched with the "
                         "same command line continues bit-identically")
    ap.add_argument("--guard", default="off",
                    choices=["off", "raise", "rollback", "reinit"],
                    help="drain-window state guard policy for non-finite "
                         "probs / out-of-range labels")
    ap.add_argument("--labels-out", metavar="PATH", default=None,
                    help="write final labels per algorithm to PATH (npz, one "
                         "array per algorithm) — lets CI diff two runs "
                         "bit-for-bit")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a perfetto-loadable trace (Chrome trace-event"
                         " JSON) covering every run to PATH; inspect with "
                         "tools/trace_report.py or at https://ui.perfetto.dev")
    args = ap.parse_args(argv)
    configure_compile_cache()

    tracer = None
    if args.trace:
        from repro import obs

        tracer = obs.Tracer()
        tracer.meta["cli"] = {"dataset": args.dataset, "scale": args.scale,
                              "k": args.k,
                              "chunk_schedule": args.chunk_schedule}

    g = load_dataset(args.dataset, scale=args.scale, seed=args.seed)
    algos = args.algo or list(available_algorithms())
    rows = []
    labels_out = {}
    for algo in algos:
        kwargs = {}
        static = isinstance(get_algorithm(algo), StaticAlgorithm)
        if not static:
            kwargs = dict(epsilon=args.epsilon,
                          chunk_schedule=args.chunk_schedule,
                          sync_every=args.sync_every, guard=args.guard)
            if args.mode != "flat":
                kwargs["mode"] = args.mode
                kwargs["coarse_n"] = args.coarse_n
                kwargs["level_decay"] = args.level_decay
            if args.chunk_schedule != "sequential":
                kwargs["assignment"] = args.assignment
            if args.chunk_schedule in ("halo", "async"):
                kwargs["halo_granularity"] = args.halo_granularity
            if args.chunk_schedule == "async":
                kwargs["staleness_bound"] = args.staleness_bound
            if args.hub_replication:
                kwargs["hub_replication"] = True
                kwargs["hub_quantile"] = args.hub_quantile
            if args.checkpoint_dir:
                # per-algo subdir: one CLI invocation runs several
                # algorithms; their checkpoints must not collide
                kwargs["checkpoint_dir"] = os.path.join(
                    args.checkpoint_dir, algo)
                kwargs["checkpoint_every"] = args.checkpoint_every
                kwargs["resume"] = args.resume
        res = run_partitioner(algo, g, args.k, seed=args.seed,
                              max_steps=args.max_steps,
                              n_blocks=args.n_blocks, trace=tracer, **kwargs)
        row = {"dataset": args.dataset, "algo": algo, "k": args.k,
               "local_edges": round(res.local_edges, 4),
               "max_norm_load": round(res.max_norm_load, 4),
               "steps": res.steps}
        if res.resumed_from:
            row["resumed_from"] = res.resumed_from
        rows.append(row)
        labels_out[algo] = res.labels
        if not args.json:
            resumed = (f" resumed_from={res.resumed_from}"
                       if res.resumed_from else "")
            print(f"{algo:10s} local_edges={row['local_edges']:.4f} "
                  f"max_norm_load={row['max_norm_load']:.4f} "
                  f"steps={row['steps']}{resumed}")
    if args.labels_out:
        import numpy as np

        np.savez(args.labels_out, **labels_out)
        if not args.json:
            print(f"labels written to {args.labels_out}")
    if args.json:
        print(json.dumps(rows))
    if tracer is not None:
        tracer.save(args.trace)
        if not args.json:
            print(f"trace written to {args.trace} "
                  f"({len(tracer.events)} events)")


if __name__ == "__main__":
    main()
