"""Readings that set the correctness limits of a cell, on the chip.

  python bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed, at the cell's own size, in one process: the graph and layout
as a run builds them, one partition job through the program, the plain
reference in float32, and the control: the reference computed in bfloat16,
put in the program's place. It prints, per seed, the compared numbers of the
program (sound runs: the lower readings) and of the control (the upper
readings) against the float32 reference. The benchmark's own runs do not run
this; the limits in limits/<cell>.json were set from its output.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchlib import harness, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    sys.path.insert(0, os.path.join(root, "src"))
    cell = spec.load_cell(root, args.workload)
    harness.configure_cache()
    harness.require_chip(cell.chips)
    import jax.numpy as jnp

    from benchlib import graphgen, reference
    from repro.core import prepare_device_graph

    k, steps = int(cell.traffic["k"]), int(cell.traffic["supersteps"])
    for seed in args.seeds:
        g = graphgen.generate(cell.config, seed, harness.log, root=root)
        dg = prepare_device_graph(g)
        layout = harness.layout_of(dg)
        prog = harness.Job(0.0, 0.0, *harness.program_job(g, dg, k, seed, steps, None))
        del dg
        ref = reference.revolver_labels(g, k, seed, steps, *layout,
                                        log=harness.log)
        ctl_labels = reference.revolver_labels(g, k, seed, steps, *layout,
                                               dtype=jnp.bfloat16, log=harness.log)
        ctl = harness.Job(0.0, 0.0, ctl_labels,
                          *reference.control_metrics(g, ctl_labels, k, jnp.bfloat16),
                          steps)
        for who, job in (("program", prog), ("control", ctl)):
            numbers, _ = harness.compare(g, k, steps, ref, [job], cell.limits)
            print(json.dumps({"cell": cell.name, "seed": seed, "who": who,
                              **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
