"""Benchmark entry: one cell of BENCHMARK.json, run on the chip.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs in one process from the root of a checkout and refuses any backend but
a TPU. Every line it prints is a log but the last, one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device` (and with `--trace 1`
`breakdown`), then `checks`, each compared number beside its limit, which
are also the last lines on standard error. See bench/benchlib/harness.py.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchlib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(root=os.path.dirname(HERE)))
