"""What `BENCHMARK.json` names, found by name under the benchmark's tree.

Nothing here knows a cell, a configuration, a graph family or a metric by
name: a cell is an entry of `workloads`; its configuration is
`configs/<config>.json` (the `file` of the matching `configs` entry), drawn
by `families/<family>.py` (the configuration's `family` key; see
`graphgen`), its traffic `traffic/<traffic>.json` and its correctness
limits `limits/<cell>.json`; a metric is read by `metrics/<metric>.py`,
whose `read(record)` returns the number or None where the run had nothing
to read, and is read in every cell that reports the end-to-end metric it
moves. A later cell, configuration, graph family, traffic mix or metric is
new files plus new entries, with no edit to a file here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Callable, Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    root: str              # the checkout its files were found in
    chips: int
    config: dict           # configs/<config>.json
    traffic: dict          # traffic/<traffic>.json
    limits: dict           # limits/<cell>.json
    end_to_end: List[dict]  # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def bench_dir(root: str) -> str:
    return os.path.join(root, "bench")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json` with its files."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bdir = bench_dir(root)
    e2e = spec["end_to_end"]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if m["moves"] in e2e_names]
    return Cell(
        name=name, root=root, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, conf["file"])),
        traffic=_load_json(os.path.join(bdir, "traffic", w["traffic"] + ".json")),
        limits=_load_json(os.path.join(bdir, "limits", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def load_module(root: str, folder: str, name: str) -> ModuleType:
    """`<root>/bench/<folder>/<name>.py`, imported once per process and kept,
    as `import` keeps a module: a family's compiled programs stay loaded on
    the device, as they would in a module of the benchmark's own."""
    path = os.path.abspath(os.path.join(bench_dir(root), folder, name + ".py"))
    if not os.path.isfile(path):
        raise ValueError(f"no {folder} entry {name!r}: {path} does not exist")
    key = "bench:" + path
    if key not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


def metric_reader(root: str, metric: str) -> Callable[[dict], Optional[float]]:
    """`read` of `bench/metrics/<metric>.py`."""
    return load_module(root, "metrics", metric).read


def read_metrics(root: str, metrics: List[dict], record: dict) -> Dict[str, dict]:
    """{name: {"value", "unit"}} for every metric whose reader found
    something to read in `record`."""
    out = {}
    for m in metrics:
        value = metric_reader(root, m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
