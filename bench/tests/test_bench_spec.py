"""The harness finds a cell's configuration, traffic, limits and metrics by
the names in BENCHMARK.json, so a later cell, configuration or metric is new
files and new entries, with no edit to a file that is there."""
import hashlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchlib import spec  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(HERE))


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            if f.endswith((".py", ".json")):
                p = os.path.join(dirpath, f)
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    open(p, "rb").read()).hexdigest()
    return out


def test_every_cell_of_the_benchmark_loads():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert cell.chips == w["chips"]
        assert {"k", "supersteps"} <= set(cell.traffic)
        assert {"labels_differ", "local_edges_gap",
                "max_norm_load_gap"} <= set(cell.limits)
        assert cell.config["name"] == w["config"]
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(ROOT, m["name"]))


def test_new_cell_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digests(root)

    bdir = os.path.join(root, "bench")
    with open(os.path.join(bdir, "configs", "toy-road.json"), "w") as f:
        json.dump({"name": "toy-road", "family": "grid_road",
                   "graph_seed": 1, "n": 4096, "drop_frac": 0.2}, f)
    with open(os.path.join(bdir, "traffic", "k4-s2.json"), "w") as f:
        json.dump({"k": 4, "supersteps": 2}, f)
    with open(os.path.join(bdir, "limits", "toy-k4.json"), "w") as f:
        json.dump({"labels_differ": 0.5, "local_edges_gap": 0.5,
                   "max_norm_load_gap": 0.5}, f)
    with open(os.path.join(bdir, "metrics", "jobs_run.py"), "w") as f:
        f.write("def read(rec):\n    return float(len(rec['jobs']))\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "toy-road", "source": "x",
                             "file": "bench/configs/toy-road.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "toy-k4", "config": "toy-road",
                               "traffic": "k4-s2", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "jobs_run", "unit": "jobs",
                               "better": "higher", "source": "host_clock",
                               "layer": "entry", "moves": "edges_per_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell(root, "toy-k4")
    assert cell.config["n"] == 4096 and cell.traffic["k"] == 4
    assert cell.limits["labels_differ"] == 0.5
    assert "jobs_run" in {m["name"] for m in cell.per_layer}
    got = spec.read_metrics(
        root, [m for m in cell.per_layer if m["name"] in ("jobs_run", "layout_s")],
        {"jobs": [{}, {}], "setup": {"layout_s": 2.0}})
    assert got["jobs_run"] == {"value": 2.0, "unit": "jobs"}
    assert got["layout_s"] == {"value": 2.0, "unit": "s"}
    # a metric whose reader finds nothing to read is left out of the line
    assert spec.read_metrics(
        root, [m for m in cell.per_layer if m["name"] == "superstep_ms"],
        {"trace": {"superstep_busy_s": 0.0, "supersteps": 0}}) == {}
    # nothing that was there changed
    after = _digests(root)
    assert {k: after[k] for k in before} == before


def test_every_per_layer_metric_is_read_in_every_cell_that_reports_what_it_moves(
        tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "bench", "limits", "usa-k8.json"),
                os.path.join(root, "bench", "limits", "other-k8.json"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    usa = {w["name"]: w for w in bench["workloads"]}["usa-k8"]
    bench["workloads"].append(dict(usa, name="other-k8"))
    bench["per_layer"].append({"name": "unreported", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "entry", "moves": "no_such_metric"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    def names(metrics):
        return [m["name"] for m in metrics]

    for name in ("usa-k8", "other-k8"):
        cell = spec.load_cell(root, name)
        assert names(cell.end_to_end) == names(bench["end_to_end"])
        assert names(cell.per_layer) == names(bench["per_layer"])[:-1]
