"""A run end to end on the CPU at a small size, with the look for a chip
skipped: a sound run comes out correct; the control (the reference in
bfloat16 in the program's place) and each fault the cell can have, planted
in the timed path, come out not correct. And the command itself refuses a
CPU backend."""
import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchlib import harness  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(HERE))
ARGS = ["--workload", "toy-k8", "--seed", "2147483659", "--seconds", "0.5",
        "--trace", "0"]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The benchmark's tree with one small road cell held to usa-k8's
    limits and traffic."""
    root = str(tmp_path_factory.mktemp("bench_root"))
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    usa = {w["name"]: w for w in bench["workloads"]}["usa-k8"]
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs", "usa-road.json")))
    cfg.update(name="toy-road", n=4096)
    with open(os.path.join(root, "bench", "configs", "toy-road.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(ROOT, "bench", "limits", "usa-k8.json"),
                os.path.join(root, "bench", "limits", "toy-k8.json"))
    bench["configs"] = [{"name": "toy-road", "source": "x", "why": "x",
                         "file": "bench/configs/toy-road.json", "reduced": []}]
    bench["workloads"] = [dict(usa, name="toy-k8", config="toy-road")]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture
def restore_jax_config():
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def _run(root, capsys, job_fn=harness.program_job, workload="toy-k8"):
    args = list(ARGS)
    args[args.index("--workload") + 1] = workload
    assert harness.main(args, root=root, chip=False, job_fn=job_fn) == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    # the compared numbers close standard error, each beside its limit
    tail = err.strip().splitlines()[-len(harness.CHECKS):]
    assert [line.split(":")[0] for line in tail] == [
        f"check {c}" for c in harness.CHECKS]
    assert list(result)[-1] == "checks"
    return result


def test_sound_run_is_correct(toy_root, capsys, restore_jax_config):
    r = _run(toy_root, capsys)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert r["checks"]["labels_differ"]["value"] == 0.0
    assert {"edges_per_s", "local_edges", "max_norm_load", "setup_s"} <= set(
        r["metrics"])


def test_control_is_not_correct(toy_root, capsys, restore_jax_config):
    r = _run(toy_root, capsys, job_fn=harness.control_job)
    assert r["correct"] is False and r["failed"] == r["attempted"]
    assert r["checks"]["labels_differ"]["value"] > 0.1


def test_reference_follows_a_permuted_layout(toy_root):
    """On a layout whose storage order permutes the blocks, the reference
    given the layout's vertex map replays the program's job label for
    label."""
    import numpy as np

    from benchlib import graphgen, reference, spec
    from repro.core import prepare_device_graph, run_partitioner
    from repro.core.device_graph import shard_device_graph
    from repro.launch.mesh import make_blocks_mesh

    cell = spec.load_cell(toy_root, "toy-k8")
    k, steps = cell.traffic["k"], cell.traffic["supersteps"]
    g = graphgen.generate(cell.config, 11)
    dg = shard_device_graph(prepare_device_graph(g), make_blocks_mesh(1),
                            assignment=np.array([5, 2, 7, 0, 1, 6, 3, 4]))
    assert dg.o2s is not None
    res = run_partitioner("revolver", g, k, seed=11, dg=dg, max_steps=steps,
                          patience=steps, chunk_schedule="sharded")
    ref = reference.revolver_labels(g, k, 11, steps, *harness.layout_of(dg))
    np.testing.assert_array_equal(res.labels, ref)
    contiguous = reference.revolver_labels(g, k, 11, steps, dg.n_blocks,
                                           dg.block_v)
    assert np.mean(contiguous != ref) > 0.1


RING = '''"""A ring: every vertex joined both ways to the next."""
import numpy as np

from benchlib import graphgen
from repro.graphs.csr import build_graph


def generate(cfg, relabel_key, log=None):
    n = int(cfg["n"])
    perm = np.asarray(graphgen.relabeling(relabel_key, n))
    v = np.arange(n)
    return build_graph(perm[v], perm[(v + 1) % n], n)
'''


def _tree_digests(root):
    return {os.path.relpath(os.path.join(d, f), root):
            hashlib.sha256(open(os.path.join(d, f), "rb").read()).hexdigest()
            for d, _, files in os.walk(os.path.join(root, "bench"))
            for f in files}


def test_new_graph_family_runs_from_its_file_alone(tmp_path, capsys,
                                                   restore_jax_config):
    """A family that is one new file, with a configuration, limits and the
    entries that name them, runs a cell end to end to `correct`; no file
    of the benchmark's tree that was there changes."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = _tree_digests(root)
    bdir = os.path.join(root, "bench")
    with open(os.path.join(bdir, "families", "ring.py"), "w") as f:
        f.write(RING)
    with open(os.path.join(bdir, "configs", "toy-ring.json"), "w") as f:
        json.dump({"name": "toy-ring", "family": "ring", "n": 2048}, f)
    shutil.copy(os.path.join(bdir, "limits", "usa-k8.json"),
                os.path.join(bdir, "limits", "ring-k8.json"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    usa = {w["name"]: w for w in bench["workloads"]}["usa-k8"]
    bench["configs"].append({"name": "toy-ring", "source": "x", "why": "x",
                             "file": "bench/configs/toy-ring.json",
                             "reduced": []})
    bench["workloads"].append(dict(usa, name="ring-k8", config="toy-ring"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    r = _run(root, capsys, workload="ring-k8")
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert r["checks"]["labels_differ"]["value"] == 0.0
    after = _tree_digests(root)
    assert {k: after[k] for k in before} == before


def _unchanged_state(monkeypatch):
    monkeypatch.setattr("repro.core.engine.superstep",
                        lambda algo, dg, cfg, state, halo=None: state)


def _half_the_blocks(monkeypatch):
    from repro.core import registry, revolver

    def rule(cfg, ctx, vert, block, loads, cap, key):
        upd = revolver._revolver_chunk_rule(cfg, ctx, vert, block, loads, cap,
                                            key)
        skip = ctx.blk_idx % 2 == 1
        old = {f: jax.lax.dynamic_slice(vert[f], (ctx.v0,), v.shape)
               for f, v in upd.vert.items()}
        return upd._replace(
            vert={f: jnp.where(skip, old[f], v) for f, v in upd.vert.items()},
            block={f: jnp.where(skip, block[f], v) for f, v in upd.block.items()},
            loads=jnp.where(skip, loads, upd.loads))

    registry.get_algorithm("revolver")
    monkeypatch.setitem(registry._REGISTRY, "revolver",
                        dataclasses.replace(revolver.REVOLVER, chunk_rule=rule))


def _labels_altered(monkeypatch):
    monkeypatch.setattr("repro.core.runner.vertices_to_original",
                        lambda dg, x: jnp.roll(x, 1))


def _metric_altered(monkeypatch):
    from repro.core import metrics

    monkeypatch.setattr(
        "repro.core.runner.local_edges",
        lambda labels, src, dst: metrics.local_edges(labels, src, dst) + 1e-3)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_the_blocks,
                                   _labels_altered, _metric_altered],
                         ids=["unchanged_state", "half_the_blocks",
                              "labels_altered", "metric_altered"])
def test_fault_is_not_correct(plant, toy_root, capsys, monkeypatch,
                              restore_jax_config):
    plant(monkeypatch)
    r = _run(toy_root, capsys)
    assert r["correct"] is False and r["failed"] == r["attempted"]
    assert any(v["value"] > v["limit"] for v in r["checks"].values())


def _command(root, tmp_env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_env_dir))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "usa-k8", "--seed",
         "7", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_cpu_backend(tmp_path):
    p = _command(ROOT, tmp_path)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert '"correct"' not in p.stdout


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(str(root), tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
