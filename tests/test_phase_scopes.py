"""The superstep's phase scopes reach the compiled program with tracing
off, and the persistent compile cache keeps scoped and unscoped
executables apart (docs/observability.md)."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core.device_graph import prepare_device_graph
from repro.core.revolver import (RevolverConfig, revolver_init,
                                 revolver_superstep)
from repro.graphs.generators import dc_sbm

PHASES = ("la-select", "edge-phase", "migrate", "la-update")
SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


@pytest.fixture(scope="module")
def superstep():
    """The sequential superstep on a small graph, and a state for it."""
    g = dc_sbm(256, 2048, n_comm=4, mixing=0.25, degree_exponent=0.5, seed=5)
    dg = prepare_device_graph(g, n_blocks=4)
    cfg = RevolverConfig(k=4)
    state = revolver_init(dg, cfg, jax.random.PRNGKey(0))
    return (lambda s: revolver_superstep(dg, cfg, s)), state


def test_compiled_superstep_carries_every_phase_scope(superstep):
    step, state = superstep
    assert obs.current() is obs.NULL_TRACER
    text = jax.jit(step).lower(state).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for phase in PHASES:
        assert any(phase in name.split("/") for name in op_names), phase


def _block_scan(jaxpr):
    """The first scan of a jaxpr, depth first: the engine's block scan."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            return eqn.params["jaxpr"].jaxpr
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                found = _block_scan(inner)
                if found is not None:
                    return found
    return None


def test_every_rule_op_sits_in_a_phase_scope(superstep):
    """Each operation the Revolver rule traces into the block scan's body
    is under exactly one phase scope; the engine's own (the splices) are in
    none."""
    step, state = superstep
    body = _block_scan(jax.make_jaxpr(step)(state).jaxpr)
    assert body is not None
    seen = {p: 0 for p in PHASES}
    engine_ops = 0
    for eqn in body.eqns:
        frames = {f.function_name for f in eqn.source_info.traceback.frames}
        scopes = [s for s in str(eqn.source_info.name_stack).split("/")
                  if s in PHASES]
        if "_revolver_chunk_rule" in frames:
            assert len(scopes) == 1, (eqn.primitive, frames)
            seen[scopes[0]] += 1
        else:
            assert scopes == [], eqn.primitive
            engine_ops += 1
    assert all(seen.values()), seen
    assert engine_ops > 0


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs included, depth first."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


@pytest.mark.parametrize("weight_mode", ["self_lambda", "neighbor_lambda"])
def test_edge_phase_reads_vertex_values_in_two_gathers(weight_mode):
    """Per block, the jnp rule makes two per-edge gathers from vertex
    vectors: the packed neighbour (label, lambda) word from the `[n_pad]`
    view, and the row's packed (action, lambda) word (`self_lambda`) or
    its action (`neighbor_lambda`) from a `[block_v]` vector. The `[k]`
    lookup `p_mig[slot]` is not one of them. The gathers and the packing
    sit in the edge phase."""
    g = dc_sbm(256, 2048, n_comm=4, mixing=0.25, degree_exponent=0.5, seed=5)
    dg = prepare_device_graph(g, n_blocks=4)
    cfg = RevolverConfig(k=4, weight_mode=weight_mode)
    state = revolver_init(dg, cfg, jax.random.PRNGKey(0))
    body = _block_scan(jax.make_jaxpr(
        lambda s: revolver_superstep(dg, cfg, s))(state).jaxpr)
    assert len({dg.n_pad, dg.block_v, dg.e_max, cfg.k}) == 4
    vertex_gathers, packing = [], []
    for eqn in _eqns(body):
        name = eqn.primitive.name
        if name == "gather":
            operand, idx = (v.aval for v in eqn.invars[:2])
            if operand.shape in ((dg.n_pad,), (dg.block_v,)) and \
                    idx.size == dg.e_max:
                vertex_gathers.append((operand.shape, eqn))
        elif name in ("shift_left", "or") and \
                eqn.outvars[0].aval.dtype == jnp.int32:   # not the PRNG's
            packing.append(eqn)
    assert sorted(shape for shape, _ in vertex_gathers) == \
        [(dg.block_v,), (dg.n_pad,)]
    assert len(packing) == (2 if weight_mode == "self_lambda" else 1) * 2
    for eqn in packing + [eqn for _, eqn in vertex_gathers]:
        assert "edge-phase" in str(eqn.source_info.name_stack).split("/")


_COMPILE = textwrap.dedent("""
    import contextlib, json, sys
    import jax, jax.numpy as jnp
    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    scoped = sys.argv[1] == "1"

    def f(x):
        with jax.named_scope("edge-phase") if scoped else contextlib.nullcontext():
            return (jnp.sin(x) * 2).sum()

    text = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
    print(json.dumps("edge-phase" in text))
""")


def _compile_in_child(cache_dir, scoped):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", _COMPILE, "1" if scoped else "0"],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_keeps_scoped_and_unscoped_apart(tmp_path):
    """A run without a scope fills the cache; a later run of the same
    function under a scope must not load that executable and lose its
    names: it compiles and caches its own."""
    assert _compile_in_child(tmp_path, scoped=False) is False
    unscoped = set(os.listdir(tmp_path))
    assert unscoped, "the unscoped executable was not cached"
    assert _compile_in_child(tmp_path, scoped=True) is True
    assert set(os.listdir(tmp_path)) > unscoped


_COMPILE_METRIC = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.core.metrics import local_edges
    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    ids = jnp.zeros(4, jnp.int32)
    jax.jit(local_edges).lower(jnp.zeros(8, jnp.int32), ids, ids).compile()
""")


def test_compile_cache_serves_a_copy_of_the_checkout(tmp_path):
    """The key holds the metadata's source files relative to the checkout:
    the same program from a copy elsewhere loads what the first compiled."""
    cache = tmp_path / "cache"
    entries = []
    for copy in ("a", "b"):
        src = tmp_path / copy / "src"
        shutil.copytree(SRC, src, ignore=shutil.ignore_patterns("__pycache__"))
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
                   JAX_PLATFORMS="cpu", PYTHONPATH=str(src))
        out = subprocess.run([sys.executable, "-c", _COMPILE_METRIC], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        entries.append(set(os.listdir(cache)))
    assert entries[0] and entries[1] == entries[0]
