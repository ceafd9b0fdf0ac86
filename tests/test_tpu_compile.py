"""Ahead-of-time compiles of the partitioner's Pallas kernels for a TPU v5e.

The kernels run in interpret mode everywhere else in the suite, which
accepts layouts the TPU compiler refuses (unaligned tiles, in-kernel
gathers, dynamic slices, VMEM overflows). Here they are compiled for one
chip of a described -- not attached -- ``v5e:2x2`` topology, at the shapes
of `chip_smoke.py`'s kernel leg: the paper's WIKI graph at scale 1.0 cut
into 2048-vertex blocks. Nothing runs; a refusal fails the test. The jnp
superstep is compiled too, at a 64th of `usa-k8`'s block shapes, to count
the gathers the TPU compiler makes of its edge phase.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports this file. The persistent compilation cache is off around the
compiles (a described-topology compile cannot be read back from it).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.device_graph import DeviceGraph
from repro.core.revolver import (RevolverConfig, RevolverState,
                                 revolver_superstep)
from repro.kernels import ops
from repro.kernels.edge_phase import (MAX_INDICATOR_ELEMS,
                                      fused_edge_phase_pallas)
from repro.kernels.la_update import la_update_pallas

K = 8
BLOCK_V = 2048               # the kernel leg's vertex block
N_PAD = 876 * BLOCK_V        # WIKI scale 1.0: 1,793,448 vertices padded
E_MAX = 73472                # its fullest block's edge slab (287 x 256)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("weight_mode", ["self_lambda", "neighbor_lambda"])
def test_edge_phase_compiles_for_v5e(one_chip, weight_mode):
    assert BLOCK_V * 256 <= MAX_INDICATOR_ELEMS   # the bound is compiled
    args = (_shape((1, E_MAX), jnp.int32, one_chip),     # dst
            _shape((1, E_MAX), jnp.int32, one_chip),     # rows
            _shape((1, E_MAX), jnp.float32, one_chip),   # weights
            _shape((N_PAD,), jnp.int32, one_chip),       # labels
            _shape((N_PAD,), jnp.int32, one_chip),       # lambda
            _shape((1, BLOCK_V), jnp.int32, one_chip),   # actions
            _shape((1, K), jnp.float32, one_chip))       # feasible
    compiled = fused_edge_phase_pallas.lower(
        *args, block_v=BLOCK_V, k=K, weight_mode=weight_mode,
        edge_chunk=256, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_la_update_compiles_for_v5e(one_chip):
    x = _shape((BLOCK_V, K), jnp.float32, one_chip)
    compiled = la_update_pallas.lower(
        x, x, x, alpha=1.0, beta=0.1, block_v=256,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("weight_mode", ["self_lambda", "neighbor_lambda"])
def test_superstep_gathers_each_vertex_pair_once_for_v5e(one_chip, weight_mode):
    """The jnp superstep, compiled for the chip, reads vertex values at the
    edges through two gathers per block: the TPU compiler keeps each packed
    pair word (`lp.gather_pair`) as the gather's operand and does not fuse
    the packing back in, which would read both halves per index again."""
    nb, block_v, e_max, m = 8, 46768, 114176, 912384   # usa-k8 / 64
    n_pad = nb * block_v
    cfg = RevolverConfig(k=K, weight_mode=weight_mode)

    def step(arrays, state):
        dg = DeviceGraph(n_pad - 8, n_pad, m, nb, block_v, e_max, *arrays)
        return revolver_superstep(dg, cfg, state)

    i32, f32 = jnp.int32, jnp.float32
    arrays = tuple(_shape(shape, dtype, one_chip) for shape, dtype in (
        ((m,), i32), ((m,), i32), ((m,), f32),            # edge_src/dst/w
        ((m,), i32), ((m,), i32),                         # dir_src/dst
        ((nb, e_max), i32), ((nb, e_max), i32), ((nb, e_max), f32),
        ((n_pad,), f32), ((n_pad,), f32), ((n_pad,), jnp.bool_)))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = RevolverState(*(_shape(shape, dtype, one_chip) for shape, dtype in (
        ((n_pad,), i32), ((n_pad,), i32), ((nb, block_v, K), f32),
        ((K,), f32), (key.shape, key.dtype), ((), i32), ((), f32))))
    text = jax.jit(step).lower(arrays, state).compile().as_text()
    gathers = re.findall(r"= s32\[(\d+)\]\S* gather\(", text)
    assert gathers == [str(e_max)] * 2


def test_edge_phase_refuses_oversized_block_v():
    """A vertex block whose row indicator cannot fit VMEM is refused with a
    ValueError before any kernel is built -- not left to hang the TPU
    compiler, and not quietly routed to the jnp path."""
    block_v = 2 * BLOCK_V
    z = jnp.zeros((1, 256), jnp.int32)
    with pytest.raises(ValueError, match="cannot hold block_v=4096"):
        ops.fused_edge_phase(z, z, jnp.zeros((1, 256), jnp.float32),
                             jnp.zeros((block_v,), jnp.int32),
                             jnp.zeros((block_v,), jnp.int32),
                             jnp.zeros((1, block_v), jnp.int32),
                             jnp.ones((1, K), jnp.float32),
                             block_v=block_v, k=K)
