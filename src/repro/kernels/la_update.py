"""Pallas TPU kernel: weighted learning-automaton probability update.

Eqs. (8)/(9) require m sequential passes over every vertex's [k]
probability vector — O(V*k^2) elementwise work with a serial dependency
along the pass axis. A naive XLA lowering round-trips the [V, k]
probability matrix through HBM once per pass (k HBM sweeps). The kernel
keeps a [Bv, k] probability tile **resident in VMEM across all k passes**
(one HBM read + one write per tile), turning the update from
memory-bound into VPU-bound.

The per-row pass schedule (penalty passes first — DESIGN.md §10.6) is
precomputed outside the kernel as an argsort and streamed in as an int32
[Bv, k] tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(p_ref, w_ref, r_ref, ord_ref, out_ref, *,
            k: int, alpha: float, beta: float, renorm: bool):
    p = p_ref[...].astype(jnp.float32)     # [Bv, k]
    w = w_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    order = ord_ref[...]                   # [Bv, k] int32 pass schedule
    bv = p.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bv, k), 1)

    def pass_t(t, p):
        # pass t's action per row; a masked lane sum, not a dynamic slice
        # (which the TPU kernel compiler does not lower)
        i = jnp.sum(jnp.where(iota == t, order, 0), axis=1, keepdims=True)
        mask = iota == i
        w_i = jnp.sum(jnp.where(mask, w, 0.0), axis=1, keepdims=True)
        # eq. (8): reward pass for action i
        p_rew = jnp.where(mask, p + alpha * w * (1.0 - p), p * (1.0 - alpha * w))
        # eq. (9): penalty pass (weighted redistribution floor)
        floor = beta * w / (k - 1)
        p_pen = jnp.where(mask, p * (1.0 - beta * w), p * (1.0 - beta * w) + floor)
        is_pen = jnp.sum(jnp.where(mask, r, 0.0), axis=1, keepdims=True) > 0
        p_new = jnp.where(is_pen, p_pen, p_rew)
        # zero-weight slot => no signal => skip the pass
        return jnp.where(w_i > 0, p_new, p)

    p = jax.lax.fori_loop(0, k, pass_t, p)
    if renorm:
        p = jnp.clip(p, 1e-12, 1.0)
        p = p / jnp.sum(p, axis=1, keepdims=True)
    out_ref[...] = p.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("alpha", "beta", "renorm", "block_v", "interpret"))
def la_update_pallas(
    probs: jax.Array,    # [V, k] f32
    weights: jax.Array,  # [V, k] f32 (normalized halves, sum=2)
    signals: jax.Array,  # [V, k] f32 (0 reward / 1 penalty)
    *,
    alpha: float,
    beta: float,
    renorm: bool = True,
    block_v: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    v, k = probs.shape
    assert v % block_v == 0, (v, block_v)
    # penalty-first schedule: stable argsort of descending r
    order = jnp.argsort(-signals, axis=-1, stable=True).astype(jnp.int32)

    grid = (v // block_v,)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, alpha=alpha, beta=beta, renorm=renorm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_v, k), lambda i: (i, 0)),
            pl.BlockSpec((block_v, k), lambda i: (i, 0)),
            pl.BlockSpec((block_v, k), lambda i: (i, 0)),
            pl.BlockSpec((block_v, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_v, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((v, k), probs.dtype),
        interpret=interpret,
    )(probs, weights, signals, order)
