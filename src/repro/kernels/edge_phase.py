"""Pallas TPU kernel: fused dual-histogram edge phase for the Revolver superstep.

The superstep's O(E) work per chunk is *two* edge-label histograms over the
same blocked edge slab (DESIGN.md §3, Section IV-D steps 3 and 5):

  * the LP-score histogram (eqs. 10-12): hist[v, l] += w(e) over v's edges
    whose neighbor currently carries label l;
  * the eq.-13 weight accumulation: w_raw[v, slot(e)] += val(e), where val
    depends on whether the neighbor's latest lambda agrees with v's selected
    action and on slot feasibility (p_mig > 0).

Run separately (`edge_histogram` twice) each histogram re-builds the
[Bv, Ec] row-indicator matrix R and re-launches the grid. This kernel
computes **both in a single pass**: one R shared across two MXU matmuls
(L_score @ R^T and L_w @ R^T), so the two [k, Bv] accumulators stay
VMEM-resident across all edge chunks of a block (grid minor dimension =
edge chunks).

The data-dependent gathers (neighbor label and lambda, the vertex's
action, slot feasibility) run in the XLA wrapper: the TPU kernel compiler
only gathers along 2-D tiles, and a VMEM-resident [n_pad] label vector
would cap the graph size besides. The wrapper turns each edge into two
(slot, value) pairs streamed as lane-major `[nb, 1, e_max]` slabs; the
kernel is left with the indicator construction and the two matmuls.

Slot-selection for the two `weight_mode`s (the eq.-13 ambiguity, DESIGN.md
§10):

  * ``neighbor_lambda`` — the weight histogram's slot is lambda(u), known
    per edge, so the kernel returns the finished w_raw.
  * ``self_lambda`` — the slot is lambda(v) = argmax score(v, :), which only
    exists *after* all edge chunks are reduced. But every edge of row v then
    lands in the same slot, so the row's contribution factors into two
    scalars independent of lambda(v):

        A[v] = sum_e agree(e) * w(e)          (agreement mass)
        N[v] = #{e : !agree(e), non-padding}  (disagreement count)

    An edge adds to exactly one of them, so the wrapper routes it to slot 0
    (A) or slot 1 (N); the caller scatters ``A + feasible(lambda(v)) * N``
    into the one-hot lambda(v) slot. The fusion is exact: every input
    (labels, lam, action, p_mig) is available before the edge phase.

VMEM bound: each grid cell holds the [block_v, edge_chunk] f32 indicator,
so ``block_v * edge_chunk`` is capped at `MAX_INDICATOR_ELEMS`; a larger
vertex block raises ValueError up front rather than being left to the TPU
compiler. See kernels/README.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lp import gather_pair

_WEIGHT_MODES = ("self_lambda", "neighbor_lambda")

# block_v * edge_chunk elements of the per-cell row indicator, ~16 B each
# with its iota: 2048 x 256 compiles for a v5e, 4096 x 256 needs 16.17 MiB
# of the 16 MiB default scoped VMEM and is refused (tests/test_tpu_compile.py
# compiles the bound)
MAX_INDICATOR_ELEMS = 2048 * 256


def _kernel(row_ref, sa_ref, va_ref, sb_ref, vb_ref, ha_ref, hb_ref, *,
            block_v: int, k: int):
    """One (vertex-block, edge-chunk) grid cell; accumulates both outputs."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ha_ref[...] = jnp.zeros_like(ha_ref)
        hb_ref[...] = jnp.zeros_like(hb_ref)

    row = row_ref[0]            # [1, Ec] int32 local row per edge
    ec = row.shape[-1]
    rows_iota = jax.lax.broadcasted_iota(jnp.int32, (block_v, ec), 0)
    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (k, ec), 0)
    r_mat = (rows_iota == row).astype(jnp.float32)     # shared R, [Bv, Ec]
    l_a = (slot_iota == sa_ref[0]).astype(jnp.float32) * va_ref[0]   # [k, Ec]
    l_b = (slot_iota == sb_ref[0]).astype(jnp.float32) * vb_ref[0]

    dn = (((1,), (1,)), ((), ()))   # contract edges: [k,Ec] x [Bv,Ec] -> [k,Bv]
    hp = jax.lax.Precision.HIGHEST
    ha_ref[0] += jax.lax.dot_general(
        l_a, r_mat, dimension_numbers=dn, precision=hp,
        preferred_element_type=jnp.float32)
    hb_ref[0] += jax.lax.dot_general(
        l_b, r_mat, dimension_numbers=dn, precision=hp,
        preferred_element_type=jnp.float32)


def _edge_slot_values(edge_dst, edge_rows, edge_vals, labels, lam, actions,
                     feasible, weight_mode: str):
    """Per-edge (slot, value) pairs of both histograms — the gathers the
    kernel leaves to XLA. Returns ``(score_slot, score_val, w_slot,
    w_val)``, each ``[nb, e_max]``."""
    nbr_lbl, lam_nbr = gather_pair(labels, lam, edge_dst)
    live = (edge_vals > 0).astype(jnp.float32)           # padding kill
    agree = jnp.take_along_axis(actions, edge_rows, axis=1) == lam_nbr
    if weight_mode == "neighbor_lambda":
        w_slot = lam_nbr
        feas_nbr = jnp.take_along_axis(feasible, lam_nbr, axis=1)
        w_val = jnp.where(agree, edge_vals, feas_nbr) * live
    else:  # self_lambda: A -> slot 0, N -> slot 1 (module docstring)
        w_slot = jnp.where(agree, 0, 1).astype(jnp.int32)
        w_val = jnp.where(agree, edge_vals, live)
    return nbr_lbl, edge_vals, w_slot, w_val


@functools.partial(jax.jit, static_argnames=(
    "block_v", "k", "weight_mode", "edge_chunk", "interpret"))
def fused_edge_phase_pallas(
    edge_dst: jax.Array,    # [nb, e_max] int32 global neighbor id
    edge_rows: jax.Array,   # [nb, e_max] int32 local row per edge
    edge_vals: jax.Array,   # [nb, e_max] f32 eq.-4 weight (0 = padding)
    labels: jax.Array,      # [n_pad] int32 current labels
    lam: jax.Array,         # [n_pad] int32 latest argmax labels
    actions: jax.Array,     # [nb, block_v] int32 LA-selected actions
    feasible: jax.Array,    # [nb, k] f32 1.0 where p_mig(l) > 0
    *,
    block_v: int,
    k: int,
    weight_mode: str = "self_lambda",
    edge_chunk: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (hist_score, w_acc), both [nb, block_v, k] f32.

    ``w_acc`` is the finished eq.-13 histogram for ``neighbor_lambda``; for
    ``self_lambda`` column 0 carries A[v] and column 1 carries N[v] (the
    caller finishes the one-hot scatter once lambda(v) is known).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if weight_mode not in _WEIGHT_MODES:
        raise ValueError(
            f"unknown weight_mode {weight_mode!r}; expected {_WEIGHT_MODES}")
    if weight_mode == "self_lambda" and k < 2:
        raise ValueError("self_lambda packing needs k >= 2 output columns")
    nb, e_max = edge_dst.shape
    if e_max % edge_chunk != 0:
        # a floored chunk count would silently drop the slab tail
        raise ValueError(f"e_max={e_max} not a multiple of edge_chunk={edge_chunk}")
    if block_v * edge_chunk > MAX_INDICATOR_ELEMS:
        raise ValueError(
            f"fused edge phase cannot hold block_v={block_v} x "
            f"edge_chunk={edge_chunk} ({block_v * edge_chunk} indicator "
            f"elements > {MAX_INDICATOR_ELEMS}); use more, smaller vertex "
            f"blocks (n_blocks) or hist_impl='jnp'")

    slabs = _edge_slot_values(edge_dst, edge_rows, edge_vals, labels, lam,
                             actions, feasible, weight_mode)
    # lane-major [nb, 1, e_max]: the (1, edge_chunk) tail of every block is
    # a full-extent sublane dim by a lane-aligned chunk
    row, sa, va, sb, vb = (x.reshape(nb, 1, e_max)
                           for x in (edge_rows, *slabs))
    slab_spec = pl.BlockSpec((1, 1, edge_chunk), lambda i, j: (i, 0, j))
    out_spec = pl.BlockSpec((1, k, block_v), lambda i, j: (i, 0, 0))
    hist_t, wacc_t = pl.pallas_call(
        functools.partial(_kernel, block_v=block_v, k=k),
        grid=(nb, e_max // edge_chunk),
        in_specs=[slab_spec] * 5,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((nb, k, block_v), jnp.float32)] * 2,
        interpret=interpret,
    )(row, sa, va, sb, vb)
    return hist_t.transpose(0, 2, 1), wacc_t.transpose(0, 2, 1)
