"""Public jit'd wrappers around the Pallas kernels.

Every op auto-selects ``interpret=True`` on a CPU backend (the test
suite's ``JAX_PLATFORMS=cpu``) and the compiled TPU path elsewhere; the
``ref.py`` oracles pin the semantics in tests/test_kernels.py, and
tests/test_tpu_compile.py compiles the partitioner's kernels for a
described v5e. Call sites in the model zoo and the partitioner
select implementations via config flags ("jnp" | "pallas") so the
dry-run can lower the pure-XLA path while TPU deployments take the
kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.edge_phase import fused_edge_phase_pallas
from repro.kernels.la_update import la_update_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.decode_attention import decode_attention_pallas

# NOTE: the single-histogram `edge_histogram` kernel no longer has a public
# op wrapper — the fused dual-histogram edge phase below superseded its
# two-launch dispatch path in the superstep. The kernel itself stays
# importable (`repro.kernels.edge_histogram.edge_histogram_pallas`) purely
# as a test/bench oracle for the fused kernel's score histogram.


def superstep_kernels(hist_impl: str, la_impl: str):
    """Resolve the partitioner engine's kernel routing.

    The ``hist_impl`` / ``la_impl`` config knobs pick between the jnp
    reference paths (scatter-add histogram in core/lp.py, fori-loop LA
    update in core/la.py) and the Pallas kernels below; this is the single
    dispatch point the superstep rules route through. Returns
    ``(edge_phase_op, la_update_op)`` with ``None`` marking "use the jnp
    reference" — rules keep their reference math inline so the pure-XLA
    lowering stays dependency-free.
    """
    for name, impl in (("hist_impl", hist_impl), ("la_impl", la_impl)):
        if impl not in ("jnp", "pallas"):
            raise ValueError(f"{name}={impl!r} is not one of ('jnp', 'pallas')")
    return (
        fused_edge_phase if hist_impl == "pallas" else None,
        la_update if la_impl == "pallas" else None,
    )


def fused_edge_phase(edge_dst, edge_rows, edge_vals, labels, lam, actions,
                     feasible, *, block_v: int, k: int,
                     weight_mode: str = "self_lambda",
                     edge_chunk: int | None = None,
                     interpret: bool | None = None):
    """(hist_score, w_acc), both [nb, block_v, k] — see kernels/edge_phase.py.

    Both Revolver edge histograms in one slab pass; `w_acc` is the finished
    eq.-13 histogram for weight_mode="neighbor_lambda", or the (A, N)
    column packing for "self_lambda". `edge_chunk=None` picks 256 when the
    slab divides (the `block_edges` invariant) or one whole-slab chunk for
    sub-256 slabs; a larger non-divisible slab raises in the kernel wrapper
    rather than silently building an oversized [e_max, block_v] indicator.
    A ``block_v`` whose row indicator does not fit VMEM raises ValueError
    (`edge_phase.MAX_INDICATOR_ELEMS`); there is no silent jnp fallback.
    """
    e_max = edge_dst.shape[-1]
    if edge_chunk is None:
        edge_chunk = e_max if (e_max < 256 and e_max % 256 != 0) else 256
    return fused_edge_phase_pallas(
        edge_dst, edge_rows, edge_vals, labels, lam, actions, feasible,
        block_v=block_v, k=k, weight_mode=weight_mode,
        edge_chunk=edge_chunk, interpret=interpret)


def la_update(probs, weights, signals, alpha: float, beta: float, *,
              renorm: bool = True, interpret: bool | None = None):
    """Weighted-LA probability update (eqs. 8/9) on [V, k] (or [..., k]).

    Rows are padded to a VMEM-friendly block multiple; padding rows carry
    zero weights (all passes skipped) and are sliced off on return.
    """
    shape = probs.shape
    k = shape[-1]
    p2 = probs.reshape(-1, k)
    w2 = weights.reshape(-1, k)
    r2 = signals.reshape(-1, k)
    v = p2.shape[0]
    block_v = 256 if v >= 256 else max(8, 1 << (v - 1).bit_length())
    pad = (-v) % block_v
    if pad:
        p2 = jnp.concatenate([p2, jnp.full((pad, k), 1.0 / k, p2.dtype)], 0)
        w2 = jnp.concatenate([w2, jnp.zeros((pad, k), w2.dtype)], 0)
        r2 = jnp.concatenate([r2, jnp.zeros((pad, k), r2.dtype)], 0)
    out = la_update_pallas(
        p2, w2, r2, alpha=alpha, beta=beta, renorm=renorm,
        block_v=block_v, interpret=interpret)
    return out[:v].reshape(shape)


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """Causal/SWA GQA flash attention — q [B,Hq,S,D], k/v [B,Hkv,S,D]."""
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)


def decode_attention(q, k_cache, v_cache, kv_len, *, block_k: int = 512,
                     interpret: bool | None = None, return_lse: bool = False):
    """Flash-decode — q [B,Hq,D] against cache [B,Hkv,S,D]."""
    return decode_attention_pallas(
        q, k_cache, v_cache, kv_len, block_k=block_k,
        interpret=interpret, return_lse=return_lse)


def wkv6(r, k, v, logw, u, state0, *, block_s: int = 128,
         interpret: bool | None = None):
    """RWKV6 recurrence with VMEM-resident [N,N] state — see kernels/wkv6.py."""
    from repro.kernels.wkv6 import wkv6_pallas
    return wkv6_pallas(r, k, v, logw, u, state0, block_s=block_s,
                       interpret=interpret)
