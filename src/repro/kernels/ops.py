"""Dispatch layer: Pallas TPU kernels on TPU, jnp references elsewhere.

All model code calls through these functions. The choice is made per-call
from (a) the default backend and (b) an explicit ``impl=`` override — so
tests can compare both paths.

Every dispatch decision bumps a trace-time counter (``kernel_counters``):
one count per *traced call site*, not per executed step, since dispatch
happens in Python while jit-tracing. ``Session.describe()["kernels"]``
reports per-session deltas; ``fallback_*`` keys mark calls where Pallas
was selected but the shape/backend combination still forced the ref path.
"""

from __future__ import annotations

import jax

from repro.kernels import ref

# Trace-time dispatch counters, keyed by implementation event. Monotonic
# process-wide; consumers snapshot and diff (see Session.describe()).
_COUNTERS: dict[str, int] = {}


def _count(event: str) -> None:
    _COUNTERS[event] = _COUNTERS.get(event, 0) + 1


def kernel_counters() -> dict[str, int]:
    """Snapshot of the trace-time dispatch counters (copy, safe to keep)."""
    return dict(_COUNTERS)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(impl: str | None) -> bool:
    if impl == "pallas":
        return True
    if impl == "ref":
        return False
    return _on_tpu()


def _interpret() -> bool:
    # explicit impl="pallas" off-TPU runs the kernels in interpret mode
    # (CPU tests); on TPU they compile for real.
    return not _on_tpu()


# --------------------------------------------------------------------------- #


def attention(q, k, v, *, causal=True, q_offset=0, block_k=512, impl=None):
    vector_off = getattr(q_offset, "ndim", 0) == 1
    traced_off = isinstance(q_offset, jax.core.Tracer) or vector_off or (
        getattr(q_offset, "ndim", None) == 0
    )
    if _use_pallas(impl):
        if causal and traced_off:
            # slot-aware kernel: per-row (or traced scalar) positions are
            # applied in-kernel; no ref fallback on the serving hot path.
            from repro.kernels import paged_attention as pa

            _count("pallas_slotted")
            import jax.numpy as jnp

            pos = jnp.asarray(q_offset, jnp.int32).reshape(-1)
            return pa.flash_attention_slotted(
                q, k, v, pos=pos, block_k=min(block_k, 128),
                interpret=_interpret())
        if not traced_off:
            # static scalar offset: the training-path flash kernel.
            _count("pallas_flash")
            from repro.kernels import flash_attention

            return flash_attention.flash_attention(
                q, k, v, causal=causal, q_offset=q_offset,
                interpret=_interpret())
        # non-causal with traced offset has no Pallas lowering; visible
        # (counted) fallback rather than a once-per-process warning.
        _count("fallback_attention_ref")
        impl = "ref"
    else:
        _count("ref_attention")
    return ref.attention(
        q, k, v, causal=causal, q_offset=q_offset, block_k=block_k
    )


def paged_attention(q, k_pool, v_pool, *, page_tables, pos, k_scale=None,
                    v_scale=None, slot_mask=None, block_k=512, impl=None):
    """Attention straight out of a paged KV pool (optionally int8 pages).

    Pallas path runs the page-table-native kernel (dequant in-kernel);
    ref path gathers + dequants with jnp and reuses ``ref.attention`` —
    identical math, so CPU tests pin the numerics.
    """
    if _use_pallas(impl):
        _count("pallas_paged")
        from repro.kernels import paged_attention as pa

        return pa.paged_attention(
            q, k_pool, v_pool, page_tables=page_tables, pos=pos,
            k_scale=k_scale, v_scale=v_scale, slot_mask=slot_mask,
            interpret=_interpret())
    _count("ref_paged")
    return ref.paged_attention(
        q, k_pool, v_pool, page_tables=page_tables, pos=pos,
        k_scale=k_scale, v_scale=v_scale, slot_mask=slot_mask,
        block_k=block_k)


def decode_attention(q, k_cache, v_cache, cache_len=None, impl=None):
    if _use_pallas(impl):
        _count("pallas_decode")
        from repro.kernels import paged_attention as pa

        return pa.decode_attention(
            q, k_cache, v_cache, cache_len, interpret=_interpret())
    _count("ref_decode")
    return ref.decode_attention(q, k_cache, v_cache, cache_len=cache_len)


def combine_decode_shards(partials):
    return ref.combine_decode_shards(partials)


def selective_scan(x, dt, A, B, C, D, *, chunk=256, h0=None,
                   return_state=False, impl=None):
    if not _use_pallas(impl):
        _count("ref_selective_scan")
    elif h0 is not None or return_state:
        # the kernel has no state in/out (training only): counted fallback
        _count("fallback_selective_scan_ref")
    else:
        _count("pallas_selective_scan")
        from repro.kernels import selective_scan as ss

        return ss.selective_scan(x, dt, A, B, C, D, chunk=chunk,
                                 interpret=_interpret())
    return ref.selective_scan(
        x, dt, A, B, C, D, chunk=chunk, h0=h0, return_state=return_state
    )


def selective_scan_step(h, x, dt, A, B, C, D):
    return ref.selective_scan_step(h, x, dt, A, B, C, D)


def mlstm_chunkwise(q, k, v, i_gate, f_gate, *, chunk=128, state=None,
                    return_state=False, impl=None):
    return ref.mlstm_chunkwise(
        q, k, v, i_gate, f_gate, chunk=chunk, state=state,
        return_state=return_state,
    )


def mlstm_step(state, q, k, v, i_gate, f_gate):
    return ref.mlstm_step(state, q, k, v, i_gate, f_gate)


def slstm_scan(x_gates, *, state=None, return_state=False, impl=None):
    return ref.slstm_scan(x_gates, state=state, return_state=return_state)


def expert_gmm(lhs, rhs, group_sizes, *, impl=None):
    """Grouped product of expert-sorted rows (``kernels/expert_gmm.py``):
    lhs [m, k] (m a multiple of ``expert_gmm.TM``), rhs [E, k, n],
    group_sizes [E]; rows past the groups are garbage on the Pallas path
    and zero on the reference path, so callers mask them."""
    from repro.kernels import expert_gmm as eg

    if _use_pallas(impl):
        _count("expert_gmm")
        return eg.expert_gmm(lhs, rhs, group_sizes, _interpret())
    _count("ref_expert_gmm")
    return eg.reference(lhs, rhs, group_sizes)


def softmax_xent(h, w_head, labels, *, chunk=8192, mask=None, impl=None):
    if _use_pallas(impl):
        _count("pallas_xent")
        from repro.kernels import fused_xent

        return fused_xent.softmax_xent(h, w_head, labels, mask=mask,
                                       interpret=_interpret())
    _count("ref_xent")
    return ref.softmax_xent(h, w_head, labels, chunk=chunk, mask=mask)
