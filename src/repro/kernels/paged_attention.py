"""Pallas TPU slot-aware attention: per-row positions + paged KV pools.

The serving hot path hands attention three things the training kernel
(`flash_attention.py`) never sees: a *per-row* position vector (every
continuous-batching slot sits at its own absolute offset), a *page
table* per request (the KV bytes live scattered in a shared page pool),
and optionally *int8 pages* with per-page×head scales. Until this
kernel, all three forced the jnp reference path. Two entry points:

  * ``flash_attention_slotted`` — contiguous cache ``[b, S, g, e]``,
    per-row int32 ``pos``. ``window=False`` applies the per-row causal
    mask ``k_pos <= pos[b] + i`` in-kernel; ``window=True`` is the
    decode-attention contract ``k_pos < pos[b]`` (``pos`` = cache_len).
    ``return_stats`` also returns flash-decoding ``(m, l, acc)``
    partials shaped exactly like ``ref.decode_attention``'s, so the
    sequence-shard merge (``combine_decode_shards`` / psum-logsumexp)
    is implementation-agnostic.
  * ``paged_attention`` — the cache never materializes per-row: the
    grid's minormost dim walks each row's page-table entries and the
    K/V BlockSpec index maps chase the (scalar-prefetched) page ids
    straight into the pool ``[n_pages, page_size, g, e]``. Sentinel
    tail entries (id 0 past a row's reserved length) drag in arbitrary
    live pages whose *logical* positions all exceed the row's causal
    offset — the same exact-causal masking zeroes them that the ref
    gather path relies on. With ``k_scale``/``v_scale`` (int8 pages)
    the dequant ``q_int8 * scale`` happens in-kernel, in f32, matching
    the ref path's gather-then-dequant bit for bit per key.

Hardware mapping follows flash_attention.py: (m, l, acc) running state
in VMEM scratch across the sequential minormost grid dim, f32
accumulation, GQA via the ``bh // rep`` K/V index fold, separate value
dim ``ev`` (MLA-absorbed: e = qk_nope + rope ≠ ev = v_head). Per-page
scales ride in SMEM (scalar per grid step). Both kernels emit the
*unnormalized* accumulator plus (m, l); the wrappers normalize — the
same final ``acc / max(l, eps)`` the reference performs.

Validated against kernels/ref.py in interpret mode by
tests/test_kernels.py (staggered pos, sentinel tails, GQA/MLA dims,
int8 error bound).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


# --------------------------------------------------------------------------- #
# Slotted (contiguous-cache, vector-pos) kernel
# --------------------------------------------------------------------------- #


def _slotted_kernel(pos_ref, q_ref, k_ref, v_ref, acc_o, m_o, l_o,
                    m_sc, l_sc, acc_sc, *, h, block_k, sk, sq_p, scale,
                    n_k, window):
    bh = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32) * scale        # [sq_p, e]
    k = k_ref[0].astype(jnp.float32)                # [bk, e]
    v = v_ref[0].astype(jnp.float32)                # [bk, ev]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [sq_p, bk]

    row = pos_ref[bh // h]                           # this batch row's pos
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (sq_p, block_k), 1)
    mask = k_pos < sk
    if window:
        # decode-attention contract: every q row sees k_pos < cache_len
        mask = mask & (k_pos < row)
    else:
        q_pos = row + jax.lax.broadcasted_iota(
            jnp.int32, (sq_p, block_k), 0)
        mask = mask & (k_pos <= q_pos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]                               # [sq_p, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(ik == n_k - 1)
    def _flush():
        acc_o[0] = acc_sc[...]
        m_o[0] = m_sc[...]
        l_o[0] = l_sc[...]


@functools.partial(
    jax.jit,
    static_argnames=("window", "return_stats", "block_k", "interpret"),
)
def flash_attention_slotted(q, k, v, *, pos, window=False,
                            return_stats=False, block_k=128,
                            interpret=False):
    """q: [b, sq, h, e]; k: [b, S, g, e]; v: [b, S, g, ev]; pos: [b] int32.

    window=False → per-row causal (k_pos <= pos[b] + i);
    window=True  → decode window (k_pos < pos[b], pos = cache_len).
    Returns [b, sq, h, ev] — with return_stats, (out, (m, l, acc)) in
    ``ref.decode_attention``'s layout (m, l: [b, h, sq]; acc f32
    [b, h, sq, ev]).
    """
    b, sq, h, e = q.shape
    _, sk, g, ev = v.shape
    rep = h // g
    scale = 1.0 / (e ** 0.5)

    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    sq_p = _ceil_to(sq, 8)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    pk = -sk % block_k
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    n_k = (sk + pk) // block_k

    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq_p, e)
    kr = k.transpose(0, 2, 1, 3).reshape(b * g, sk + pk, e)
    vr = v.transpose(0, 2, 1, 3).reshape(b * g, sk + pk, ev)

    kernel = functools.partial(
        _slotted_kernel, h=h, block_k=block_k, sk=sk, sq_p=sq_p,
        scale=scale, n_k=n_k, window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, n_k),
        in_specs=[
            pl.BlockSpec((1, sq_p, e), lambda bh, ik, pr: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, e),
                         lambda bh, ik, pr, rep=rep: (bh // rep, ik, 0)),
            pl.BlockSpec((1, block_k, ev),
                         lambda bh, ik, pr, rep=rep: (bh // rep, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, sq_p, ev), lambda bh, ik, pr: (bh, 0, 0)),
            pl.BlockSpec((1, sq_p, 1), lambda bh, ik, pr: (bh, 0, 0)),
            pl.BlockSpec((1, sq_p, 1), lambda bh, ik, pr: (bh, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((sq_p, 1), jnp.float32),
            pltpu.VMEM((sq_p, 1), jnp.float32),
            pltpu.VMEM((sq_p, ev), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, ev), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sq_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sq_p, 1), jnp.float32),
        ],
        interpret=interpret,
    )(pos, qr, kr, vr)

    out = acc / jnp.maximum(l, 1e-30)
    out = out.reshape(b, h, sq_p, ev)[:, :, :sq]
    o_bqhe = out.transpose(0, 2, 1, 3).astype(q.dtype)
    if not return_stats:
        return o_bqhe
    m = m.reshape(b, h, sq_p)[:, :, :sq]
    l = l.reshape(b, h, sq_p)[:, :, :sq]
    acc = acc.reshape(b, h, sq_p, ev)[:, :, :sq]
    return o_bqhe, (m, l, acc)


def decode_attention(q, k_cache, v_cache, cache_len=None, *, block_k=128,
                     interpret=False):
    """Drop-in for ``ref.decode_attention`` on the slotted kernel.

    q: [b, sq, h, e]; caches [b, S, g, e/ev]; cache_len scalar or [b]
    (None → the full cache is valid). Returns (out, (m, l, acc)).
    """
    S = k_cache.shape[1]
    if cache_len is None:
        cache_len = S
    return flash_attention_slotted(
        q, k_cache, v_cache, pos=cache_len, window=True,
        return_stats=True, block_k=block_k, interpret=interpret)


# --------------------------------------------------------------------------- #
# Paged (page-table-native) kernel
# --------------------------------------------------------------------------- #


def _paged_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, *refs, h, rep,
                  ps, sq_p, scale, ppr, quant):
    if quant:
        ks_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc = refs
    else:
        o_ref, m_sc, l_sc, acc_sc = refs
    ib = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # logical key positions of this page-table entry; sentinel tail
    # entries sit past the row's causal offset, so the exact causal
    # mask zeroes whatever live page their id 0 happens to alias.
    row = pos_ref[ib]
    k_pos = ip * ps + jax.lax.broadcasted_iota(jnp.int32, (sq_p, ps), 1)
    q_pos = row + jax.lax.broadcasted_iota(jnp.int32, (sq_p, ps), 0)
    mask = k_pos <= q_pos

    # One grid step holds the whole page [ps, g, e]; each q head reads
    # its kv head's [ps, e] rows with a static index (a strided sublane
    # load — a one-head *block* of the page would break Mosaic's tiling).
    for i in range(h):
        j = i // rep
        q = q_ref[0, i].astype(jnp.float32) * scale   # [sq_p, e]
        k = k_ref[0, :, j, :].astype(jnp.float32)     # [ps, e]
        v = v_ref[0, :, j, :].astype(jnp.float32)     # [ps, ev]
        if quant:
            k = k * ks_ref[0, 0, j]
            v = v * vs_ref[0, 0, j]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [sq_p, ps]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[i]                             # [sq_p, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[i] = l_sc[i] * corr + p.sum(axis=1, keepdims=True)
        acc_sc[i] = acc_sc[i] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[i] = m_new

    @pl.when(ip == ppr - 1)
    def _flush():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pool, v_pool, *, page_tables, pos, k_scale=None,
                    v_scale=None, slot_mask=None, interpret=False):
    """Attention straight out of the page pool — no per-row gather.

    q: [b, sq, h, e]; pools [n_pages, ps, g, e] / [n_pages, ps, g, ev]
    (int8 with ``k_scale``/``v_scale`` [n_pages, g] f32, else float);
    page_tables: [b, ppr] int32 shard-local page ids (sentinel tails
    allowed); pos: [b] int32 first absolute position of each row's q.
    ``slot_mask`` [b] bool: masked-off rows emit zeros (their page
    tables may be stale). Returns [b, sq, h, ev] in q.dtype.

    Grid (row, page-table entry): each step stages one whole page (all
    kv heads) and updates every q head of the row, so a page is read
    once per row rather than once per head.
    """
    b, sq, h, e = q.shape
    n_pages, ps, g, ev = v_pool.shape
    rep = h // g
    scale = 1.0 / (e ** 0.5)
    quant = k_scale is not None

    pt = jnp.clip(page_tables.astype(jnp.int32), 0, n_pages - 1)
    ppr = pt.shape[1]
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    if slot_mask is not None:
        # masked rows: push the causal offset below every key position
        # so the row is fully masked (l == 0 → output exactly 0).
        pos = jnp.where(slot_mask, pos, -sq)

    sq_p = _ceil_to(sq, 8)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    qr = q.transpose(0, 2, 1, 3)                     # [b, h, sq_p, e]

    def page_map(ib, ip, pt_ref, pos_ref):
        return (pt_ref[ib, ip], 0, 0, 0)

    def scale_map(ib, ip, pt_ref, pos_ref):
        return (pt_ref[ib, ip], 0, 0)

    def row_map(ib, ip, pt_ref, pos_ref):
        return (ib, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, h, sq_p, e), row_map),
        pl.BlockSpec((1, ps, g, e), page_map),
        pl.BlockSpec((1, ps, g, ev), page_map),
    ]
    operands = [qr, k_pool, v_pool]
    if quant:
        in_specs += [
            pl.BlockSpec((1, 1, g), scale_map, memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g), scale_map, memory_space=pltpu.SMEM),
        ]
        # [n_pages, 1, g]: a page's scales are a whole trailing block
        operands += [k_scale.astype(jnp.float32)[:, None],
                     v_scale.astype(jnp.float32)[:, None]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, ppr),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, sq_p, ev), row_map),
        scratch_shapes=[
            pltpu.VMEM((h, sq_p, 1), jnp.float32),
            pltpu.VMEM((h, sq_p, 1), jnp.float32),
            pltpu.VMEM((h, sq_p, ev), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, h=h, rep=rep, ps=ps, sq_p=sq_p,
                          scale=scale, ppr=ppr, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, ev), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
    )(pt, pos, *operands)
    return out[:, :, :sq].transpose(0, 2, 1, 3)
