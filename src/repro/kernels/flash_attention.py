"""Pallas TPU flash attention (forward), causal + GQA + MLA-style dims.

Hardware mapping (TPU v5e target):
  * grid = (batch·q_heads, n_q_blocks, n_k_blocks); the k-block axis is the
    minormost grid dim, so it iterates sequentially per (bh, iq) and the
    running (m, l, acc) live in VMEM scratch across those steps.
  * BlockSpecs stage [block_q, e] of Q and [block_k, e] of K/V into VMEM;
    head dims are kept whole (128–576 ≤ VMEM budget), block sizes are
    multiples of 128 so the MXU sees aligned contractions.
  * GQA: the K/V index map folds q-head → kv-head (h // rep) so grouped
    heads reuse the same K/V tiles.
  * separate value dim ``ev`` (MLA uses e=192, ev=128).
  * accumulation in fp32 regardless of input dtype.

Validated in interpret mode against kernels/ref.py:attention for a sweep of
shapes/dtypes in tests/test_kernels.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                causal: bool, block_q: int, block_k: int, sk: int,
                scale: float, n_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32) * scale        # [bq, e]
    k = k_ref[0].astype(jnp.float32)                # [bk, e]
    v = v_ref[0].astype(jnp.float32)                # [bk, ev]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [bq, bk]

    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = k_pos < sk
    if causal:
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = mask & (k_pos <= q_pos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]                               # [bq]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=1)
    acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(ik == n_k - 1)
    def _flush():
        o_ref[0] = (
            acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, q_offset=0, block_q=128,
                    block_k=128, interpret=False):
    """q: [b, sq, h, e]; k: [b, sk, g, e]; v: [b, sk, g, ev] → [b, sq, h, ev].

    q_offset shifts absolute q positions (decode windows); the kernel
    assumes q_offset == 0 for the causal mask when sq == sk (training) —
    decode uses ops.decode_attention instead.

    Differentiable through a ``custom_vjp`` whose backward is the vjp of
    the jnp reference (``ref.attention``): the forward runs the Pallas
    kernel, the backward recomputes attention in XLA from the saved
    q/k/v. Each traced backward bumps the ``ref_attention_bwd`` kernel
    counter, so the reference backward is visible in
    ``Session.describe()["kernels"]``.
    """
    return _flash_vjp(q, k, v, causal, q_offset, block_q, block_k,
                      interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_vjp(q, k, v, causal, q_offset, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                      block_k=block_k, interpret=interpret)


def _flash_vjp_fwd(q, k, v, causal, q_offset, block_q, block_k, interpret):
    out = _flash_vjp(q, k, v, causal, q_offset, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_vjp_bwd(causal, q_offset, block_q, block_k, interpret, res, g):
    ops._count("ref_attention_bwd")
    _, vjp = jax.vjp(
        functools.partial(ref.attention, causal=causal, q_offset=q_offset),
        *res)
    return vjp(g)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def _flash_fwd(q, k, v, *, causal, block_q, block_k, interpret):
    b, sq, h, e = q.shape
    _, sk, g, ev = v.shape
    rep = h // g
    scale = 1.0 / (e ** 0.5)

    # pad sequence dims to block multiples
    pq = -sq % block_q
    pk = -sk % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    sq_p, sk_p = sq + pq, sk + pk
    n_q, n_k = sq_p // block_q, sk_p // block_k

    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq_p, e)
    kr = k.transpose(0, 2, 1, 3).reshape(b * g, sk_p, e)
    vr = v.transpose(0, 2, 1, 3).reshape(b * g, sk_p, ev)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
        sk=sk, scale=scale, n_k=n_k)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, e), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, e),
                         lambda bh, iq, ik, rep=rep: (bh // rep, ik, 0)),
            pl.BlockSpec((1, block_k, ev),
                         lambda bh, iq, ik, rep=rep: (bh // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, ev),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, ev), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),   # running max m
            pltpu.VMEM((block_q,), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, ev), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qr, kr, vr)
    out = out.reshape(b, h, sq_p, ev).transpose(0, 2, 1, 3)
    return out[:, :sq]
