"""Pallas TPU fused chunked-vocab softmax cross-entropy (fwd + bwd).

The LM-head loss at 100k–256k vocabs is memory-bound if [n, vocab] logits
ever hit HBM. Two kernels stream vocab tiles through VMEM:

  pass 1: grid=(n_blocks, v_blocks) — logits tile = h·W tile on the MXU,
          running (m, l) and the label logit in VMEM scratch; emits
          per-row (lse, label_logit).
  pass 2: two kernels recompute the tile and form dlogits = softmax −
          onehot in VMEM. One accumulates dh over the (minor) vocab axis;
          the other, on the transposed grid, accumulates each dW tile over
          the (minor) row axis — on the chip an output block is only
          written back, never re-read, so each accumulation must run
          along the minor grid axis. Logits are never materialized
          outside VMEM.

Per-row vectors (labels, lse, label logit, loss scale) travel as
``[n, 1]`` columns with ``(block_n, 1)`` blocks: Mosaic tiles the last
two dims of every block, and 1-D ``(block_n,)`` blocks of a longer
vector do not match the layout XLA gives the operand.

Validated in interpret mode against kernels/ref.py:softmax_xent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# A v5e core has 128 MiB of VMEM; the 16 MiB default scoped limit is
# below what a resident f32 dW tile [d, block_v] plus the double-buffered
# h and W tiles need at d_model ~2k-4k.
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=48 * 1024 * 1024)


def _p1_kernel(h_ref, w_ref, lab_ref, lse_ref, labl_ref, m_sc, l_sc, ll_sc,
               *, block_v: int, vocab: int, n_v: int):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        ll_sc[...] = jnp.zeros_like(ll_sc)

    h = h_ref[...].astype(jnp.float32)          # [bn, d]
    w = w_ref[...].astype(jnp.float32)          # [d, bv]
    logits = jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # [bn, bv]
    col = iv * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    valid = col < vocab
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_sc[...]                           # [bn, 1]
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
    l_sc[...] = l_sc[...] * jnp.exp(m_prev - m_new) + p.sum(
        axis=1, keepdims=True)
    m_sc[...] = m_new

    lab = lab_ref[...]                           # [bn, 1]
    hit = (col == lab) & valid
    ll_sc[...] = ll_sc[...] + jnp.where(hit, logits, 0.0).sum(
        axis=1, keepdims=True)

    @pl.when(iv == n_v - 1)
    def _flush():
        lse_ref[...] = m_sc[...] + jnp.log(jnp.maximum(l_sc[...], 1e-30))
        labl_ref[...] = ll_sc[...]


def _dlogits(h_ref, w_ref, lab_ref, lse_ref, scale_ref, iv, *,
             block_v: int, vocab: int):
    """Recompute one logits tile; return (h, w, dlogits) in f32."""
    h = h_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    col = iv * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    valid = col < vocab
    lse = lse_ref[...]                            # [bn, 1]
    p = jnp.where(valid, jnp.exp(logits - lse), 0.0)
    oh = ((col == lab_ref[...]) & valid).astype(jnp.float32)
    return h, w, (p - oh) * scale_ref[...]        # dlog: [bn, bv]


def _p2_dh_kernel(h_ref, w_ref, lab_ref, lse_ref, scale_ref, dh_ref, dh_sc,
                  *, block_v: int, vocab: int, n_v: int):
    # grid (n_blocks, v_blocks): dh accumulates over the minor vocab axis
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        dh_sc[...] = jnp.zeros_like(dh_sc)

    _, w, dlog = _dlogits(h_ref, w_ref, lab_ref, lse_ref, scale_ref, iv,
                          block_v=block_v, vocab=vocab)
    dh_sc[...] = dh_sc[...] + jax.lax.dot_general(
        dlog, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(iv == n_v - 1)
    def _flush():
        dh_ref[...] = dh_sc[...].astype(dh_ref.dtype)


def _p2_dw_kernel(h_ref, w_ref, lab_ref, lse_ref, scale_ref, dw_ref, *,
                  block_v: int, vocab: int):
    # grid (v_blocks, n_blocks): the dW tile stays resident in VMEM while
    # the minor row axis accumulates into it, and is written back once.
    iv = pl.program_id(0)
    h, _, dlog = _dlogits(h_ref, w_ref, lab_ref, lse_ref, scale_ref, iv,
                          block_v=block_v, vocab=vocab)
    contrib = jax.lax.dot_general(
        h, dlog, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _dw0():
        dw_ref[...] = contrib

    @pl.when(pl.program_id(1) != 0)
    def _dwn():
        dw_ref[...] = dw_ref[...] + contrib


@functools.partial(jax.jit, static_argnames=("block_n", "block_v",
                                             "interpret"))
def softmax_xent(h, w_head, labels, *, mask=None, block_n=256,
                 block_v=512, interpret=False):
    """Same contract as ref.softmax_xent: (loss, (dh, dW))."""
    n, d = h.shape
    vocab = w_head.shape[1]
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    pn = -n % block_n
    pv = -vocab % block_v
    hp = jnp.pad(h, ((0, pn), (0, 0)))
    wp = jnp.pad(w_head, ((0, 0), (0, pv)))
    labp = jnp.pad(labels, ((0, pn),), constant_values=0)[:, None]
    np_, vp_ = n + pn, vocab + pv
    n_n, n_v = np_ // block_n, vp_ // block_v
    row_spec = pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))

    lse, labl = pl.pallas_call(
        functools.partial(_p1_kernel, block_v=block_v, vocab=vocab,
                          n_v=n_v),
        grid=(n_n, n_v),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            row_spec,
        ],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, 1), jnp.float32)] * 3,
        compiler_params=_PARAMS,
        interpret=interpret,
    )(hp, wp, labp)

    maskp = jnp.pad(mask, ((0, pn),))[:, None]
    loss = ((lse - labl) * maskp).sum() / denom
    scale = maskp / denom

    kw = dict(block_v=block_v, vocab=vocab)
    dh = pl.pallas_call(
        functools.partial(_p2_dh_kernel, n_v=n_v, **kw),
        grid=(n_n, n_v),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            row_spec, row_spec, row_spec,
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(hp, wp, labp, lse, scale)

    row_spec_t = pl.BlockSpec((block_n, 1), lambda j, i: (i, 0))
    dw = pl.pallas_call(
        functools.partial(_p2_dw_kernel, **kw),
        grid=(n_v, n_n),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((d, block_v), lambda j, i: (0, j)),
            row_spec_t, row_spec_t, row_spec_t,
        ],
        out_specs=pl.BlockSpec((d, block_v), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((d, vp_), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(hp, wp, labp, lse, scale)
    dw_full = dw[:, :vocab].astype(w_head.dtype)
    return loss, (dh[:n], dw_full)
