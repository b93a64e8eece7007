"""Pallas TPU flash attention (forward), causal + GQA + MLA-style dims.

Hardware mapping (TPU v5e target):
  * grid = (batch·q_heads, n_q_blocks, n_k_blocks); the k-block axis is the
    minormost grid dim, so it iterates sequentially per (bh, iq) and the
    running (m, l, acc) live in VMEM scratch across those steps.
  * ``fwd_plan`` tiles a call from its shapes alone: each sequence in
    the fewest blocks of at most 1024 rows, evened out so that little of
    them is padding (multiples of 128, or of 16 for one block). A grid
    step costs about a microsecond of its own, so at seq 1024 on a v5e
    one block per head beat every finer tiling (PERF.md). Explicit
    ``block_q``/``block_k`` are honoured.
  * every k-block is computed under the element mask (``sk`` padding,
    and the diagonal when causal); no block is skipped.
  * MXU operands in the input dtype (bf16 in training), accumulation in
    f32: ``QKᵀ`` takes q and k as they arrive and is scaled in f32; ``PV``
    takes p cast to v's dtype. f32 inputs stay f32 end to end.
  * m and l are lane-dense ``(block_q, 128)`` f32 scratch, so no
    relayout runs per step.
  * VMEM: a 1024 × 1024 step holds its f32 ``s``/``p`` tiles (4 MiB
    each) beside double-buffered q/k/v blocks. Under the 48 MiB limit,
    forward and backward compile for a described v5e at seq 1024 with
    head widths e/ev 64/64, 96/96 and 192/128 (tests/test_tpu_compile.py)
    and 256/256, 512/512 and 576/512 (checked once by hand).
  * GQA: the K/V index map folds q-head → kv-head (h // rep) so grouped
    heads reuse the same K/V tiles.
  * separate value dim ``ev`` (MLA uses e=192, ev=128).

Validated in interpret mode against kernels/ref.py for a sweep of
shapes/dtypes in tests/test_kernels.py.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref

NEG_INF = -1e30
LANES = 128
MAX_BLOCK = 1024
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=48 * 1024 * 1024)


@dataclasses.dataclass(frozen=True)
class FwdPlan:
    """How one forward call is tiled: the blocks and the grid."""

    block_q: int
    block_k: int
    grid: tuple[int, int, int]


def _default_block(s: int) -> int:
    n = -(-s // MAX_BLOCK)                # fewest blocks of <= MAX_BLOCK
    align = 16 if n == 1 else LANES
    per_block = -(-s // n)
    return -(-per_block // align) * align


def fwd_plan(q_shape, k_shape, *, block_q=None, block_k=None) -> FwdPlan:
    """The forward's tiling for q [b, sq, h, e] and k [b, sk, g, e]: a
    pure function of the shapes."""
    b, sq, h, _ = q_shape
    sk = k_shape[1]
    block_q = block_q or _default_block(sq)
    block_k = block_k or _default_block(sk)
    return FwdPlan(block_q, block_k,
                   (b * h, -(-sq // block_q), -(-sk // block_k)))


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

    v = v_ref[0]                                    # [bk, ev]
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bk]

    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = k_pos < sk
    if causal:
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = mask & (k_pos <= q_pos)
    s = jnp.where(mask, s, NEG_INF)

    # k-block 0 holds a visible key for every row, so m is finite from the
    # first step on; a row's keys that are masked in a later step give
    # exp(NEG_INF - m) = 0 there.
    m_prev = m_sc[...]                                # [bq, 128]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * corr[:, :1] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(ik == n_k - 1)
    def _flush():
        o_ref[0] = (acc_sc[...] / l_sc[:, :1]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, q_offset=0, block_q=None,
                    block_k=None, interpret=False):
    """q: [b, sq, h, e]; k: [b, sk, g, e]; v: [b, sk, g, ev] → [b, sq, h, ev].

    q_offset shifts absolute q positions (decode windows); the kernel
    assumes q_offset == 0 for the causal mask when sq == sk (training) —
    decode uses ops.decode_attention instead. ``block_q``/``block_k``
    default to ``fwd_plan``'s choice for the shapes.

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
    plan = fwd_plan(q.shape, k.shape, block_q=block_q, block_k=block_k)
    block_q, block_k = plan.block_q, plan.block_k
    _, n_q, n_k = plan.grid

    # pad sequence dims to block multiples
    pq = -sq % block_q
    pk = -sk % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    sq_p, sk_p = sq + pq, sk + pk

    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq_p, e)
    kr = k.transpose(0, 2, 1, 3).reshape(b * g, sk_p, e)
    vr = v.transpose(0, 2, 1, 3).reshape(b * g, sk_p, ev)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q, block_k=block_k,
        sk=sk, scale=scale, n_k=n_k)

    out = pl.pallas_call(
        kernel,
        grid=plan.grid,
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
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running sum l
            pltpu.VMEM((block_q, ev), jnp.float32),     # output accumulator
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(qr, kr, vr)
    out = out.reshape(b, h, sq_p, ev).transpose(0, 2, 1, 3)
    return out[:, :sq]
