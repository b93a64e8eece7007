"""Grouped matrix product over the experts held here (Pallas TPU).

``lhs`` [m, k] holds token rows sorted by expert, ``group_sizes`` [E]
how many rows each expert has, in order; ``rhs`` [E, k, n] the experts'
weights. Row r of the result is ``lhs[r] @ rhs[expert of r]``. Rows past
``sum(group_sizes)`` are left as the kernel leaves them (not written):
the caller masks them.

The kernels are JAX's megablox ``gmm`` and ``tgmm``
(``jax.experimental.pallas.ops.tpu.megablox``), called from jitted
wrappers of this module's own, so a device trace names them
``_expert_gmm`` (forward and dx) and ``_expert_tgmm`` (dW). The
backward is this module's ``custom_vjp``: dx is a grouped product with
the weights transposed, dW a transposed grouped product per expert.

Tiling (TPU v5e): a grid step covers ``TM`` rows of one group. Only the
tiles that hold a group's rows run, so a group is padded to ``TM`` and
never to a capacity. In the forward and dx a weight block spans the
whole contraction, so each expert's block is read once per output tile
column as its rows pass; dW holds an output block per expert in f32.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the megablox module (its package re-exports a function of that name)
_MB = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

TM = 128                  # rows a grid step covers
_RHS_BLOCK = 6 << 20      # bytes of one weight block in the forward


def rows(m: int) -> int:
    """``m`` rounded up to the kernel's row tile."""
    return -(-m // TM) * TM


def _cols(k: int, n: int, itemsize: int, budget: int) -> int:
    """The widest output column tile (a multiple of 128 that divides
    ``n``, or ``n`` itself) whose ``k`` x cols block fits ``budget``."""
    if n % 128:
        return n
    best = 128
    for c in range(128, n + 1, 128):
        if n % c == 0 and k * c * itemsize <= budget:
            best = c
    return best


def _gmm_tiling(k, n, itemsize):
    return TM, k, _cols(k, n, itemsize, _RHS_BLOCK)


def _tgmm_tiling(k, n):
    # the f32 accumulator of a (tk, tn) output block stays in VMEM
    tk = k if k % 128 else min(k, 512)
    while k % tk:
        tk -= 128
    return TM, tk, _cols(tk, n, 4, _RHS_BLOCK)


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def _expert_gmm(lhs, rhs, group_sizes, *, transpose_rhs, interpret):
    k = lhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return _MB.gmm.__wrapped__(
        lhs, rhs, group_sizes, lhs.dtype,
        _gmm_tiling(k, n, rhs.dtype.itemsize),
        transpose_rhs=transpose_rhs, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _expert_tgmm(lhs, dy, group_sizes, *, interpret):
    k, n = lhs.shape[1], dy.shape[1]
    return _MB.tgmm.__wrapped__(
        lhs.swapaxes(0, 1), dy, group_sizes, lhs.dtype,
        _tgmm_tiling(k, n), num_actual_groups=group_sizes.shape[0],
        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def expert_gmm(lhs, rhs, group_sizes, interpret=False):
    """lhs [m, k] (``m`` a multiple of ``TM``), rhs [E, k, n],
    group_sizes [E] int32 -> [m, n] in lhs's dtype."""
    return _expert_gmm(lhs, rhs, group_sizes, transpose_rhs=False,
                       interpret=interpret)


def _fwd(lhs, rhs, group_sizes, interpret):
    return expert_gmm(lhs, rhs, group_sizes, interpret), \
        (lhs, rhs, group_sizes)


def _bwd(interpret, res, dy):
    lhs, rhs, group_sizes = res
    dy = dy.astype(lhs.dtype)
    dx = _expert_gmm(dy, rhs, group_sizes, transpose_rhs=True,
                     interpret=interpret)
    dw = _expert_tgmm(lhs, dy, group_sizes, interpret=interpret)
    return dx, dw.astype(rhs.dtype), None


expert_gmm.defvjp(_fwd, _bwd)


def reference(lhs, rhs, group_sizes):
    """The same product by ``lax.ragged_dot``; rows past the groups are 0."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)
