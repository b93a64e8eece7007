"""Pallas TPU selective-scan (Mamba-1 diagonal SSM), chunked.

Hardware mapping: grid = (batch, d-blocks, chunks) with the chunk axis
minormost, so each (b, dblk) pair walks its chunks sequentially with the
[block_d, n] state held in VMEM scratch. Within a chunk the diagonal
recurrence is solved with the log-space cumulative-sum trick (exact
because dt·A ≤ 0), turning the sequential scan into VPU-friendly cumsums
plus one [chunk, n] contraction per block — this is the TPU-native
re-blocking of the CUDA kernel's warp-parallel scan (DESIGN.md §3).

Validated in interpret mode against kernels/ref.py:selective_scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, h_sc, *,
                 n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_sc[...] = jnp.zeros_like(h_sc)

    x = x_ref[0].astype(jnp.float32)        # [c, bd]
    dt = dt_ref[0].astype(jnp.float32)      # [c, bd]
    A = a_ref[...].astype(jnp.float32)      # [bd, n]
    Bm = b_ref[0].astype(jnp.float32)       # [c, n]
    Cm = c_ref[0].astype(jnp.float32)       # [c, n]
    Dd = d_ref[...].astype(jnp.float32)     # [bd]

    # h_t = a_t h_{t-1} + u_t, a_t = exp(dt·A) ∈ (0,1]; associative scan
    # keeps everything bounded (no exp(+cumsum) overflow).
    a = jnp.exp(dt[:, :, None] * A[None])   # [c, bd, n]
    u = dt[:, :, None] * Bm[:, None, :] * x[:, :, None]

    def comb(l, r):
        (la, lu), (ra, ru) = l, r
        return la * ra, lu * ra + ru

    A_cum, U_cum = jax.lax.associative_scan(comb, (a, u), axis=0)
    h_all = A_cum * h_sc[...][None] + U_cum      # [c, bd, n]
    y = jnp.einsum("cdn,cn->cd", h_all, Cm) + x * Dd[None]
    y_ref[0] = y.astype(y_ref.dtype)
    h_sc[...] = h_all[-1]


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def selective_scan(x, dt, A, B, C, D, *, chunk=128, block_d=256,
                   interpret=False):
    """Same contract as ref.selective_scan without state in/out (training
    only — ops.py routes stateful prefill to the reference, counted)."""
    b, s, d = x.shape
    n = A.shape[1]
    pc = -s % chunk
    pd = -d % block_d
    if pc:
        z2 = lambda a: jnp.pad(a, ((0, 0), (0, pc), (0, 0)))
        x, dt, B, C = z2(x), z2(dt), z2(B), z2(C)
    if pd:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pd)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pd)))
        A = jnp.pad(A, ((0, pd), (0, 0)))
        D = jnp.pad(D, ((0, pd),))
    sp, dp = s + pc, d + pd
    n_chunks, n_d = sp // chunk, dp // block_d

    out = pl.pallas_call(
        functools.partial(_scan_kernel, n_chunks=n_chunks),
        grid=(b, n_d, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d),
                         lambda ib, idb, ic: (ib, ic, idb)),
            pl.BlockSpec((1, chunk, block_d),
                         lambda ib, idb, ic: (ib, ic, idb)),
            pl.BlockSpec((block_d, n), lambda ib, idb, ic: (idb, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, idb, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, idb, ic: (ib, ic, 0)),
            pl.BlockSpec((block_d,), lambda ib, idb, ic: (idb,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d),
                               lambda ib, idb, ic: (ib, ic, idb)),
        out_shape=jax.ShapeDtypeStruct((b, sp, dp), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, B, C, D)
    return out[:, :s, :d]
