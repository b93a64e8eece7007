"""Pure-jnp oracles for every compute hot-spot.

These are (a) the CPU execution path, (b) the numerical ground truth each
Pallas kernel is validated against, and (c) written blockwise/streaming so
their memory behaviour matches the TPU kernels (no O(S²) materialization),
which keeps the dry-run's compiled memory analysis honest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------------- #
# Attention (flash-style streaming softmax, causal / bidirectional, GQA)
# --------------------------------------------------------------------------- #


def _kv_blocks(k, v, block_k):
    """k and v in f32, padded to whole blocks of ``block_k`` rows and
    stacked block first, with each block's index: the scans' ``xs``."""
    b, sk, g, e = k.shape
    n_blocks = -(-sk // block_k)
    pad = n_blocks * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kf = k.astype(jnp.float32).reshape(b, n_blocks, block_k, g, e)
    vf = v.astype(jnp.float32).reshape(b, n_blocks, block_k, g, v.shape[-1])
    return jnp.moveaxis(kf, 1, 0), jnp.moveaxis(vf, 1, 0), jnp.arange(n_blocks)


def _block_scores(qf, kb, blk_idx, q_pos, sk, causal):
    """One k-block's scores ``[b, h, sq, block_k]``, -inf where masked
    (above the diagonal, or sk padding), and the mask."""
    sq, h = qf.shape[1], qf.shape[2]
    block_k = kb.shape[1]
    k_pos = blk_idx * block_k + jnp.arange(block_k)
    kb_h = jnp.repeat(kb, h // kb.shape[2], axis=2)  # [b, block_k, h, e]
    s = jnp.einsum("bqhe,bkhe->bhqk", qf, kb_h.astype(jnp.float32))
    mask = k_pos[None, :] <= q_pos[..., :, None] if causal else (
        k_pos[None, :] >= 0
    ) & jnp.ones((sq, 1), bool)
    valid = k_pos < sk  # mask out sk padding
    mask = mask & valid[None, :]
    # [sq, bk] -> [1, 1, sq, bk]; per-row [b, sq, bk] -> [b, 1, sq, bk]
    mask = mask[:, None] if mask.ndim == 3 else mask[None, None]
    return jnp.where(mask, s, -jnp.inf), mask


def _online_softmax(qf, blocks, q_pos, sk, causal):
    """The streaming softmax over k-blocks: each row's running max ``m``,
    normaliser ``l`` and unnormalised output ``acc`` (f32)."""
    b, sq, h, _ = qf.shape
    ev = blocks[1].shape[-1]  # may differ from e (e.g. MLA)

    def body(carry, blk):
        m, l, acc = carry
        kb, vb, blk_idx = blk  # kb/vb: [b, block_k, g, e]
        s, mask = _block_scores(qf, kb, blk_idx, q_pos, sk, causal)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        vb_h = jnp.repeat(vb, h // vb.shape[2], axis=2).astype(jnp.float32)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhe->bhqe", p, vb_h
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, ev), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), blocks)
    return m, l, acc


def attention(
    q: jnp.ndarray,  # [b, sq, h, e]
    k: jnp.ndarray,  # [b, sk, g, e]   g == kv heads, h % g == 0
    v: jnp.ndarray,  # [b, sk, g, e]
    *,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    block_k: int = 512,
    scale: float | None = None,
) -> jnp.ndarray:
    """Streaming-softmax attention; O(sq * block_k) live memory.

    ``q_offset`` is the absolute position of q[0] (used for decode where
    sq << sk); a ``[b]`` vector gives each batch row its own offset
    (slotted serving, where every slot sits at a different position).
    Accumulation in f32 regardless of input dtype.
    """
    sq, e = q.shape[1], q.shape[3]
    scale = scale if scale is not None else (1.0 / e ** 0.5)
    qf = (q * scale).astype(jnp.float32)
    off = jnp.asarray(q_offset)
    per_row = off.ndim == 1  # [b] vector: per-slot absolute positions
    q_pos = jnp.arange(sq) + (off[:, None] if per_row else off)
    _, l, acc = _online_softmax(qf, _kv_blocks(k, v, block_k), q_pos,
                                k.shape[1], causal)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhqe->bqhe", out).astype(q.dtype)


def attention_bwd(q, k, v, do, *, causal=True, q_offset=0, block_k=512):
    """The gradient of causal or full attention (``attention`` with a
    static ``q_offset``) by k-blocks, as flash attention's backward
    takes it: one pass recomputes the output and each row's
    log-sum-exp; the second takes each block's scores again and returns
    its dk and dv, accumulating dq. Nothing a block makes outlives it,
    and nothing but the first pass runs before ``do`` is known.
    Returns (dq, dk, dv) in the dtypes of (q, k, v)."""
    b, sq, h, e = q.shape
    sk, g = k.shape[1], k.shape[2]
    rep = h // g
    scale = 1.0 / e ** 0.5
    blocks = _kv_blocks(k, v, block_k)
    qf = q.astype(jnp.float32) * scale                 # [b, sq, h, e]
    q_pos = jnp.arange(sq) + q_offset
    m, l, acc = _online_softmax(qf, blocks, q_pos, sk, causal)
    l = jnp.maximum(l, 1e-30)
    lse = jnp.where(jnp.isfinite(m), m, 0.0) + jnp.log(l)
    dof = jnp.einsum("bqhe->bhqe", do.astype(jnp.float32))
    delta = (dof * acc / l[..., None]).sum(-1)         # rowsum(do * o)

    def backward(dq, blk):
        kb, vb, j = blk
        s, mask = _block_scores(qf, kb, j, q_pos, sk, causal)
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        dv = jnp.einsum("bhqk,bhqe->bkhe", p, dof)
        dp = jnp.einsum("bhqe,bkhe->bhqk", dof, jnp.repeat(vb, rep, axis=2))
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhqk,bkhe->bqhe", ds,
                             jnp.repeat(kb, rep, axis=2))
        dk = jnp.einsum("bhqk,bqhe->bkhe", ds, qf)
        group = lambda a: a.reshape(a.shape[:2] + (g, rep, a.shape[-1])  # noqa
                                    ).sum(3)
        return dq, (group(dk), group(dv))

    dq, (dk, dv) = jax.lax.scan(
        backward, jnp.zeros((b, sq, h, e), jnp.float32), blocks)
    unblock = lambda a: a.swapaxes(0, 1).reshape(  # noqa: E731
        (b, -1) + a.shape[3:])[:, :sk]
    return ((dq * scale).astype(q.dtype), unblock(dk).astype(k.dtype),
            unblock(dv).astype(v.dtype))


def attention_naive(q, k, v, *, causal=True, q_offset=0, scale=None):
    """O(S²) reference-of-the-reference for small-shape validation."""
    b, sq, h, e = q.shape
    _, sk, g, _ = k.shape
    rep = h // g
    scale = scale if scale is not None else (1.0 / e ** 0.5)
    kh = jnp.repeat(k, rep, axis=2)
    vh = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhe,bkhe->bhqk", q * scale, kh).astype(jnp.float32)
    if causal:
        q_pos = jnp.arange(sq) + q_offset
        mask = jnp.arange(sk)[None, :] <= q_pos[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhe->bqhe", p, vh).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len=None, scale=None):
    """Single-token decode attention. q: [b, 1, h, e], caches [b, S, g, e].

    ``cache_len``: number of valid cache positions (scalar or [b]).
    Returns [b, 1, h, e] plus (m, l) stats for cross-shard combination.
    """
    b, sq, h, e = q.shape
    _, S, g, _ = k_cache.shape
    rep = h // g
    scale = scale if scale is not None else (1.0 / e ** 0.5)
    kh = jnp.repeat(k_cache, rep, axis=2)
    vh = jnp.repeat(v_cache, rep, axis=2)
    s = jnp.einsum("bqhe,bkhe->bhqk", (q * scale).astype(jnp.float32), kh.astype(jnp.float32))
    if cache_len is not None:
        valid = jnp.arange(S)[None, :] < jnp.reshape(cache_len, (-1, 1))
        s = jnp.where(valid[:, None, None], s, -jnp.inf)
    m = s.max(axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhqk,bkhe->bhqe", p, vh.astype(jnp.float32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhqe->bqhe", out).astype(q.dtype), (m, l, acc)


def paged_gather(pool, page_tables, scale=None):
    """Materialize per-row KV from a page pool.

    pool: [n_pages, ps, ...]; page_tables: [b, ppr] int32 (sentinel tail
    ids allowed — callers mask those positions causally); scale:
    optional [n_pages, ...head-dims] per-page dequant scales for int8
    pools. Returns [b, ppr*ps, ...] in f32 when dequantizing, else the
    pool dtype.
    """
    pt = jnp.clip(page_tables.astype(jnp.int32), 0, pool.shape[0] - 1)
    g = jnp.take(pool, pt, axis=0)  # [b, ppr, ps, ...]
    if scale is not None:
        sg = jnp.take(scale.astype(jnp.float32), pt, axis=0)  # [b, ppr, ...]
        sg = sg.reshape(sg.shape[:2] + (1,) + sg.shape[2:] + (1,))
        g = g.astype(jnp.float32) * sg
    return g.reshape((pt.shape[0], -1) + pool.shape[2:])


def paged_attention(q, k_pool, v_pool, *, page_tables, pos, k_scale=None,
                    v_scale=None, slot_mask=None, block_k=512, scale=None):
    """Oracle for the paged Pallas kernel: gather + dequant + attention.

    q: [b, sq, h, e]; pools [n_pages, ps, g, e/ev] (int8 with
    k_scale/v_scale [n_pages, g]); page_tables [b, ppr]; pos [b] int32
    absolute position of q[:, 0]. Sentinel tail pages are masked by the
    exact causal mask (their logical positions exceed pos). slot_mask
    [b] bool zeroes masked-off rows.
    """
    sq = q.shape[1]
    k = paged_gather(k_pool, page_tables, k_scale)
    v = paged_gather(v_pool, page_tables, v_scale)
    off = jnp.asarray(pos, jnp.int32).reshape(-1)
    if slot_mask is not None:
        off = jnp.where(slot_mask, off, -sq)
    return attention(q, k, v, causal=True, q_offset=off, block_k=block_k,
                     scale=scale)


def combine_decode_shards(partials):
    """Flash-decoding combine: merge per-shard (m, l, acc) stats.

    partials: list of (m, l, acc) with m,l [b,h,1], acc [b,h,1,e].
    """
    m = functools.reduce(jnp.maximum, [p[0] for p in partials])
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    l = sum(
        p_l * jnp.where(jnp.isfinite(p_m), jnp.exp(p_m - m_safe), 0.0)
        for p_m, p_l, _ in partials
    )
    acc = sum(
        p_acc * jnp.where(jnp.isfinite(p_m), jnp.exp(p_m - m_safe), 0.0)[..., None]
        for p_m, _, p_acc in partials
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhqe->bqhe", out)


# --------------------------------------------------------------------------- #
# Selective scan (Mamba-1 diagonal SSM), chunked
# --------------------------------------------------------------------------- #


def selective_scan(
    x: jnp.ndarray,      # [b, s, d]      (post-conv activations)
    dt: jnp.ndarray,     # [b, s, d]      (softplus'd timestep)
    A: jnp.ndarray,      # [d, n]         (negative; A = -exp(A_log))
    B: jnp.ndarray,      # [b, s, n]
    C: jnp.ndarray,      # [b, s, n]
    D: jnp.ndarray,      # [d]
    *,
    chunk: int = 256,
    h0: jnp.ndarray | None = None,  # [b, d, n] initial state
    return_state: bool = False,
):
    """y_t = C_t · h_t + D x_t with h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t.

    Chunked: within a chunk the diagonal recurrence is solved with a
    log-space cumulative sum; chunks are chained with a [b, d, n] state.
    """
    b, s, d = x.shape
    n = A.shape[1]
    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))

    xf = x.astype(jnp.float32).reshape(b, nc, chunk, d)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, d)
    Bf = B.astype(jnp.float32).reshape(b, nc, chunk, n)
    Cf = C.astype(jnp.float32).reshape(b, nc, chunk, n)
    Af = A.astype(jnp.float32)

    def body(h, blk):
        xc, dtc, Bc, Cc = blk  # [b, chunk, ...]
        # h_t = a_t h_{t-1} + u_t with a_t = exp(dt_t A) ∈ (0, 1]:
        # solved with a numerically-safe associative scan (no exp(+G)).
        a = jnp.exp(dtc[..., None] * Af[None, None])          # [b,c,d,n]
        u = dtc[..., None] * Bc[:, :, None, :] * xc[..., None]

        def comb(l, r):
            (la, lu), (ra, ru) = l, r
            return la * ra, lu * ra + ru

        A_cum, U_cum = jax.lax.associative_scan(comb, (a, u), axis=1)
        h_all = A_cum * h[:, None] + U_cum  # [b, c, d, n]
        y = jnp.einsum("bcdn,bcn->bcd", h_all, Cc)
        return h_all[:, -1], y

    h = (
        h0.astype(jnp.float32)
        if h0 is not None
        else jnp.zeros((b, d, n), jnp.float32)
    )
    h, ys = jax.lax.scan(
        body,
        h,
        (
            jnp.moveaxis(xf, 1, 0),
            jnp.moveaxis(dtf, 1, 0),
            jnp.moveaxis(Bf, 1, 0),
            jnp.moveaxis(Cf, 1, 0),
        ),
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * chunk, d)[:, :s]
    y = y + x[:, :s].astype(jnp.float32) * D[None, None]
    y = y.astype(x.dtype)
    if return_state:
        return y, h
    return y


def selective_scan_step(h, x, dt, A, B, C, D):
    """One decode step. h: [b, d, n]; x, dt: [b, d]; B, C: [b, n]."""
    g = jnp.exp(dt[..., None] * A[None])  # [b, d, n]
    h_new = g * h + dt[..., None] * B[:, None, :] * x[..., None]
    y = jnp.einsum("bdn,bn->bd", h_new, C) + D[None] * x
    return h_new, y.astype(x.dtype)


# --------------------------------------------------------------------------- #
# mLSTM (xLSTM matrix-memory cell), chunkwise-parallel
# --------------------------------------------------------------------------- #


def mlstm_chunkwise(
    q: jnp.ndarray,   # [b, s, h, e]
    k: jnp.ndarray,   # [b, s, h, e]
    v: jnp.ndarray,   # [b, s, h, e]
    i_gate: jnp.ndarray,  # [b, s, h]  (pre-exp log input gate)
    f_gate: jnp.ndarray,  # [b, s, h]  (pre-sigmoid forget gate logits)
    *,
    chunk: int = 128,
    state: tuple | None = None,
    return_state: bool = False,
):
    """Chunkwise mLSTM: within-chunk quadratic, cross-chunk O(e²) state.

    Stabilized per the xLSTM paper with a running max-log-gate m.
    """
    b, s, h, e = q.shape
    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        z = lambda a, cv=0.0: jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2),
            constant_values=cv)
        q, k, v = z(q), z(k), z(v)
        # padded steps must be identity for the carried state:
        # i → -inf (no write), f-logit → +inf (log-sigmoid 0, no decay)
        i_gate = z(i_gate, -1e30)
        f_gate = z(f_gate, 1e30)

    qf = q.astype(jnp.float32).reshape(b, nc, chunk, h, e) * (e ** -0.5)
    kf = k.astype(jnp.float32).reshape(b, nc, chunk, h, e)
    vf = v.astype(jnp.float32).reshape(b, nc, chunk, h, e)
    ig = i_gate.astype(jnp.float32).reshape(b, nc, chunk, h)
    fg = jax.nn.log_sigmoid(f_gate.astype(jnp.float32)).reshape(b, nc, chunk, h)

    def body(carry, blk):
        # Stabilized chunkwise form. State is stored pre-scaled by exp(-m):
        #   C_hat = C * exp(-m),  n_hat = n * exp(-m).
        C, nrm, m = carry  # C: [b,h,e,e], nrm: [b,h,e], m: [b,h]
        qc, kc, vc, ic, fc = blk
        c = qc.shape[1]
        F = jnp.cumsum(fc, axis=1)  # [b, c, h] inclusive cumulative log-f
        # per-position stabilizer m_t = max(m_prev + F_t, F_t + cummax(i_j - F_j))
        Mi = jax.lax.cummax(ic - F, axis=1)  # [b, c, h]
        m_t = jnp.maximum(m[:, None] + F, F + Mi)  # [b, c, h]
        # old-state contribution, weight exp(m_prev + F_t - m_t)
        w_old = jnp.exp(m[:, None] + F - m_t)  # [b, c, h]
        out_inter = (
            jnp.einsum("bche,bhef->bchf", qc, C) * w_old[..., None]
        )
        nrm_inter = jnp.einsum("bche,bhe->bch", qc, nrm) * w_old
        # intra-chunk pair weights w_tj = exp(F_t - F_j + i_j - m_t), j <= t
        lw = (
            F[:, :, None, :] - F[:, None, :, :] + ic[:, None, :, :]
            - m_t[:, :, None, :]
        )  # [b, t, j, h]
        cpos = jnp.arange(c)
        causal_m = (cpos[None, :] <= cpos[:, None])[None, :, :, None]
        w_pair = jnp.where(causal_m, jnp.exp(lw), 0.0)
        sc = jnp.einsum("bche,bjhe->bcjh", qc, kc)  # [b, t, j, h]
        sw = sc * w_pair
        out_intra = jnp.einsum("bcjh,bjhe->bche", sw, vc)
        nrm_intra = sw.sum(axis=2)  # [b, t, h]
        nrm_t = nrm_inter + nrm_intra
        denom = jnp.maximum(jnp.abs(nrm_t), jnp.exp(-m_t))
        yc = (out_inter + out_intra) / denom[..., None]
        # new state at chunk end
        m_end = m_t[:, -1]  # [b, h]
        decay = jnp.exp(m + F[:, -1] - m_end)  # [b, h]
        w_j = jnp.exp(F[:, -1:, :] - F + ic - m_end[:, None])  # [b, c, h]
        C_new = C * decay[..., None, None] + jnp.einsum(
            "bche,bchf,bch->bhef", kc, vc, w_j
        )
        nrm_new = nrm * decay[..., None] + jnp.einsum(
            "bche,bch->bhe", kc, w_j
        )
        return (C_new, nrm_new, m_end), yc

    if state is None:
        C0 = jnp.zeros((b, h, e, e), jnp.float32)
        n0 = jnp.zeros((b, h, e), jnp.float32)
        m0 = jnp.zeros((b, h), jnp.float32)
    else:
        C0, n0, m0 = state
    (C, nrm, m), ys = jax.lax.scan(
        body,
        (C0, n0, m0),
        (
            jnp.moveaxis(qf, 1, 0),
            jnp.moveaxis(kf, 1, 0),
            jnp.moveaxis(vf, 1, 0),
            jnp.moveaxis(ig, 1, 0),
            jnp.moveaxis(fg, 1, 0),
        ),
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nc * chunk, h, e)[:, :s]
    if return_state:
        return y.astype(q.dtype), (C, nrm, m)
    return y.astype(q.dtype)


def mlstm_step(state, q, k, v, i_gate, f_gate):
    """One decode step. q/k/v: [b, h, e]; gates [b, h]."""
    C, nrm, m = state
    e = q.shape[-1]
    qf = q.astype(jnp.float32) * (e ** -0.5)
    lf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))
    m_new = jnp.maximum(lf + m, i_gate.astype(jnp.float32))
    i_w = jnp.exp(i_gate - m_new)
    decay = jnp.exp(lf + m - m_new)
    C_new = C * decay[..., None, None] + jnp.einsum(
        "bhe,bhf,bh->bhef", k.astype(jnp.float32), v.astype(jnp.float32), i_w
    )
    n_new = nrm * decay[..., None] + k.astype(jnp.float32) * i_w[..., None]
    num = jnp.einsum("bhe,bhef->bhf", qf, C_new)
    den = jnp.maximum(
        jnp.abs(jnp.einsum("bhe,bhe->bh", qf, n_new)), jnp.exp(-m_new)
    )
    y = num / den[..., None]
    return (C_new, n_new, m_new), y.astype(q.dtype)


# --------------------------------------------------------------------------- #
# sLSTM (scalar-memory cell with exponential gating), sequential scan
# --------------------------------------------------------------------------- #


def slstm_scan(
    x_gates: jnp.ndarray,  # [b, s, h, 4, e] pre-activations (i, f, z, o)
    *,
    state: tuple | None = None,
    return_state: bool = False,
):
    """sLSTM recurrence (no recurrent weights — block-diagonal simplification
    with R=0 keeps the cell exactly computable as a scan; the recurrent-R
    variant is noted in DESIGN.md as a deviation)."""
    b, s, h, _, e = x_gates.shape
    xg = x_gates.astype(jnp.float32)

    def body(carry, g):
        c, n, m = carry  # [b, h, e] each
        gi, gf, gz, go = g[:, :, 0], g[:, :, 1], g[:, :, 2], g[:, :, 3]
        lf = jax.nn.log_sigmoid(gf)
        m_new = jnp.maximum(lf + m, gi)
        i_w = jnp.exp(gi - m_new)
        f_w = jnp.exp(lf + m - m_new)
        c_new = f_w * c + i_w * jnp.tanh(gz)
        n_new = f_w * n + i_w
        y = jax.nn.sigmoid(go) * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, m_new), y

    if state is None:
        z = jnp.zeros((b, h, e), jnp.float32)
        state = (z, z, z)
    state, ys = jax.lax.scan(body, state, jnp.moveaxis(xg, 1, 0))
    y = jnp.moveaxis(ys, 0, 1).astype(x_gates.dtype)
    if return_state:
        return y, state
    return y


# --------------------------------------------------------------------------- #
# Chunked-vocab softmax cross-entropy (fwd + explicit bwd)
# --------------------------------------------------------------------------- #


def softmax_xent(
    h: jnp.ndarray,        # [n, d] final hiddens
    w_head: jnp.ndarray,   # [d, vocab]
    labels: jnp.ndarray,   # [n] int32
    *,
    chunk: int = 8192,
    mask: jnp.ndarray | None = None,  # [n] 1.0 = count this token
):
    """Returns (mean loss, (dh, dW)) without materializing [n, vocab].

    The backward is hand-derived: dlogits = softmax - onehot, streamed over
    vocab chunks; this also serves as the oracle for the fused_xent kernel.
    """
    n, d = h.shape
    vocab = w_head.shape[1]
    nc = -(-vocab // chunk)
    padded = nc * chunk
    # pad the head so chunk slices never clamp (dynamic_slice clamps OOB
    # starts, which would double-count the tail columns)
    w_pad = jnp.pad(w_head, ((0, 0), (0, padded - vocab)))
    hf = h.astype(jnp.float32)
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)

    def pass1(carry, ci):
        m, l, corr = carry
        lo = ci * chunk
        wc = jax.lax.dynamic_slice(w_pad, (0, lo), (d, chunk))
        logits = hf @ wc.astype(jnp.float32)  # [n, chunk]
        col = lo + jnp.arange(chunk)
        valid = col < vocab
        logits = jnp.where(valid[None], logits, -jnp.inf)
        m_new = jnp.maximum(m, logits.max(axis=1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        l_new = l * jnp.exp(jnp.where(jnp.isfinite(m), m, m_safe) - m_safe) + jnp.where(
            valid[None], jnp.exp(logits - m_safe[:, None]), 0.0
        ).sum(axis=1)
        # label logit: grab if in this chunk
        in_chunk = (labels >= lo) & (labels < lo + chunk)
        idx = jnp.clip(labels - lo, 0, chunk - 1)
        lab_logit = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        corr = jnp.where(in_chunk, lab_logit, corr)
        return (m_new, l_new, corr), None

    m0 = jnp.full((n,), -jnp.inf, jnp.float32)
    (m, l, lab), _ = jax.lax.scan(
        pass1, (m0, jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32)),
        jnp.arange(nc),
    )
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    loss_tok = (lse - lab) * mask
    loss = loss_tok.sum() / denom

    def pass2(carry, ci):
        dh = carry
        lo = ci * chunk
        wc = jax.lax.dynamic_slice(w_pad, (0, lo), (d, chunk))
        logits = hf @ wc.astype(jnp.float32)
        col = lo + jnp.arange(chunk)
        valid = col < vocab
        p = jnp.where(valid[None], jnp.exp(logits - lse[:, None]), 0.0)
        onehot = (labels[:, None] == col[None]).astype(jnp.float32)
        dlog = (p - onehot) * (mask / denom)[:, None]  # [n, chunk]
        dh = dh + dlog @ wc.astype(jnp.float32).T
        dwc = hf.T @ dlog  # [d, chunk]
        return dh, dwc

    dh, dws = jax.lax.scan(pass2, jnp.zeros((n, d), jnp.float32), jnp.arange(nc))
    dw = jnp.moveaxis(dws, 0, 1).reshape(d, padded)[:, :vocab]
    return loss, (dh.astype(h.dtype), dw.astype(w_head.dtype))


def softmax_xent_naive(h, w_head, labels, mask=None):
    logits = h.astype(jnp.float32) @ w_head.astype(jnp.float32)
    if mask is None:
        mask = jnp.ones(h.shape[:1], jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=1)
    lab = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    return ((lse - lab) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
