"""Vocab-sharded embedding + LM-head loss under manual SPMD.

The "data" mesh axis does double duty: it shards the batch *and* the
embedding/head vocab dim. Each data-rank therefore holds different tokens
AND a different vocab shard, so:

  * embed lookup: psum over "data" of masked local-window lookups;
  * loss: a *ring* over vocab shards — rotate the local head chunk around
    the data axis, maintaining streaming (m, l, label-logit) stats, then a
    second ring for dlogits → (dh, ring-reduced dW). Two rotations of the
    head per drained micro-batch, no [n, vocab] materialization;
  * embed grads: contributions to other ranks' rows are dispatched with a
    capacity-padded all_to_all (same machinery as MoE dispatch; capacity
    factor 2, drop counts surfaced in metrics).

When vocab % data_size != 0 (whisper's 51866) or under single-device smoke
tests, everything falls back to the exact replicated path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops

DATA = "data"


def vocab_shard(vocab: int, dsize: int) -> int | None:
    """Rows per shard, or None -> replicated."""
    if dsize > 1 and vocab % dsize == 0 and vocab // dsize >= 8:
        return vocab // dsize
    return None


# --------------------------------------------------------------------------- #
# Embedding
# --------------------------------------------------------------------------- #


def embed_lookup(table, ids, vloc: int | None, dtype):
    """table: [vloc|vocab, d] local; ids [b, s] int32 (per-rank tokens).

    Sharded path: each data-rank holds a different vocab window AND
    different tokens, so gather everyone's ids, serve lookups from the
    local window, psum, and slice back this rank's block.
    """
    if vloc is None:
        return table[ids].astype(dtype)
    r = jax.lax.axis_index(DATA)
    ids_all = jax.lax.all_gather(ids, DATA, axis=0, tiled=True)  # [D·b, s]
    lo = r * vloc
    loc = jnp.clip(ids_all - lo, 0, vloc - 1)
    hit = (ids_all >= lo) & (ids_all < lo + vloc)
    e = table[loc] * hit[..., None].astype(table.dtype)
    e = jax.lax.psum(e, DATA)
    b = ids.shape[0]
    return jax.lax.dynamic_slice_in_dim(e, r * b, b, 0).astype(dtype)


def embed_grad(ids, dx, vloc: int | None, vocab: int, acc):
    """Scatter-add dx into the (possibly sharded) table-grad accumulator.

    Sharded path: capacity-padded all_to_all dispatch to row owners.
    Returns (acc, n_dropped).
    """
    n = ids.size
    d = dx.shape[-1]
    idf = ids.reshape(n)
    dxf = dx.reshape(n, d).astype(acc.dtype)
    if vloc is None:
        return acc.at[idf].add(dxf), jnp.zeros((), jnp.int32)
    dsize = vocab // vloc
    dest = idf // vloc
    cap = max(8, -(-2 * n // dsize))
    oh = jax.nn.one_hot(dest, dsize, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - oh
    slot = (pos * oh).sum(-1)
    keep = slot < cap
    dropped = n - keep.sum()
    slot = jnp.where(keep, slot, cap)
    buf = jnp.zeros((dsize, cap + 1, d), acc.dtype)
    buf = buf.at[dest, slot].add(dxf)
    rbuf = jnp.zeros((dsize, cap + 1), jnp.int32)
    rbuf = rbuf.at[dest, slot].set(
        jnp.where(keep, idf % vloc + 1, 0)  # +1: 0 = empty slot
    )
    buf = jax.lax.all_to_all(buf[:, :cap], DATA, split_axis=0,
                             concat_axis=0, tiled=True)
    rbuf = jax.lax.all_to_all(rbuf[:, :cap], DATA, split_axis=0,
                              concat_axis=0, tiled=True)
    rows = rbuf.reshape(-1)
    vals = buf.reshape(-1, d)
    ok = rows > 0
    acc = acc.at[jnp.where(ok, rows - 1, vloc)].add(
        jnp.where(ok[:, None], vals, 0.0),
        mode="drop",
    )
    return acc, dropped.astype(jnp.int32)


# --------------------------------------------------------------------------- #
# Loss (final RMS/LayerNorm + softmax-xent) with explicit backward
# --------------------------------------------------------------------------- #


def _final_norm_fwd(cfg, io_p, h, norm_key="final_norm"):
    hf = h.astype(jnp.float32)
    scale = io_p[f"{norm_key}.scale"].astype(jnp.float32)
    if cfg.norm == "layernorm" and norm_key == "final_norm":
        mu = hf.mean(-1, keepdims=True)
        var = ((hf - mu) ** 2).mean(-1, keepdims=True)
        inv = jax.lax.rsqrt(var + 1e-5)
        hn = (hf - mu) * inv
        y = hn * scale + io_p["final_norm.bias"].astype(jnp.float32)
        return y, (hf, hn, inv, scale)  # layernorm path
    inv = jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True)
                        + cfg.norm_eps)
    return hf * inv * scale, (hf, hf * inv, inv, scale)


def _final_norm_bwd(cfg, res, dy, norm_key="final_norm"):
    hf, hn, inv, scale = res
    d = hf.shape[-1]
    dscale = (dy * hn).sum(axis=tuple(range(dy.ndim - 1)))
    g = dy * scale
    if cfg.norm == "layernorm" and norm_key == "final_norm":
        dbias = dy.sum(axis=tuple(range(dy.ndim - 1)))
        gm = g.mean(-1, keepdims=True)
        ghn = (g * hn).mean(-1, keepdims=True)
        dh = inv * (g - gm - hn * ghn)
        return dh, {"final_norm.scale": dscale, "final_norm.bias": dbias}
    dot = (g * hf).mean(-1, keepdims=True)
    dh = inv * g - hf * (inv ** 3) * dot
    return dh, {f"{norm_key}.scale": dscale}


def loss_and_dy(cfg, rc, io_p, h, labels, denom: float, vloc: int | None,
                dsize: int, norm_key: str = "final_norm", mask=None):
    """h: [n, d] final hiddens (one micro-batch, flattened), labels [n].

    Returns (loss_sum_scaled, dh, io_grad_deltas). ``denom`` is the global
    token count — gradients come out mean-normalized. ``mask`` [n] zeroes
    positions (MTP's last column); ``norm_key`` selects the pre-head norm.
    """
    if mask is None:
        mask = jnp.ones(h.shape[:1], jnp.float32)
    hn, res = _final_norm_fwd(cfg, io_p, h, norm_key)
    tied = cfg.tie_embeddings
    w = io_p["embed.table"] if tied else io_p["head.w"]
    n, d = h.shape

    if vloc is None:
        wl = (w.T if tied else w).astype(jnp.float32)  # [d, vocab]
        loss, (dhn, dw) = ops.softmax_xent(
            hn, wl, labels, chunk=rc.vocab_chunk, mask=mask,
            impl=rc.kernel_impl)
        # softmax_xent normalizes by the mask's count; this loss (and its
        # gradients) by the global token count
        rescale = jnp.maximum(mask.sum(), 1.0) / denom
        loss, dhn, dw = loss * rescale, dhn * rescale, dw * rescale
        dh, ng = _final_norm_bwd(cfg, res, dhn, norm_key)
        grads = dict(ng)
        key = "embed.table" if tied else "head.w"
        grads[key] = dw.T if tied else dw
        return loss, dh.astype(h.dtype), grads

    # ---- gather-tokens formulation --------------------------------------- #
    # Every data-rank holds a different vocab shard AND different tokens.
    # Gather all shards' tokens (all_gather over "data"), compute this
    # rank's vocab-shard logits for *all* tokens, psum-combine streaming
    # softmax stats, then dW is complete locally and dh psum-reduces.
    # Only all_gather/psum are used — they are group-local collectives and
    # therefore legal inside rank-conditional branches (DESIGN.md §3).
    lo = jax.lax.axis_index(DATA) * vloc
    hn_all = jax.lax.all_gather(hn, DATA, axis=0, tiled=True)  # [D·n, d]
    lab_all = jax.lax.all_gather(labels, DATA, axis=0, tiled=True)
    mask_all = jax.lax.all_gather(mask, DATA, axis=0, tiled=True)
    wl = (w.T if tied else w).astype(jnp.float32)              # [d, vloc]
    na = hn_all.shape[0]
    chunk = min(vloc, max(512, rc.vocab_chunk))
    nc = -(-vloc // chunk)
    pad_v = nc * chunk - vloc
    wl_p = jnp.pad(wl, ((0, 0), (0, pad_v)))

    idx = jnp.clip(lab_all - lo, 0, vloc - 1)
    inw = (lab_all >= lo) & (lab_all < lo + vloc)

    def p1(carry, ci):
        m, l, lab = carry
        wc = jax.lax.dynamic_slice(wl_p, (0, ci * chunk), (d, chunk))
        lg = hn_all @ wc
        col = ci * chunk + jnp.arange(chunk)
        valid = col < vloc
        lg = jnp.where(valid[None], lg, -jnp.inf)
        m_new = jnp.maximum(m, lg.max(-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.where(valid[None],
                                     jnp.exp(lg - m_safe[:, None]),
                                     0.0).sum(-1)
        inc = (idx >= ci * chunk) & (idx < (ci + 1) * chunk) & inw
        lv = jnp.take_along_axis(
            lg, jnp.clip(idx - ci * chunk, 0, chunk - 1)[:, None], 1)[:, 0]
        lab = jnp.where(inc, lv, lab)
        return (m_new, l_new, lab), None

    m0 = jnp.full((na,), -jnp.inf, jnp.float32)
    (m_loc, l_loc, lv_loc), _ = jax.lax.scan(
        p1, (m0, jnp.zeros((na,), jnp.float32),
             jnp.zeros((na,), jnp.float32)), jnp.arange(nc))
    m = jax.lax.pmax(m_loc, DATA)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    l = jax.lax.psum(l_loc * jnp.where(jnp.isfinite(m_loc),
                                       jnp.exp(m_loc - m_safe), 0.0), DATA)
    lab_logit = jax.lax.psum(jnp.where(inw, lv_loc, 0.0), DATA)
    lse = m_safe + jnp.log(jnp.maximum(l, 1e-30))
    # each rank reports the loss of its OWN tokens (avoids double count)
    r0 = jax.lax.axis_index(DATA)
    mine = jax.lax.dynamic_slice_in_dim(
        (lse - lab_logit) * mask_all, r0 * n, n, 0)
    loss = mine.sum() / denom

    def p2(carry, ci):
        dhn_all, dw = carry
        wc = jax.lax.dynamic_slice(wl_p, (0, ci * chunk), (d, chunk))
        lg = hn_all @ wc
        col = ci * chunk + jnp.arange(chunk)
        valid = col < vloc
        p = jnp.where(valid[None], jnp.exp(lg - lse[:, None]), 0.0)
        inc = (idx >= ci * chunk) & (idx < (ci + 1) * chunk) & inw
        oh = jax.nn.one_hot(jnp.clip(idx - ci * chunk, 0, chunk - 1),
                            chunk, dtype=jnp.float32) * inc[:, None]
        dlog = (p - oh) * mask_all[:, None] / denom
        dhn_all = dhn_all + dlog @ wc.T
        dw = jax.lax.dynamic_update_slice(
            dw, hn_all.T @ dlog, (0, ci * chunk))
        return (dhn_all, dw), None

    (dhn_all, dw_p), _ = jax.lax.scan(
        p2, (jnp.zeros((na, d), jnp.float32),
             jnp.zeros((d, nc * chunk), jnp.float32)), jnp.arange(nc))
    dw = dw_p[:, :vloc]
    dhn_all = jax.lax.psum(dhn_all, DATA)                       # [D·n, d]
    dhn = jax.lax.dynamic_slice_in_dim(dhn_all, r0 * n, n, 0)
    dh, ng = _final_norm_bwd(cfg, res, dhn, norm_key)
    grads = dict(ng)
    key = "embed.table" if tied else "head.w"
    grads[key] = dw.T if tied else dw
    return loss, dh.astype(h.dtype), grads


def serve_logits(cfg, rc, io_p, h, vloc: int | None):
    """Full next-token logits from final hiddens h [b, d] (float32).

    Replicated head: [b, vocab] for this rank's own rows. Sharded head:
    every data-rank gathers all rows and computes its vocab slice →
    [D·b, vloc] (globally [D·b, vocab] with the vocab axis on "data").
    Feeds the host-side sampling layer; greedy decoding never calls this.
    """
    hn, _ = _final_norm_fwd(cfg, io_p, h)
    tied = cfg.tie_embeddings
    w = io_p["embed.table"] if tied else io_p["head.w"]
    wl = (w.T if tied else w).astype(jnp.float32)
    if vloc is None:
        return hn @ wl
    hn_all = jax.lax.all_gather(hn, DATA, axis=0, tiled=True)
    return hn_all @ wl


def greedy_sample(cfg, rc, io_p, h, vloc: int | None):
    """Greedy next token from final hiddens h [b, d] (sharded head)."""
    hn, _ = _final_norm_fwd(cfg, io_p, h)
    tied = cfg.tie_embeddings
    w = io_p["embed.table"] if tied else io_p["head.w"]
    wl = (w.T if tied else w).astype(jnp.float32)
    logits = hn @ wl  # [b, vloc or vocab]
    if vloc is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # each data-rank holds different rows AND a different vocab shard:
    # gather rows, reduce the argmax across shards, slice own rows back
    b = hn.shape[0]
    r = jax.lax.axis_index(DATA)
    hn_all = jax.lax.all_gather(hn, DATA, axis=0, tiled=True)
    logits = hn_all @ wl                      # [D·b, vloc]
    lmax = logits.max(-1)
    lidx = jnp.argmax(logits, -1).astype(jnp.int32)
    gmax = jax.lax.pmax(lmax, DATA)
    lo = r * vloc
    cand = jnp.where(lmax >= gmax, lidx + lo, 0)
    tok_all = jax.lax.pmax(cand, DATA).astype(jnp.int32)
    return jax.lax.dynamic_slice_in_dim(tok_all, r * b, b, 0)
