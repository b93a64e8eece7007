"""Transformer / MoE / SSM / xLSTM blocks written against the ZeroPP tape.

Every parameterized GEMM goes through ``Tape.dense`` (deferred dW → the W
task); everything else is a generic prim (immediate small grads in B).
Each block also has a ``*_decode`` pure-jnp variant for cached serving.

Naming: params are flat dicts; a layer's params are prefixed ``L{j}.``
by the stage assembly in model.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.tape import Tape, TVal
from repro.kernels import expert_gmm, ops
from repro.models.common import (
    MLACfg,
    ModelConfig,
    ParamSpec,
    RunConfig,
    apply_rope,
)

# --------------------------------------------------------------------------- #
# Context threaded through layer application
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class LayerCtx:
    cfg: ModelConfig
    rc: RunConfig
    rope: dict[int, tuple[jnp.ndarray, jnp.ndarray]]  # head_dim -> (cos, sin)
    causal: bool = True
    ep_axis: str | None = None       # all_to_all axis for EP MoE (under shard_map)
    enc_memory: Any = None           # TVal [b, enc_ctx, d] for cross-attn
    decode: bool = False
    rope_full: dict | None = None    # head_dim -> full-cache rope tables (decode)
    kv_seq_shard: bool = False       # 500k path: KV cache sharded on seq
    kv_shards: int = 1               # over this many "data" ranks
    slot_mask: Any = None            # [b] bool: rows allowed to write their
    #                                  cache slot (continuous batching);
    #                                  None = every row writes
    page_tables: Any = None          # [b, pages_per_req] int32 local page
    #                                  ids (paged KV cache); None = the
    #                                  contiguous per-row cache layout
    page_size: int = 0               # tokens per page when paged
    moe_stats: Any = None            # None (off) | list collector: apply_moe
    #                                  appends (pfx, load[routed], dropped,
    #                                  pairs held) per layer when set
    #                                  (RunConfig.moe_stats)


# --------------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------------- #


def norm_specs(cfg: ModelConfig, pfx: str) -> dict[str, ParamSpec]:
    d = cfg.d_model
    if cfg.norm == "layernorm":
        return {
            f"{pfx}.scale": ParamSpec((d,), "ones", fsdp_dim=0),
            f"{pfx}.bias": ParamSpec((d,), "zeros", fsdp_dim=0),
        }
    return {f"{pfx}.scale": ParamSpec((d,), "ones", fsdp_dim=0)}


def rms_norm(v, scale, eps):
    """RMSNorm in f32, the result in ``v``'s dtype."""
    vf = v.astype(jnp.float32)
    y = vf * jax.lax.rsqrt(jnp.mean(vf * vf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(v.dtype)


def apply_norm(t: Tape, cfg: ModelConfig, pfx: str, x: TVal) -> TVal:
    if cfg.norm == "layernorm":
        def ln(scale, bias, v):
            vf = v.astype(jnp.float32)
            mu = vf.mean(axis=-1, keepdims=True)
            var = ((vf - mu) ** 2).mean(axis=-1, keepdims=True)
            y = (vf - mu) * jax.lax.rsqrt(var + 1e-5)
            return (y * scale + bias).astype(v.dtype)

        return t.prim(ln, x, pnames=(f"{pfx}.scale", f"{pfx}.bias"))
    return _rms_sub(t, cfg, f"{pfx}.scale", x)


def norm_fwd(cfg, params, pfx, x):
    """Pure fwd (decode path)."""
    if cfg.norm == "layernorm":
        xf = x.astype(jnp.float32)
        mu = xf.mean(axis=-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        return (y * params[f"{pfx}.scale"] + params[f"{pfx}.bias"]).astype(x.dtype)
    return rms_norm(x, params[f"{pfx}.scale"], cfg.norm_eps)


# --------------------------------------------------------------------------- #
# GQA attention
# --------------------------------------------------------------------------- #


def attn_specs(cfg: ModelConfig, pfx: str, cross: bool = False):
    d, h, g, e = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    sp = {
        f"{pfx}.wq": ParamSpec((d, h, e), fsdp_dim=0),
        f"{pfx}.wk": ParamSpec((d, g, e), fsdp_dim=0),
        f"{pfx}.wv": ParamSpec((d, g, e), fsdp_dim=0),
        f"{pfx}.wo": ParamSpec((h, e, d), fsdp_dim=2),
    }
    return sp


def apply_attn(
    t: Tape, ctx: LayerCtx, pfx: str, x: TVal, *, cross: bool = False
) -> TVal:
    cfg, rc = ctx.cfg, ctx.rc
    q = t.dense(x, f"{pfx}.wq", "bsd,dhe->bshe")
    kv_src = ctx.enc_memory if cross else x
    k = t.dense(kv_src, f"{pfx}.wk", "bsd,dge->bsge")
    v = t.dense(kv_src, f"{pfx}.wv", "bsd,dge->bsge")
    if not cross:
        cos, sin = ctx.rope[cfg.head_dim]

        def core(qv, kv, vv):
            qr = apply_rope(qv, cos, sin)
            kr = apply_rope(kv, cos, sin)
            return ops.attention(
                qr, kr, vv, causal=ctx.causal, block_k=rc.attn_block_k,
                impl=rc.kernel_impl,
            )

        o = t.prim(core, q, k, v)
    else:

        def core(qv, kv, vv):
            return ops.attention(qv, kv, vv, causal=False,
                                 block_k=rc.attn_block_k,
                                 impl=rc.kernel_impl)

        o = t.prim(core, q, k, v)
    return t.dense(o, f"{pfx}.wo", "bshe,hed->bsd")


def attn_decode(ctx: LayerCtx, params, pfx, x, cache, pos):
    """x: [b, 1, d]; cache: dict(k: [b,S,g,e], v: [b,S,g,e]); pos scalar."""
    cfg = ctx.cfg
    q = jnp.einsum("bsd,dhe->bshe", x, params[f"{pfx}.wq"])
    k = jnp.einsum("bsd,dge->bsge", x, params[f"{pfx}.wk"])
    v = jnp.einsum("bsd,dge->bsge", x, params[f"{pfx}.wv"])
    cos, sin = ctx.rope[cfg.head_dim]  # [1, e/2] at current pos
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0)
    )
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0)
    )
    o, _ = ops.decode_attention(q, k_cache, v_cache, cache_len=pos + 1,
                                impl=ctx.rc.kernel_impl)
    y = jnp.einsum("bshe,hed->bsd", o, params[f"{pfx}.wo"])
    return y, {"k": k_cache, "v": v_cache}


def cross_attn_decode(ctx, params, pfx, x, memory):
    q = jnp.einsum("bsd,dhe->bshe", x, params[f"{pfx}.wq"])
    k = jnp.einsum("bsd,dge->bsge", memory, params[f"{pfx}.wk"])
    v = jnp.einsum("bsd,dge->bsge", memory, params[f"{pfx}.wv"])
    o = ops.attention(q, k, v, causal=False)
    return jnp.einsum("bshe,hed->bsd", o, params[f"{pfx}.wo"])


# --------------------------------------------------------------------------- #
# MLA (DeepSeek multi-head latent attention)
# --------------------------------------------------------------------------- #


def mla_specs(cfg: ModelConfig, pfx: str):
    m: MLACfg = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    e = m.qk_nope + m.rope_dims
    if m.q_lora:
        sp = {
            f"{pfx}.wdq": ParamSpec((d, m.q_lora), fsdp_dim=0),
            f"{pfx}.qnorm.scale": ParamSpec((m.q_lora,), "ones"),
            f"{pfx}.wuq": ParamSpec((m.q_lora, h, e), fsdp_dim=0),
        }
    else:
        sp = {f"{pfx}.wq": ParamSpec((d, h, e), fsdp_dim=0)}
    return sp | {
        f"{pfx}.wdkv": ParamSpec((d, m.kv_lora + m.rope_dims), fsdp_dim=0),
        f"{pfx}.kvnorm.scale": ParamSpec((m.kv_lora,), "ones"),
        f"{pfx}.wuk": ParamSpec((m.kv_lora, h, m.qk_nope), fsdp_dim=0),
        f"{pfx}.wuv": ParamSpec((m.kv_lora, h, m.v_head), fsdp_dim=0),
        f"{pfx}.wo": ParamSpec((h, m.v_head, d), fsdp_dim=2),
    }


def _mla_q(cfg, params, pfx, x):
    """q [b, s, h, qk_nope + rope] of the decode and cached paths."""
    if not cfg.mla.q_lora:
        return jnp.einsum("bsd,dhe->bshe", x, params[f"{pfx}.wq"])
    cq = jnp.einsum("bsd,dr->bsr", x, params[f"{pfx}.wdq"])
    cq = rms_norm(cq, params[f"{pfx}.qnorm.scale"], cfg.norm_eps)
    return jnp.einsum("bsr,rhe->bshe", cq, params[f"{pfx}.wuq"])


def apply_mla(t: Tape, ctx: LayerCtx, pfx: str, x: TVal) -> TVal:
    """Attention scores are scaled by 1/sqrt(qk_nope + rope), the width
    of q and k, as ``ops.attention`` scales them."""
    cfg = ctx.cfg
    m: MLACfg = cfg.mla
    if m.q_lora:
        cq = t.dense(x, f"{pfx}.wdq", "bsd,dr->bsr")
        cq = _rms_sub(t, cfg, f"{pfx}.qnorm.scale", cq)
        q = t.dense(cq, f"{pfx}.wuq", "bsr,rhe->bshe")  # e = qk_nope + rope
    else:
        q = t.dense(x, f"{pfx}.wq", "bsd,dhe->bshe")
    ckv = t.dense(x, f"{pfx}.wdkv", "bsd,dc->bsc")  # c = kv_lora + rope

    def split_norm(scale, c):
        return rms_norm(c[..., : m.kv_lora], scale, cfg.norm_eps), \
            c[..., m.kv_lora:]

    c_kv, k_rope = t.prim(
        split_norm, ckv, pnames=(f"{pfx}.kvnorm.scale",), n_out=2
    )
    k_nope = t.dense(c_kv, f"{pfx}.wuk", "bsc,che->bshe")
    vv = t.dense(c_kv, f"{pfx}.wuv", "bsc,che->bshe")
    cos, sin = ctx.rope[m.rope_dims]

    def core(qv, knope, krope, val):
        q_nope, q_rope = qv[..., : m.qk_nope], qv[..., m.qk_nope:]
        q_rope = apply_rope(q_rope, cos, sin)
        k_rope_r = apply_rope(krope[:, :, None, :], cos, sin)
        k_rope_b = jnp.broadcast_to(
            k_rope_r, knope.shape[:3] + (m.rope_dims,)
        )
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        kf = jnp.concatenate([knope, k_rope_b], axis=-1)
        return ops.attention(
            qf, kf, val, causal=ctx.causal, block_k=ctx.rc.attn_block_k,
            impl=ctx.rc.kernel_impl,
        )

    o = t.prim(core, q, k_nope, k_rope, vv)
    return t.dense(o, f"{pfx}.wo", "bshe,hed->bsd")


def mla_decode(ctx, params, pfx, x, cache, pos):
    """Cache holds the *compressed* ckv [b, S, kv_lora + rope_dims]."""
    cfg = ctx.cfg
    m: MLACfg = cfg.mla
    q = _mla_q(cfg, params, pfx, x)
    ckv = jnp.einsum("bsd,dc->bsc", x, params[f"{pfx}.wdkv"])
    cache_new = jax.lax.dynamic_update_slice(
        cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, pos, 0)
    )
    full = cache_new  # [b, S, c]
    c_kv, k_rope = full[..., : m.kv_lora], full[..., m.kv_lora:]
    c_kv = rms_norm(c_kv.astype(jnp.float32), params[f"{pfx}.kvnorm.scale"],
                    cfg.norm_eps).astype(x.dtype)
    k_nope = jnp.einsum("bsc,che->bshe", c_kv, params[f"{pfx}.wuk"])
    v = jnp.einsum("bsc,che->bshe", c_kv, params[f"{pfx}.wuv"])
    cos_q, sin_q = ctx.rope[m.rope_dims]          # [1, rope/2] current pos
    cos_k, sin_k = ctx.rope_full[m.rope_dims]     # [S, rope/2]
    q_nope, q_rope = q[..., : m.qk_nope], q[..., m.qk_nope:]
    q_rope = apply_rope(q_rope, cos_q, sin_q)
    k_rope = apply_rope(k_rope[:, :, None, :], cos_k, sin_k)
    k_rope = jnp.broadcast_to(k_rope, k_nope.shape[:3] + (m.rope_dims,))
    qf = jnp.concatenate([q_nope, q_rope], -1)
    kf = jnp.concatenate([k_nope, k_rope], -1)
    o, _ = ops.decode_attention(qf, kf, v, cache_len=pos + 1,
                                impl=ctx.rc.kernel_impl)
    y = jnp.einsum("bshe,hed->bsd", o, params[f"{pfx}.wo"])
    return y, {"ckv": cache_new}


def _rms_sub(t: Tape, cfg: ModelConfig, scale_name: str, x: TVal) -> TVal:
    return t.prim(lambda scale, v: rms_norm(v, scale, cfg.norm_eps), x,
                  pnames=(scale_name,))


# --------------------------------------------------------------------------- #
# Dense FFN (SwiGLU or GELU-MLP)
# --------------------------------------------------------------------------- #


def ffn_specs(cfg: ModelConfig, pfx: str, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act == "gelu_mlp":
        return {
            f"{pfx}.wi": ParamSpec((d, f), fsdp_dim=1),
            f"{pfx}.wd": ParamSpec((f, d), fsdp_dim=0),
        }
    return {
        f"{pfx}.wg": ParamSpec((d, f), fsdp_dim=1),
        f"{pfx}.wu": ParamSpec((d, f), fsdp_dim=1),
        f"{pfx}.wd": ParamSpec((f, d), fsdp_dim=0),
    }


def apply_ffn(t: Tape, ctx: LayerCtx, pfx: str, x: TVal) -> TVal:
    if ctx.cfg.act == "gelu_mlp":
        h = t.dense(x, f"{pfx}.wi", "bsd,df->bsf")
        h = t.elementwise(jax.nn.gelu, h)
        return t.dense(h, f"{pfx}.wd", "bsf,fd->bsd")
    g = t.dense(x, f"{pfx}.wg", "bsd,df->bsf")
    u = t.dense(x, f"{pfx}.wu", "bsd,df->bsf")
    h = t.prim(lambda a, b: jax.nn.silu(a) * b, g, u)
    return t.dense(h, f"{pfx}.wd", "bsf,fd->bsd")


def ffn_fwd(ctx, params, pfx, x):
    if ctx.cfg.act == "gelu_mlp":
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, params[f"{pfx}.wi"]))
        return jnp.einsum("bsf,fd->bsd", h, params[f"{pfx}.wd"])
    g = jnp.einsum("bsd,df->bsf", x, params[f"{pfx}.wg"])
    u = jnp.einsum("bsd,df->bsf", x, params[f"{pfx}.wu"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, params[f"{pfx}.wd"])


# --------------------------------------------------------------------------- #
# MoE: shared experts + routed top-k over the experts held here
# --------------------------------------------------------------------------- #


def moe_specs(cfg: ModelConfig, pfx: str):
    mo = cfg.moe
    d, fe = cfg.d_model, mo.d_ff_expert
    sp = {
        f"{pfx}.router": ParamSpec((d, mo.routed), fsdp_dim=0, scale=0.1),
        f"{pfx}.e_wg": ParamSpec((mo.n_experts, d, fe), fsdp_dim=2, ep=True),
        f"{pfx}.e_wu": ParamSpec((mo.n_experts, d, fe), fsdp_dim=2, ep=True),
        f"{pfx}.e_wd": ParamSpec((mo.n_experts, fe, d), fsdp_dim=1, ep=True),
    }
    if mo.scoring == "sigmoid":
        sp[f"{pfx}.router_bias"] = ParamSpec((mo.routed,), "zeros")
    if mo.n_shared:
        fs = mo.d_ff_shared or fe * mo.n_shared
        sp.update({
            f"{pfx}.s_wg": ParamSpec((d, fs), fsdp_dim=1),
            f"{pfx}.s_wu": ParamSpec((d, fs), fsdp_dim=1),
            f"{pfx}.s_wd": ParamSpec((fs, d), fsdp_dim=0),
        })
    return sp


def _capacity(n_tok: int, mo) -> int:
    c = int(n_tok * mo.top_k / mo.n_experts * mo.capacity_factor) + 1
    return max(8, -(-c // 8) * 8)  # round up to 8


def route(mo, lgf, bias=None):
    """The gate over all ``mo.routed`` experts: f32 logits [n, routed]
    -> (weights [n, K] f32, experts [n, K], balance term).

    softmax: top-k of the softmax, renormalised; Switch balance term.
    sigmoid (DeepSeek-V3 ``noaux_tc``): top-k of sigmoid + ``bias``,
    which chooses and never weighs (no gradient reaches it); the chosen
    sigmoid scores renormalised; no balance term. Both scale the weights
    by ``mo.routed_scale``."""
    K = mo.top_k
    if mo.scoring == "sigmoid":
        sc = jax.nn.sigmoid(lgf)
        _, topi = jax.lax.top_k(
            sc + jax.lax.stop_gradient(bias.astype(jnp.float32)), K)
        topw = jnp.take_along_axis(sc, topi, -1)
        topw = topw / (topw.sum(-1, keepdims=True) + 1e-20)
        return topw * mo.routed_scale, topi, jnp.zeros((), jnp.float32)
    probs = jax.nn.softmax(lgf, axis=-1)
    topw, topi = jax.lax.top_k(probs, K)          # [n, K]
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    # Switch-style balance term over every routed expert
    first = jax.nn.one_hot(topi[:, 0], mo.routed, dtype=jnp.float32)
    aux = (first.mean(axis=0) * probs.mean(axis=0)).sum() * mo.routed
    return topw * mo.routed_scale, topi, aux


def apply_moe(t: Tape, ctx: LayerCtx, pfx: str, x: TVal) -> tuple[TVal, TVal]:
    """Returns (y, aux_loss).

    The router scores every routed expert; this layer computes the part
    of the result that the experts it holds give (``MoECfg.n_experts``
    from ``held_offset`` on), plus the shared experts, and leaves out
    what experts held elsewhere would add. Without an expert-parallel
    axis the held pairs run dropless through the grouped expert product;
    under ``moe_mode="ep"`` (every routed expert in the layer) they go
    through capacity buffers and ``all_to_all``."""
    cfg, mo = ctx.cfg, ctx.cfg.moe
    b, s, d = x.shape
    n, K, R = b * s, mo.top_k, mo.routed

    # Routing (indices exit the tape as closure captures; weights stay on it).
    holder = {}

    def gate(lgf, bias):
        topw, topi, aux = route(mo, lgf, bias)
        holder["topi"] = topi
        if ctx.moe_stats is not None:
            # dispatch observability (RunConfig.moe_stats): routed
            # assignment count per expert over all routed experts, and
            # the (token, k) pairs whose expert is held here. Slotted
            # serving pads inactive rows; mask them out so the histogram
            # counts only live requests' tokens.
            if ctx.slot_mask is not None:
                live = jnp.repeat(ctx.slot_mask.astype(jnp.int32), s * K)
            else:
                live = jnp.ones((n * K,), jnp.int32)
            flat = topi.reshape(-1)
            held = (flat >= mo.held_offset) & (
                flat < mo.held_offset + mo.n_experts)
            holder["load"] = jnp.zeros((R,), jnp.int32).at[flat].add(live)
            holder["held"] = (held.astype(jnp.int32) * live).sum()
        return topw, aux

    if mo.scoring == "sigmoid":
        def sigmoid_gate(w, bias, xv):
            # the published gate computes its logits in f32
            lgf = jnp.einsum("nd,de->ne", xv.reshape(n, d).astype(jnp.float32),
                             w.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            return gate(lgf, bias)

        topw, aux = t.prim(sigmoid_gate, x, n_out=2,
                           pnames=(f"{pfx}.router", f"{pfx}.router_bias"))
    else:
        logits = t.dense(x, f"{pfx}.router", "bsd,de->bse")
        topw, aux = t.prim(
            lambda lg: gate(lg.reshape(n, R).astype(jnp.float32), None),
            logits, n_out=2)

    if ctx.ep_axis is None:
        y = _grouped_experts(t, ctx, pfx, x, topw, holder)
        holder["dropped"] = jnp.zeros((), jnp.int32)
    else:
        y = _capacity_experts(t, ctx, pfx, x, topw, holder)

    if mo.n_shared:
        g2 = t.dense(x, f"{pfx}.s_wg", "bsd,df->bsf")
        u2 = t.dense(x, f"{pfx}.s_wu", "bsd,df->bsf")
        h2 = t.prim(lambda a, b2: jax.nn.silu(a) * b2, g2, u2)
        y2 = t.dense(h2, f"{pfx}.s_wd", "bsf,fd->bsd")
        y = t.add(y, y2)
    if ctx.moe_stats is not None:
        ctx.moe_stats.append((pfx, holder["load"], holder["dropped"],
                              holder["held"]))
    return y, aux


def _grouped_experts(t: Tape, ctx: LayerCtx, pfx: str, x: TVal, topw: TVal,
                     holder: dict) -> TVal:
    """The held experts' part, dropless: the (token, k) pairs whose
    expert is held, sorted by expert, through one grouped product per
    projection (``ops.expert_gmm``), padded to the kernel's row tile.
    The expert weights' gradients are taken in B (the grouped dW needs
    the group sizes, which the W stash does not carry)."""
    mo = ctx.cfg.moe
    b, s, d = x.shape
    n, K, E = b * s, mo.top_k, mo.n_experts
    impl = ctx.rc.kernel_impl
    rows = expert_gmm.rows(n * min(K, E))   # the most pairs held here

    def experts(wg, wu, wd, xv, wv):
        local = holder["topi"].reshape(-1) - mo.held_offset     # [n K]
        held = (local >= 0) & (local < E)
        key = jnp.where(held, local, E)
        order = jnp.argsort(key, stable=True)  # held pairs first, by expert
        sizes = jnp.zeros((E + 1,), jnp.int32).at[key].add(1)[:E]
        pick = order[:rows] if rows <= n * K else jnp.pad(
            order, (0, rows - n * K))
        valid = jnp.arange(rows) < sizes.sum()
        # rows past the groups are masked with selects: the kernel leaves
        # them unwritten, forward and backward
        xs = jnp.where(valid[:, None], xv.reshape(n, d)[pick // K], 0)
        g = ops.expert_gmm(xs, wg, sizes, impl=impl)
        u = ops.expert_gmm(xs, wu, sizes, impl=impl)
        ye = ops.expert_gmm(jax.nn.silu(g) * u, wd, sizes, impl=impl)
        rank = jnp.zeros((n * K,), jnp.int32).at[order].set(
            jnp.arange(n * K, dtype=jnp.int32))
        yp = ye[jnp.minimum(rank, rows - 1)].astype(jnp.float32)
        yp = jnp.where(held[:, None], yp, 0.0).reshape(n, K, d)
        out = (yp * wv[..., None]).sum(axis=1)
        return out.astype(xv.dtype).reshape(b, s, d)

    # rematerialised in its backward, so that no layer's buffers (sized
    # for every pair a token can send here) outlive its own backward
    return t.prim(jax.checkpoint(experts), x, topw,
                  pnames=(f"{pfx}.e_wg", f"{pfx}.e_wu", f"{pfx}.e_wd"))


def _capacity_experts(t: Tape, ctx: LayerCtx, pfx: str, x: TVal,
                      topw: TVal, holder: dict) -> TVal:
    """Every routed expert's part through [E, cap, d] buffers split over
    the expert-parallel axis by ``all_to_all``; pairs past an expert's
    capacity are dropped (counted in ``holder["dropped"]``)."""
    mo = ctx.cfg.moe
    b, s, d = x.shape
    n, K, E = b * s, mo.top_k, mo.n_experts
    if mo.routed != E:
        raise ValueError("moe_mode='ep' holds every routed expert in the "
                         f"layer; this layer holds {E} of {mo.routed}")
    cap = _capacity(n, mo)
    topi = holder["topi"]
    # position of each (token, k) within its expert
    flat_oh = jax.nn.one_hot(topi, E, dtype=jnp.int32).reshape(n * K, E)
    pos = jnp.cumsum(flat_oh, axis=0) - flat_oh         # rank within expert
    slot = (pos * flat_oh).sum(-1).reshape(n, K)        # [n, K]
    keep = slot < cap
    slot = jnp.where(keep, slot, cap)                   # cap = drop slot
    if ctx.moe_stats is not None:
        live = (jnp.ones((n * K,), jnp.int32) if ctx.slot_mask is None else
                jnp.repeat(ctx.slot_mask.astype(jnp.int32), s * K))
        holder["dropped"] = ((~keep).reshape(-1).astype(jnp.int32)
                             * live).sum()

    def dispatch(xv):
        xf = xv.reshape(n, d)
        buf = jnp.zeros((E, cap + 1, d), xv.dtype)
        xk = jnp.repeat(xf, K, axis=0)
        return buf.at[topi.reshape(-1), slot.reshape(-1)].add(xk)[:, :cap]

    xe = t.prim(dispatch, x)  # [E, cap, d]
    ax = ctx.ep_axis

    # all_to_all: split experts over the data axis, concat capacity.
    xe = t.prim(lambda v: jax.lax.all_to_all(v, ax, split_axis=0,
                                             concat_axis=1, tiled=True), xe)
    g = t.dense(xe, f"{pfx}.e_wg", "ecd,edf->ecf")     # [E/D, cap*D, ...]
    u = t.dense(xe, f"{pfx}.e_wu", "ecd,edf->ecf")
    hh = t.prim(lambda a, b2: jax.nn.silu(a) * b2, g, u)
    ye = t.dense(hh, f"{pfx}.e_wd", "ecf,efd->ecd")
    ye = t.prim(lambda v: jax.lax.all_to_all(v, ax, split_axis=1,
                                             concat_axis=0, tiled=True), ye)

    def combine(yv, wv):
        ypad = jnp.pad(yv, ((0, 0), (0, 1), (0, 0)))  # drop slot reads zeros
        gathered = ypad[topi, slot]    # [n, K, d]
        out = (gathered * wv[..., None].astype(yv.dtype)).sum(axis=1)
        return out.reshape(b, s, d)

    return t.prim(combine, ye, topw)


def moe_fwd(ctx, params, pfx, x):
    """Decode/plain path (no tape, gathered experts)."""
    t = Tape(params, mode="fwd")
    y, _ = apply_moe(t, ctx, pfx, t.value(x))
    return y.val


# --------------------------------------------------------------------------- #
# Mamba (selective SSM)
# --------------------------------------------------------------------------- #


def _mamba_dims(cfg):
    mc = cfg.mamba
    di = mc.expand * cfg.d_model
    dt_rank = mc.dt_rank or max(1, cfg.d_model // 16)
    return mc, di, dt_rank


def mamba_specs(cfg: ModelConfig, pfx: str):
    mc, di, dt_rank = _mamba_dims(cfg)
    d, n = cfg.d_model, mc.d_state
    return {
        f"{pfx}.w_in": ParamSpec((d, 2 * di), fsdp_dim=1),
        f"{pfx}.conv_w": ParamSpec((mc.d_conv, di), "small", fsdp_dim=1,
                                   scale=0.5),
        f"{pfx}.conv_b": ParamSpec((di,), "zeros"),
        f"{pfx}.w_x": ParamSpec((di, dt_rank + 2 * n), fsdp_dim=0),
        f"{pfx}.w_dt": ParamSpec((dt_rank, di), fsdp_dim=1),
        f"{pfx}.dt_bias": ParamSpec((di,), "zeros"),
        f"{pfx}.A_log": ParamSpec((di, n), "ones"),
        f"{pfx}.Dd": ParamSpec((di,), "ones"),
        f"{pfx}.w_out": ParamSpec((di, d), fsdp_dim=0),
    }


def apply_mamba(t: Tape, ctx: LayerCtx, pfx: str, x: TVal) -> TVal:
    cfg = ctx.cfg
    mc, di, dt_rank = _mamba_dims(cfg)
    n = mc.d_state
    xz = t.dense(x, f"{pfx}.w_in", "bsd,de->bse")  # e = 2*di

    def conv_split(cw, cb, v):
        xs, z = v[..., :di], v[..., di:]
        # causal depthwise conv over seq
        pad = jnp.pad(xs, ((0, 0), (mc.d_conv - 1, 0), (0, 0)))
        out = sum(
            pad[:, i: i + xs.shape[1]] * cw[i][None, None]
            for i in range(mc.d_conv)
        ) + cb
        return jax.nn.silu(out), z

    xs, z = t.prim(
        conv_split, xz, pnames=(f"{pfx}.conv_w", f"{pfx}.conv_b"), n_out=2
    )
    bcdt = t.dense(xs, f"{pfx}.w_x", "bse,er->bsr")  # r = dt_rank + 2n

    def ssm(w_dt, dt_bias, a_log, dd, xs_v, bcdt_v, z_v):
        dt_in = bcdt_v[..., :dt_rank]
        Bm = bcdt_v[..., dt_rank: dt_rank + n].astype(jnp.float32)
        Cm = bcdt_v[..., dt_rank + n:].astype(jnp.float32)
        dt = jax.nn.softplus(
            jnp.einsum("bsr,re->bse", dt_in, w_dt) + dt_bias
        ).astype(jnp.float32)
        A = -jnp.exp(a_log.astype(jnp.float32))
        y = ops.selective_scan(
            xs_v.astype(jnp.float32), dt, A, Bm, Cm,
            dd.astype(jnp.float32),
        )
        return (y * jax.nn.silu(z_v.astype(jnp.float32))).astype(xs_v.dtype)

    y = t.prim(
        ssm, xs, bcdt, z,
        pnames=(f"{pfx}.w_dt", f"{pfx}.dt_bias", f"{pfx}.A_log", f"{pfx}.Dd"),
    )
    return t.dense(y, f"{pfx}.w_out", "bse,ed->bsd")


def mamba_decode(ctx, params, pfx, x, cache, pos):
    """cache: {"conv": [b, d_conv-1, di], "h": [b, di, n]}; x [b, 1, d]."""
    cfg = ctx.cfg
    mc, di, dt_rank = _mamba_dims(cfg)
    n = mc.d_state
    xz = jnp.einsum("bsd,de->bse", x, params[f"{pfx}.w_in"])[:, 0]
    xs, z = xz[..., :di], xz[..., di:]
    conv_in = jnp.concatenate([cache["conv"], xs[:, None]], axis=1)
    cw = params[f"{pfx}.conv_w"]
    out = sum(conv_in[:, i] * cw[i][None] for i in range(mc.d_conv))
    xs_c = jax.nn.silu(out + params[f"{pfx}.conv_b"])
    bcdt = jnp.einsum("be,er->br", xs_c, params[f"{pfx}.w_x"])
    dt = jax.nn.softplus(
        jnp.einsum("br,re->be", bcdt[..., :dt_rank], params[f"{pfx}.w_dt"])
        + params[f"{pfx}.dt_bias"]
    ).astype(jnp.float32)
    Bm = bcdt[..., dt_rank: dt_rank + n].astype(jnp.float32)
    Cm = bcdt[..., dt_rank + n:].astype(jnp.float32)
    A = -jnp.exp(params[f"{pfx}.A_log"].astype(jnp.float32))
    h_new, y = ops.selective_scan_step(
        cache["h"], xs_c.astype(jnp.float32), dt, A, Bm, Cm,
        params[f"{pfx}.Dd"].astype(jnp.float32),
    )
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    y = jnp.einsum("be,ed->bd", y, params[f"{pfx}.w_out"])[:, None]
    return y, {"conv": conv_in[:, 1:], "h": h_new}


# --------------------------------------------------------------------------- #
# xLSTM blocks
# --------------------------------------------------------------------------- #


def mlstm_specs(cfg: ModelConfig, pfx: str):
    d = cfg.d_model
    di = int(cfg.xlstm.proj_factor * d)
    h = cfg.n_heads
    e = di // h
    return {
        f"{pfx}.w_up": ParamSpec((d, 2 * di), fsdp_dim=1),
        f"{pfx}.wq": ParamSpec((di, h, e), fsdp_dim=0),
        f"{pfx}.wk": ParamSpec((di, h, e), fsdp_dim=0),
        f"{pfx}.wv": ParamSpec((di, h, e), fsdp_dim=0),
        f"{pfx}.w_if": ParamSpec((di, 2, h), fsdp_dim=0, scale=0.1),
        f"{pfx}.if_bias": ParamSpec((2, h), "zeros"),
        f"{pfx}.w_out": ParamSpec((di, d), fsdp_dim=0),
    }


def apply_mlstm(t: Tape, ctx: LayerCtx, pfx: str, x: TVal) -> TVal:
    cfg = ctx.cfg
    d = cfg.d_model
    di = int(cfg.xlstm.proj_factor * d)
    up = t.dense(x, f"{pfx}.w_up", "bsd,de->bse")
    xb, z = t.prim(lambda v: (v[..., :di], v[..., di:]), up, n_out=2)
    q = t.dense(xb, f"{pfx}.wq", "bse,ehf->bshf")
    k = t.dense(xb, f"{pfx}.wk", "bse,ehf->bshf")
    v = t.dense(xb, f"{pfx}.wv", "bse,ehf->bshf")

    def core(w_if, if_bias, xbv, qv, kv, vv, zv):
        gates = jnp.einsum("bse,egh->bsgh", xbv.astype(jnp.float32),
                           w_if.astype(jnp.float32)) + if_bias
        ig, fg = gates[:, :, 0], gates[:, :, 1] + 1.0
        y = ops.mlstm_chunkwise(qv, kv, vv, ig, fg)
        y = y.reshape(y.shape[0], y.shape[1], -1)
        return y * jax.nn.silu(zv)

    y = t.prim(core, xb, q, k, v, z,
               pnames=(f"{pfx}.w_if", f"{pfx}.if_bias"))
    return t.dense(y, f"{pfx}.w_out", "bse,ed->bsd")


def mlstm_decode(ctx, params, pfx, x, cache, pos):
    cfg = ctx.cfg
    d = cfg.d_model
    di = int(cfg.xlstm.proj_factor * d)
    h = cfg.n_heads
    e = di // h
    up = jnp.einsum("bsd,de->bse", x, params[f"{pfx}.w_up"])[:, 0]
    xb, z = up[..., :di], up[..., di:]
    q = jnp.einsum("be,ehf->bhf", xb, params[f"{pfx}.wq"])
    k = jnp.einsum("be,ehf->bhf", xb, params[f"{pfx}.wk"])
    v = jnp.einsum("be,ehf->bhf", xb, params[f"{pfx}.wv"])
    gates = jnp.einsum("be,egh->bgh", xb.astype(jnp.float32),
                       params[f"{pfx}.w_if"].astype(jnp.float32))
    gates = gates + params[f"{pfx}.if_bias"]
    ig, fg = gates[:, 0], gates[:, 1] + 1.0
    state = (cache["C"], cache["n"], cache["m"])
    state, y = ops.mlstm_step(state, q, k, v, ig, fg)
    y = (y.reshape(y.shape[0], -1) * jax.nn.silu(z)).astype(x.dtype)
    y = jnp.einsum("be,ed->bd", y, params[f"{pfx}.w_out"])[:, None]
    return y, {"C": state[0], "n": state[1], "m": state[2]}


def slstm_specs(cfg: ModelConfig, pfx: str):
    d = cfg.d_model
    h = cfg.n_heads
    e = d // h
    return {
        f"{pfx}.w_gates": ParamSpec((d, h, 4, e), fsdp_dim=0),
        f"{pfx}.g_bias": ParamSpec((h, 4, e), "zeros"),
        f"{pfx}.w_out": ParamSpec((d, d), fsdp_dim=0),
    }


def apply_slstm(t: Tape, ctx: LayerCtx, pfx: str, x: TVal) -> TVal:
    g = t.dense(x, f"{pfx}.w_gates", "bsd,dhge->bshge")

    def core(g_bias, gv):
        gv = gv + g_bias
        # reorder to [b, s, h, 4, e]
        gv = jnp.einsum("bshge->bshge", gv)
        return ops.slstm_scan(gv)

    y = t.prim(core, g, pnames=(f"{pfx}.g_bias",))
    y = t.prim(lambda v: v.reshape(v.shape[0], v.shape[1], -1), y)
    return t.dense(y, f"{pfx}.w_out", "bsd,de->bse")


def slstm_decode(ctx, params, pfx, x, cache, pos):
    g = jnp.einsum("bsd,dhge->bshge", x, params[f"{pfx}.w_gates"])
    g = (g + params[f"{pfx}.g_bias"])[:, 0]  # [b, h, 4, e]
    state = (cache["c"], cache["n"], cache["m"])
    y, state = ops.slstm_scan(g[:, None], state=state, return_state=True)
    y = y[:, 0].reshape(x.shape[0], -1)
    y = jnp.einsum("bd,de->be", y, params[f"{pfx}.w_out"])[:, None]
    return y, {"c": state[0], "n": state[1], "m": state[2]}


# --------------------------------------------------------------------------- #
# Unified cached execution (prefill s>1 / decode s=1) for serving
# --------------------------------------------------------------------------- #


def _rope_slice(ctx, e, pos, s):
    cos, sin = ctx.rope[e]  # full tables [max_seq, e/2]
    if getattr(pos, "ndim", 0):  # per-slot [b] positions -> [b, s, e/2]
        return jax.vmap(lambda p: (
            jax.lax.dynamic_slice_in_dim(cos, p, s, 0),
            jax.lax.dynamic_slice_in_dim(sin, p, s, 0)))(pos)
    return (jax.lax.dynamic_slice_in_dim(cos, pos, s, 0),
            jax.lax.dynamic_slice_in_dim(sin, pos, s, 0))


def _slot_scatter(ctx, cache_arr, new, pos):
    """Write ``new`` [b, s, ...] into ``cache_arr`` [b, S, ...] at the
    per-row position ``pos`` [b], honouring ``ctx.slot_mask``: masked-off
    rows keep their cache bytes untouched (their window is read back and
    rewritten unchanged), so a prefill into one slot can never clobber a
    neighbouring in-flight request."""
    s = new.shape[1]
    mask = ctx.slot_mask
    if mask is None:
        mask = jnp.ones((new.shape[0],), bool)

    def upd(row, new_row, p, m):
        old = jax.lax.dynamic_slice_in_dim(row, p, s, 0)
        win = jnp.where(m, new_row.astype(row.dtype), old)
        return jax.lax.dynamic_update_slice_in_dim(row, win, p, 0)

    return jax.vmap(upd)(cache_arr, new, pos, mask)


def _paged_gather(ctx, pool, scale=None, width=None):
    """Assemble each row's K/V window from the shared page pool.

    pool: [n_pages_loc, ps, ...]; ctx.page_tables: [b, ppr] local page
    ids. Returns [b, ppr*ps, ...] — same shape and same values at every
    causally-visible position as the contiguous per-row cache, so the
    attention that follows is bitwise identical to the slotted path.
    Sentinel table entries (unreserved tail) drag in arbitrary live
    pages; every such position sits beyond the row's causal offset and
    is masked to exact -inf before the softmax.

    ``scale`` (int8 pools): [n_pages_loc, ...head-dims] per-page dequant
    scales — the gather dequantizes to f32 with the exact per-element
    product the Pallas paged kernel computes in-kernel.
    """
    pt = jnp.clip(ctx.page_tables, 0, pool.shape[0] - 1)
    g = jnp.take(pool, pt, axis=0)            # [b, ppr, ps, ...]
    if scale is not None:
        sg = jnp.take(scale.astype(jnp.float32), pt, axis=0)
        sg = sg.reshape(sg.shape[:2] + (1,) + sg.shape[2:] + (1,))
        g = g.astype(jnp.float32) * sg
    g = g.reshape((pt.shape[0], -1) + pool.shape[2:])
    if width is not None and g.shape[1] != width:
        g = g[:, :width]
    return g


def _paged_scatter(ctx, pool, new, pos, scale=None):
    """Write ``new`` [b, s, ...] into the page pool at each row's
    absolute positions ``pos + [0, s)``, routed through its page table.
    Masked-off rows (``ctx.slot_mask``) are redirected out of bounds and
    dropped — the paged analogue of :func:`_slot_scatter`'s read-back.
    Rows never share writable pages (shared prefix pages are read-only
    by construction and prefill resumes past them), so the flat indices
    are collision-free. Returns ``(pool, scale)``.

    With ``scale`` (int8 pages, [n_loc, ...head-dims] f32): per-page
    scales only ever grow (scatter-max of amax/127), existing page
    content is requantized by the old/new ratio — exactly 1.0 for every
    untouched page, so shared prefix pages stay bitwise stable — and the
    incoming tokens are quantized with their page's updated scale.
    """
    b, s = new.shape[:2]
    ps = ctx.page_size
    n_loc = pool.shape[0]
    t = pos[:, None] + jnp.arange(s, dtype=pos.dtype)[None]   # [b, s]
    page = jnp.take_along_axis(ctx.page_tables, t // ps, axis=1)
    mask = ctx.slot_mask
    if mask is None:
        mask = jnp.ones((b,), bool)
    page = jnp.where(mask[:, None], page, n_loc)  # OOB -> dropped
    flat = page * ps + t % ps
    if scale is not None:
        nf = new.astype(jnp.float32)
        # per-token amax at the scale granularity: [b, s] + scale dims
        amax = jnp.abs(nf).max(axis=-1)
        scale_new = scale.at[page.reshape(-1)].max(
            (amax / 127.0).reshape((-1,) + scale.shape[1:]), mode="drop")
        # requantize existing bytes where this write grew a page's scale
        # (ratio is exactly 1.0 everywhere else — identity round-trip)
        ratio = jnp.where(scale_new > 0,
                          scale / jnp.maximum(scale_new, 1e-30), 1.0)
        ratio = ratio.reshape((n_loc, 1) + scale.shape[1:] + (1,))
        pool = jnp.clip(jnp.round(pool.astype(jnp.float32) * ratio),
                        -127, 127).astype(pool.dtype)
        # quantize the incoming tokens with their page's final scale
        sc_tok = scale_new[jnp.clip(page, 0, n_loc - 1).reshape(-1)]
        sc_tok = sc_tok.reshape((b, s) + scale.shape[1:])[..., None]
        new = jnp.clip(jnp.round(nf / jnp.maximum(sc_tok, 1e-30)),
                       -127, 127)
        scale = scale_new
    pool_flat = pool.reshape((n_loc * ps,) + pool.shape[2:])
    pool_flat = pool_flat.at[flat.reshape(-1)].set(
        new.reshape((b * s,) + new.shape[2:]).astype(pool.dtype),
        mode="drop")
    return pool_flat.reshape(pool.shape), scale


def _slot_state(ctx, old, new):
    """Per-row select for positionless (recurrent) caches: masked-off rows
    keep their previous state. No-op without a slot mask (legacy path)."""
    mask = ctx.slot_mask
    if mask is None:
        return new
    out = {}
    for n, v in new.items():
        m = mask.reshape((-1,) + (1,) * (v.ndim - 1))
        out[n] = jnp.where(m, v.astype(old[n].dtype), old[n])
    return out


def attn_cached(ctx: LayerCtx, params, pfx, x, cache, pos):
    """x: [b, s, d]; cache k/v: [b, S, g, e]; pos: first absolute position.

    ``pos`` may be a [b] vector (slotted serving): each row scatters into
    its cache at its own position, writes gated by ``ctx.slot_mask``, and
    attends with a per-row causal offset.

    s == 1 with ctx.kv_seq_shard uses flash-decoding combine over "data"
    (the 500k-context path: the KV cache is sequence-sharded).
    """
    cfg = ctx.cfg
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhe->bshe", x, params[f"{pfx}.wq"])
    k = jnp.einsum("bsd,dge->bsge", x, params[f"{pfx}.wk"])
    v = jnp.einsum("bsd,dge->bsge", x, params[f"{pfx}.wv"])
    cos, sin = _rope_slice(ctx, cfg.head_dim, pos, s)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if ctx.page_tables is not None:
        # paged KV: scatter this step's K/V through the page tables
        # (quantizing when the pool is int8), then attend straight out
        # of the pool — the page-table-native kernel (or its jnp mirror)
        # applies the per-row causal offset and sentinel masking itself.
        # The pool (not a per-row window) is the cache state.
        ksc, vsc = cache.get("k_scale"), cache.get("v_scale")
        kp, ksc = _paged_scatter(ctx, cache["k"], k, pos, ksc)
        vp, vsc = _paged_scatter(ctx, cache["v"], v, pos, vsc)
        o = ops.paged_attention(
            q, kp, vp, page_tables=ctx.page_tables, pos=pos,
            k_scale=ksc, v_scale=vsc, slot_mask=ctx.slot_mask,
            block_k=ctx.rc.attn_block_k, impl=ctx.rc.kernel_impl)
        cache = {"k": kp, "v": vp}
        if ksc is not None:
            cache["k_scale"], cache["v_scale"] = ksc, vsc
    elif getattr(pos, "ndim", 0):
        kc = _slot_scatter(ctx, cache["k"], k, pos)
        vc = _slot_scatter(ctx, cache["v"], v, pos)
        o = ops.attention(q, kc, vc, causal=True, q_offset=pos,
                          block_k=ctx.rc.attn_block_k,
                          impl=ctx.rc.kernel_impl)
        cache = {"k": kc, "v": vc}
    elif getattr(ctx, "kv_seq_shard", False):
        # cache local window [b, S/D, g, e]; only the owner of `pos` writes
        dsz = ctx.kv_shards
        S_loc = cache["k"].shape[1]
        r = jax.lax.axis_index("data")
        lo = r * S_loc
        in_win = (pos >= lo) & (pos < lo + S_loc)
        off = jnp.clip(pos - lo, 0, S_loc - 1)
        k_old = jax.lax.dynamic_slice_in_dim(cache["k"], off, s, axis=1)
        v_old = jax.lax.dynamic_slice_in_dim(cache["v"], off, s, axis=1)
        kc = jax.lax.dynamic_update_slice(
            cache["k"], jnp.where(in_win, k, k_old).astype(
                cache["k"].dtype), (0, off, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], jnp.where(in_win, v, v_old).astype(
                cache["v"].dtype), (0, off, 0, 0))
        # local partial attention with global positions
        n_valid = jnp.clip(pos + s - lo, 0, S_loc)
        _, (m, l, acc) = ops.decode_attention(q, kc, vc, cache_len=n_valid,
                                              impl=ctx.rc.kernel_impl)
        # combine across shards: psum-logsumexp (all data ranks aligned)
        m_g = jax.lax.pmax(m, "data")
        m_safe = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
        w_ = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_g = jax.lax.psum(l * w_, "data")
        acc_g = jax.lax.psum(acc * w_[..., None], "data")
        o = (acc_g / jnp.maximum(l_g, 1e-30)[..., None])
        o = jnp.einsum("bhqe->bqhe", o).astype(x.dtype)
        cache = {"k": kc, "v": vc}
    else:
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
        o = ops.attention(q, kc, vc, causal=True, q_offset=pos,
                          block_k=ctx.rc.attn_block_k,
                          impl=ctx.rc.kernel_impl)
        cache = {"k": kc, "v": vc}
    y = jnp.einsum("bshe,hed->bsd", o, params[f"{pfx}.wo"])
    return y, cache


def mla_cached(ctx, params, pfx, x, cache, pos):
    cfg = ctx.cfg
    m: MLACfg = cfg.mla
    b, s, d = x.shape
    q = _mla_q(cfg, params, pfx, x)
    ckv = jnp.einsum("bsd,dc->bsc", x, params[f"{pfx}.wdkv"])
    ckv_sc = None
    if ctx.page_tables is not None:  # paged latent cache
        # MLA always gathers the latent pages (the up-projection makes
        # dense K/V before attention), so int8 dequant happens here —
        # identically under both kernel implementations — and only the
        # attention after routes through the slot-aware Pallas kernel.
        ckv_sc = cache.get("ckv_scale")
        cache_new, ckv_sc = _paged_scatter(ctx, cache["ckv"], ckv, pos,
                                           ckv_sc)
        full = _paged_gather(ctx, cache_new, ckv_sc)
        if ckv_sc is not None:
            full = full.astype(x.dtype)
    elif getattr(pos, "ndim", 0):  # per-slot positions (slotted serving)
        cache_new = _slot_scatter(ctx, cache["ckv"], ckv, pos)
        full = cache_new
    else:
        cache_new = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, pos, 0))
        full = cache_new
    c_kv, k_rope = full[..., : m.kv_lora], full[..., m.kv_lora:]
    c_kv = rms_norm(c_kv.astype(jnp.float32), params[f"{pfx}.kvnorm.scale"],
                    cfg.norm_eps).astype(x.dtype)
    k_nope = jnp.einsum("bsc,che->bshe", c_kv, params[f"{pfx}.wuk"])
    vv = jnp.einsum("bsc,che->bshe", c_kv, params[f"{pfx}.wuv"])
    cos_q, sin_q = _rope_slice(ctx, m.rope_dims, pos, s)
    cos_k, sin_k = ctx.rope[m.rope_dims]
    q_nope, q_rope = q[..., : m.qk_nope], q[..., m.qk_nope:]
    q_rope = apply_rope(q_rope, cos_q, sin_q)
    k_rope = apply_rope(k_rope[:, :, None, :], cos_k[: full.shape[1]],
                        sin_k[: full.shape[1]])
    k_rope = jnp.broadcast_to(k_rope, k_nope.shape[:3] + (m.rope_dims,))
    qf = jnp.concatenate([q_nope, q_rope], -1)
    kf = jnp.concatenate([k_nope, k_rope], -1)
    o = ops.attention(qf, kf, vv, causal=True, q_offset=pos,
                      block_k=ctx.rc.attn_block_k,
                      impl=ctx.rc.kernel_impl)
    y = jnp.einsum("bshe,hed->bsd", o, params[f"{pfx}.wo"])
    out_cache = {"ckv": cache_new}
    if ckv_sc is not None:
        out_cache["ckv_scale"] = ckv_sc
    return y, out_cache


def mamba_cached(ctx, params, pfx, x, cache, pos):
    """Prefill runs the chunked scan (state out); decode steps the SSM."""
    cfg = ctx.cfg
    mc, di, dt_rank = _mamba_dims(cfg)
    n = mc.d_state
    b, s, d = x.shape
    if s == 1:
        return mamba_decode(ctx, params, pfx, x, cache, pos)
    xz = jnp.einsum("bsd,de->bse", x, params[f"{pfx}.w_in"])
    xs, z = xz[..., :di], xz[..., di:]
    pad = jnp.pad(xs, ((0, 0), (mc.d_conv - 1, 0), (0, 0)))
    cw = params[f"{pfx}.conv_w"]
    out = sum(pad[:, i: i + s] * cw[i][None, None]
              for i in range(mc.d_conv)) + params[f"{pfx}.conv_b"]
    xs_c = jax.nn.silu(out)
    bcdt = jnp.einsum("bse,er->bsr", xs_c, params[f"{pfx}.w_x"])
    dt = jax.nn.softplus(
        jnp.einsum("bsr,re->bse", bcdt[..., :dt_rank], params[f"{pfx}.w_dt"])
        + params[f"{pfx}.dt_bias"]).astype(jnp.float32)
    Bm = bcdt[..., dt_rank: dt_rank + n].astype(jnp.float32)
    Cm = bcdt[..., dt_rank + n:].astype(jnp.float32)
    A = -jnp.exp(params[f"{pfx}.A_log"].astype(jnp.float32))
    y, h = ops.selective_scan(
        xs_c.astype(jnp.float32), dt, A, Bm, Cm,
        params[f"{pfx}.Dd"].astype(jnp.float32), return_state=True)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    y = jnp.einsum("bse,ed->bsd", y, params[f"{pfx}.w_out"])
    conv_state = xs[:, -(mc.d_conv - 1):]
    return y, {"conv": conv_state.astype(cache["conv"].dtype), "h": h}


def mlstm_cached(ctx, params, pfx, x, cache, pos):
    cfg = ctx.cfg
    if x.shape[1] == 1:
        return mlstm_decode(ctx, params, pfx, x, cache, pos)
    d = cfg.d_model
    di = int(cfg.xlstm.proj_factor * d)
    up = jnp.einsum("bsd,de->bse", x, params[f"{pfx}.w_up"])
    xb, z = up[..., :di], up[..., di:]
    q = jnp.einsum("bse,ehf->bshf", xb, params[f"{pfx}.wq"])
    k = jnp.einsum("bse,ehf->bshf", xb, params[f"{pfx}.wk"])
    v = jnp.einsum("bse,ehf->bshf", xb, params[f"{pfx}.wv"])
    gates = jnp.einsum("bse,egh->bsgh", xb.astype(jnp.float32),
                       params[f"{pfx}.w_if"].astype(jnp.float32))
    gates = gates + params[f"{pfx}.if_bias"]
    ig, fg = gates[:, :, 0], gates[:, :, 1] + 1.0
    y, state = ops.mlstm_chunkwise(q, k, v, ig, fg, return_state=True)
    y = y.reshape(y.shape[0], y.shape[1], -1)
    y = (y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype))
    y = jnp.einsum("bse,ed->bsd", y.astype(x.dtype),
                   params[f"{pfx}.w_out"])
    return y, {"C": state[0], "n": state[1], "m": state[2]}


def slstm_cached(ctx, params, pfx, x, cache, pos):
    if x.shape[1] == 1:
        return slstm_decode(ctx, params, pfx, x, cache, pos)
    g = jnp.einsum("bsd,dhge->bshge", x, params[f"{pfx}.w_gates"])
    g = g + params[f"{pfx}.g_bias"]
    state = (cache["c"], cache["n"], cache["m"])
    y, state = ops.slstm_scan(g, state=state, return_state=True)
    y = y.reshape(x.shape[0], x.shape[1], -1).astype(x.dtype)
    y = jnp.einsum("bsd,de->bse", y, params[f"{pfx}.w_out"])
    return y, {"c": state[0], "n": state[1], "m": state[2]}
