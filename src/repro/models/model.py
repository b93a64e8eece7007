"""Architecture assembly: pipeline geometry, stage params, stage apply.

Pipeline geometry
-----------------
The model axis of the production mesh (16 ranks) is factored into
``groups × pp`` pipeline groups (a beyond-paper generalization that lets
every assigned architecture divide evenly into stages with *statically*
uniform layer kinds — see DESIGN.md §4).  Within a group, the paper's
circular placement is used: stage ``s = v·pp + p`` lives on group-rank
``p``, local slot ``v``.  Each stage holds ``k`` consecutive layers
(``i = s·k + j``); architectures whose layer-kind pattern has period
``q`` require ``q | k`` so that the kind of slot ``j`` is static.

Parameters are stored *rank-major*: stacked index ``p·V + v`` ↦ stage
``v·pp + p``, so a contiguous shard over the model axis gives each rank
exactly its V stages.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import ARCH_REGISTRY as _ARCH_REGISTRY
from repro.core.tape import Tape, TVal
from repro.kernels import ops
from repro.models import blocks
from repro.models.common import (
    ModelConfig,
    ParamSpec,
    RunConfig,
    SHAPES,
    init_params,
    rope_tables,
)

# --------------------------------------------------------------------------- #
# Geometry
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class Segment:
    name: str                # "main" | "enc" | "dec"
    n_layers: int            # real (unpadded) layers
    vpp: int                 # V
    k: int                   # layers per stage
    kinds: tuple[str, ...]   # static kind per layer slot j (len k)
    causal: bool = True

    @property
    def n_stages(self):
        return None  # filled via geometry (needs pp)


@dataclasses.dataclass(frozen=True)
class Geometry:
    pp: int                  # ranks per pipeline group
    groups: int              # pipeline groups on the model axis
    segments: tuple[Segment, ...]

    @property
    def model_ranks(self):
        return self.pp * self.groups

    def seg_stages(self, seg: Segment) -> int:
        return self.pp * seg.vpp

    def padded_layers(self, seg: Segment) -> int:
        return self.pp * seg.vpp * seg.k


def build_geometry(cfg: ModelConfig, rc: RunConfig) -> Geometry:
    """Derive (and validate) the static stage layout."""
    segs = []
    if cfg.encdec is not None:
        enc_kinds = ("enc",)
        dec_kinds = ("dec",)
        v_enc = max(1, cfg.encdec.enc_layers // rc.pp)
        v_dec = max(1, cfg.n_layers // rc.pp)
        segs.append(Segment("enc", cfg.encdec.enc_layers, v_enc, 1,
                            enc_kinds, causal=False))
        segs.append(Segment("dec", cfg.n_layers, v_dec, 1, dec_kinds))
    else:
        L = cfg.n_layers
        pv = rc.pp * rc.vpp
        k = -(-L // pv)
        kinds = tuple(cfg.layer_kind(j) for j in range(k))
        # static-kind check: kind(i) must equal kind(i mod k)
        for i in range(L):
            if cfg.layer_kind(i) != kinds[i % k]:
                raise ValueError(
                    f"{cfg.name}: layer kinds are not static per slot with "
                    f"pp={rc.pp} vpp={rc.vpp} (k={k}); adjust geometry"
                )
        segs.append(Segment("main", L, rc.vpp, k, kinds))
    return Geometry(rc.pp, rc.groups, tuple(segs))


# --------------------------------------------------------------------------- #
# Param specs
# --------------------------------------------------------------------------- #


def layer_slot_specs(cfg: ModelConfig, kind: str, pfx: str):
    """Specs for one layer slot of the given static kind."""
    mix, ffn = kind.split(":") if ":" in kind else (kind, "none")
    sp: dict[str, ParamSpec] = {}
    if kind == "enc":
        sp.update(blocks.norm_specs(cfg, f"{pfx}.ln1"))
        sp.update(blocks.attn_specs(cfg, f"{pfx}.mix"))
        sp.update(blocks.norm_specs(cfg, f"{pfx}.ln2"))
        sp.update(blocks.ffn_specs(cfg, f"{pfx}.ffn"))
        return sp
    if kind == "dec":
        sp.update(blocks.norm_specs(cfg, f"{pfx}.ln1"))
        sp.update(blocks.attn_specs(cfg, f"{pfx}.mix"))
        sp.update(blocks.norm_specs(cfg, f"{pfx}.ln2"))
        sp.update(blocks.attn_specs(cfg, f"{pfx}.xattn"))
        sp.update(blocks.norm_specs(cfg, f"{pfx}.ln3"))
        sp.update(blocks.ffn_specs(cfg, f"{pfx}.ffn"))
        return sp
    sp.update(blocks.norm_specs(cfg, f"{pfx}.ln1"))
    if mix == "attn":
        sp.update(blocks.attn_specs(cfg, f"{pfx}.mix"))
    elif mix == "mla":
        sp.update(blocks.mla_specs(cfg, f"{pfx}.mix"))
    elif mix == "mamba":
        sp.update(blocks.mamba_specs(cfg, f"{pfx}.mix"))
    elif mix == "mlstm":
        sp.update(blocks.mlstm_specs(cfg, f"{pfx}.mix"))
    elif mix == "slstm":
        sp.update(blocks.slstm_specs(cfg, f"{pfx}.mix"))
    else:
        raise ValueError(mix)
    if ffn != "none":
        sp.update(blocks.norm_specs(cfg, f"{pfx}.ln2"))
        if ffn == "moe":
            sp.update(blocks.moe_specs(cfg, f"{pfx}.ffn"))
        else:
            sp.update(blocks.ffn_specs(cfg, f"{pfx}.ffn"))
    return sp


def stage_specs(cfg: ModelConfig, seg: Segment) -> dict[str, ParamSpec]:
    sp = {}
    for j, kind in enumerate(seg.kinds):
        sp.update(layer_slot_specs(cfg, kind, f"L{j}"))
    return sp


def io_specs(cfg: ModelConfig) -> dict[str, ParamSpec]:
    """Embedding / final norm / head (+MTP) params, outside the pipeline."""
    d, vcb = cfg.d_model, cfg.vocab
    sp = {
        "embed.table": ParamSpec((vcb, d), fsdp_dim=0, scale=1.0),
        "final_norm.scale": ParamSpec((d,), "ones"),
    }
    if cfg.norm == "layernorm":
        sp["final_norm.bias"] = ParamSpec((d,), "zeros")
    if not cfg.tie_embeddings:
        sp["head.w"] = ParamSpec((d, vcb), fsdp_dim=1)
    if cfg.mtp:
        sp.update({
            "mtp.proj": ParamSpec((2 * d, d), fsdp_dim=0),
            "mtp.norm.scale": ParamSpec((d,), "ones"),
        })
        sp.update(layer_slot_specs(cfg, cfg.layer_kind(cfg.n_layers - 1),
                                   "mtp.layer"))
    if cfg.frontend == "audio":
        # conv frontend is stubbed: inputs are precomputed frame embeddings.
        pass
    return sp


# --------------------------------------------------------------------------- #
# Stage application (tape — train/prefill path)
# --------------------------------------------------------------------------- #


def apply_layer(
    t: Tape,
    ctx: blocks.LayerCtx,
    kind: str,
    pfx: str,
    x: TVal,
    keep,  # traced 0/1 (pad masking) or python 1
) -> tuple[TVal, TVal | None]:
    """Pre-norm residual layer. Returns (y, aux_loss or None)."""
    aux = None
    mix, ffn = kind.split(":") if ":" in kind else (kind, "none")

    def res_add(a, b):
        return t.prim(lambda u, v: u + v * keep, a, b)

    if kind == "enc":
        h = apply_mix(t, ctx, "attn", f"{pfx}", x, causal=False)
        x = res_add(x, h)
        h2 = blocks.apply_norm(t, ctx.cfg, f"{pfx}.ln2", x)
        h2 = blocks.apply_ffn(t, ctx, f"{pfx}.ffn", h2)
        return res_add(x, h2), aux
    if kind == "dec":
        h = apply_mix(t, ctx, "attn", f"{pfx}", x, causal=True)
        x = res_add(x, h)
        h2 = blocks.apply_norm(t, ctx.cfg, f"{pfx}.ln2", x)
        h2 = blocks.apply_attn(t, ctx, f"{pfx}.xattn", h2, cross=True)
        x = res_add(x, h2)
        h3 = blocks.apply_norm(t, ctx.cfg, f"{pfx}.ln3", x)
        h3 = blocks.apply_ffn(t, ctx, f"{pfx}.ffn", h3)
        return res_add(x, h3), aux

    h = apply_mix(t, ctx, mix, pfx, x, causal=ctx.causal)
    x = res_add(x, h)
    if ffn != "none":
        h2 = blocks.apply_norm(t, ctx.cfg, f"{pfx}.ln2", x)
        if ffn == "moe":
            h2, aux = blocks.apply_moe(t, ctx, f"{pfx}.ffn", h2)
        else:
            h2 = blocks.apply_ffn(t, ctx, f"{pfx}.ffn", h2)
        x = res_add(x, h2)
    return x, aux


def apply_mix(t, ctx, mix, pfx, x, causal=True):
    h = blocks.apply_norm(t, ctx.cfg, f"{pfx}.ln1", x)
    ctx2 = dataclasses.replace(ctx, causal=causal)
    if mix == "attn":
        return blocks.apply_attn(t, ctx2, f"{pfx}.mix", h)
    if mix == "mla":
        return blocks.apply_mla(t, ctx2, f"{pfx}.mix", h)
    if mix == "mamba":
        return blocks.apply_mamba(t, ctx2, f"{pfx}.mix", h)
    if mix == "mlstm":
        return blocks.apply_mlstm(t, ctx2, f"{pfx}.mix", h)
    if mix == "slstm":
        return blocks.apply_slstm(t, ctx2, f"{pfx}.mix", h)
    raise ValueError(mix)


def apply_stage(
    t: Tape,
    ctx: blocks.LayerCtx,
    seg: Segment,
    x: TVal,
    stage_id,  # traced int (v·pp + p)
) -> tuple[TVal, TVal]:
    """Apply the k layers of one stage. Returns (y, aux_scalar)."""
    aux_total = t.value(jnp.zeros((), jnp.float32))
    for j, kind in enumerate(seg.kinds):
        layer_id = stage_id * seg.k + j
        keep = jnp.asarray(layer_id < seg.n_layers).astype(x.val.dtype)
        x, aux = apply_layer(t, ctx, kind, f"L{j}", x, keep)
        if aux is not None:
            aux_total = t.prim(
                lambda a, b: a + b.astype(jnp.float32)
                * keep.astype(jnp.float32),
                aux_total, aux,
            )
    return x, aux_total


# --------------------------------------------------------------------------- #
# Decode path (pure jnp, cached)
# --------------------------------------------------------------------------- #


def layer_cache_spec(cfg, rc, kind, batch, max_seq) -> dict[str, Any]:
    """ShapeDtypeStructs for one layer's decode cache."""
    mix = kind.split(":")[0] if ":" in kind else kind
    f32, cdt = jnp.float32, jnp.dtype(rc.compute_dtype)
    # KV storage dtype is decoupled from compute: fp32/bf16 for accuracy/
    # memory, int8 for quantized pages (per-page scales live in separate
    # "<name>_scale" pool leaves added by init_serve_caches). Recurrent
    # state caches (mamba/mlstm/slstm) always keep the compute dtype.
    kv_dt = jnp.dtype({
        None: rc.compute_dtype, "fp32": jnp.float32,
        "bf16": jnp.bfloat16, "int8": jnp.int8,
    }[rc.kv_cache_dtype])
    g, e = cfg.n_kv_heads, cfg.head_dim
    if mix in ("attn", "dec"):
        return {
            "k": jax.ShapeDtypeStruct((batch, max_seq, g, e), kv_dt),
            "v": jax.ShapeDtypeStruct((batch, max_seq, g, e), kv_dt),
        }
    if mix == "enc":
        return {}
    if mix == "mla":
        m = cfg.mla
        return {"ckv": jax.ShapeDtypeStruct(
            (batch, max_seq, m.kv_lora + m.rope_dims), kv_dt)}
    if mix == "mamba":
        mc, di, _ = blocks._mamba_dims(cfg)
        return {
            "conv": jax.ShapeDtypeStruct((batch, mc.d_conv - 1, di), cdt),
            "h": jax.ShapeDtypeStruct((batch, di, mc.d_state), f32),
        }
    if mix == "mlstm":
        d = cfg.d_model
        di = int(cfg.xlstm.proj_factor * d)
        h = cfg.n_heads
        e2 = di // h
        return {
            "C": jax.ShapeDtypeStruct((batch, h, e2, e2), f32),
            "n": jax.ShapeDtypeStruct((batch, h, e2), f32),
            "m": jax.ShapeDtypeStruct((batch, h), f32),
        }
    if mix == "slstm":
        h = cfg.n_heads
        e2 = cfg.d_model // h
        z = jax.ShapeDtypeStruct((batch, h, e2), f32)
        return {"c": z, "n": z, "m": z}
    raise ValueError(mix)


def decode_layer(ctx, params, kind, pfx, x, cache, pos):
    """One cached decode step for one layer. Returns (y, new_cache)."""
    cfg = ctx.cfg
    mix = kind.split(":")[0] if ":" in kind else kind
    ffn = kind.split(":")[1] if ":" in kind else (
        "dense" if kind in ("enc", "dec") else "none"
    )
    h = blocks.norm_fwd(cfg, params, f"{pfx}.ln1", x)
    if mix in ("attn", "dec"):
        dh, cache = blocks.attn_decode(ctx, params, f"{pfx}.mix", h, cache, pos)
    elif mix == "mla":
        dh, cache = blocks.mla_decode(ctx, params, f"{pfx}.mix", h, cache, pos)
    elif mix == "mamba":
        dh, cache = blocks.mamba_decode(ctx, params, f"{pfx}.mix", h, cache, pos)
    elif mix == "mlstm":
        dh, cache = blocks.mlstm_decode(ctx, params, f"{pfx}.mix", h, cache, pos)
    elif mix == "slstm":
        dh, cache = blocks.slstm_decode(ctx, params, f"{pfx}.mix", h, cache, pos)
    else:
        raise ValueError(mix)
    x = x + dh
    if mix == "dec":
        h2 = blocks.norm_fwd(cfg, params, f"{pfx}.ln2", x)
        x = x + blocks.cross_attn_decode(ctx, params, f"{pfx}.xattn", h2,
                                         ctx.enc_memory)
        h3 = blocks.norm_fwd(cfg, params, f"{pfx}.ln3", x)
        x = x + blocks.ffn_fwd(ctx, params, f"{pfx}.ffn", h3)
        return x, cache
    if ffn != "none":
        h2 = blocks.norm_fwd(cfg, params, f"{pfx}.ln2", x)
        if ffn == "moe":
            x = x + blocks.moe_fwd(ctx, params, f"{pfx}.ffn", h2)
        else:
            x = x + blocks.ffn_fwd(ctx, params, f"{pfx}.ffn", h2)
    return x, cache


def decode_stage(ctx, seg: Segment, params, x, caches, stage_id, pos):
    """caches: list of per-slot cache dicts."""
    new_caches = []
    for j, kind in enumerate(seg.kinds):
        layer_id = stage_id * seg.k + j
        keep = (layer_id < seg.n_layers).astype(x.dtype)
        y, cj = decode_layer(ctx, params, kind, f"L{j}", x, caches[j], pos)
        x = x + (y - x) * keep
        new_caches.append(cj)
    return x, new_caches


# --------------------------------------------------------------------------- #
# Embedding / head / loss (outside the pipeline body)
# --------------------------------------------------------------------------- #


def embed_tokens(params, tokens, cfg, dtype):
    """tokens int32 [b, s] OR pre-computed embeddings float [b, s, d]."""
    if jnp.issubdtype(tokens.dtype, jnp.floating):
        return tokens.astype(dtype)  # stubbed modality frontend
    return params["embed.table"][tokens].astype(dtype)


def head_loss(params, cfg, rc, h, labels, mask=None):
    """Final norm + chunked-vocab xent. Returns loss and (dh, dW, dnorm…)
    via explicit formulas (no jax.grad) so the drain tick stays cheap.

    h: [n, d] f32/bf16, labels [n]. Returns (loss, dh, head_grads dict).
    """
    d = cfg.d_model
    scale = params["final_norm.scale"]
    hf = h.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True)
                        + cfg.norm_eps)
    hn = hf * rms * scale
    w = params["embed.table"].T if cfg.tie_embeddings else params["head.w"]
    loss, (dhn, dw) = ops.softmax_xent(
        hn.astype(h.dtype), w, labels, chunk=rc.vocab_chunk, mask=mask
    )
    dhn = dhn.astype(jnp.float32)
    dscale = (dhn * hf * rms).sum(0)
    dh_pre = dhn * scale * rms
    # d/dh of rms normalizer term
    dot = jnp.sum(dhn * scale * hf, -1, keepdims=True)
    dh = dh_pre - hf * (rms ** 3) * dot / d
    grads = {"final_norm.scale": dscale}
    if cfg.tie_embeddings:
        grads["embed.table"] = dw.T
    else:
        grads["head.w"] = dw
    return loss, dh.astype(h.dtype), grads


# --------------------------------------------------------------------------- #
# Single-device reference model (numerics oracle, smoke tests)
# --------------------------------------------------------------------------- #


def make_rope_ctx(cfg: ModelConfig, rc: RunConfig, seq: int, offset=0,
                  decode=False, full_seq: int | None = None):
    dims = {cfg.head_dim}
    if cfg.mla is not None:
        dims.add(cfg.mla.rope_dims)
    rope = {}
    rope_full = {}
    for e in dims:
        cos, sin = rope_tables(seq if not decode else 1, e, cfg.rope_theta)
        if decode:
            cos_f, sin_f = rope_tables(full_seq, e, cfg.rope_theta)
            # current position table computed via dynamic slice by caller
            rope[e] = (cos_f, sin_f)  # caller slices
            rope_full[e] = (cos_f, sin_f)
        else:
            rope[e] = (cos, sin)
    return rope, rope_full


def init_all_params(cfg: ModelConfig, rc: RunConfig, key=None):
    """Full (unsharded) parameter tree: {io: {...}, segments: {name: {name: [S,...]}}}."""
    key = key if key is not None else jax.random.PRNGKey(0)
    dtype = jnp.dtype(rc.param_dtype)
    geo = build_geometry(cfg, rc)
    kio, kseg = jax.random.split(key)
    io = init_params(kio, io_specs(cfg), dtype)
    segments = {}
    for seg in geo.segments:
        sp = stage_specs(cfg, seg)
        S = geo.seg_stages(seg)
        # crc32, not hash(): str hashes change with PYTHONHASHSEED, so
        # the same key would give other weights in another process
        keys = jax.random.split(
            jax.random.fold_in(kseg, zlib.crc32(seg.name.encode()) % 2**31),
            S)
        stacked = None
        per_stage = [init_params(keys[s], sp, dtype) for s in range(S)]
        stacked = {
            name: jnp.stack([ps[name] for ps in per_stage])
            for name in sp
        }
        segments[seg.name] = stacked
    return {"io": io, "segments": segments}


def storage_index(p: int, v: int, V: int) -> int:
    """Rank-major stacked index for logical stage s = v·pp + p."""
    return p * V + v


def reference_logits(cfg, rc, params, tokens, enc_tokens=None,
                     return_hidden=False):
    """Full forward on one device, looping stages in logical order."""
    geo = build_geometry(cfg, rc)
    dtype = jnp.dtype(rc.compute_dtype)
    io = params["io"]
    aux_total = jnp.zeros((), jnp.float32)

    def run_segment(seg, x):
        nonlocal aux_total
        rope, _ = make_rope_ctx(cfg, rc, x.shape[1])
        ctx = blocks.LayerCtx(cfg=cfg, rc=rc, rope=rope, causal=seg.causal)
        if seg.name == "dec":
            ctx.enc_memory = None  # set by caller below
        stacked = params["segments"][seg.name]
        S = geo.seg_stages(seg)
        for s in range(S):
            p, v = s % geo.pp, s // geo.pp
            idx = storage_index(p, v, seg.vpp)
            sp = {n: a[idx] for n, a in stacked.items()}
            t = Tape(sp, mode="fwd")
            if ctx.enc_memory is not None and not isinstance(
                ctx.enc_memory, TVal
            ):
                ctx.enc_memory = t.value(ctx.enc_memory)
            xv, aux = apply_stage(t, ctx, seg, t.value(x), s)
            x = xv.val
            aux_total = aux_total + aux.val
            ctx.enc_memory = (
                ctx.enc_memory.val if isinstance(ctx.enc_memory, TVal)
                else ctx.enc_memory
            )
        return x

    if cfg.encdec is not None:
        enc_x = embed_tokens(io, enc_tokens, cfg, dtype)
        seg_e, seg_d = geo.segments
        memory = run_segment(seg_e, enc_x)
        x = embed_tokens(io, tokens, cfg, dtype)
        # decoder segment with cross-attention memory
        rope, _ = make_rope_ctx(cfg, rc, x.shape[1])
        ctx = blocks.LayerCtx(cfg=cfg, rc=rc, rope=rope, causal=True)
        stacked = params["segments"]["dec"]
        for s in range(geo.seg_stages(seg_d)):
            p, v = s % geo.pp, s // geo.pp
            idx = storage_index(p, v, seg_d.vpp)
            sp = {n: a[idx] for n, a in stacked.items()}
            t = Tape(sp, mode="fwd")
            ctx.enc_memory = t.value(memory)
            xv, _ = apply_stage(t, ctx, seg_d, t.value(x), s)
            x = xv.val
    else:
        x = embed_tokens(io, tokens, cfg, dtype)
        x = run_segment(geo.segments[0], x)

    scale = io["final_norm.scale"]
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        hn = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * scale \
            + io["final_norm.bias"]
    else:
        hn = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, -1, keepdims=True) + cfg.norm_eps) * scale
    w = io["embed.table"].T if cfg.tie_embeddings else io["head.w"]
    logits = hn.astype(dtype) @ w
    if return_hidden:
        return logits, aux_total, x
    return logits, aux_total


def reference_loss(cfg, rc, params, tokens, labels, enc_tokens=None):
    logits, aux = reference_logits(cfg, rc, params, tokens,
                                   enc_tokens=enc_tokens)
    n = logits.shape[0] * logits.shape[1]
    lf = logits.reshape(n, -1).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    lab = jnp.take_along_axis(lf, labels.reshape(n)[:, None], axis=1)[:, 0]
    loss = (lse - lab).mean()
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_weight * aux
    if cfg.mtp:
        _, _, h_final = reference_logits(cfg, rc, params, tokens,
                                         enc_tokens=enc_tokens,
                                         return_hidden=True)
        loss = loss + MTP_WEIGHT * mtp_reference_loss(
            cfg, rc, params["io"], h_final, tokens, labels)
    return loss


# --------------------------------------------------------------------------- #
# Registry (delegates to the plug-in registry in repro.api.registry)
# --------------------------------------------------------------------------- #


def __getattr__(name):
    # ARCHS is a live view of the registry (PEP 562), so archs added via
    # repro.api.register_arch appear here too.
    if name == "ARCHS":
        return _ARCH_REGISTRY.names()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def get_arch(name: str):
    """Returns the config module/object for an architecture id.

    Resolution (canonical names, aliases, custom registrations) lives in
    ``repro.api.registry``; plug new architectures in with
    ``repro.api.register_arch`` instead of editing this file.
    """
    return _ARCH_REGISTRY.get(name)


# --------------------------------------------------------------------------- #
# Cached stage execution (prefill / decode serving)
# --------------------------------------------------------------------------- #


def cached_layer(ctx, params, kind, pfx, x, cache, pos):
    """Unified prefill (s>1) / decode (s=1) for one layer."""
    cfg = ctx.cfg
    mix = kind.split(":")[0] if ":" in kind else kind
    ffn = kind.split(":")[1] if ":" in kind else (
        "dense" if kind in ("enc", "dec") else "none")
    h = blocks.norm_fwd(cfg, params, f"{pfx}.ln1", x)
    if kind == "enc":
        o = _enc_attn_fwd(ctx, params, f"{pfx}.mix", h)
        x = x + o
        h2 = blocks.norm_fwd(cfg, params, f"{pfx}.ln2", x)
        return x + blocks.ffn_fwd(ctx, params, f"{pfx}.ffn", h2), cache
    if mix in ("attn", "dec"):
        dh, cache = blocks.attn_cached(ctx, params, f"{pfx}.mix", h, cache,
                                       pos)
    elif mix == "mla":
        dh, cache = blocks.mla_cached(ctx, params, f"{pfx}.mix", h, cache,
                                      pos)
    elif mix == "mamba":
        dh, c2 = blocks.mamba_cached(ctx, params, f"{pfx}.mix", h, cache,
                                     pos)
        cache = blocks._slot_state(ctx, cache, c2)
    elif mix == "mlstm":
        dh, c2 = blocks.mlstm_cached(ctx, params, f"{pfx}.mix", h, cache,
                                     pos)
        cache = blocks._slot_state(ctx, cache, c2)
    elif mix == "slstm":
        dh, c2 = blocks.slstm_cached(ctx, params, f"{pfx}.mix", h, cache,
                                     pos)
        cache = blocks._slot_state(ctx, cache, c2)
    else:
        raise ValueError(mix)
    x = x + dh
    if mix == "dec":
        h2 = blocks.norm_fwd(cfg, params, f"{pfx}.ln2", x)
        x = x + blocks.cross_attn_decode(ctx, params, f"{pfx}.xattn", h2,
                                         ctx.enc_memory)
        h3 = blocks.norm_fwd(cfg, params, f"{pfx}.ln3", x)
        return x + blocks.ffn_fwd(ctx, params, f"{pfx}.ffn", h3), cache
    if ffn != "none":
        h2 = blocks.norm_fwd(cfg, params, f"{pfx}.ln2", x)
        if ffn == "moe":
            x = x + blocks.moe_fwd(ctx, params, f"{pfx}.ffn", h2)
        else:
            x = x + blocks.ffn_fwd(ctx, params, f"{pfx}.ffn", h2)
    return x, cache


def _enc_attn_fwd(ctx, params, pfx, x):
    q = jnp.einsum("bsd,dhe->bshe", x, params[f"{pfx}.wq"])
    k = jnp.einsum("bsd,dge->bsge", x, params[f"{pfx}.wk"])
    v = jnp.einsum("bsd,dge->bsge", x, params[f"{pfx}.wv"])
    from repro.kernels import ops as _ops
    o = _ops.attention(q, k, v, causal=False, block_k=ctx.rc.attn_block_k)
    return jnp.einsum("bshe,hed->bsd", o, params[f"{pfx}.wo"])


def cached_stage(ctx, seg, params, x, caches, stage_id, pos):
    """caches: list (per layer slot j) of cache dicts (possibly empty)."""
    new_caches = []
    for j, kind in enumerate(seg.kinds):
        layer_id = stage_id * seg.k + j
        keep = jnp.asarray(layer_id < seg.n_layers).astype(x.dtype)
        y, cj = cached_layer(ctx, params, kind, f"L{j}", x, caches[j], pos)
        x = x + (y - x) * keep
        new_caches.append(cj)
    return x, new_caches


# --------------------------------------------------------------------------- #
# DeepSeek MTP (multi-token prediction) auxiliary head
# --------------------------------------------------------------------------- #

MTP_WEIGHT = 0.1


def mtp_hidden(cfg, rc, io_params, h, emb_next, ep_axis=None):
    """DeepSeek MTP module: RMSNorm(h) ∥ RMSNorm(emb_{t+1}) → proj →
    one transformer layer → hidden for predicting token t+2.

    h: [b, s, d] final backbone hiddens; emb_next: [b, s, d] embeddings of
    the next token. MTP params are replicated io params, so the layer runs
    in gathered mode with no collectives.
    """
    from repro.core.tape import Tape

    def rms(v):
        vf = v.astype(jnp.float32)
        return (vf * jax.lax.rsqrt(jnp.mean(vf * vf, -1, keepdims=True)
                                   + cfg.norm_eps)).astype(v.dtype)

    cat = jnp.concatenate([rms(h), rms(emb_next)], axis=-1)
    x = jnp.einsum("bse,ed->bsd", cat, io_params["mtp.proj"])
    # one full backbone-style layer (params under "mtp.layer.")
    sub = {"L0." + n[len("mtp.layer."):]: a
           for n, a in io_params.items() if n.startswith("mtp.layer.")}
    t = Tape(sub, mode="fwd")
    kind = cfg.layer_kind(cfg.n_layers - 1)
    dims = {cfg.head_dim}
    if cfg.mla is not None:
        dims.add(cfg.mla.rope_dims)
    rope = {e: rope_tables(h.shape[1], e, cfg.rope_theta) for e in dims}
    ctx = blocks.LayerCtx(cfg=cfg, rc=rc, rope=rope, causal=True,
                          ep_axis=ep_axis)
    y, _ = apply_layer(t, ctx, kind, "L0", t.value(x), jnp.float32(1.0))
    return y.val


def mtp_reference_loss(cfg, rc, io_params, h, tokens, labels):
    """Mean xent of predicting token t+2 (reference path, replicated)."""
    b, s, d = h.shape
    emb_next = io_params["embed.table"][labels].astype(h.dtype)
    hm = mtp_hidden(cfg, rc, io_params, h, emb_next)
    # labels for t+2: shift labels left; mask the last position
    lab2 = jnp.concatenate([labels[:, 1:], labels[:, -1:]], axis=1)
    mask = jnp.concatenate(
        [jnp.ones((b, s - 1)), jnp.zeros((b, 1))], axis=1)
    scale = io_params["mtp.norm.scale"]
    hf = hm.astype(jnp.float32)
    hn = hf * jax.lax.rsqrt(
        jnp.mean(hf * hf, -1, keepdims=True) + cfg.norm_eps) * scale
    w = (io_params["embed.table"].T if cfg.tie_embeddings
         else io_params["head.w"])
    logits = hn @ w.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    lab_logit = jnp.take_along_axis(
        logits.reshape(b * s, -1), lab2.reshape(b * s)[:, None], 1
    ).reshape(b, s)
    return ((lse - lab_logit) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
