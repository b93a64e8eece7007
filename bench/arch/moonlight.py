"""Moonlight-16B-A3B (a DeepSeek-V3 block), one chip's share, as the
program runs it: pre-norm layers, RMSNorm (eps from the configuration)
with a scale; multi-head latent attention with q projected directly (no
q LoRA): k and v up-projected from a normed 512-wide latent, a 64-wide
rotary part of k shared by the heads (rotate-half over its two halves,
theta from the configuration), scores scaled by 1/sqrt(qk_nope + rope);
layer 0 a SwiGLU MLP, every later layer a mixture of experts: the
sigmoid gate over all ``n_routed`` experts in f32 picks the top-k of
score + ``router_bias`` (the bias chooses and never weighs, and no
gradient reaches it), weighs the chosen by their scores renormalised
times ``routed_scale``; only the experts held here (``n_experts`` from
``held_offset`` on) add their part, beside the ungated shared SwiGLU
experts. A final RMSNorm and an untied head over the vocabulary slice.
Loss is the mean next-token cross-entropy over every token.

The expert layer runs every held expert on every token, each weighted by
its routing weight (0 where not chosen): independent of the program's
sort into groups. Attention runs a head at a time, so that a 4096-row
score matrix stays small in float32.

The module exports the interface of an architecture
(``common.load_arch``) and the shape-only operation counts of the cell's
kernels; it imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import flops
import model

FAULT_LEAF = "ffn.e_wg"
BIAS_FAN_IN = 400   # router_bias drawn from the seed: std 1/sqrt(400) = 0.05


class _Group(dict):
    """A group of the configuration (its "moe" and "mla" blocks) that
    hashes by value, as the reference's jitted steps need of a static
    configuration (``model.freeze`` freezes only the top level)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _groups_hashable(m: dict) -> None:
    """Make ``m``'s groups hash by value, in place: the harness hands the
    same "model" block to ``layer_leaves`` and ``io_leaves`` before it
    freezes it for a jitted reference step."""
    for k in ("moe", "mla"):
        if type(m.get(k)) is dict:
            m[k] = _Group(m[k])


def _dims(m: dict):
    mla = m["mla"]
    return (m["d_model"], m["n_heads"], mla["qk_nope"] + mla["rope_dims"],
            mla["v_head"], mla["kv_lora"], mla["rope_dims"])


def _is_moe(m: dict, layer: int) -> bool:
    mo = m["moe"]
    return layer >= mo["first_dense"] and \
        layer % mo["every"] == mo["offset"]


def _routed(mo: dict) -> int:
    return mo["n_routed"] or mo["n_experts"]


def layer_leaves(m: dict, layer: int) -> dict:
    """Layer ``layer``'s leaves: name -> (shape, init, fan_in)."""
    _groups_hashable(m)
    d, h, e, ev, c, r = _dims(m)
    out = {
        "ln1.scale": ((d,), "ones", 0),
        "mix.wq": ((d, h, e), "normal", d),
        "mix.wdkv": ((d, c + r), "normal", d),
        "mix.kvnorm.scale": ((c,), "ones", 0),
        "mix.wuk": ((c, h, e - r), "normal", c),
        "mix.wuv": ((c, h, ev), "normal", c),
        "mix.wo": ((h, ev, d), "normal", h * ev),
        "ln2.scale": ((d,), "ones", 0),
    }
    if not _is_moe(m, layer):
        f = m["d_ff"]
        return out | {"ffn.wg": ((d, f), "normal", d),
                      "ffn.wu": ((d, f), "normal", d),
                      "ffn.wd": ((f, d), "normal", f)}
    mo = m["moe"]
    n, fe = mo["n_experts"], mo["d_ff_expert"]
    fs = mo["d_ff_shared"] or fe * mo["n_shared"]
    return out | {
        "ffn.router": ((d, _routed(mo)), "normal", d),
        "ffn.router_bias": ((_routed(mo),), "normal", BIAS_FAN_IN),
        "ffn.e_wg": ((n, d, fe), "normal", d),
        "ffn.e_wu": ((n, d, fe), "normal", d),
        "ffn.e_wd": ((n, fe, d), "normal", fe),
        "ffn.s_wg": ((d, fs), "normal", d),
        "ffn.s_wu": ((d, fs), "normal", d),
        "ffn.s_wd": ((fs, d), "normal", fs),
    }


def io_leaves(m: dict) -> dict:
    _groups_hashable(m)
    d, v = m["d_model"], m["vocab"]
    return {
        "embed.table": ((v, d), "normal", 1),
        "final_norm.scale": ((d,), "ones", 0),
        "head.w": ((d, v), "normal", d),
    }


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, seq: int, theta: float):
    """x [b, s, h, e]; rotate-half over the two halves of e."""
    e = x.shape[-1]
    ang = np.arange(seq)[:, None] / theta ** (np.arange(0, e, 2) / e)
    c = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    s = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(mm, q, k, v):
    """Causal softmax attention, one head at a time: q, k [b, s, h, e],
    v [b, s, h, ev] -> [b, s, h, ev]."""
    s, e = q.shape[1], q.shape[-1]
    causal = jnp.tril(jnp.ones((s, s), bool))[None]

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                      # [b, s, e], [b, s, ev]
        sc = mm("bqe,bke->bqk", qh, kh) / math.sqrt(e)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return mm("bqk,bke->bqe", p, vh)

    o = jax.lax.map(head, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    return jnp.moveaxis(o, 0, 2)


def _mla(m, mm, a, p):
    _, s, _ = a.shape
    _, h, e, _, c, r = _dims(m)
    theta = m["rope_theta"]
    q = mm("bsd,dhe->bshe", a, p["mix.wq"])
    ckv = mm("bsd,dc->bsc", a, p["mix.wdkv"])
    ckv_n = _rms(ckv[..., :c], p["mix.kvnorm.scale"], m["norm_eps"])
    k_nope = mm("bsc,che->bshe", ckv_n, p["mix.wuk"])
    v = mm("bsc,che->bshe", ckv_n, p["mix.wuv"])
    q = jnp.concatenate([q[..., :e - r], _rope(q[..., e - r:], s, theta)], -1)
    k_rope = _rope(ckv[:, :, None, c:], s, theta)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + (r,))], -1)
    return mm("bshe,hed->bsd", _attention(mm, q, k, v), p["mix.wo"])


def gate(mo: dict, lgf, bias):
    """Routing weights over all routed experts, [..., routed] (0 where
    not chosen), from f32 logits: top-k of sigmoid + bias chooses, the
    chosen sigmoid scores renormalised and scaled weigh."""
    sc = jax.nn.sigmoid(lgf)
    _, idx = jax.lax.top_k(sc + jax.lax.stop_gradient(bias), mo["top_k"])
    chosen = jnp.take_along_axis(sc, idx, -1)
    w = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * mo["routed_scale"]
    onehot = jax.nn.one_hot(idx, lgf.shape[-1], dtype=lgf.dtype)
    return (w[..., None] * onehot).sum(-2)


def _moe(m, mm, a, p):
    mo = m["moe"]
    w = gate(mo, mm("bsd,de->bse", a, p["ffn.router"]), p["ffn.router_bias"])
    lo = mo["held_offset"]
    w = w[..., lo:lo + mo["n_experts"]]                 # [b, s, held]
    hid = jax.nn.silu(mm("bsd,edf->bsef", a, p["ffn.e_wg"])) \
        * mm("bsd,edf->bsef", a, p["ffn.e_wu"])
    routed = mm("bsef,efd->bsd", hid * w[..., None], p["ffn.e_wd"])
    shared = mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", a, p["ffn.s_wg"]))
                * mm("bsd,df->bsf", a, p["ffn.s_wu"]), p["ffn.s_wd"])
    return routed + shared


def _dense(mm, a, p):
    hid = jax.nn.silu(mm("bsd,df->bsf", a, p["ffn.wg"])) \
        * mm("bsd,df->bsf", a, p["ffn.wu"])
    return mm("bsf,fd->bsd", hid, p["ffn.wd"])


def _layer(m, mm, x, p):
    eps = m["norm_eps"]
    x = x + _mla(m, mm, _rms(x, p["ln1.scale"], eps), p)
    a = _rms(x, p["ln2.scale"], eps)
    return x + (_moe(m, mm, a, p) if "ffn.router" in p else
                _dense(mm, a, p))


def logits(m: dict, params, tokens, mm):
    io = params["io"]
    x = model.scan_layers(lambda x, p: _layer(m, mm, x, p),
                          io["embed.table"][tokens], params["layers"])
    return mm("bsd,dv->bsv", _rms(x, io["final_norm.scale"], m["norm_eps"]),
              io["head.w"])


def objective(m: dict, params, tokens, labels, mm):
    """The mean cross-entropy, reported and differentiated alike (the
    sigmoid gate has no balance term)."""
    loss = model.xent(logits(m, params, tokens, mm), labels)
    return loss, loss


def _mla_weights(m: dict) -> int:
    d, h, e, ev, c, r = _dims(m)
    return d * h * e + d * (c + r) + c * h * (e - r) + c * h * ev \
        + h * ev * d


def train_flops_per_token(m: dict, seq: int) -> float:
    """6 FLOPs per matrix weight a token passes through, forward and
    backward, with the routed experts at the expected top_k x held /
    routed experts a token; plus causal attention's QK^T and PV, 3 h seq
    (e + ev) a layer. No recomputation counted."""
    d, h, e, ev, _, _ = _dims(m)
    mo, n = m["moe"], m["n_layers"]
    moe = sum(_is_moe(m, i) for i in range(n))
    fe = mo["d_ff_expert"]
    fs = mo["d_ff_shared"] or fe * mo["n_shared"]
    per_moe = d * _routed(mo) + 3 * d * fs \
        + mo["top_k"] * mo["n_experts"] / _routed(mo) * 3 * d * fe
    weights = n * _mla_weights(m) + (n - moe) * 3 * d * m["d_ff"] \
        + moe * per_moe + d * m["vocab"]
    return 6.0 * weights + n * 3.0 * h * seq * (e + ev)


# --------------------------------------------------------------------------- #
# The kernels' operations and bytes, from shapes
# --------------------------------------------------------------------------- #


def flash_fwd(m: dict, batch: int, seq: int, elem: int = 2):
    """One causal flash-attention forward over [batch, seq], all heads,
    at q/k width e = qk_nope + rope and v width ev: QK^T and PV over the
    causal pairs; q, k, v read and o written (k per head: MLA's heads
    share no key)."""
    _, h, e, ev, _, _ = _dims(m)
    pairs = seq * (seq + 1) / 2
    return 2.0 * batch * h * pairs * (e + ev), \
        elem * batch * seq * h * (2 * e + 2 * ev)


# Grouped products a micro-batch runs in each expert layer, one a
# projection (gate, up, down): forward or dx (``_expert_gmm``; the B
# tick's re-forward runs as ``jvp_jit__expert_gmm__``) in the F tick's
# forward, the B tick's forward, its rematerialisation in the backward
# and dx; dW (``_expert_tgmm``).
GMM_CALLS_PER_LAYER, TGMM_CALLS_PER_LAYER = 3 * 4, 3


def expert_gmm_step(m: dict, rows_held, load, microbatches: int,
                    tokens: int, peaks: dict) -> dict:
    """{"gmm": (calls, seconds), "tgmm": (calls, seconds)}: one training
    step's grouped expert products and the least time they could take.
    ``rows_held`` is the program's ``moe_rows_held`` (a layer's held rows
    over the step), ``load`` its ``moe_load`` (rows an expert a layer,
    every routed expert), ``tokens`` a micro-batch's. A call does 2 rows
    d d_ff_expert FLOPs and reads its rows and writes its result. The
    forward and dx read a held expert's weights only where its group is
    not empty: at least ceil(load / tokens) of the step's micro-batches,
    since a token picks an expert once. dW writes every held expert's.
    The least time of a call is that of the step's calls on average, so
    the work is never counted above what the kernel can skip."""
    d, mo = m["d_model"], m["moe"]
    fe, n, lo = mo["d_ff_expert"], mo["n_experts"], mo["held_offset"]
    held = np.asarray(load, np.float64)[:, lo:lo + n]
    out = {"gmm": [0, 0.0], "tgmm": [0, 0.0]}
    for r, per_expert in zip(np.asarray(rows_held, np.float64).ravel(), held):
        if r <= 0:
            continue
        r = r / microbatches
        reads = np.ceil(per_expert / tokens).sum() / microbatches
        act = 2 * r * (d + fe)
        for kind, calls, weights in (("gmm", GMM_CALLS_PER_LAYER, reads),
                                     ("tgmm", TGMM_CALLS_PER_LAYER, n)):
            c = calls * microbatches
            out[kind][0] += c
            out[kind][1] += c * flops.roofline_s(
                2.0 * r * d * fe, act + 2 * weights * d * fe, peaks)
    return {k: tuple(v) for k, v in out.items()}
