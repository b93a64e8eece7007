"""The paper's GPT, as the program runs it: pre-norm layers, LayerNorm
(eps 1e-5) with scale and bias, multi-head attention with rotary
positions (rotate-half, theta from the configuration) and no projection
bias, a tanh-GELU MLP, a final LayerNorm and an untied head. Loss is the
mean next-token cross-entropy over every token of the batch.

Every layer has the same leaves. The module exports the interface of an
architecture (``common.load_arch``); it imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import flops
import model

FAULT_LEAF = "ffn.wi"


def layer_leaves(m: dict, layer: int) -> dict:
    """Layer ``layer``'s leaves: name -> (shape, init, fan_in)."""
    d, h, g, f = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"]
    e = m["d_head"] or d // h
    return {
        "ln1.scale": ((d,), "ones", 0), "ln1.bias": ((d,), "zeros", 0),
        "mix.wq": ((d, h, e), "normal", d),
        "mix.wk": ((d, g, e), "normal", d),
        "mix.wv": ((d, g, e), "normal", d),
        "mix.wo": ((h, e, d), "normal", h * e),
        "ln2.scale": ((d,), "ones", 0), "ln2.bias": ((d,), "zeros", 0),
        "ffn.wi": ((d, f), "normal", d),
        "ffn.wd": ((f, d), "normal", f),
    }


def io_leaves(m: dict) -> dict:
    d, v = m["d_model"], m["vocab"]
    return {
        "embed.table": ((v, d), "normal", 1),
        "final_norm.scale": ((d,), "ones", 0),
        "final_norm.bias": ((d,), "zeros", 0),
        "head.w": ((d, v), "normal", d),
    }


def _ln(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _rope_tables(seq: int, e: int, theta: float):
    inv = 1.0 / theta ** (np.arange(0, e, 2) / e)
    ang = np.arange(seq)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(ang), jnp.float32), \
        jnp.asarray(np.sin(ang), jnp.float32)


def _rope(x, cos, sin):
    """x [b, s, h, e]; rotate-half over the two halves of e."""
    e = x.shape[-1]
    x1, x2 = x[..., :e // 2], x[..., e // 2:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(m, mm, x, p, cos, sin):
    b, s, d = x.shape
    h, g = m["n_heads"], m["n_kv_heads"]
    e = m["d_head"] or d // h
    a = _ln(x, p["ln1.scale"], p["ln1.bias"])
    q = _rope(mm("bsd,dhe->bshe", a, p["mix.wq"]), cos, sin)
    k = _rope(mm("bsd,dge->bsge", a, p["mix.wk"]), cos, sin)
    v = mm("bsd,dge->bsge", a, p["mix.wv"])
    k, v = jnp.repeat(k, h // g, axis=2), jnp.repeat(v, h // g, axis=2)
    sc = mm("bqhe,bkhe->bhqk", q, k) / math.sqrt(e)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    o = mm("bhqk,bkhe->bqhe", jax.nn.softmax(sc, axis=-1), v)
    x = x + mm("bshe,hed->bsd", o, p["mix.wo"])
    a = _ln(x, p["ln2.scale"], p["ln2.bias"])
    return x + mm("bsf,fd->bsd", _gelu(mm("bsd,df->bsf", a, p["ffn.wi"])),
                  p["ffn.wd"])


def logits(m: dict, params, tokens, mm):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    io = params["io"]
    cos, sin = _rope_tables(tokens.shape[1], m["d_head"] or
                            m["d_model"] // m["n_heads"], m["rope_theta"])
    x = model.scan_layers(lambda x, p: _layer(m, mm, x, p, cos, sin),
                          io["embed.table"][tokens], params["layers"])
    hn = _ln(x, io["final_norm.scale"], io["final_norm.bias"])
    return mm("bsd,dv->bsv", hn, io["head.w"])


def objective(m: dict, params, tokens, labels, mm):
    """The mean cross-entropy, reported and differentiated alike."""
    loss = model.xent(logits(m, params, tokens, mm), labels)
    return loss, loss


def train_flops_per_token(m: dict, seq: int) -> float:
    """Model FLOPs of one trained token, forward and backward, with no
    recomputation counted (Narayanan et al. 2021, eq. 3 without the
    recompute pass): 72 L h^2 (1 + s / 6h + V / 12 h L)."""
    d, _, _, _, _, v, n = flops.dims(m)
    return 72.0 * n * d * d * (1 + seq / (6.0 * d) + v / (12.0 * d * n))


def serve_call_flops(m: dict, width: int, positions) -> float:
    """Model FLOPs of one serve step over its rows: every token through
    every layer's matrices and attention, and the head once per row."""
    d, h, g, e, f, v, n = flops.dims(m)
    per_token = 2.0 * n * (2 * d * h * e + 2 * d * g * e + 2 * d * f)
    attn, _ = flops.paged_attn_call(m, width, positions)
    return len(positions) * (width * per_token + 2.0 * d * v) + n * attn
