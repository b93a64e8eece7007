"""A Llama-style decoder, as the program runs it with ``norm="rmsnorm"``,
``act="swiglu"`` and fewer key/value heads than query heads: pre-norm
layers, RMSNorm (eps 1e-6) with a scale, grouped-query attention with
rotary positions (rotate-half) and no projection bias, a SwiGLU MLP, a
final RMSNorm and an untied head. Loss is the mean next-token
cross-entropy over every token of the batch.

The architecture module of the test that adds a second architecture to
a copy of the benchmark with new files only (``common.load_arch``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import model

FAULT_LEAF = "ffn.wg"


def _dims(m: dict):
    d, h = m["d_model"], m["n_heads"]
    return d, h, m["n_kv_heads"], m["d_head"] or d // h, m["d_ff"]


def layer_leaves(m: dict, layer: int) -> dict:
    d, h, g, e, f = _dims(m)
    return {
        "ln1.scale": ((d,), "ones", 0),
        "mix.wq": ((d, h, e), "normal", d),
        "mix.wk": ((d, g, e), "normal", d),
        "mix.wv": ((d, g, e), "normal", d),
        "mix.wo": ((h, e, d), "normal", h * e),
        "ln2.scale": ((d,), "ones", 0),
        "ffn.wg": ((d, f), "normal", d),
        "ffn.wu": ((d, f), "normal", d),
        "ffn.wd": ((f, d), "normal", f),
    }


def io_leaves(m: dict) -> dict:
    d, v = m["d_model"], m["vocab"]
    return {
        "embed.table": ((v, d), "normal", 1),
        "final_norm.scale": ((d,), "ones", 0),
        "head.w": ((d, v), "normal", d),
    }


def _rms(x, scale):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * scale


def _rope(x, seq: int, theta: float):
    """x [b, s, h, e]; rotate-half over the two halves of e."""
    e = x.shape[-1]
    ang = np.arange(seq)[:, None] / theta ** (np.arange(0, e, 2) / e)
    c = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    s = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :e // 2], x[..., e // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(m, mm, x, p):
    _, s, _ = x.shape
    _, h, g, e, _ = _dims(m)
    a = _rms(x, p["ln1.scale"])
    q = _rope(mm("bsd,dhe->bshe", a, p["mix.wq"]), s, m["rope_theta"])
    k = _rope(mm("bsd,dge->bsge", a, p["mix.wk"]), s, m["rope_theta"])
    v = mm("bsd,dge->bsge", a, p["mix.wv"])
    k, v = jnp.repeat(k, h // g, axis=2), jnp.repeat(v, h // g, axis=2)
    sc = mm("bqhe,bkhe->bhqk", q, k) / math.sqrt(e)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], sc,
                   -jnp.inf)
    o = mm("bhqk,bkhe->bqhe", jax.nn.softmax(sc, axis=-1), v)
    x = x + mm("bshe,hed->bsd", o, p["mix.wo"])
    a = _rms(x, p["ln2.scale"])
    hid = jax.nn.silu(mm("bsd,df->bsf", a, p["ffn.wg"])) \
        * mm("bsd,df->bsf", a, p["ffn.wu"])
    return x + mm("bsf,fd->bsd", hid, p["ffn.wd"])


def logits(m: dict, params, tokens, mm):
    io = params["io"]
    x = model.scan_layers(lambda x, p: _layer(m, mm, x, p),
                          io["embed.table"][tokens], params["layers"])
    return mm("bsd,dv->bsv", _rms(x, io["final_norm.scale"]), io["head.w"])


def objective(m: dict, params, tokens, labels, mm):
    loss = model.xent(logits(m, params, tokens, mm), labels)
    return loss, loss


def train_flops_per_token(m: dict, seq: int) -> float:
    """6 FLOPs per matrix weight and token, forward and backward, plus
    causal attention's QK^T and PV (half the pairs) over ``seq``."""
    d, h, g, e, f = _dims(m)
    weights = d * (2 * h * e + 2 * g * e + 3 * f)
    return 6.0 * (m["n_layers"] * (weights + h * e * seq)
                  + d * m["vocab"])
