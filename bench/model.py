"""Weights from the seed, and the plain reference of the GPT that the
configuration files describe.

Both the program and the reference take their weights from ``leaf()``:
the program in its own stacked pipeline layout, built on the device in
one jitted call; the reference one leaf at a time, as float32 copies of
the same bfloat16 numbers. The reference imports nothing of the program.

The GPT here is the one the program runs: pre-norm layers, LayerNorm
(eps 1e-5) with scale and bias, multi-head attention with rotary
positions (rotate-half, theta from the configuration) and no projection
bias, a tanh-GELU MLP, a final LayerNorm and an untied head. Loss is the
mean next-token cross-entropy over every token of the batch.
"""

from __future__ import annotations

import functools
import math
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------------------------- #
# Leaves and their initialisation
# --------------------------------------------------------------------------- #


def layer_leaves(m: dict) -> dict:
    """Per-layer leaves: name -> (shape, init, fan_in)."""
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


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (folded in 16-bit parts, so
    seeds past 32 bits are fine)."""
    key = jax.random.PRNGKey(0)
    seed = int(seed)
    for _ in range(4):
        key = jax.random.fold_in(key, seed & 0xFFFF)
        seed >>= 16
    return key


def leaf(key, layer: int, name: str, spec, dtype=jnp.bfloat16):
    """One leaf's weights: layer -1 is the embedding/head side."""
    shape, init, fan_in = spec
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                           zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


# --------------------------------------------------------------------------- #
# The program's layout
# --------------------------------------------------------------------------- #

_SLOT = re.compile(r"L(\d+)\.(.+)")


def stage_layers(geo, seg) -> dict:
    """Row of a stacked leaf -> logical stage, for the program's
    rank-major layout (row ``mr * V + v`` holds stage ``v * pp + p``,
    ``p = mr % pp``; rows repeat per pipeline group)."""
    out = {}
    rows = geo.groups * geo.pp * seg.vpp
    for row in range(rows):
        mr, v = divmod(row, seg.vpp)
        out[row] = v * geo.pp + mr % geo.pp
    return out


def layer_of(geo, seg, row: int, slot: int) -> int:
    return stage_layers(geo, seg)[row] * seg.k + slot


def check_layout(m: dict, shapes) -> None:
    """The program's parameter tree must hold exactly this GPT's leaves."""
    io, lw = io_leaves(m), layer_leaves(m)
    got_io = {n: tuple(s.shape) for n, s in shapes["io"].items()}
    want_io = {n: spec[0] for n, spec in io.items()}
    if got_io != want_io:
        raise ValueError(f"program io leaves {got_io} are not the "
                         f"configuration's {want_io}")
    for seg_name, leaves in shapes["segments"].items():
        for full, s in leaves.items():
            mt = _SLOT.fullmatch(full)
            if not mt or mt.group(2) not in lw \
                    or tuple(s.shape[1:]) != lw[mt.group(2)][0]:
                raise ValueError(f"program leaf {seg_name}.{full} "
                                 f"{tuple(s.shape)} is not a layer leaf of "
                                 "the configuration")


def program_tree(m: dict, geo, shapes):
    """``build(key)``: the program's parameter tree from a key, in its
    stacked layout (traceable; rows of padding layers are zeros)."""
    check_layout(m, shapes)
    io, lw = io_leaves(m), layer_leaves(m)
    segs = {s.name: s for s in geo.segments}

    def build(key):
        out = {"io": {n: leaf(key, -1, n, io[n], s.dtype)
                      for n, s in shapes["io"].items()}, "segments": {}}
        for seg_name, leaves in shapes["segments"].items():
            seg = segs[seg_name]
            out["segments"][seg_name] = {}
            for full, s in leaves.items():
                slot, name = _SLOT.fullmatch(full).groups()
                rows = []
                for row in range(s.shape[0]):
                    layer = layer_of(geo, seg, row, int(slot))
                    rows.append(
                        leaf(key, layer, name, lw[name], s.dtype)
                        if layer < m["n_layers"]
                        else jnp.zeros(s.shape[1:], s.dtype))
                out["segments"][seg_name][full] = jnp.stack(rows)
        return out

    return build


def program_maker(m: dict, geo, shapes):
    """``make(key)``: one jitted call that makes the program's parameter
    tree on its devices, in the dtype and sharding it serves them in.
    Calling the same ``make`` again gives the same bits; another program
    that makes them (fused otherwise) may round a few differently."""
    return jax.jit(program_tree(m, geo, shapes),
                   out_shardings=jax.tree.map(lambda s: s.sharding, shapes))


def program_params(seed: int, m: dict, geo, shapes):
    """The program's parameter tree from the seed (``program_maker``)."""
    return program_maker(m, geo, shapes)(seed_key(seed))


# --------------------------------------------------------------------------- #
# The reference's layout: {"io": {name: a}, "layers": {name: [L, ...]}}
# --------------------------------------------------------------------------- #


def reference_params(seed: int, m: dict, dtype=jnp.float32):
    """The same weights as ``program_params``, as float32 copies of the
    bfloat16 numbers, layers stacked for a scan."""
    io, lw = io_leaves(m), layer_leaves(m)

    @jax.jit
    def build(key):
        return {
            "io": {n: leaf(key, -1, n, s).astype(dtype)
                   for n, s in io.items()},
            "layers": {n: jnp.stack([leaf(key, i, n, s).astype(dtype)
                                     for i in range(m["n_layers"])])
                       for n, s in lw.items()},
        }

    return build(seed_key(seed))


def _q8(a):
    """float8 (e4m3) with one scale per tensor: the nearest precision
    below bfloat16, for the control."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def make_mm(precision: str):
    """Matrix products of the reference: float32 at "highest", or with
    both operands rounded to float8 first (the control)."""
    def mm(eq, a, b):
        if precision == "fp8":
            a, b = _q8(a), _q8(b)
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
    return mm


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


def logits(m: dict, params, tokens, precision: str = "f32"):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    mm = make_mm(precision)
    io = params["io"]
    cos, sin = _rope_tables(tokens.shape[1], m["d_head"] or
                            m["d_model"] // m["n_heads"], m["rope_theta"])
    x = io["embed.table"][tokens]

    @jax.checkpoint
    def body(x, p):
        return _layer(m, mm, x, p, cos, sin), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    hn = _ln(x, io["final_norm.scale"], io["final_norm.bias"])
    return mm("bsd,dv->bsv", hn, io["head.w"])


def loss(m: dict, params, tokens, labels, precision: str = "f32"):
    lg = logits(m, params, tokens, precision)
    lse = jax.nn.logsumexp(lg, -1)
    lab = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return (lse - lab).mean()


# --------------------------------------------------------------------------- #
# Training reference: AdamW as the configuration states it
# --------------------------------------------------------------------------- #


def _decays(name: str, optim: dict) -> bool:
    return not any(t in name for t in optim["no_decay"])


def norms(tree) -> dict:
    """(part, name) -> norm per layer (one entry for io leaves)."""
    out = {("io", n): jnp.linalg.norm(a)[None] for n, a in tree["io"].items()}
    for n, a in tree["layers"].items():
        out[("layers", n)] = jnp.sqrt(
            jnp.sum(jnp.square(a.reshape(a.shape[0], -1)), 1))
    return out


@functools.partial(jax.jit, static_argnames=("m", "precision", "fault"))
def _step_grad(params, tokens, labels, *, m, precision, fault):
    """Mean loss over the batch's rows and its gradient; rows run one at
    a time, as the program's micro-batches do."""
    def mean_loss(p):
        def body(acc, row):
            t, lab = row
            return acc + loss(dict(m), p, t[None], lab[None], precision), None
        tot, _ = jax.lax.scan(jax.checkpoint(body), jnp.float32(0.0),
                              (tokens, labels))
        return tot / tokens.shape[0]

    lv, g = jax.value_and_grad(mean_loss)(params)
    if fault == "grad_altered":
        g["layers"]["ffn.wi"] = g["layers"]["ffn.wi"] * 2.0
    return lv, g


@functools.partial(jax.jit, static_argnames=("step", "optim"),
                   donate_argnums=(0, 2))
def _adamw(params, grads, state, *, step: int, optim):
    """One AdamW step (decoupled decay, global-norm clip) as the
    configuration states it; returns the new params and state and the
    norms of the clipped gradient the update used."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, optim["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    b1, b2 = optim["b1"], optim["b2"]
    new = {"io": {}, "layers": {}}
    st = {"m": {"io": {}, "layers": {}}, "v": {"io": {}, "layers": {}}}
    clipped = {"io": {}, "layers": {}}
    for part in ("io", "layers"):
        for n, p in params[part].items():
            g = grads[part][n] * clip
            mo = b1 * state["m"][part][n] + (1 - b1) * g
            vo = b2 * state["v"][part][n] + (1 - b2) * g * g
            delta = (mo / (1 - b1 ** step)) / (
                jnp.sqrt(vo / (1 - b2 ** step)) + optim["eps"])
            if _decays(n, optim):
                delta = delta + optim["weight_decay"] * p
            new[part][n] = p - optim["lr"] * delta
            clipped[part][n] = g
            st["m"][part][n], st["v"][part][n] = mo, vo
    return new, st, norms(clipped)


class _Frozen(dict):
    """A hashable dict, for static arguments of jitted functions."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def freeze(d: dict) -> _Frozen:
    return _Frozen({k: tuple(v) if isinstance(v, list) else v
                    for k, v in d.items()})


def _to_numpy(nrm: dict) -> dict:
    return {k: np.asarray(v, np.float64) for k, v in nrm.items()}


def train_reference(m: dict, seed: int, batches, optim: dict,
                    precision: str = "f32", fault: str | None = None):
    """The first ``len(batches)`` steps from the seed's weights: each
    step's loss, the norms of the first step's clipped gradient, and the
    norms of each leaf's change after the last step (per layer).

    ``fault`` plants a fault a training step can have, in the reference
    put in the program's place: "half_batch" (half of the rows left out,
    the mean taken over the rest) or "grad_altered" (one leaf's gradient
    doubled where it is made)."""
    mh, opt = freeze(m), freeze(optim)
    with jax.default_matmul_precision("highest"):
        params = reference_params(seed, m)
        state = {k: jax.tree.map(jnp.zeros_like, params) for k in "mv"}
        losses, grad1 = [], None
        for step, batch in enumerate(batches, 1):
            toks, labs = batch["tokens"], batch["labels"]
            if fault == "half_batch":
                toks, labs = toks[: len(toks) // 2], labs[: len(labs) // 2]
            lv, grads = _step_grad(params, jnp.asarray(toks),
                                   jnp.asarray(labs), m=mh,
                                   precision=precision, fault=fault)
            losses.append(float(lv))
            params, state, gn = _adamw(params, grads, state, step=step,
                                       optim=opt)
            del grads
            if step == 1:
                grad1 = _to_numpy(gn)
        del state
        p0 = reference_params(seed, m)
        delta = _to_numpy(jax.jit(lambda a, b: norms(
            jax.tree.map(jnp.subtract, a, b)))(params, p0))
    return {"losses": losses, "grad1": grad1, "delta": delta}


# --------------------------------------------------------------------------- #
# Serving reference
# --------------------------------------------------------------------------- #


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _served_gaps(params, tokens, served, start, n, *, m, precision):
    """For one request padded to the context: at the positions that
    produced its served tokens, the gap by which each served token's
    reference logit lies below the reference's best, and (with a lower
    precision) the gap of the token that precision puts first."""
    lg = logits(dict(m), params, tokens[None], "f32")[0]
    idx = start + jnp.arange(served.shape[0])
    ref = lg[idx]
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    valid = jnp.arange(served.shape[0]) < n
    if precision == "f32":
        return jnp.where(valid, gap, 0.0), jnp.zeros_like(gap)
    low = logits(dict(m), params, tokens[None], precision)[0][idx]
    pick = low.argmax(-1)
    cgap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return jnp.where(valid, gap, 0.0), jnp.where(valid, cgap, 0.0)


def serve_reference(m: dict, seed: int, requests, context: int,
                    control: str | None = None):
    """requests: (prompt, served tokens) pairs. Returns the widest gap of
    a served token below the reference's best and, with ``control``
    ("fp8"), the widest gap of the control's first choice."""
    with jax.default_matmul_precision("highest"):
        params = reference_params(seed, m)
        mh = _Frozen(m)
        widest, widest_ctl, tokens_compared = 0.0, 0.0, 0
        for prompt, served in requests:
            seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
            toks = np.zeros(context, np.int32)
            toks[:len(seq)] = seq
            pad = np.zeros(context, np.int32)
            pad[:len(served)] = served
            gap, cgap = _served_gaps(
                params, jnp.asarray(toks), jnp.asarray(pad),
                jnp.int32(len(prompt) - 1), jnp.int32(len(served)), m=mh,
                precision=control or "f32")
            widest = max(widest, float(gap.max()))
            widest_ctl = max(widest_ctl, float(cgap.max()))
            tokens_compared += len(served)
    return {"gap": widest, "control_gap": widest_ctl if control else None,
            "tokens": tokens_compared}
