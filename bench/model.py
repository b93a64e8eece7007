"""Weights from the seed, the program's stacked layout, and the plain
reference of whatever architecture a configuration names.

Both the program and the reference take their weights from ``leaf()``:
the program in its own stacked pipeline layout, built on the device in
one jitted call; the reference one leaf at a time, as float32 copies of
the same bfloat16 numbers. The reference imports nothing of the program.

What belongs to one architecture (its leaves per layer, its layer, its
loss) is the module ``bench/arch/<name>.py`` (``common.load_arch``);
every function here takes that module as ``arch``. Layers may have
different leaves. The reference's layout is ``{"io": {name: a},
"layers": [group, ...]}``: each group stacks one run of consecutive
layers with equal leaf tables, ``{name: [n, ...]}``, for a scan
(``scan_layers``); a model whose layers are all alike is one group.
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


def layer_tables(arch, m: dict) -> list:
    """Each layer's leaf table, ``arch.layer_leaves(m, layer)``."""
    return [arch.layer_leaves(m, i) for i in range(m["n_layers"])]


def layer_runs(tables: list) -> list:
    """(first layer, layers) of each run of consecutive equal tables."""
    runs = []
    for i, t in enumerate(tables):
        if runs and tables[runs[-1][0]] == t:
            runs[-1][1] += 1
        else:
            runs.append([i, 1])
    return [tuple(r) for r in runs]


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


def check_layout(arch, m: dict, geo, shapes) -> None:
    """The program's parameter tree must hold exactly the configuration's
    leaves: the io leaves, and in each row of each layer slot the leaves
    of the layer that row holds (rows of padding layers hold zeros and
    are not checked)."""
    io, tables = arch.io_leaves(m), layer_tables(arch, m)
    got_io = {n: tuple(s.shape) for n, s in shapes["io"].items()}
    want_io = {n: spec[0] for n, spec in io.items()}
    if got_io != want_io:
        raise ValueError(f"program io leaves {got_io} are not the "
                         f"configuration's {want_io}")
    segs = {s.name: s for s in geo.segments}
    held = [set() for _ in tables]
    for seg_name, leaves in shapes["segments"].items():
        for full, s in leaves.items():
            mt = _SLOT.fullmatch(full)
            if not mt:
                raise ValueError(f"program leaf {seg_name}.{full} is not "
                                 "in a layer slot")
            slot, name = int(mt.group(1)), mt.group(2)
            for row in range(s.shape[0]):
                layer = layer_of(geo, segs[seg_name], row, slot)
                if layer >= len(tables):
                    continue
                spec = tables[layer].get(name)
                if spec is None or tuple(s.shape[1:]) != spec[0]:
                    raise ValueError(
                        f"program leaf {seg_name}.{full} {tuple(s.shape)} "
                        f"is not a leaf of layer {layer} of the "
                        "configuration")
                held[layer].add(name)
    for layer, (names, table) in enumerate(zip(held, tables)):
        if names != set(table):
            raise ValueError(f"layer {layer}: the program holds "
                             f"{sorted(names)}, the configuration "
                             f"{sorted(table)}")


def program_tree(arch, m: dict, geo, shapes):
    """``build(key)``: the program's parameter tree from a key, in its
    stacked layout (traceable; rows of padding layers are zeros)."""
    check_layout(arch, m, geo, shapes)
    io, tables = arch.io_leaves(m), layer_tables(arch, m)
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
                        leaf(key, layer, name, tables[layer][name], s.dtype)
                        if layer < m["n_layers"]
                        else jnp.zeros(s.shape[1:], s.dtype))
                out["segments"][seg_name][full] = jnp.stack(rows)
        return out

    return build


def program_maker(arch, m: dict, geo, shapes):
    """``make(key)``: one jitted call that makes the program's parameter
    tree on its devices, in the dtype and sharding it serves them in.
    Calling the same ``make`` again gives the same bits; another program
    that makes them (fused otherwise) may round a few differently."""
    return jax.jit(program_tree(arch, m, geo, shapes),
                   out_shardings=jax.tree.map(lambda s: s.sharding, shapes))


def program_params(arch, seed: int, m: dict, geo, shapes):
    """The program's parameter tree from the seed (``program_maker``)."""
    return program_maker(arch, m, geo, shapes)(seed_key(seed))


# --------------------------------------------------------------------------- #
# The reference's layout: {"io": {name: a}, "layers": [{name: [n, ...]}]}
# --------------------------------------------------------------------------- #


def reference_params(arch, seed: int, m: dict, dtype=jnp.float32):
    """The same weights as ``program_params``, as float32 copies of the
    bfloat16 numbers, each run of alike layers stacked for a scan."""
    io, tables = arch.io_leaves(m), layer_tables(arch, m)

    @jax.jit
    def build(key):
        return {
            "io": {n: leaf(key, -1, n, s).astype(dtype)
                   for n, s in io.items()},
            "layers": [{n: jnp.stack([leaf(key, i, n, s).astype(dtype)
                                      for i in range(first, first + k)])
                        for n, s in tables[first].items()}
                       for first, k in layer_runs(tables)],
        }

    return build(seed_key(seed))


def scan_layers(layer, x, groups):
    """``x`` through every layer in order: ``layer(x, p)`` scanned over
    each group's stacked leaves, rematerialised in the backward pass."""
    body = jax.checkpoint(lambda x, p: (layer(x, p), None))
    for g in groups:
        x, _ = jax.lax.scan(body, x, g)
    return x


def xent(lg, labels):
    """Mean next-token cross-entropy of logits [b, s, V] at labels."""
    lse = jax.nn.logsumexp(lg, -1)
    lab = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return (lse - lab).mean()


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


# --------------------------------------------------------------------------- #
# Training reference: AdamW as the configuration states it
# --------------------------------------------------------------------------- #


def _decays(name: str, optim: dict) -> bool:
    return not any(t in name for t in optim["no_decay"])


def _map_named(f, tree, *rest):
    """``f(name, leaf, *leaves of rest)`` over a reference-layout tree."""
    return {"io": {n: f(n, a, *(r["io"][n] for r in rest))
                   for n, a in tree["io"].items()},
            "layers": [{n: f(n, a, *(r["layers"][i][n] for r in rest))
                        for n, a in g.items()}
                       for i, g in enumerate(tree["layers"])]}


def norms(tree) -> dict:
    """(part, name) -> norm of each layer that holds the leaf, in layer
    order (one entry for io leaves)."""
    out = {("io", n): jnp.linalg.norm(a)[None] for n, a in tree["io"].items()}
    per_layer: dict[str, list] = {}
    for g in tree["layers"]:
        for n, a in g.items():
            per_layer.setdefault(n, []).append(jnp.sqrt(
                jnp.sum(jnp.square(a.reshape(a.shape[0], -1)), 1)))
    out.update({("layers", n): jnp.concatenate(v)
                for n, v in per_layer.items()})
    return out


@functools.partial(jax.jit, static_argnames=("arch", "m", "precision",
                                             "fault"))
def _step_grad(params, tokens, labels, *, arch, m, precision, fault):
    """Mean reported loss over the batch's rows, and the gradient of the
    mean differentiated one; rows run one at a time, as the program's
    micro-batches do."""
    mm = make_mm(precision)

    def mean_loss(p):
        def body(acc, row):
            t, lab = row
            rep, dif = arch.objective(dict(m), p, t[None], lab[None], mm)
            return (acc[0] + dif, acc[1] + rep), None
        zero = jnp.float32(0.0)
        (dif, rep), _ = jax.lax.scan(jax.checkpoint(body), (zero, zero),
                                     (tokens, labels))
        return dif / tokens.shape[0], rep / tokens.shape[0]

    (_, lv), g = jax.value_and_grad(mean_loss, has_aux=True)(params)
    if fault == "grad_altered":
        for grp in g["layers"]:
            if arch.FAULT_LEAF in grp:
                grp[arch.FAULT_LEAF] = grp[arch.FAULT_LEAF] * 2.0
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

    def update(n, p, g, mo, vo):
        g = g * clip
        mo = b1 * mo + (1 - b1) * g
        vo = b2 * vo + (1 - b2) * g * g
        delta = (mo / (1 - b1 ** step)) / (
            jnp.sqrt(vo / (1 - b2 ** step)) + optim["eps"])
        if _decays(n, optim):
            delta = delta + optim["weight_decay"] * p
        return p - optim["lr"] * delta, g, mo, vo

    out = _map_named(update, params, grads, state["m"], state["v"])
    part = lambda i: _map_named(lambda n, t: t[i], out)  # noqa: E731
    return part(0), {"m": part(2), "v": part(3)}, norms(part(1))


class _Frozen(dict):
    """A hashable dict, for static arguments of jitted functions."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def freeze(d: dict) -> _Frozen:
    return _Frozen({k: tuple(v) if isinstance(v, list) else v
                    for k, v in d.items()})


def _to_numpy(nrm: dict) -> dict:
    return {k: np.asarray(v, np.float64) for k, v in nrm.items()}


def train_reference(arch, m: dict, seed: int, batches, optim: dict,
                    precision: str = "f32", fault: str | None = None):
    """The first ``len(batches)`` steps from the seed's weights: each
    step's reported loss, the norms of the first step's clipped
    gradient, and the norms of each leaf's change after the last step
    (per layer that holds it).

    ``fault`` plants a fault a training step can have, in the reference
    put in the program's place: "half_batch" (half of the rows left out,
    the mean taken over the rest) or "grad_altered" (the gradient of
    ``arch.FAULT_LEAF`` doubled where it is made)."""
    mh, opt = freeze(m), freeze(optim)
    with jax.default_matmul_precision("highest"):
        params = reference_params(arch, seed, m)
        state = {k: jax.tree.map(jnp.zeros_like, params) for k in "mv"}
        losses, grad1 = [], None
        for step, batch in enumerate(batches, 1):
            toks, labs = batch["tokens"], batch["labels"]
            if fault == "half_batch":
                toks, labs = toks[: len(toks) // 2], labs[: len(labs) // 2]
            lv, grads = _step_grad(params, jnp.asarray(toks),
                                   jnp.asarray(labs), arch=arch, m=mh,
                                   precision=precision, fault=fault)
            losses.append(float(lv))
            params, state, gn = _adamw(params, grads, state, step=step,
                                       optim=opt)
            del grads
            if step == 1:
                grad1 = _to_numpy(gn)
        del state
        p0 = reference_params(arch, seed, m)
        delta = _to_numpy(jax.jit(lambda a, b: norms(
            jax.tree.map(jnp.subtract, a, b)))(params, p0))
    return {"losses": losses, "grad1": grad1, "delta": delta}


# --------------------------------------------------------------------------- #
# Serving reference
# --------------------------------------------------------------------------- #


@functools.partial(jax.jit, static_argnames=("arch", "m", "precision"))
def _served_gaps(params, tokens, served, start, n, *, arch, m, precision):
    """For one request padded to the context: at the positions that
    produced its served tokens, the gap by which each served token's
    reference logit lies below the reference's best, and (with a lower
    precision) the gap of the token that precision puts first."""
    lg = arch.logits(dict(m), params, tokens[None], make_mm("f32"))[0]
    idx = start + jnp.arange(served.shape[0])
    ref = lg[idx]
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    valid = jnp.arange(served.shape[0]) < n
    if precision == "f32":
        return jnp.where(valid, gap, 0.0), jnp.zeros_like(gap)
    low = arch.logits(dict(m), params, tokens[None],
                      make_mm(precision))[0][idx]
    pick = low.argmax(-1)
    cgap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return jnp.where(valid, gap, 0.0), jnp.where(valid, cgap, 0.0)


def serve_reference(arch, m: dict, seed: int, requests, context: int,
                    control: str | None = None):
    """requests: (prompt, served tokens) pairs. Returns the widest gap of
    a served token below the reference's best and, with ``control``
    ("fp8"), the widest gap of the control's first choice."""
    with jax.default_matmul_precision("highest"):
        params = reference_params(arch, seed, m)
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
                jnp.int32(len(prompt) - 1), jnp.int32(len(served)),
                arch=arch, m=mh, precision=control or "f32")
            widest = max(widest, float(gap.max()))
            widest_ctl = max(widest_ctl, float(cgap.max()))
            tokens_compared += len(served)
    return {"gap": widest, "control_gap": widest_ctl if control else None,
            "tokens": tokens_compared}
