"""FSDP layout + collectives for the ZeroPP runtime.

Parameter layout (DESIGN.md §4):
  * stage params are stacked ``[M·V, ...]`` where M = model-axis ranks
    (= groups × pp); stacked index ``mr·V + v`` holds the params of logical
    stage ``v·pp + (mr % pp)`` of pipeline group ``mr // pp`` — groups
    duplicate stage params (grads are butterfly-reduced across groups).
  * dim0 shards over "model"; each tensor additionally FSDP-shards over
    "data" on ``spec.fsdp_dim`` when divisible (else replicated).
  * EP params (``spec.ep`` and moe_mode=="ep") shard their expert dim over
    "data" permanently and are never gathered.
  * the "pod" axis always replicates parameters (hybrid-sharded DP, Zhao
    et al.; §5.2 of the paper) — pods only all-reduce gradients.

DESIGN — flat-segment coalescing (``RunConfig.coalesce="flat"``, default):

The blockwise FSDP events of §3.3 assume ONE bandwidth-bound transfer per
stage block, but a stage block is a dict of tensors — issuing one
collective per tensor turns each gather/reduce tick into dozens of small
latency-bound collectives. The flat-segment layout coalesces them:

  * every gatherable tensor of a stage (data-divisible, non-EP) is packed
    into one contiguous per-slot buffer. A tensor enters the pack with its
    data-sharded dim ``ld`` moved to axis 0 and flattened, so tiling over
    "data" on the flat axis is exactly the tensor's per-rank FSDP shard.
  * the pack is *shard-major*: each rank's local slab is the entry-order
    concatenation of its local shards (``FlatLayout.local_size`` long),
    and the gathered segment is the rank-order concatenation of slabs.
    ``FlatEntry.offset/size`` are therefore static LOCAL offsets; the
    gathered view of tensor ``i`` is
    ``seg.reshape(dsize, local_size)[:, off:off+size]`` reshaped back —
    a zero-copy view for ``ld == 0`` tensors (one transpose otherwise).
  * the tick engine then issues ONE ``lax.all_gather`` per gather tick and
    ONE ``lax.psum_scatter`` per reduce tick, independent of tensor count.
    Values are bit-identical to the per-tensor path: both collectives are
    element-exact and the per-element cross-rank reduction order is
    unchanged — only the element layout differs.
  * tensors the layout cannot cover (replicated because non-divisible, or
    EP-sharded) keep the per-tensor path: resident stacks for gathers and
    ``psum``/local accumulation for reduces. ``coalesce="none"`` restores
    the per-tensor path wholesale as an escape hatch.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import numpy as np

from repro.models.common import FlatEntry, FlatLayout, ParamSpec

DATA, MODEL, POD = "data", "model", "pod"


# --------------------------------------------------------------------------- #
# PartitionSpecs
# --------------------------------------------------------------------------- #


def stage_pspec(spec: ParamSpec, dsize: int, ep: bool) -> P:
    """PartitionSpec for a stacked stage param [M·V, *shape]."""
    dims: list = [MODEL] + [None] * len(spec.shape)
    if spec.ep and ep:
        dims[1] = DATA  # expert dim
    elif spec.shape and spec.shape[spec.fsdp_dim] % dsize == 0 and (
        spec.shape[spec.fsdp_dim] // dsize > 0
    ):
        dims[1 + spec.fsdp_dim] = DATA
    return P(*dims)


def io_pspec(spec: ParamSpec, dsize: int) -> P:
    dims: list = [None] * len(spec.shape)
    if spec.shape and spec.shape[spec.fsdp_dim] % dsize == 0:
        dims[spec.fsdp_dim] = DATA
    return P(*dims)


def local_dim(spec: ParamSpec, dsize: int, ep: bool) -> int | None:
    """Which (unstacked) dim is data-sharded locally, or None."""
    if spec.ep and ep:
        return 0
    if spec.shape and spec.shape[spec.fsdp_dim] % dsize == 0:
        return spec.fsdp_dim
    return None


# --------------------------------------------------------------------------- #
# Collectives (inside shard_map)
# --------------------------------------------------------------------------- #


def gather_param(x, spec: ParamSpec, dsize: int, ep: bool):
    """All-gather one (already v-indexed) stage param over "data"."""
    d = local_dim(spec, dsize, ep)
    if d is None or (spec.ep and ep):
        return x
    return jax.lax.all_gather(x, DATA, axis=d, tiled=True)


def reduce_scatter_grad(g, spec: ParamSpec, dsize: int, ep: bool,
                        pod: bool = False):
    """Reduce a full-size gradient back to the sharded layout (+pod psum)."""
    d = local_dim(spec, dsize, ep)
    if spec.ep and ep:
        out = g  # expert grads are already local
    elif d is None:
        out = jax.lax.psum(g, DATA)
    else:
        out = jax.lax.psum_scatter(g, DATA, scatter_dimension=d, tiled=True)
    if pod:
        out = jax.lax.psum(out, POD)
    return out


def group_allreduce(x, groups: int, pp: int):
    """Butterfly all-reduce across pipeline groups on the model axis.

    Rank id = g·pp + p; partners differ in one bit of g. groups must be a
    power of two (1, 2, 4 used here).
    """
    if groups == 1:
        return x
    n = groups * pp
    step = 1
    while step < groups:
        pairs = [(r, (((r // pp) ^ step) * pp) + (r % pp)) for r in range(n)]
        x = x + jax.lax.ppermute(x, MODEL, pairs)
        step *= 2
    return x


def pipe_perm(pp: int, groups: int, direction: int):
    """ppermute pairs for the intra-group stage ring (+1 fwd / −1 bwd)."""
    pairs = []
    for g in range(groups):
        base = g * pp
        for p in range(pp):
            src = base + p
            dst = base + (p + direction) % pp
            pairs.append((src, dst))
    return pairs


# --------------------------------------------------------------------------- #
# Flat-segment coalescing (see the DESIGN note in the module docstring)
# --------------------------------------------------------------------------- #


def build_flat_layout(specs: dict, gatherable, dsize: int, ep: bool,
                      *, ep_segment: bool = False) -> FlatLayout | None:
    """Static offsets for one stage segment's flat buffer (None if empty).

    ``ep_segment=True`` builds the *expert* segment instead: every named
    tensor must be EP-sharded (expert dim 0 over "data") and its expert
    dim must divide the data axis — the layout then packs each tensor's
    local expert shard (``ld == 0``), so one slab collective covers the
    stage's whole expert bank. A non-divisible expert dim returns None
    (per-tensor fallback).
    """
    entries = []
    off = 0
    for n in sorted(gatherable):
        sp = specs[n]
        if ep_segment:
            if not (sp.ep and ep) or not sp.shape or sp.shape[0] % dsize:
                return None  # mixed / non-divisible expert set: fall back
            ld = 0
        else:
            ld = local_dim(sp, dsize, ep)
            assert ld is not None and not (sp.ep and ep), (
                f"{n} is not flat-packable (replicated or EP)")
        size = int(np.prod(sp.shape)) // dsize
        entries.append(FlatEntry(name=n, shape=tuple(sp.shape), ld=ld,
                                 offset=off, size=size))
        off += size
    if not entries:
        return None
    return FlatLayout(entries=tuple(entries), local_size=off, dsize=dsize)


def _rest_shape(e: FlatEntry) -> tuple[int, ...]:
    return tuple(s for i, s in enumerate(e.shape) if i != e.ld)


def pack_flat_stack(seg_p: dict, fl: FlatLayout):
    """[V, local_size] slab stack from the per-rank local param stacks.

    ``seg_p[n]`` is the shard_map-local ``[V, *local_shape]`` stack (dim
    ``ld`` already divided by dsize). Packed once per step — the gather
    tick then just indexes a row.
    """
    parts = []
    V = None
    for e in fl.entries:
        x = seg_p[e.name]
        V = x.shape[0]
        parts.append(jnp.moveaxis(x, e.ld + 1, 1).reshape(V, e.size))
    return jnp.concatenate(parts, axis=1)


def all_gather_flat(local_slab, fl: FlatLayout):
    """ONE all-gather for the whole stage segment: [local] -> [full]."""
    return jax.lax.all_gather(local_slab, DATA, axis=0, tiled=True)


def unpack_flat(seg, fl: FlatLayout) -> dict:
    """Per-tensor views of a gathered [full_size] segment (static offsets)."""
    m = seg.reshape(fl.dsize, fl.local_size)
    out = {}
    for e in fl.entries:
        rest = _rest_shape(e)
        t = m[:, e.offset:e.offset + e.size].reshape(
            (e.shape[e.ld],) + rest)
        out[e.name] = jnp.moveaxis(t, 0, e.ld)
    return out


def unpack_flat_local(loc, fl: FlatLayout) -> dict:
    """Per-tensor local shards of a [local_size] slab (post reduce-scatter)."""
    out = {}
    for e in fl.entries:
        rest = _rest_shape(e)
        t = loc[e.offset:e.offset + e.size].reshape(
            (e.shape[e.ld] // fl.dsize,) + rest)
        out[e.name] = jnp.moveaxis(t, 0, e.ld)
    return out


def unpack_flat_stack(slab, fl: FlatLayout) -> dict:
    """Inverse of :func:`pack_flat_stack`: [V, local_size] slab stack back
    to the per-tensor ``{n: [V, *local_shape]}`` stacks."""
    V = slab.shape[0]
    out = {}
    for e in fl.entries:
        rest = _rest_shape(e)
        t = slab[:, e.offset:e.offset + e.size].reshape(
            (V, e.shape[e.ld] // fl.dsize) + rest)
        out[e.name] = jnp.moveaxis(t, 1, e.ld + 1)
    return out


def ep_allreduce_flat(slab, groups: int, pp: int, pod: bool = False):
    """Cross-group (+ cross-pod) reduction of one EP gradient slab.

    EP expert grads are already local-complete over "data"; the only
    collectives they need are the group butterfly and the pod psum.
    Coalescing a stage's expert tensors into ONE [V, ep_local_size] slab
    turns the per-tensor ppermute/psum chains into one collective each —
    bitwise identical values (both are element-exact and the per-element
    reduction order is unchanged; only the wire layout is coalesced).
    """
    out = group_allreduce(slab, groups, pp)
    if pod:
        out = jax.lax.psum(out, POD)
    return out


def ep_allreduce_flat_int8(slab, groups: int, pp: int, pod: bool = False):
    """int8 EP slab reduction: shared-scale quantize → int32 sum → dequant.

    The scale is pmax-shared over the summed axes so the integer
    accumulation is exact. Like the per-tensor EP int8 path there is no
    error-feedback buffer — the EP reduction runs once per step, so no
    later tick exists to re-inject feedback into. Identity meshes
    (groups == 1, no pods) skip quantization entirely: nothing is summed,
    so there is no wire to compress.
    """
    if groups == 1 and not pod:
        return slab
    gf = slab.astype(jnp.float32)
    local_scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    axes = (MODEL,) + ((POD,) if pod else ())
    scale = jax.lax.pmax(local_scale, axes)
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int32)
    acc = group_allreduce(q, groups, pp)
    if pod:
        acc = jax.lax.psum(acc, POD)
    return acc.astype(jnp.float32) * scale


def _pack_full_flat(grads: dict, fl: FlatLayout, dtype):
    """[full_size] shard-major flat buffer from full-size per-rank grads."""
    parts = []
    for e in fl.entries:
        g = jnp.moveaxis(grads[e.name], e.ld, 0).astype(dtype)
        parts.append(g.reshape(fl.dsize, e.size))
    return jnp.concatenate(parts, axis=1).reshape(-1)


def reduce_scatter_flat(grads: dict, fl: FlatLayout, rs_dtype) -> dict:
    """ONE psum_scatter for the whole stage segment's gradients.

    ``grads`` are full-size per-rank accumulations; returns each tensor's
    reduced LOCAL shard (same values, bit-for-bit, as per-tensor
    ``reduce_scatter_grad`` — only the wire layout is coalesced).
    """
    flat = _pack_full_flat(grads, fl, jnp.dtype(rs_dtype))
    red = jax.lax.psum_scatter(flat, DATA, scatter_dimension=0, tiled=True)
    return unpack_flat_local(red, fl)


def reduce_scatter_flat_int8(grads: dict, err_flat, fl: FlatLayout):
    """int8 flat reduce with error feedback over the whole segment.

    Like :func:`reduce_scatter_grad_int8` but with ONE collective and one
    pmax-shared scale for the entire flat segment (coarser than the
    per-tensor scale — the error-feedback buffer absorbs the difference).
    ``err_flat`` is the [full_size] fp32 feedback carried across reduce
    ticks; returns (per-tensor local shards, new err_flat).
    """
    gf = _pack_full_flat(grads, fl, jnp.float32) + err_flat
    local_scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    scale = jax.lax.pmax(local_scale, DATA)
    q = jnp.clip(jnp.round(gf / scale), -127, 127)
    red = jax.lax.psum_scatter(
        q.astype(jnp.int32), DATA, scatter_dimension=0, tiled=True
    ).astype(jnp.float32) * scale
    new_err = gf - q * scale
    return unpack_flat_local(red, fl), new_err


# --------------------------------------------------------------------------- #
# Optional int8 gradient compression with error feedback
# --------------------------------------------------------------------------- #


def reduce_scatter_grad_int8(g, err, spec: ParamSpec, dsize: int, ep: bool,
                             pod: bool = False):
    """int8 reduce path: shared-scale quantize → sum in int32 → dequantize.

    Quarters (vs fp32) the reduce traffic; quantization noise is carried in
    the per-tensor error-feedback buffer and re-injected next step
    (Karimireddy et al. semantics). The scale is pmax-shared over "data" so
    the integer sum is exact.
    """
    gf = g.astype(jnp.float32) + err
    local_scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    d = local_dim(spec, dsize, ep)
    if spec.ep and ep:
        scale = local_scale
        q = jnp.clip(jnp.round(gf / scale), -127, 127)
        out = q * scale
    else:
        scale = jax.lax.pmax(local_scale, DATA)
        q = jnp.clip(jnp.round(gf / scale), -127, 127)
        acc = q.astype(jnp.int32)
        if d is None:
            out = jax.lax.psum(acc, DATA).astype(jnp.float32) * scale
        else:
            out = jax.lax.psum_scatter(
                acc, DATA, scatter_dimension=d, tiled=True
            ).astype(jnp.float32) * scale
    new_err = gf - q * scale
    if pod:
        out = jax.lax.psum(out, POD)
    return out, new_err
