"""The shared SPMD tick machine and its train/serve handler sets.

One ``TickEngine`` runs every schedule-plan table — train, serve,
encoder/decoder segments, ZeroPP and all baselines. Each tick it:

  1. stores incoming wires (activations fwd / input-grads bwd) into
     micro-batch buffers per the plan's static receive maps;
  2. conditionally issues this tick's blockwise FSDP all-gather (§3.3)
     into a rotating two-slot buffer;
  3. dispatches this rank's table cell through a branch-handler table
     ({NOP, F, B, W} for training, {NOP, F} for serving);
  4. (training) conditionally reduce-scatters a finished stage block's
     gradients (once per scheduling unit, §3.3);
  5. runs the boundary ``ppermute``s around the intra-group stage ring.

Steps 1/2/4/5 — the gather/reduce/wire plumbing — live here once; the
bodies below (``train_body`` / ``serve_body``) only supply the branch
handlers (F/B/W math, loss seeding, KV-cache get/put) and the carry
extras those handlers need. ``core/pipeline.py`` keeps the Runtime and
the jit/shard_map step builders on top of these bodies.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import fsdp
from repro.core.plan import PackedTable
from repro.core.tape import Tape
from repro.models import blocks, model as M
from repro.models.common import rope_tables

DATA, MODEL, POD = "data", "model", "pod"


# --------------------------------------------------------------------------- #
# Small dynamic-index helpers (shared by all handlers)
# --------------------------------------------------------------------------- #


def _dyn_set2(buf, i, j, val):
    """buf[i, j] = val with dynamic scalar indices."""
    row = jax.lax.dynamic_index_in_dim(buf, i, 0, keepdims=False)
    row = jax.lax.dynamic_update_index_in_dim(row, val, j, 0)
    return jax.lax.dynamic_update_index_in_dim(buf, row, i, 0)


def _dyn_get2(buf, i, j):
    row = jax.lax.dynamic_index_in_dim(buf, i, 0, keepdims=False)
    return jax.lax.dynamic_index_in_dim(row, j, 0, keepdims=False)


def _dyn_add(buf, i, val):
    old = jax.lax.dynamic_index_in_dim(buf, i, 0, keepdims=False)
    return jax.lax.dynamic_update_index_in_dim(buf, old + val, i, 0)


def _gathered_shape(spec, dsize, ep):
    return spec.shape


def _local_shape(spec, dsize, ep):
    ld = fsdp.local_dim(spec, dsize, ep)
    if ld is None:
        return spec.shape
    sh = list(spec.shape)
    sh[ld] = sh[ld] // dsize
    return tuple(sh)


def _loss_iog_proto(cfg, io_p, vloc):
    names = ["final_norm.scale"]
    if cfg.norm == "layernorm":
        names.append("final_norm.bias")
    names.append("embed.table" if cfg.tie_embeddings else "head.w")
    if cfg.mtp:
        names += [n for n in io_p
                  if n.startswith(("mtp.proj", "mtp.layer", "mtp.norm"))]
        if not cfg.tie_embeddings:
            names.append("embed.table")  # MTP ties emb grads in too
    return {n: io_p[n] for n in names}


def _rope_for(cfg, rc, seq):
    dims = {cfg.head_dim}
    if cfg.mla is not None:
        dims.add(cfg.mla.rope_dims)
    return {e: rope_tables(seq, e, cfg.rope_theta) for e in dims}


def make_tok_slice(g_rank, Btot: int, mbs: int) -> Callable:
    """This rank's micro-batch slice of a [global_batch, ...] array."""
    def tok_slice(arr, u):
        start = (g_rank * Btot + u) * mbs
        return jax.lax.dynamic_slice_in_dim(arr, start, mbs, axis=0)
    return tok_slice


# --------------------------------------------------------------------------- #
# The tick engine
# --------------------------------------------------------------------------- #


_FLAT = "__flat__"  # gbuf key of the coalesced flat segment


def validate_unit_stash_packed(pt: PackedTable) -> None:
    """Reject packed tables whose task spacing exceeds the U-deep buffers.

    The engine's ``fstash``/``wx``/``wdy``/``xbuf``/``bbuf`` carries are
    ``pt.U`` deep and indexed by ``mb % U``, so micro-batch ``u + U``
    overwrites ``u``'s slot; a table where a postponed W (or a late B)
    outlives its slot would silently replay the *wrong* micro-batch's
    stash. ``pack_table`` already gates TickTables; this re-checks the
    packed arrays at the engine boundary — with the SAME window rules
    (``schedules.stash_window_violations``) — so an injected PackedTable
    can never scan with an illegal stash depth. (Cheap: one pass over
    the [T, Pe] grids at trace time.)
    """
    from repro.core.schedules import stash_window_violations

    U, n_mb = pt.U, pt.n_mb
    if not (0 < U < n_mb):
        return
    tick: dict[tuple, int] = {}
    for t in range(pt.T):
        for r in range(pt.Pe):
            k = int(pt.kind[t, r])
            if k:
                s = int(pt.v[t, r]) * pt.Pe + r
                tick[(k, int(pt.mb[t, r]), s)] = t
    bad = stash_window_violations(tick, U, n_mb, pt.Pe * pt.V)
    if bad:
        raise ValueError(
            f"packed table illegal at unit depth U={U}: "
            f"{len(bad)} stash violation(s), first: {bad[0]}")


@dataclasses.dataclass
class TickEngine:
    """Scans one PackedTable with the shared gather/reduce/wire plumbing.

    Handlers receive ``(carry, row)`` and return the updated carry; they
    read stage parameters via ``stage_params`` and may use any extra
    carry entries the body placed there. ``rs_dtype`` enables the
    per-unit reduce-scatter step (training only).

    With a ``flat`` layout (``RunConfig.coalesce="flat"``), the gather
    tick issues ONE ``all_gather`` of the pre-packed per-slot slab
    (``seg_flat``) and the reduce tick ONE ``psum_scatter`` of the
    coalesced gradient segment, regardless of tensor count; per-tensor
    views come from the gathered slab via static offsets. Tensors the
    layout cannot cover (replicated / EP) keep the per-tensor path.
    """

    pt: PackedTable
    Pe: int
    G: int
    V: int
    specs: dict
    gatherable: list
    seg_p: dict
    dsize: int
    ep: bool
    cdt: Any
    p_rank: Any
    g_rank: Any
    backward: bool = False
    rs_dtype: Any = None
    flat: Any = None        # FlatLayout | None (coalesced collectives)
    seg_flat: Any = None    # [V, local_size] pre-packed local slabs
    grad_compress: str = "none"   # none | int8 (error-feedback reduce)

    def __post_init__(self):
        # Unit-gated tables (stash depth U < n_mb) are only runnable when
        # every stash/wire slot is read before its mb+U overwrite lands.
        if self.backward:
            validate_unit_stash_packed(self.pt)

    # ------------------------------------------------------------------ #
    def stage_params(self, v, use_slot, gbuf):
        """Params of local slot v: gathered buffer or resident stack."""
        out = {}
        if self.flat is not None and self.gatherable:
            slab = jax.lax.dynamic_index_in_dim(
                gbuf[_FLAT], jnp.clip(use_slot, 0, 1), 0, keepdims=False)
            out.update(fsdp.unpack_flat(slab, self.flat))
        for n in self.specs:
            if n in self.gatherable:
                if self.flat is None:
                    out[n] = jax.lax.dynamic_index_in_dim(
                        gbuf[n], jnp.clip(use_slot, 0, 1), 0,
                        keepdims=False)
            else:
                out[n] = jax.lax.dynamic_index_in_dim(
                    self.seg_p[n], jnp.clip(v, 0, self.V - 1), 0,
                    keepdims=False)
        return out

    def init_gbuf(self):
        """Rotating two-slot buffer for blockwise FSDP gathers."""
        if self.flat is not None:
            if not self.gatherable:
                return {}
            return {_FLAT: jnp.zeros((2, self.flat.full_size), self.cdt)}
        return {
            n: jnp.zeros(
                (2, *_gathered_shape(self.specs[n], self.dsize, self.ep)),
                self.cdt)
            for n in self.gatherable
        }

    def init_gerr(self):
        """fp32 error-feedback buffers for the int8 reduce path.

        int8 compression covers the gatherable (FSDP reduce-scatter) set —
        the bulk of the traffic; replicated/EP tensors keep fp reduces.
        """
        if self.grad_compress != "int8" or not self.gatherable:
            return None
        if self.flat is not None:
            return {_FLAT: jnp.zeros((self.V, self.flat.full_size),
                                     jnp.float32)}
        return {
            n: jnp.zeros((self.V, *self.specs[n].shape), jnp.float32)
            for n in self.gatherable
        }

    # ------------------------------------------------------------------ #
    def _store_wires(self, c, row):
        """Step 1: land last boundary's wires in the mb buffers."""
        Btot, U = self.pt.n_mb, self.pt.U
        ruf = row["recv_f_u"]
        c["xbuf"] = jax.lax.cond(
            ruf >= 0,
            lambda b: jax.lax.dynamic_update_index_in_dim(
                b, c["recv_f"], jnp.clip(ruf, 0, Btot) % U, 0),
            lambda b: b, c["xbuf"])
        if self.backward:
            rub = row["recv_b_u"]
            c["bbuf"] = jax.lax.cond(
                rub >= 0,
                lambda b: jax.lax.dynamic_update_index_in_dim(
                    b, c["recv_b"], jnp.clip(rub, 0, Btot) % U, 0),
                lambda b: b, c["bbuf"])
        return c

    def _gather_step(self, c, row):
        """Step 2: blockwise FSDP gather into the rotating slot.

        Flat layout: ONE all_gather of the slot's pre-packed slab; else
        one all_gather per gatherable tensor.
        """
        gv, gs = row["gather_v"], row["gather_slot"]

        def do_gather(gb):
            gb = dict(gb)
            if self.flat is not None:
                pv = jax.lax.dynamic_index_in_dim(
                    self.seg_flat, jnp.clip(gv, 0, self.V - 1), 0,
                    keepdims=False)
                full = fsdp.all_gather_flat(pv, self.flat)
                gb[_FLAT] = jax.lax.dynamic_update_index_in_dim(
                    gb[_FLAT], full.astype(self.cdt), jnp.clip(gs, 0, 1),
                    0)
                return gb
            for n in self.gatherable:
                pv = jax.lax.dynamic_index_in_dim(
                    self.seg_p[n], jnp.clip(gv, 0, self.V - 1), 0,
                    keepdims=False)
                ld = fsdp.local_dim(self.specs[n], self.dsize, self.ep)
                full = jax.lax.all_gather(pv, DATA, axis=ld, tiled=True)
                gb[n] = jax.lax.dynamic_update_index_in_dim(
                    gb[n], full.astype(self.cdt), jnp.clip(gs, 0, 1), 0)
            return gb

        if self.gatherable:
            c["gbuf"] = jax.lax.cond(gv >= 0, do_gather, lambda gb: gb,
                                     c["gbuf"])
        return c

    def _reduce_step(self, c, row):
        """Step 4: per-unit blockwise reduce-scatter of finished grads.

        Flat layout: ONE psum_scatter coalesces every gatherable tensor's
        gradient; replicated/EP leftovers keep their per-tensor reduces.
        ``grad_compress="int8"`` routes the gatherable set through the
        error-feedback int8 path (``c["gerr"]`` carries the feedback).

        Overlap safety: the plan places each reduce at its unit's last-W
        tick, and the scattered shard is only consumed after the scan (by
        the optimizer step), never by a later tick — so XLA is free to
        run the collective asynchronously under the next unit's B/W
        compute. The simulator models exactly this window (a tail
        reduce-scatter overlapping the following unit; see
        ``core/simulator.py``), and it is sound even if a next-unit B
        accumulates into ``acc_full`` before this tick's scatter drains
        it: reduce-scatter is linear and every contribution passes
        through exactly one scatter, so the per-shard sum is unchanged.
        """
        if self.dsize == 1:
            # one-wide data axis: grads accumulate straight into
            # acc_shard (no acc_full) and there is nothing to scatter
            return c
        rv = row["reduce_v"]
        rs_dt = jnp.dtype(self.rs_dtype)
        flat_set = set(self.gatherable) if self.flat is not None else set()
        int8 = self.grad_compress == "int8" and bool(self.gatherable)

        def do_reduce(args):
            full, shard = dict(args[0]), dict(args[1])
            gerr = dict(args[2]) if int8 else None
            rv_c = jnp.clip(rv, 0, self.V - 1)
            if flat_set:
                grads = {n: jax.lax.dynamic_index_in_dim(
                    full[n], rv_c, 0, keepdims=False) for n in flat_set}
                if int8:
                    err_v = jax.lax.dynamic_index_in_dim(
                        gerr[_FLAT], rv_c, 0, keepdims=False)
                    red, new_err = fsdp.reduce_scatter_flat_int8(
                        grads, err_v, self.flat)
                    gerr[_FLAT] = jax.lax.dynamic_update_index_in_dim(
                        gerr[_FLAT], new_err, rv_c, 0)
                else:
                    red = fsdp.reduce_scatter_flat(grads, self.flat, rs_dt)
                for n, r in red.items():
                    shard[n] = _dyn_add(shard[n], rv,
                                        r.astype(jnp.float32))
                    full[n] = jax.lax.dynamic_update_index_in_dim(
                        full[n], jnp.zeros_like(grads[n]), rv_c, 0)
            for n in full:
                if n in flat_set:
                    continue
                g = jax.lax.dynamic_index_in_dim(full[n], rv_c, 0,
                                                 keepdims=False)
                if int8 and self.flat is None and n in self.gatherable:
                    err_v = jax.lax.dynamic_index_in_dim(
                        gerr[n], rv_c, 0, keepdims=False)
                    red_t, new_err = fsdp.reduce_scatter_grad_int8(
                        g, err_v, self.specs[n], self.dsize, self.ep)
                    gerr[n] = jax.lax.dynamic_update_index_in_dim(
                        gerr[n], new_err, rv_c, 0)
                else:
                    red_t = fsdp.reduce_scatter_grad(g.astype(rs_dt),
                                                     self.specs[n],
                                                     self.dsize, self.ep)
                shard[n] = _dyn_add(shard[n], rv,
                                    red_t.astype(jnp.float32))
                full[n] = jax.lax.dynamic_update_index_in_dim(
                    full[n], jnp.zeros_like(g), rv_c, 0)
            out = (full, shard) + ((gerr,) if int8 else ())
            return out

        operands = (c["acc_full"], c["acc_shard"]) + (
            (c["gerr"],) if int8 else ())
        res = jax.lax.cond(rv >= 0, do_reduce, lambda a: a, operands)
        c["acc_full"], c["acc_shard"] = res[0], res[1]
        if int8:
            c["gerr"] = res[2]
        return c

    def _boundary(self, c):
        """Step 5: boundary permutes (intra-group stage rings)."""
        c["recv_f"] = jax.lax.ppermute(c["send_f"], MODEL,
                                       fsdp.pipe_perm(self.Pe, self.G, +1))
        if self.backward:
            c["recv_b"] = jax.lax.ppermute(
                c["send_b"], MODEL, fsdp.pipe_perm(self.Pe, self.G, -1))
        return c

    # ------------------------------------------------------------------ #
    def run(self, carry, branches: list):
        """lax.scan the plan's ticks, dispatching cells to ``branches``.

        ``branches`` is the {NOP, F[, B, W]} handler table; a 2-entry
        table (serving) clamps B/W cells to the F handler's index so
        forward-only tables never index out of range.
        """
        def tick(c, row_all):
            row = {k: a[self.p_rank] for k, a in row_all.items()}
            c = dict(c)
            c = self._store_wires(c, row)
            c = self._gather_step(c, row)
            kind = (row["kind"] if len(branches) == 4
                    else jnp.minimum(row["kind"], len(branches) - 1))
            c = jax.lax.switch(kind, branches, c, row)
            if self.rs_dtype is not None:
                c = self._reduce_step(c, row)
            c = self._boundary(c)
            return c, ()

        carry, _ = jax.lax.scan(tick, carry, self.pt.rows())
        return carry


# --------------------------------------------------------------------------- #
# Training: segment scan as F/B/W handlers over the engine
# --------------------------------------------------------------------------- #


def segment_train_scan(
    rt, seg, pt: PackedTable, seg_p, io_p, batch, mbs, seq,
    vloc, denom, aux_seed, io_g0, metrics0, p_rank, g_rank, *,
    inject: str, seed: str | None, membuf, dmembuf, seed_buf=None,
    carry_in=None, tmpl_override=None,
):
    """Run one segment's schedule-plan as a tick-engine scan.

    inject:  batch key providing stage-0 inputs (int tokens or float embeds)
    seed:    "loss" (LM head at last stage) | "buffer" (seed_buf[u]) | None
    membuf:  None | "collect" (store drain outputs) | array [U, mbs, ctx, d]
             (cross-attention memory for decoder segments)
    dmembuf: "collect" to accumulate d(enc_memory) during B tasks
    carry_in: reuse stash buffers from a previous scan of the same segment
    """
    cfg, rc = rt.cfg, rt.rc
    from repro.core import vocab as Vb

    cdt = jnp.dtype(rc.compute_dtype)
    d = cfg.d_model
    V, Pe, G, U = seg.vpp, rt.Pe, rt.G, pt.U
    Btot = pt.n_mb
    S = Pe * V
    specs = rt.stage_specs[seg.name]
    gatherable = rt.gatherable[seg.name]
    ep_names = set(rt.ep_names[seg.name])
    ep_axis = DATA if (rt.ep and any(
        k.endswith(":moe") for k in seg.kinds)) else None
    has_cross = membuf is not None and not isinstance(membuf, str)
    cross_ctx = cfg.encdec.enc_ctx if (has_cross and cfg.encdec) else None
    # Fused-backward baselines have no W tasks: every dense's dW is
    # computed immediately inside B (classic 1F1B/GPipe semantics).
    if tmpl_override is not None:
        no_defer, tmpl = tmpl_override
    else:
        no_defer = set(ep_names) if pt.has_w else set(specs)
        if rc.no_defer_extra and pt.has_w:
            no_defer |= {n for n in specs
                         if any(sub in n for sub in rc.no_defer_extra)}
        tmpl = rt._stash_tmpl(seg, (mbs, seq), no_defer,
                              cross_ctx=cross_ctx)
    tokens = batch[inject]
    int_tokens = jnp.issubdtype(tokens.dtype, jnp.integer)
    labels = batch.get("labels")

    rope = _rope_for(cfg, rc, seq)
    dsize = rt.dsize
    # where B/W accumulate full-size grads: the per-unit buffer that the
    # reduce tick scatters, or — on a one-wide data axis, where the
    # scatter is the identity — the returned shard accumulator itself
    acc_key = "acc_full" if dsize > 1 else "acc_shard"

    flat = rt.flat_layouts.get(seg.name)
    eng = TickEngine(
        pt=pt, Pe=Pe, G=G, V=V, specs=specs, gatherable=gatherable,
        seg_p=seg_p, dsize=dsize, ep=rt.ep, cdt=cdt,
        p_rank=p_rank, g_rank=g_rank, backward=True,
        rs_dtype=rc.grad_rs_dtype, flat=flat,
        seg_flat=(fsdp.pack_flat_stack(seg_p, flat)
                  if flat is not None else None),
        grad_compress=rc.grad_compress)
    tok_slice = make_tok_slice(g_rank, Btot, mbs)
    stage_params = eng.stage_params

    # ---- carry ------------------------------------------------------------ #
    act = (mbs, seq, d)
    zeros_act = jnp.zeros(act, cdt)
    gerr0 = eng.init_gerr()
    if carry_in is None:
        carry = dict(
            send_f=zeros_act, send_b=zeros_act,
            recv_f=zeros_act, recv_b=zeros_act,
            xbuf=jnp.zeros((U, *act), cdt),
            bbuf=jnp.zeros((U, *act), cdt),
            fstash=jnp.zeros((V, U, *act), cdt),
            wx=[jnp.zeros((V, U, *sh), dt) for sh, dt in tmpl.x_shapes],
            wdy=[jnp.zeros((V, U, *sh), dt) for sh, dt in tmpl.dy_shapes],
            gbuf=eng.init_gbuf(),
            acc_full={n: jnp.zeros((V, *specs[n].shape), jnp.float32)
                      for n in specs
                      if n not in ep_names and acc_key == "acc_full"},
            **({"gerr": gerr0} if gerr0 is not None else {}),
            acc_shard={n: jnp.zeros(
                (V, *_local_shape(specs[n], dsize, rt.ep)), jnp.float32)
                for n in specs},
            io_g=io_g0,
            metrics=metrics0,
        )
    else:
        carry = carry_in
        carry["io_g"] = io_g0
        carry["metrics"] = metrics0
    if membuf == "collect":
        carry["membuf"] = jnp.zeros((Btot, mbs, seq, d), cdt)
    if dmembuf == "collect":
        enc_ctx2 = cfg.encdec.enc_ctx
        carry["dmembuf"] = jnp.zeros((Btot, mbs, enc_ctx2, d), cdt)

    # ---- branch bodies ----------------------------------------------------#
    track_moe = "moe_load" in metrics0 and any(
        k.endswith(":moe") for k in seg.kinds)  # rc.moe_stats histograms

    def make_ctx(tape, u):
        """Returns (ctx, mem_tval or None)."""
        mem = None
        if has_cross:
            mem = tape.value(jax.lax.dynamic_index_in_dim(
                membuf, u, 0, keepdims=False))
        ctx = blocks.LayerCtx(cfg=cfg, rc=rc, rope=rope, causal=seg.causal,
                              ep_axis=ep_axis, enc_memory=mem)
        return ctx, mem

    def get_input(c, u, v):
        uu = u % U
        x = jax.lax.dynamic_index_in_dim(c["xbuf"], uu, 0, keepdims=False)
        is_inject = (p_rank == 0) & (v == 0)

        def do_embed(_):
            ids_or_emb = tok_slice(tokens, u)
            if int_tokens:
                return Vb.embed_lookup(io_p["embed.table"], ids_or_emb,
                                       vloc, cdt)
            return ids_or_emb.astype(cdt)

        return jax.lax.cond(is_inject, do_embed, lambda _: x, None)

    def f_branch(c, row):
        u, v = row["mb"], row["v"]
        uu = u % U
        x = get_input(c, u, v)
        params_v = stage_params(v, row["use_slot"], c["gbuf"])
        t = Tape(params_v, mode="fwd", no_defer=frozenset(no_defer))
        stage_id = v * Pe + p_rank
        ctx, _ = make_ctx(t, u)
        y, _aux = M.apply_stage(t, ctx, seg, t.value(x), stage_id)
        c = dict(c)
        c["fstash"] = _dyn_set2(c["fstash"], v, uu, x)
        c["send_f"] = y.val
        if "membuf" in c:
            is_drain = (p_rank == Pe - 1) & (v == V - 1)
            c["membuf"] = jax.lax.cond(
                is_drain,
                lambda mb: jax.lax.dynamic_update_index_in_dim(
                    mb, y.val, u, 0),
                lambda mb: mb, c["membuf"])
        return c

    def b_branch(c, row):
        u, v = row["mb"], row["v"]
        uu = u % U
        x = jax.lax.dynamic_index_in_dim(c["fstash"], jnp.clip(v, 0, V - 1),
                                         0, keepdims=False)
        x = jax.lax.dynamic_index_in_dim(x, uu, 0, keepdims=False)
        params_v = stage_params(v, row["use_slot"], c["gbuf"])
        t = Tape(params_v, mode="bwd", no_defer=frozenset(no_defer))
        ctx, mem_tv = make_ctx(t, u)
        if track_moe:
            # accumulate only in B (once per micro-batch per stage; the
            # F pass of the same micro-batch would double-count)
            ctx.moe_stats = []
        stage_id = v * Pe + p_rank
        xin = t.value(x)
        out, aux = M.apply_stage(t, ctx, seg, xin, stage_id)

        is_last = (p_rank == Pe - 1) & (v == V - 1)
        c = dict(c)
        if seed == "loss":
            def with_loss(_):
                h = out.val.reshape(mbs * seq, d)
                lab_u = tok_slice(labels, u).reshape(mbs * seq)
                loss, dh, iog = Vb.loss_and_dy(
                    cfg, rc, io_p, h, lab_u, denom, vloc, dsize)
                if cfg.mtp:
                    # DeepSeek multi-token-prediction aux head: one extra
                    # layer over [norm(h); emb(label_t)] predicting t+2.
                    lam = M.MTP_WEIGHT
                    lab2d = tok_slice(labels, u)
                    emb_next = Vb.embed_lookup(
                        io_p["embed.table"], lab2d, vloc, out.val.dtype)
                    mtp_ep = DATA if rt.ep else None
                    hm, mtp_vjp = jax.vjp(
                        lambda hh, ee, mp: M.mtp_hidden(
                            cfg, rc, {**io_p, **mp}, hh, ee,
                            ep_axis=mtp_ep),
                        out.val, emb_next,
                        {n: a for n, a in io_p.items()
                         if n.startswith(("mtp.proj", "mtp.layer"))})
                    lab_mtp = jnp.concatenate(
                        [lab2d[:, 1:], lab2d[:, -1:]], 1).reshape(-1)
                    mask = jnp.concatenate(
                        [jnp.ones((mbs, seq - 1), jnp.float32),
                         jnp.zeros((mbs, 1), jnp.float32)], 1).reshape(-1)
                    denom_mtp = float(denom / seq * (seq - 1))
                    l_m, dhm, iog_m = Vb.loss_and_dy(
                        cfg, rc, io_p, hm.reshape(mbs * seq, d), lab_mtp,
                        denom_mtp, vloc, dsize, norm_key="mtp.norm",
                        mask=mask)
                    dh_b, demb, dmtp = mtp_vjp(
                        (lam * dhm).reshape(mbs, seq, d).astype(hm.dtype))
                    dh2 = dh.reshape(mbs, seq, d) + dh_b.astype(dh.dtype)
                    loss = loss + lam * l_m
                    proto = _loss_iog_proto(cfg, io_p, vloc)
                    for nk, v2 in proto.items():
                        if nk not in iog:
                            iog[nk] = jnp.zeros(v2.shape, jnp.float32)
                    for nk, gv in iog_m.items():
                        iog[nk] = iog[nk] + lam * gv
                    for nk, gv in dmtp.items():
                        iog[nk] = iog[nk] + gv.astype(jnp.float32)
                    # emb_next gradient scatters into the embedding rows
                    iog["__emb_mtp_ids"] = lab2d
                    iog["__emb_mtp_dx"] = demb.astype(jnp.float32)
                    return dh2, loss, iog
                proto = _loss_iog_proto(cfg, io_p, vloc)
                for nk, v2 in proto.items():
                    if nk not in iog:
                        iog[nk] = jnp.zeros(v2.shape, jnp.float32)
                return dh.reshape(mbs, seq, d), loss, iog

            def no_loss(_):
                dy = jax.lax.dynamic_index_in_dim(c["bbuf"], uu, 0,
                                                  keepdims=False)
                iog = {n: jnp.zeros(v2.shape, jnp.float32) for n, v2 in
                       _loss_iog_proto(cfg, io_p, vloc).items()}
                if cfg.mtp:
                    iog["__emb_mtp_ids"] = jnp.zeros((mbs, seq), jnp.int32)
                    iog["__emb_mtp_dx"] = jnp.zeros((mbs, seq, d),
                                                    jnp.float32)
                return dy, jnp.zeros((), jnp.float32), iog

            dy, loss_d, iog_d = jax.lax.cond(is_last, with_loss, no_loss,
                                             None)
            c["io_g"] = dict(c["io_g"])
            c["metrics"] = dict(c["metrics"])
            if cfg.mtp:
                ids_m = iog_d.pop("__emb_mtp_ids")
                dx_m = iog_d.pop("__emb_mtp_dx")
                acc_m, dr_m = Vb.embed_grad(
                    ids_m, dx_m, vloc, cfg.vocab,
                    c["io_g"]["embed.table"])
                c["io_g"]["embed.table"] = acc_m
                c["metrics"]["emb_dropped"] = (
                    c["metrics"]["emb_dropped"] + dr_m)
            for n, g in iog_d.items():
                c["io_g"][n] = c["io_g"][n] + g
            c["metrics"] = dict(c["metrics"])
            c["metrics"]["loss_sum"] = c["metrics"]["loss_sum"] + loss_d
        elif seed == "buffer":
            dy_seed = jax.lax.dynamic_index_in_dim(seed_buf, u, 0,
                                                   keepdims=False)
            dy_wire = jax.lax.dynamic_index_in_dim(c["bbuf"], uu, 0,
                                                   keepdims=False)
            dy = jnp.where(is_last, dy_seed.astype(cdt), dy_wire)
        else:
            dy = jax.lax.dynamic_index_in_dim(c["bbuf"], uu, 0,
                                              keepdims=False)

        seeds = {out.idx: dy.astype(out.val.dtype)}
        if aux is not None:
            seeds[aux.idx] = jnp.asarray(aux_seed, jnp.float32)
        cots, igrads, stash = t.backward(seeds)
        dx = cots[xin.idx]
        c["send_b"] = dx.astype(cdt)

        # stash (x, dy) pairs for the deferred W task
        sx: dict[int, Any] = {}
        for (pname, spec_s, xs_i, dy_i), s in zip(tmpl.entries, stash):
            if xs_i not in sx:
                c["wx"][xs_i] = _dyn_set2(c["wx"][xs_i], v, uu,
                                          s.x.astype(c["wx"][xs_i].dtype))
                sx[xs_i] = True
            c["wdy"][dy_i] = _dyn_set2(c["wdy"][dy_i], v, uu,
                                       s.dy.astype(c["wdy"][dy_i].dtype))
        c["wx"] = list(c["wx"])
        c["wdy"] = list(c["wdy"])

        # immediate grads: EP experts -> sharded accum; small -> full accum
        for n, g in igrads.items():
            if n in ep_names:
                c["acc_shard"] = dict(c["acc_shard"])
                c["acc_shard"][n] = _dyn_add(c["acc_shard"][n], v,
                                             g.astype(jnp.float32))
            else:
                c[acc_key] = dict(c[acc_key])
                c[acc_key][n] = _dyn_add(c[acc_key][n], v,
                                         g.astype(jnp.float32))

        # embedding gradient at the first stage
        if int_tokens:
            is_first = (p_rank == 0) & (v == 0)

            def emb_g(args):
                acc, drop = args
                ids = tok_slice(tokens, u)
                acc2, dr = Vb.embed_grad(ids, dx.astype(jnp.float32), vloc,
                                         cfg.vocab, acc)
                return acc2, drop + dr

            c["io_g"] = dict(c["io_g"])
            c["metrics"] = dict(c["metrics"])
            acc2, drop2 = jax.lax.cond(
                is_first, emb_g, lambda a: a,
                (c["io_g"]["embed.table"], c["metrics"]["emb_dropped"]))
            c["io_g"]["embed.table"] = acc2
            c["metrics"]["emb_dropped"] = drop2

        if "dmembuf" in c and has_cross and mem_tv is not None:
            # cotangent of the cross-attention memory input
            dmem = cots.get(mem_tv.idx)
            if dmem is not None:
                c["dmembuf"] = _dyn_add(c["dmembuf"], u,
                                        dmem.astype(cdt))

        c["metrics"] = dict(c["metrics"])
        c["metrics"]["aux_sum"] = (
            c["metrics"]["aux_sum"] + aux.val.astype(jnp.float32))
        if track_moe and ctx.moe_stats:
            Ls = len(seg.kinds)
            mt = c["metrics"]
            ml, dr, hr = mt["moe_load"], mt["moe_dropped"], mt["moe_rows_held"]
            for pfx_, load, dropped, held in ctx.moe_stats:
                row = stage_id * Ls + int(pfx_.split(".", 1)[0][1:])
                ml = ml.at[row].add(load)              # "L{j}.ffn" -> j
                hr = hr.at[row].add(held)
                dr = dr + dropped
            mt["moe_load"], mt["moe_dropped"], mt["moe_rows_held"] = \
                ml, dr, hr
        return c

    def w_branch(c, row):
        u, v = row["mb"], row["v"]
        uu = u % U
        c = dict(c)
        c["acc_full"] = dict(c["acc_full"])
        c["acc_shard"] = dict(c["acc_shard"])
        for (pname, spec_s, xs_i, dy_i) in tmpl.entries:
            xv = _dyn_get2(c["wx"][xs_i], v, uu)
            dyv = _dyn_get2(c["wdy"][dy_i], v, uu)
            g = jnp.einsum(spec_s, xv, dyv).astype(jnp.float32)
            c[acc_key][pname] = _dyn_add(c[acc_key][pname], v, g)
        return c

    def nop_branch(c, row):
        return c

    carry = eng.run(carry, [nop_branch, f_branch, b_branch, w_branch])

    return {
        "stage_grads": carry["acc_shard"],
        "io_grads": carry["io_g"],
        "metrics": carry["metrics"],
        "membuf": carry.get("membuf"),
        "dmembuf": carry.get("dmembuf"),
        "carry_out": carry,
    }


# --------------------------------------------------------------------------- #
# Train body (the SPMD program under shard_map)
# --------------------------------------------------------------------------- #


def train_body(params, batch, *, rt, shape_cfg, mbs, vloc,
               denom, aux_seed):
    """The SPMD program (runs per device under shard_map)."""
    cfg, rc = rt.cfg, rt.rc

    io_p = params["io"]
    mr = jax.lax.axis_index(MODEL)
    Pe, G, V = rt.Pe, rt.G, rc.vpp
    p_rank = mr % Pe
    g_rank = mr // Pe

    # io params arrive in their local (possibly vocab-sharded) shapes
    io_zero = {n: jnp.zeros(a.shape, jnp.float32) for n, a in io_p.items()}

    metrics0 = {"loss_sum": jnp.zeros((), jnp.float32),
                "aux_sum": jnp.zeros((), jnp.float32),
                "emb_dropped": jnp.zeros((), jnp.int32)}
    if rc.moe_stats and cfg.moe is not None:
        # per-(stage, stage-layer) expert-load histogram over every
        # routed expert, and the (token, k) pairs computed here: row
        # stage_id * len(seg.kinds) + j is global (padded) layer j of
        # stage stage_id; the final psum totals it across ranks.
        seg_m = rt.segs["dec" if cfg.encdec is not None else "main"]
        rows = rt.Pe * seg_m.vpp * len(seg_m.kinds)
        metrics0["moe_load"] = jnp.zeros((rows, cfg.moe.routed), jnp.int32)
        metrics0["moe_rows_held"] = jnp.zeros((rows,), jnp.int32)
        metrics0["moe_dropped"] = jnp.zeros((), jnp.int32)

    if cfg.encdec is None:
        seg = rt.segs["main"]
        pt = rt.tables["main"]
        res = segment_train_scan(
            rt, seg, pt, params["segments"]["main"], io_p,
            batch, mbs, shape_cfg.seq_len, vloc, denom, aux_seed,
            io_zero, metrics0, p_rank, g_rank,
            inject="tokens", seed="loss", membuf=None, dmembuf=None,
        )
        seg_grads = {"main": res["stage_grads"]}
        io_g, metrics = res["io_grads"], res["metrics"]
    else:
        seg_e, seg_d = rt.segs["enc"], rt.segs["dec"]
        enc_ctx = cfg.encdec.enc_ctx
        # the enc forward scan must allocate the stash buffers its later
        # backward scan (which *does* defer W) will fill
        enc_nd = set(rt.ep_names["enc"])
        enc_tmpl = (enc_nd, rt._stash_tmpl(seg_e, (mbs, enc_ctx), enc_nd))
        # 1) encoder forward (stash inputs for its later backward)
        res_e = segment_train_scan(
            rt, seg_e, rt.tables["enc_fwd"], params["segments"]["enc"],
            io_p, batch, mbs, enc_ctx, vloc, denom, aux_seed,
            io_zero, metrics0, p_rank, g_rank,
            inject="enc_tokens", seed=None, membuf="collect", dmembuf=None,
            tmpl_override=enc_tmpl,
        )
        membuf = jax.lax.psum(res_e["membuf"], MODEL)
        # 2) decoder train (full F/B/W) with cross-attention memory
        res_d = segment_train_scan(
            rt, seg_d, rt.tables["dec"], params["segments"]["dec"], io_p,
            batch, mbs, shape_cfg.seq_len, vloc, denom, aux_seed,
            res_e["io_grads"], res_e["metrics"], p_rank, g_rank,
            inject="tokens", seed="loss", membuf=membuf, dmembuf="collect",
        )
        dmem = jax.lax.psum(res_d["dmembuf"], MODEL)
        # 3) encoder backward (B/W only, seeded by accumulated dMemory)
        res_eb = segment_train_scan(
            rt, seg_e, rt.tables["enc_bwd"], params["segments"]["enc"],
            io_p, batch, mbs, enc_ctx, vloc, denom, aux_seed,
            res_d["io_grads"], res_d["metrics"], p_rank, g_rank,
            inject="enc_tokens", seed="buffer", membuf=None, dmembuf=None,
            seed_buf=dmem, carry_in=res_e["carry_out"],
            tmpl_override=enc_tmpl,
        )
        seg_grads = {"enc": res_eb["stage_grads"],
                     "dec": res_d["stage_grads"]}
        io_g, metrics = res_eb["io_grads"], res_eb["metrics"]

    # ---- cross-group / cross-pod gradient reduction ----------------------- #
    # EP expert grads are local-complete over "data"; they only need the
    # cross-group butterfly + cross-pod psum. With flat coalescing each
    # stage's expert bank rides ONE slab collective (bitwise identical to
    # the per-tensor chain); int8 grad compression quantizes the slab wire.
    for sname in seg_grads:
        sg = seg_grads[sname]
        efl = rt.ep_flat_layouts.get(sname)
        out_g = {}
        if efl is not None:
            slab = fsdp.pack_flat_stack(sg, efl)
            if rc.grad_compress == "int8":
                slab = fsdp.ep_allreduce_flat_int8(slab, rt.G, Pe,
                                                   pod=rt.multi_pod)
            else:
                slab = fsdp.ep_allreduce_flat(slab, rt.G, Pe,
                                              pod=rt.multi_pod)
            out_g.update(fsdp.unpack_flat_stack(slab, efl))
        for n, g in sg.items():
            if n in out_g:
                continue
            g = fsdp.group_allreduce(g, rt.G, Pe)
            if rt.multi_pod:
                g = jax.lax.psum(g, POD)
            out_g[n] = g
        seg_grads[sname] = out_g
    io_g = {n: jax.lax.psum(g, MODEL) for n, g in io_g.items()}
    if rt.multi_pod:
        io_g = {n: jax.lax.psum(g, POD) for n, g in io_g.items()}
    # replicated io params need the data-sum of per-shard contributions;
    # vocab-sharded embed/head rows and EP-sharded MTP experts are already
    # local-complete.
    ep_io = {n for n, sp_ in rt.io_specs.items() if sp_.ep and rt.ep}
    for n in io_g:
        if n in ep_io:
            continue
        if vloc is None or n not in ("embed.table", "head.w"):
            io_g[n] = jax.lax.psum(io_g[n], DATA)

    metrics = {k: jax.lax.psum(v, (DATA, MODEL) + ((POD,) if rt.multi_pod
                                                   else ()))
               for k, v in metrics.items()}
    grads = {"io": io_g, "segments": seg_grads}
    return grads, metrics


# --------------------------------------------------------------------------- #
# Serving: KV-cache hooks + F handler over the same engine
# --------------------------------------------------------------------------- #


def make_cache_io(cfg, rc, seg, *, seq_shard: bool, g_rank, Btot: int,
                  mbs: int, paged: bool = False):
    """(cache_get, cache_put) hooks for one segment's layer-cache tree.

    ``paged``: leaves are page pools ([V, n_loc, page_size, ...] locally)
    shared by every row — hand the stage the whole pool; the attention
    path scatters/gathers through each row's page table instead of this
    hook slicing per-micro-batch rows.
    """

    def cache_get(tree, j, v, u):
        # iterate the tree's own keys (not the layer spec's) so extra
        # leaves riding beside the pools — e.g. int8 per-page scales —
        # flow to the stage; the trailing dot keeps L1 from matching L10
        out = {}
        pfx = f"L{j}."
        for key in tree:
            if not key.startswith(pfx):
                continue
            n = key[len(pfx):]
            a = tree[key]
            av = jax.lax.dynamic_index_in_dim(a, v, 0, keepdims=False)
            if paged or seq_shard:
                out[n] = av  # whole pool / full local batch
            else:
                start = (g_rank * Btot + u) * mbs
                out[n] = jax.lax.dynamic_slice_in_dim(av, start, mbs, 0)
        return out

    def cache_put(tree, j, v, u, cd):
        for n, val in cd.items():
            a = tree[f"L{j}.{n}"]
            av = jax.lax.dynamic_index_in_dim(a, v, 0, keepdims=False)
            if paged or seq_shard:
                av = val.astype(a.dtype)
            else:
                start = (g_rank * Btot + u) * mbs
                av = jax.lax.dynamic_update_slice_in_dim(
                    av, val.astype(a.dtype), start, 0)
            tree[f"L{j}.{n}"] = jax.lax.dynamic_update_index_in_dim(
                a, av, v, 0)
        return tree

    return cache_get, cache_put


def serve_body(params, caches, batch, *, rt, shape_cfg, mbs,
               Btot, vloc, prompt_len, max_seq, seq_shard,
               page_size=0, want_logits=False):
    cfg, rc = rt.cfg, rt.rc
    from repro.core import vocab as Vb

    io_p = params["io"]
    mr = jax.lax.axis_index(MODEL)
    Pe, G = rt.Pe, rt.G
    p_rank = mr % Pe
    g_rank = mr // Pe
    cdt = jnp.dtype(rc.compute_dtype)
    d = cfg.d_model
    s = prompt_len
    tokens = batch["tokens"]
    pos = batch.get("pos", jnp.zeros((), jnp.int32))
    slot_mask = batch.get("slot_mask")
    page_tables = batch.get("page_tables")
    # pos may be a [gb] per-slot vector (continuous batching): every slot
    # sits at its own absolute position and only the rows flagged in
    # slot_mask commit cache writes. Sliced per micro-batch below.
    per_slot = getattr(pos, "ndim", 0) == 1
    if per_slot and seq_shard:
        raise NotImplementedError(
            "per-slot pos vectors need a batch-sharded cache; this shape "
            "fell back to the sequence-sharded (500k) cache layout — use "
            "a global_batch divisible by the data axis")
    if page_tables is not None and not (per_slot and page_size > 0):
        raise ValueError(
            "page_tables require a per-slot pos vector and page_size > 0")

    seg = rt.segs["dec"] if cfg.encdec is not None else rt.segs["main"]
    seg_key = "dec" if cfg.encdec is not None else "main"
    seg_p = params["segments"][seg_key]
    specs = rt.stage_specs[seg_key]
    gatherable = rt.gatherable[seg_key]
    V = seg.vpp
    pt = rt.tables["serve_dec" if cfg.encdec is not None else "serve_main"]
    U = pt.U
    cache_tree = caches[seg_key]

    dims = {cfg.head_dim}
    if cfg.mla is not None:
        dims.add(cfg.mla.rope_dims)
    rope = {e: rope_tables(max_seq, e, cfg.rope_theta) for e in dims}
    ctx = blocks.LayerCtx(
        cfg=cfg, rc=rc, rope=rope, causal=True,
        ep_axis=DATA if rt.ep else None,
        kv_seq_shard=seq_shard, kv_shards=rt.dsize,
        page_size=page_size)
    if cfg.encdec is not None:
        ctx.enc_memory = None  # set per micro-batch below

    # The engine's wire buffers are indexed per the serve table
    # (pt.n_mb / pt.U); the caller's Btot — which make_serve_step may
    # shrink below rc.microbatches on degenerate tiny batches — only
    # governs token slicing, cache addressing and the out_tok layout.
    flat = rt.flat_layouts.get(seg_key)
    eng = TickEngine(
        pt=pt, Pe=Pe, G=G, V=V, specs=specs, gatherable=gatherable,
        seg_p=seg_p, dsize=rt.dsize, ep=rt.ep, cdt=cdt,
        p_rank=p_rank, g_rank=g_rank, backward=False, rs_dtype=None,
        flat=flat, seg_flat=(fsdp.pack_flat_stack(seg_p, flat)
                             if flat is not None else None))
    tok_slice = make_tok_slice(g_rank, Btot, mbs)
    stage_params = eng.stage_params
    cache_get, cache_put = make_cache_io(
        cfg, rc, seg, seq_shard=seq_shard, g_rank=g_rank, Btot=Btot,
        mbs=mbs, paged=page_tables is not None)

    act = (mbs, s, d)
    track_moe = (rc.moe_stats and cfg.moe is not None
                 and any(k.endswith(":moe") for k in seg.kinds))
    carry = dict(
        send_f=jnp.zeros(act, cdt),
        recv_f=jnp.zeros(act, cdt),
        xbuf=jnp.zeros((U, *act), cdt),
        gbuf=eng.init_gbuf(),
        caches=dict(cache_tree),
        out_tok=jnp.zeros((G * Btot, mbs), jnp.int32),
    )
    if track_moe:
        rows_m = Pe * V * len(seg.kinds)
        carry["moe_load"] = jnp.zeros((rows_m, cfg.moe.routed), jnp.int32)
        carry["moe_dropped"] = jnp.zeros((), jnp.int32)
    if want_logits:
        # per-u drain logits land here; vloc path: every data rank
        # computes its vocab slice for ALL data ranks' rows (the
        # all_gather inside serve_logits), hence the D·mbs row block.
        lrows = (rt.dsize if vloc else 1) * mbs
        carry["out_logits"] = jnp.zeros(
            (G * Btot, lrows, vloc or cfg.vocab), jnp.float32)

    def f_branch(c, row):
        u, v = row["mb"], row["v"]
        uu = u % U
        is_inject = (p_rank == 0) & (v == 0)

        def do_embed(_):
            ids = tok_slice(tokens, u) if not seq_shard else tokens
            if jnp.issubdtype(tokens.dtype, jnp.integer):
                return Vb.embed_lookup(io_p["embed.table"], ids, vloc, cdt)
            return ids.astype(cdt)

        x = jax.lax.cond(
            is_inject, do_embed,
            lambda _: jax.lax.dynamic_index_in_dim(c["xbuf"], uu, 0,
                                                   keepdims=False), None)
        params_v = stage_params(v, row["use_slot"], c["gbuf"])
        if cfg.encdec is not None:
            mem = caches["enc_memory"]
            ctx.enc_memory = (mem if seq_shard else tok_slice(mem, u))
        stage_id = v * Pe + p_rank
        pos_u = tok_slice(pos, u) if per_slot else pos
        ctx.slot_mask = (tok_slice(slot_mask, u)
                         if slot_mask is not None else None)
        ctx.page_tables = (tok_slice(page_tables, u)
                           if page_tables is not None else None)
        ch = [cache_get(c["caches"], j, v, u)
              for j in range(len(seg.kinds))]
        if track_moe:
            ctx.moe_stats = []
        y, ch2 = M.cached_stage(ctx, seg, params_v, x, ch, stage_id, pos_u)
        c = dict(c)
        if track_moe and ctx.moe_stats:
            Ls = len(seg.kinds)
            ml, dr = c["moe_load"], c["moe_dropped"]
            for pfx_, load, dropped, _ in ctx.moe_stats:
                j = int(pfx_.split(".", 1)[0][1:])
                ml = ml.at[stage_id * Ls + j].add(load)
                dr = dr + dropped
            c["moe_load"], c["moe_dropped"] = ml, dr
        c["caches"] = dict(c["caches"])
        for j in range(len(seg.kinds)):
            c["caches"] = cache_put(c["caches"], j, v, u, ch2[j])
        c["send_f"] = y

        is_drain = (p_rank == Pe - 1) & (v == V - 1)

        if want_logits:
            def sample_l(bufs):
                ot, ol = bufs
                h_last = y[:, -1]
                idx = g_rank * Btot + (u % Btot)
                tok = Vb.greedy_sample(cfg, rc, io_p, h_last, vloc)
                ot = jax.lax.dynamic_update_index_in_dim(ot, tok, idx, 0)
                lg = Vb.serve_logits(cfg, rc, io_p, h_last, vloc)
                ol = jax.lax.dynamic_update_index_in_dim(
                    ol, lg.astype(ol.dtype), idx, 0)
                return ot, ol

            c["out_tok"], c["out_logits"] = jax.lax.cond(
                is_drain, sample_l, lambda bufs: bufs,
                (c["out_tok"], c["out_logits"]))
            return c

        def sample(ot):
            h_last = y[:, -1]
            tok = Vb.greedy_sample(cfg, rc, io_p, h_last, vloc)
            return jax.lax.dynamic_update_index_in_dim(
                ot, tok, g_rank * Btot + (u % Btot), 0)

        c["out_tok"] = jax.lax.cond(is_drain, sample, lambda ot: ot,
                                    c["out_tok"])
        return c

    def nop_branch(c, row):
        return c

    carry = eng.run(carry, [nop_branch, f_branch])

    out_tok = carry["out_tok"].reshape(-1)
    # drain ranks hold the sampled tokens; share them
    out_tok = jax.lax.psum(
        jnp.where((p_rank == Pe - 1), out_tok, jnp.zeros_like(out_tok)),
        MODEL)
    caches_out = dict(caches)
    caches_out[seg_key] = carry["caches"]
    moe_out = None
    if track_moe:
        # MODEL totals the per-stage rows; the data/pod axes hold
        # disjoint slot shards only when the batch is sharded (seq_shard
        # replicates the batch — summing would multiply by dsize).
        axes = (MODEL,) + (() if seq_shard else
                           ((POD, DATA) if rt.multi_pod else (DATA,)))
        moe_out = {"load": jax.lax.psum(carry["moe_load"], axes),
                   "dropped": jax.lax.psum(carry["moe_dropped"], axes)}
    if want_logits:
        ol = carry["out_logits"]  # [G·Btot, (D·)mbs, vloc|vocab]
        if vloc:
            # reorder to [D, b_loc, vloc] -> [D·b_loc, vloc]: global row
            # r of the gb batch is data-rank r // b_loc's local row
            # r % b_loc, and each u-block holds all D ranks' mbs rows.
            D = rt.dsize
            ol = ol.reshape(G * Btot, D, mbs, vloc)
            ol = ol.transpose(1, 0, 2, 3).reshape(D * G * Btot * mbs, vloc)
        else:
            ol = ol.reshape(G * Btot * mbs, cfg.vocab)
        ol = jax.lax.psum(
            jnp.where((p_rank == Pe - 1), ol, jnp.zeros_like(ol)), MODEL)
        return ((out_tok, ol, caches_out, moe_out) if track_moe
                else (out_tok, ol, caches_out))
    return ((out_tok, caches_out, moe_out) if track_moe
            else (out_tok, caches_out))
