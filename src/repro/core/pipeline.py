"""Runtime + step builders for the table-driven SPMD pipeline (ZeroPP).

One jitted program per step: ``shard_map`` over the production mesh,
inside which each segment's ``SchedulePlan`` runs on the shared tick
engine (``core/executor.py``). The plan objects (``core/plan.py``) bundle
the TickTable the simulator analyzes with the PackedTable the executor
scans, so what we analyze is exactly what runs — structurally.

This module owns the *static* side only:

  * ``Runtime`` — builds the per-segment SchedulePlans (train, serve,
    encoder/decoder), parameter specs + shardings, and the W-stash
    templates the executor's B/W handlers replay;
  * ``make_train_step`` / ``make_serve_step`` — wrap the executor bodies
    in ``shard_map`` + ``jit`` with the right in/out specs;
  * serve-cache construction (``init_serve_caches``).

All rank-varying branching is driven by *static* numpy tables indexed by
the dynamic model-axis rank — see DESIGN.md §3 for why this is the
TPU-native realization of the paper's per-rank GPU kernel queues.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import fsdp
from repro.core.executor import (
    _rope_for,
    serve_body as _serve_body,
    train_body as _train_body,
)
from repro.core.generators import SchedParams, generate
from repro.core.plan import (
    UNIT_GATED_SCHEDULES,
    PackedTable,
    SchedulePlan,
    pack_table,
    strip_fwd as _strip_fwd,
)
from repro.core.tape import Tape
from repro.models import blocks, model as M
from repro.models.common import ModelConfig, RunConfig

DATA, MODEL, POD = "data", "model", "pod"


# --------------------------------------------------------------------------- #
# W-stash template (traced shapes, deduped GEMM operands)
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class StashTemplate:
    entries: list          # [(pname, dw_spec, x_slot, dy_slot)]
    x_shapes: list         # slot -> (shape, dtype)
    dy_shapes: list
    igrad_names: list      # param names receiving immediate grads in B


def stash_template(cfg, rc, seg, specs, mb_shape, no_defer,
                   cross_ctx: int | None = None) -> StashTemplate:
    """Abstractly trace stage_bwd once to learn the stash structure.

    The template is traced with ep_axis=None (collective-free); EP expert
    GEMMs are in ``no_defer`` so their shapes never enter the stash, and
    the remaining entries are EP-independent.
    """
    cdt = jnp.dtype(rc.compute_dtype)
    rope = _rope_for(cfg, rc, mb_shape[1])
    params = {
        n: jax.ShapeDtypeStruct(sp.shape, jnp.dtype(rc.param_dtype))
        for n, sp in specs.items()
    }
    x = jax.ShapeDtypeStruct((*mb_shape, cfg.d_model), cdt)
    mem = (jax.ShapeDtypeStruct((mb_shape[0], cross_ctx, cfg.d_model), cdt)
           if cross_ctx else None)
    template: dict = {}

    def run(params, x, dy, mem):
        t = Tape(params, mode="bwd", no_defer=frozenset(no_defer))
        xin = t.value(x)
        ctx = blocks.LayerCtx(
            cfg=cfg, rc=rc, rope=rope, causal=seg.causal, ep_axis=None,
            enc_memory=t.value(mem) if mem is not None else None)
        out, aux = M.apply_stage(t, ctx, seg, xin, jnp.int32(0))
        cots, igrads, stash = t.backward(
            {out.idx: dy, aux.idx: jnp.zeros((), jnp.float32)}
        )
        xs, dys, entries = [], [], []
        xid = {}
        for s in stash:
            key = id(s.x)
            if key not in xid:
                xid[key] = len(xs)
                xs.append((s.x.shape, s.x.dtype))
            dyid = len(dys)
            dys.append((s.dy.shape, s.dy.dtype))
            entries.append((s.pname, s.dw_spec, xid[key], dyid))
        template["entries"] = entries
        template["x_shapes"] = xs
        template["dy_shapes"] = dys
        template["igrads"] = sorted(igrads)
        return cots[xin.idx]

    if mem is None:
        jax.eval_shape(lambda p, xx, dd: run(p, xx, dd, None), params, x, x)
    else:
        jax.eval_shape(run, params, x, x, mem)
    return StashTemplate(
        template["entries"], template["x_shapes"], template["dy_shapes"],
        template["igrads"],
    )


# --------------------------------------------------------------------------- #
# Runtime
# --------------------------------------------------------------------------- #


class Runtime:
    """Builds and runs the SPMD train/prefill/decode programs for one
    (ModelConfig, RunConfig) on a ("data","model"[, "pod"]) mesh.

    ``plan`` (optional) injects a pre-selected :class:`SchedulePlan` —
    e.g. the winner of ``schedule="auto"`` — for the trainable segment
    ("main", or "dec" for enc-dec families) instead of regenerating the
    table from ``rc.schedule``.
    """

    def __init__(self, cfg: ModelConfig, rc: RunConfig, mesh,
                 multi_pod: bool = False,
                 plan: SchedulePlan | None = None):
        self.cfg, self.rc, self.mesh = cfg, rc, mesh
        self.geo = M.build_geometry(cfg, rc)
        self.multi_pod = multi_pod
        ax = dict(mesh.shape)
        self.dsize = ax[DATA]
        self.pods = ax.get(POD, 1)
        assert ax[MODEL] == self.geo.model_ranks, (
            f"mesh model axis {ax[MODEL]} != groups*pp "
            f"{self.geo.model_ranks}"
        )
        self.Pe = rc.pp
        self.G = rc.groups
        self.ep = rc.moe_mode == "ep" and cfg.moe is not None
        if cfg.encdec is not None:
            assert self.G == 1, "enc-dec uses a single pipeline group"

        # --- schedule plans per segment ------------------------------------ #
        # Scheduling units only gate ZeroPP-family schedules; other methods
        # keep the whole batch live, so their buffers must be n_mb deep.
        unit = (rc.unit_size if rc.schedule in UNIT_GATED_SCHEDULES
                else rc.microbatches)
        sp = SchedParams(P=rc.pp, V=rc.vpp, n_mb=rc.microbatches,
                         unit=unit)
        pf = rc.gather_prefetch

        def build(name, sp_):
            return SchedulePlan.build(name, sp_, prefetch=pf)
        self.plans: dict[str, SchedulePlan] = {}
        segs = {s.name: s for s in self.geo.segments}
        self.segs = segs
        if cfg.encdec is not None:
            # encoder passes are not unit-gated (fwd-only, then stripped
            # bwd) so their buffers must hold every micro-batch
            enc_sp = dataclasses.replace(sp, V=segs["enc"].vpp,
                                         unit=rc.microbatches)
            dec_sp = dataclasses.replace(sp, V=segs["dec"].vpp)
            self.plans["enc_fwd"] = build("fwd_only", enc_sp)
            self.plans["dec"] = (self._adopt(plan, dec_sp)
                                 if plan is not None else
                                 build(rc.schedule, dec_sp))
            enc_full = generate(rc.schedule, enc_sp)
            self.plans["enc_bwd"] = SchedulePlan.from_table(
                f"strip_fwd[{rc.schedule}]", enc_sp,
                _strip_fwd(enc_full), prefetch=pf)
        else:
            self.plans["main"] = (self._adopt(plan, sp)
                                  if plan is not None else
                                  build(rc.schedule, sp))
        # serving plans (forward-only pipeline; not unit-gated, so the
        # buffers hold every micro-batch)
        sp_full = dataclasses.replace(sp, unit=rc.microbatches)
        if cfg.encdec is not None:
            self.plans["serve_main"] = self.plans["enc_fwd"]
            self.plans["serve_dec"] = build(
                "fwd_only", dataclasses.replace(dec_sp,
                                                unit=rc.microbatches))
        else:
            self.plans["serve_main"] = build("fwd_only", sp_full)

        # --- parameter specs & shardings ---------------------------------- #
        self.stage_specs = {
            s.name: M.stage_specs(cfg, segs[s.name]) for s in
            self.geo.segments
        }
        self.io_specs = M.io_specs(cfg)
        self.pspecs = {
            "io": {n: fsdp.io_pspec(sp_, self.dsize)
                   for n, sp_ in self.io_specs.items()},
            "segments": {
                sname: {n: fsdp.stage_pspec(sp_, self.dsize, self.ep)
                        for n, sp_ in sps.items()}
                for sname, sps in self.stage_specs.items()
            },
        }
        # a one-wide data axis has nothing to gather: stage params stay
        # resident (read in place, no two-slot gathered copy)
        self.gatherable = {
            sname: sorted(
                n for n, sp_ in sps.items()
                if self.dsize > 1
                and fsdp.local_dim(sp_, self.dsize, self.ep) is not None
                and not (sp_.ep and self.ep)
            )
            for sname, sps in self.stage_specs.items()
        }
        if rc.serve_resident:
            # weight-resident serving (beyond-paper, §Perf): non-EP params
            # live fully gathered on each model rank — zero per-step FSDP
            # gathers, at V×stage_params HBM cost.
            for sname, sps in self.stage_specs.items():
                for n in self.gatherable[sname]:
                    self.pspecs["segments"][sname][n] = P(
                        MODEL, *([None] * len(sps[n].shape)))
                self.gatherable[sname] = []
        self.ep_names = {
            sname: sorted(n for n, sp_ in sps.items() if sp_.ep and self.ep)
            for sname, sps in self.stage_specs.items()
        }
        # --- flat-segment coalescing (one collective per tick) ------------- #
        if rc.coalesce not in ("flat", "none"):
            raise ValueError(
                f"unknown coalesce mode {rc.coalesce!r}; pick 'flat' (one "
                "all-gather / reduce-scatter per stage segment per tick) "
                "or 'none' (per-tensor collectives)")
        self.flat_layouts: dict[str, object] = {
            sname: (fsdp.build_flat_layout(
                        self.stage_specs[sname], self.gatherable[sname],
                        self.dsize, self.ep)
                    if rc.coalesce == "flat" else None)
            for sname in self.stage_specs
        }
        # EP expert tensors get their own flat segment: the cross-group /
        # cross-pod gradient reduction then runs as ONE slab collective
        # per stage instead of one per expert tensor (per-tensor fallback
        # when the expert dim does not divide the data axis).
        self.ep_flat_layouts: dict[str, object] = {
            sname: (fsdp.build_flat_layout(
                        self.stage_specs[sname], self.ep_names[sname],
                        self.dsize, self.ep, ep_segment=True)
                    if rc.coalesce == "flat" and self.ep_names[sname]
                    else None)
            for sname in self.stage_specs
        }
        # io params: only the vocab-dim of embed/head shards (per the
        # vocab-shard decision); everything else is replicated — io params
        # are consumed outside the gather machinery.
        from repro.core import vocab as Vb
        vloc = Vb.vocab_shard(cfg.vocab, self.dsize)
        for n, sp_ in self.io_specs.items():
            if n in ("embed.table", "head.w") and vloc is not None:
                dims = [None] * len(sp_.shape)
                dims[sp_.fsdp_dim] = DATA
                self.pspecs["io"][n] = P(*dims)
            elif sp_.ep and self.ep:
                # MTP expert bank: EP-sharded like the stage experts
                self.pspecs["io"][n] = P(DATA,
                                         *([None] * (len(sp_.shape) - 1)))
            else:
                self.pspecs["io"][n] = P(*([None] * len(sp_.shape)))
        self._tmpl_cache: dict = {}

    def _adopt(self, plan: SchedulePlan, sp: SchedParams) -> SchedulePlan:
        """Validate an injected plan against this runtime's geometry and
        re-pack it for this runtime's gather-prefetch depth."""
        pp = plan.params
        if (pp.P, pp.V, pp.n_mb) != (sp.P, sp.V, sp.n_mb):
            raise ValueError(
                f"injected plan {plan.name!r} was built for "
                f"(P={pp.P}, V={pp.V}, B={pp.n_mb}) but this runtime "
                f"needs (P={sp.P}, V={sp.V}, B={sp.n_mb})")
        return plan.with_prefetch(self.rc.gather_prefetch)

    @property
    def tables(self) -> dict[str, PackedTable]:
        """Device-ready packed tables per segment (plan view)."""
        return {k: p.packed for k, p in self.plans.items()}

    def _stash_tmpl(self, seg, mb_shape, no_defer, cross_ctx=None):
        key = (seg.name, tuple(mb_shape), cross_ctx,
               tuple(sorted(no_defer)))
        if key not in self._tmpl_cache:
            self._tmpl_cache[key] = stash_template(
                self.cfg, self.rc, seg, self.stage_specs[seg.name],
                mb_shape, no_defer, cross_ctx=cross_ctx)
        return self._tmpl_cache[key]

    # ------------------------------------------------------------------ #
    def init_params(self, key=None):
        """Host init with the pipeline's duplicated-stage layout, then
        device_put with the runtime shardings."""
        from jax.sharding import NamedSharding

        key = key if key is not None else jax.random.PRNGKey(0)
        base = M.init_all_params(self.cfg, self.rc, key)
        segs = {}
        for seg in self.geo.segments:
            st = base["segments"][seg.name]
            V, Pe, G = seg.vpp, self.Pe, self.G
            order = []
            for mr in range(G * Pe):
                p = mr % Pe
                for v in range(V):
                    order.append(M.storage_index(p, v, V))
            segs[seg.name] = {
                n: jnp.stack([a[i] for i in order]) for n, a in st.items()
            }
        params = {"io": base["io"], "segments": segs}
        out = jax.tree.map(
            lambda a, spec: jax.device_put(
                a, NamedSharding(self.mesh, spec)),
            params,
            {"io": self.pspecs["io"], "segments": self.pspecs["segments"]},
        )
        return out

    def param_shapes(self):
        """ShapeDtypeStructs (for dry-run lowering without allocation)."""
        from jax.sharding import NamedSharding

        dt = jnp.dtype(self.rc.param_dtype)
        segs = {}
        for seg in self.geo.segments:
            V = seg.vpp
            segs[seg.name] = {
                n: jax.ShapeDtypeStruct(
                    (self.G * self.Pe * V, *sp_.shape), dt,
                    sharding=NamedSharding(
                        self.mesh,
                        self.pspecs["segments"][seg.name][n]),
                )
                for n, sp_ in self.stage_specs[seg.name].items()
            }
        io = {
            n: jax.ShapeDtypeStruct(
                sp_.shape, dt,
                sharding=NamedSharding(self.mesh, self.pspecs["io"][n]))
            for n, sp_ in self.io_specs.items()
        }
        return {"io": io, "segments": segs}

    # ------------------------------------------------------------------ #
    def batch_pspec(self):
        return P((POD, DATA)) if self.multi_pod else P(DATA)

    def input_specs(self, shape_cfg, max_seq=None):
        """ShapeDtypeStructs for the step inputs (see launch/dryrun.py)."""
        from jax.sharding import NamedSharding

        cfg, rc = self.cfg, self.rc
        gb, s = shape_cfg.global_batch, shape_cfg.seq_len
        shards = self.pods * self.dsize
        batch_shardable = gb % shards == 0 and gb >= shards
        sh = NamedSharding(
            self.mesh,
            self.batch_pspec() if batch_shardable else P())
        rep = NamedSharding(self.mesh, P())
        if shape_cfg.kind == "train":
            toks = (
                jax.ShapeDtypeStruct((gb, s, cfg.d_model), jnp.bfloat16,
                                     sharding=sh)
                if cfg.frontend == "vision"
                else jax.ShapeDtypeStruct((gb, s), jnp.int32, sharding=sh)
            )
            batch = {"tokens": toks,
                     "labels": jax.ShapeDtypeStruct((gb, s), jnp.int32,
                                                    sharding=sh)}
            if cfg.encdec is not None:
                batch["enc_tokens"] = jax.ShapeDtypeStruct(
                    (gb, cfg.encdec.enc_ctx, cfg.d_model), jnp.bfloat16,
                    sharding=sh)
                batch["tokens"] = jax.ShapeDtypeStruct(
                    (gb, s), jnp.int32, sharding=sh)
            return batch
        if shape_cfg.kind == "prefill":
            toks = (
                jax.ShapeDtypeStruct((gb, s, cfg.d_model), jnp.bfloat16,
                                     sharding=sh)
                if cfg.frontend == "vision"
                else jax.ShapeDtypeStruct((gb, s), jnp.int32, sharding=sh)
            )
            batch = {"tokens": toks}
            if cfg.encdec is not None:
                batch["enc_tokens"] = jax.ShapeDtypeStruct(
                    (gb, cfg.encdec.enc_ctx, cfg.d_model), jnp.bfloat16,
                    sharding=sh)
                batch["tokens"] = jax.ShapeDtypeStruct(
                    (gb, min(s, 448)), jnp.int32, sharding=sh)
            return batch
        # decode: one new token against a cache of length max_seq
        batch = {"tokens": jax.ShapeDtypeStruct((gb, 1), jnp.int32,
                                                sharding=sh),
                 "pos": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
        if cfg.encdec is not None:
            batch["enc_tokens"] = jax.ShapeDtypeStruct(
                (gb, cfg.encdec.enc_ctx, cfg.d_model), jnp.bfloat16,
                sharding=sh)
        return batch


# --------------------------------------------------------------------------- #
# Train step
# --------------------------------------------------------------------------- #


def make_train_step(rt: Runtime, shape_cfg):
    """Returns jit(step)(params, batch) -> (grads, metrics)."""
    cfg, rc = rt.cfg, rt.rc
    from repro.core import vocab as Vb

    seq = shape_cfg.seq_len
    gb = shape_cfg.global_batch
    n_local = gb // (rt.pods * rt.dsize)
    Btot = rc.microbatches
    mbs = max(n_local // (rt.G * Btot), 1)
    assert mbs * rt.G * Btot == n_local, (
        f"global_batch {gb} must split into pods*data*groups*microbatches"
    )
    vloc = Vb.vocab_shard(cfg.vocab, rt.dsize)
    denom = float(gb * seq)  # global token count
    # Reference semantics: loss += w * sum over (stages, micro-batches of
    # per-token-mean aux); each micro-batch contributes aux/B_global.
    aux_seed = (
        cfg.moe.router_aux_weight / (Btot * rt.G * rt.dsize * rt.pods)
        if cfg.moe else 0.0
    )

    mesh = rt.mesh
    batch_spec = rt.batch_pspec()

    def step(params, batch):
        in_specs = (
            {"io": rt.pspecs["io"], "segments": rt.pspecs["segments"]},
            jax.tree.map(lambda _: batch_spec, batch),
        )
        grad_specs = {"io": rt.pspecs["io"],
                      "segments": rt.pspecs["segments"]}
        out_specs = (grad_specs, P())
        fn = jax.shard_map(
            partial(_train_body, rt=rt, shape_cfg=shape_cfg, mbs=mbs,
                    vloc=vloc, denom=denom, aux_seed=aux_seed),
            mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return fn(params, batch)

    return jax.jit(step)


# --------------------------------------------------------------------------- #
# Serving: prefill (s = prompt len) and decode (s = 1) steps
# --------------------------------------------------------------------------- #


def _cache_specs_for(rt: Runtime, seg, b_loc: int, max_seq: int,
                     seq_shard: bool):
    """ShapeDtypeStructs per layer slot (batch = full local batch)."""
    cfg, rc = rt.cfg, rt.rc
    out = []
    for j, kind in enumerate(seg.kinds):
        cs = M.layer_cache_spec(cfg, rc, kind, b_loc, max_seq)
        if seq_shard:
            cs = {
                n: (jax.ShapeDtypeStruct(
                    (s.shape[0], s.shape[1] // rt.dsize) + s.shape[2:],
                    s.dtype)
                    if n in ("k", "v", "ckv") else s)
                for n, s in cs.items()
            }
        out.append(cs)
    return out


def serve_cache_pspecs(rt: Runtime, shape_cfg, paged: bool = False):
    """PartitionSpecs for the serving cache tree.

    Paged caches reuse the batch-shardable layout verbatim: a
    ``[M·V, n_pages, page_size, ...]`` leaf has the same rank as the
    contiguous ``[M·V, gb, max_seq, ...]`` one and shards its page axis
    exactly like the batch axis (each pods×data shard owns a block of
    pages, gathered locally through per-row page tables).
    """
    gb = shape_cfg.global_batch
    batch_shardable = gb % (rt.pods * rt.dsize) == 0 and gb >= (
        rt.pods * rt.dsize)
    seq_shard = not batch_shardable
    if paged and seq_shard:
        raise ValueError(
            "paged KV caches need the batch-shardable cache layout; "
            f"global_batch={gb} fell back to sequence sharding — use a "
            "slot count divisible by the pods×data axes")
    bspec = ((POD, DATA) if rt.multi_pod else DATA) if batch_shardable \
        else None
    tree = {}
    for seg in rt.geo.segments:
        if seg.name == "enc":
            continue
        slots = {}
        for j, kind in enumerate(seg.kinds):
            cs = M.layer_cache_spec(rt.cfg, rt.rc, kind, 1, 1)
            for n, s in cs.items():
                if seq_shard and n in ("k", "v", "ckv"):
                    dims = [MODEL, None, DATA] + [None] * (len(s.shape) - 2)
                else:
                    dims = [MODEL, bspec] + [None] * (len(s.shape) - 1)
                slots[f"L{j}.{n}"] = P(*dims)
                if paged and rt.rc.kv_cache_dtype == "int8" and n in (
                        "k", "v", "ckv"):
                    # per-page(×head) scales: [M·V, n_pages, ...] — the
                    # page axis shards exactly like its pool's.
                    slots[f"L{j}.{n}_scale"] = P(
                        *([MODEL, bspec] + [None] * (len(s.shape) - 3)))
        tree[seg.name] = slots
    if rt.cfg.encdec is not None:
        tree["enc_memory"] = P(bspec)
    return tree, seq_shard, bspec


def init_serve_caches(rt: Runtime, shape_cfg, max_seq=None, abstract=True,
                      *, page_size: int = 0, n_pages: int = 0):
    """Cache tree: {seg: {"L{j}.{name}": [M·V, b_loc, ...]}}.

    With ``page_size > 0`` the attention leaves come out paged —
    ``[M·V, n_pages, page_size, ...]`` — and rows address them through
    the per-request page tables the serve step is handed each tick.
    """
    from jax.sharding import NamedSharding

    cfg, rc = rt.cfg, rt.rc
    gb = shape_cfg.global_batch
    max_seq = max_seq or shape_cfg.seq_len
    pspecs, seq_shard, bspec = serve_cache_pspecs(
        rt, shape_cfg, paged=page_size > 0)
    tree = {}
    for seg in rt.geo.segments:
        if seg.name == "enc":
            continue
        V = seg.vpp
        slots = {}
        for j, kind in enumerate(seg.kinds):
            cs = dict(M.layer_cache_spec(cfg, rc, kind, gb, max_seq))
            for n in list(cs):
                s = cs[n]
                if page_size and n in ("k", "v", "ckv"):
                    cs[n] = jax.ShapeDtypeStruct(
                        (n_pages, page_size) + s.shape[2:], s.dtype)
                    if rc.kv_cache_dtype == "int8":
                        # scales live beside the pool and move with its
                        # pages through reset_pages/copy_pages (any leaf
                        # with the page axis at dim 1 is handled there)
                        cs[n + "_scale"] = jax.ShapeDtypeStruct(
                            (n_pages,) + s.shape[2:-1], jnp.float32)
                elif page_size:
                    raise ValueError(
                        f"paged serving covers attention caches only; "
                        f"layer kind {kind!r} keeps per-slot state "
                        f"({n!r}) that has no page layout — set "
                        "prefix_sharing='off' / page_size=0 for this "
                        "architecture")
            for n, s in cs.items():
                shape = (rt.G * rt.Pe * V,) + s.shape
                sh = NamedSharding(rt.mesh, pspecs[seg.name][f"L{j}.{n}"])
                slots[f"L{j}.{n}"] = (
                    jax.ShapeDtypeStruct(shape, s.dtype, sharding=sh)
                    if abstract else
                    jax.device_put(jnp.zeros(shape, s.dtype), sh))
        tree[seg.name] = slots
    if cfg.encdec is not None:
        shape = (gb, cfg.encdec.enc_ctx, cfg.d_model)
        sh = NamedSharding(rt.mesh, pspecs["enc_memory"])
        tree["enc_memory"] = (
            jax.ShapeDtypeStruct(shape, jnp.dtype(rc.compute_dtype),
                                 sharding=sh)
            if abstract else jax.device_put(
                jnp.zeros(shape, jnp.dtype(rc.compute_dtype)), sh))
    return tree


def reset_slot_caches(caches, slot_mask):
    """Zero the cache rows of the slots flagged in ``slot_mask`` [gb].

    Used by the continuous-batching engine when a slot is reclaimed for a
    new request: attention caches are overwritten position-by-position
    anyway, but recurrent state (mamba/xlstm) and any stale bytes beyond
    the new request's horizon must not leak between requests. Cache leaves
    are [M·V, gb, ...] (batch on axis 1); ``enc_memory`` is [gb, ...].
    """
    out = {}
    for key, sub in caches.items():
        if key == "enc_memory":
            m = slot_mask.reshape((-1,) + (1,) * (sub.ndim - 1))
            out[key] = jnp.where(m, jnp.zeros((), sub.dtype), sub)
        else:
            out[key] = {
                n: jnp.where(
                    slot_mask.reshape((1, -1) + (1,) * (a.ndim - 2)),
                    jnp.zeros((), a.dtype), a)
                for n, a in sub.items()
            }
    return out


def reset_pages(caches, page_mask):
    """Zero the pages flagged in ``page_mask`` [n_pages] of every paged
    leaf ([M·V, n_pages, page_size, ...]; page axis 1).

    The paged analogue of ``reset_slot_caches``: freshly allocated pages
    must read as zeros (the contiguous path zeroes whole slot rows on
    admission, and greedy parity leans on identical gathered bytes) —
    shared prefix pages keep their contents, so the mask carries only a
    request's *fresh* pages.
    """
    return {
        key: {
            n: jnp.where(
                page_mask.reshape((1, -1) + (1,) * (a.ndim - 2)),
                jnp.zeros((), a.dtype), a)
            for n, a in sub.items()
        }
        for key, sub in caches.items()
    }


def copy_pages(caches, src, dst):
    """Copy page ``src[i]`` -> ``dst[i]`` (int32 [w] *global* page ids)
    in every paged leaf.

    Cross-partition prefix reuse: the radix found the pages in another
    pods×data shard's block, so the bytes move on device (XLA lowers the
    axis-1 gather/scatter across the page sharding) instead of being
    recomputed by a prefill. ``dst`` entries must be distinct except as
    exact repeats of the same (src, dst) pair — fixed-width callers pad
    by repeating their first real pair, so duplicate writes carry
    identical values.
    """
    return {
        key: {n: a.at[:, dst].set(a[:, src]) for n, a in sub.items()}
        for key, sub in caches.items()
    }


def serve_tiling(rt: Runtime, gb: int, seq_shard: bool):
    """(b_loc, Btot, mbs): how the serve step tiles a local batch into
    (groups × micro-batches × mbs). Shared by ``make_serve_step`` and
    the slot-count validation — rows beyond G·Btot·mbs would silently
    never be computed, so slotted callers must check exact coverage."""
    shards = rt.pods * rt.dsize if rt.multi_pod else rt.dsize
    b_loc = gb // shards if not seq_shard else gb
    Btot = min(rt.rc.microbatches, b_loc)
    mbs = b_loc // (rt.G * Btot) if b_loc >= rt.G * Btot else 1
    # degenerate tiny batches: one microbatch per group
    if b_loc < rt.G * Btot:
        Btot = max(b_loc // rt.G, 1)
        mbs = 1
    return b_loc, Btot, mbs


def make_serve_step(rt: Runtime, shape_cfg, *, prompt_len: int = 1,
                    max_seq: int | None = None, page_size: int = 0,
                    want_logits: bool = False):
    """Returns jit(step)(params, caches, batch) -> (tokens_out, caches)
    — or (tokens_out, logits, caches) with ``want_logits``.

    prompt_len == 1  → decode step (batch["pos"] gives the position).
    prompt_len > 1   → prefill: runs the prompt through the pipeline,
                       filling caches, and samples the first token.
    page_size > 0    → paged caches: batch carries "page_tables"
                       (int32 [gb, max_seq // page_size] shard-local
                       page ids) and the attention leaves are page
                       pools instead of per-slot rows.
    want_logits      → additionally return the drain rank's full
                       next-token logits [gb, vocab] (float32) so the
                       engine can sample host-side; the in-graph greedy
                       token stream is unchanged.
    """
    cfg, rc = rt.cfg, rt.rc
    from repro.core import vocab as Vb

    gb = shape_cfg.global_batch
    max_seq = max_seq or shape_cfg.seq_len
    pspecs, seq_shard, bspec = serve_cache_pspecs(
        rt, shape_cfg, paged=page_size > 0)
    b_loc, Btot, mbs = serve_tiling(rt, gb, seq_shard)
    vloc = Vb.vocab_shard(cfg.vocab, rt.dsize)
    batch_spec = P(bspec) if bspec else P()
    if want_logits and seq_shard:
        raise NotImplementedError(
            "logits return needs the batch-shardable serve layout")
    if want_logits and rt.multi_pod:
        raise NotImplementedError(
            "logits return is not wired for multi-pod meshes")

    mesh = rt.mesh

    def step(params, caches, batch):
        # scalar pos is replicated; a per-slot [gb] pos vector (and the
        # slot_mask / page_tables that ride with it) shards with the
        # batch rows.
        bsp = {k: (P() if k == "pos" and not getattr(batch[k], "ndim", 0)
                   else batch_spec) for k in batch}
        in_specs = (
            {"io": rt.pspecs["io"], "segments": rt.pspecs["segments"]},
            pspecs if cfg.encdec is not None else {
                k: v for k, v in pspecs.items() if k != "enc_memory"},
            bsp,
        )
        tok_spec = P(bspec) if bspec else P()
        seg_m = rt.segs["dec" if cfg.encdec is not None else "main"]
        track_moe = (rc.moe_stats and cfg.moe is not None
                     and any(k.endswith(":moe") for k in seg_m.kinds))
        moe_spec = ({"load": P(), "dropped": P()},) if track_moe else ()
        if want_logits:
            # vocab-sharded head: every data rank computes its vocab
            # slice for ALL rows -> [gb, vloc] local, vocab axis sharded.
            # replicated head: each rank holds its own rows' full vocab.
            logit_spec = P(None, DATA) if vloc else P(bspec)
            out_specs = (tok_spec, logit_spec, in_specs[1]) + moe_spec
        else:
            out_specs = (tok_spec, in_specs[1]) + moe_spec
        fn = jax.shard_map(
            partial(_serve_body, rt=rt, shape_cfg=shape_cfg, mbs=mbs,
                    Btot=Btot, vloc=vloc, prompt_len=prompt_len,
                    max_seq=max_seq, seq_shard=seq_shard,
                    page_size=page_size, want_logits=want_logits),
            mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return fn(params, caches, batch)

    return jax.jit(step, donate_argnums=(1,))
