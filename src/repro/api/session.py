"""The Session facade: one object for train / serve / dry-run.

Replaces the hand-assembled ritual (`get_arch -> replace(RunConfig) ->
build_geometry -> make_mesh -> Runtime -> ShapeConfig -> make_*_step ->
adamw`) that every entry point used to repeat::

    sess = repro.api.session("llama3.2-1b",
                             overrides=dict(microbatches=4, unit=2))
    params = sess.init_params()
    opt = sess.init_opt_state(params)
    grads, metrics = sess.train_step(params, sess.stream().batch(0))
    params, opt, om = sess.opt_step(params, grads, opt)

Heavy state (mesh, Runtime, jitted steps) is built lazily and cached, so
constructing a Session — and calling ``describe()`` — needs no devices.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import numpy as np

from repro.api.spec import SessionError, SessionSpec
from repro.core.generators import SchedParams
from repro.core.pipeline import (
    Runtime,
    init_serve_caches,
    make_serve_step,
    make_train_step,
    serve_cache_pspecs,
)
from repro.core.plan import (
    UNIT_GATED_SCHEDULES,
    PlanAnalysis,
    SchedulePlan,
    fused_cost_model,
    preset_cost_model,
    select_plan,
)
from repro.data.pipeline import DataConfig, SyntheticStream
from repro.models import model as M
from repro.models.common import ShapeConfig
from repro.optim import adamw

_OPT_FIELDS = {f.name for f in dataclasses.fields(adamw.AdamWConfig)}


def session(arch: str, *, mode: str = "train", shape=None, overrides=None,
            **kw) -> "Session":
    """Build a validated Session. See SessionSpec for every knob.

    ``schedule="auto"`` (or ``overrides=dict(schedule="auto")``) runs the
    §4 plan selection: every registered schedule plus both autogen
    heuristics (full-depth ``autogen`` and unit-gated ``autogen_gated``)
    is simulated under ``cost_preset`` ("a800" | "tpu_v5e") and the
    minimum-makespan plan is what the session executes; pass
    ``mem_budget=<bytes>`` to cap the simulated peak memory (candidates
    over budget lose to any that fits — the real memory/makespan
    trade-off). The winner and every candidate's simulated
    makespan/peak-mem/stash-depth show in ``describe()``.

    ``schedule="auto_profiled"`` (train mode) runs the same screen, then
    compiles and *times* the ``profile_top_k`` best survivors on the
    live mesh (warmup + median-of-3 real steps, wall-clock capped by
    ``profile_budget_s``) and picks the minimum measured us/call. Both
    auto modes read/write the persisted plan cache
    (``~/.cache/repro/plans.json``, ``REPRO_PLAN_CACHE`` overrides), so
    an identical later session skips the search and the measurements.
    """
    spec = SessionSpec(arch=arch, mode=mode, shape=shape,
                       overrides=dict(overrides or {}), **kw)
    return Session(spec)


class Session:
    """A bound (arch × RunConfig × shape × mesh) with cached step fns."""

    def __init__(self, spec: SessionSpec):
        self.spec = spec.validate()
        self.arch_mod, self.cfg, self.rc = spec.resolve_configs()
        try:
            self.geo = M.build_geometry(self.cfg, self.rc)
        except ValueError as e:
            raise SessionError(
                f"invalid geometry for {spec.arch!r}: {e}. Adjust the "
                "pp/vpp/groups overrides.") from e
        self._mesh = spec.mesh
        self._shape_cfg: ShapeConfig | None = (
            spec.shape if isinstance(spec.shape, ShapeConfig)
            else M.SHAPES[spec.shape] if isinstance(spec.shape, str)
            else None)
        self._data: int | None = spec.data
        # hardware topology (spec.topology): owns the axis layout + the
        # device bootstrap when set; the derived layout is cached because
        # multi_pod/pods_size consult it before any mesh exists.
        from repro.runtime.topology import resolve_topology
        self._topology = resolve_topology(spec.topology)
        self._topo_layout: dict | None = None
        if self._topology is not None and self._topology.data is not None:
            self._data = self._topology.data
        self._fault_tolerance = None    # TrainController (attach()es)
        self._rt: Runtime | None = None
        self._steps: dict[Any, Any] = {}
        # baseline for the per-session kernel-dispatch counters: counts
        # are process-wide and trace-time, so describe() reports deltas
        # accumulated since this session was constructed.
        from repro.kernels import ops as _ops
        self._kernel_counter_base = _ops.kernel_counters()
        # schedule="auto": run the §4 plan selection now (device-free —
        # pure table generation + discrete-event simulation), so the rest
        # of the session sees a concrete schedule name + plan.
        # schedule="auto_profiled" additionally compiles and *times* the
        # top-K simulated survivors on the live mesh (needs devices) and
        # lets the measured us/call pick the winner. Both consult the
        # persisted plan cache first — a warm hit skips everything.
        self.plan_selection = None
        self._plan_source = None   # memory-hit | persisted-hit | search |
        #                            search+measured — THIS construction's
        #                            lookup outcome, for describe()
        self._moe_mode_auto = None  # moe_mode="auto" resolution summary
        self._engine_stats = None   # serving EngineStats (engine attaches)
        if self.rc.schedule in ("auto", "auto_profiled"):
            profiled = self.rc.schedule == "auto_profiled"
            if self.rc.moe_mode == "auto":
                self.plan_selection, mode = self._auto_select_moe(profiled)
                self.rc = dataclasses.replace(self.rc, moe_mode=mode)
            else:
                self.plan_selection = self._auto_select(profiled=profiled)
            self.rc = dataclasses.replace(
                self.rc, schedule=self.plan_selection.selected.name)
        elif self.rc.moe_mode == "auto":
            self.rc = dataclasses.replace(
                self.rc, moe_mode=self._resolve_moe_mode_fixed())

    # ------------------------------------------------------------------ #
    # Lazy distribution state
    # ------------------------------------------------------------------ #

    def _topology_layout(self) -> dict:
        """The topology's derived pods×data×model layout (device-free —
        hardware description + cost-preset rules only). Cached: the
        layout is consulted by multi_pod/pods_size before any mesh
        exists and must agree with the mesh eventually built."""
        if self._topo_layout is None:
            from repro.runtime.topology import TopologyError
            try:
                self._topo_layout = self._topology.axis_layout(
                    self.geo.model_ranks, self.spec.cost_preset)
            except TopologyError as e:
                raise SessionError(str(e)) from e
        return self._topo_layout

    @property
    def multi_pod(self) -> bool:
        if self._topology is not None:
            # the layout may *derive* a pod axis (e.g. the a800 rule
            # confining FSDP to the NVLink island), so judge the derived
            # layout, not the topology's nominal pods field
            return self._topology_layout()["pods"] > 1
        return self.spec.pods is not None

    @property
    def pods_size(self) -> int:
        """Width of the hybrid-sharded DP ("pod") axis (1 = no pod
        axis). The topology's derived layout wins over spec.pods."""
        if self._topology is not None:
            return self._topology_layout()["pods"]
        return self.spec.pods or 1

    @property
    def mesh(self):
        if self._mesh is None:
            if self._topology is not None:
                self._topology.ensure_devices()
                lay = self._topology_layout()
                n_dev = jax.device_count()
                if lay["devices_used"] > n_dev:
                    raise SessionError(
                        f"topology {self._topology.label()} lays out "
                        f"pods×data×model = {lay['pods']}×{lay['data']}×"
                        f"{lay['model']} = {lay['devices_used']} devices "
                        f"but the backend provides {n_dev}; shrink the "
                        "topology (data=) or fix the device bootstrap")
                self._mesh = self._topology.build_mesh(
                    self.geo.model_ranks, self.spec.cost_preset)
                self._data = lay["data"]
                return self._mesh
            if self.spec.devices is not None:
                from repro.api.devices import ensure_host_devices
                ensure_host_devices(self.spec.devices)
            # the devices JAX has: one chip, a four-chip host, or the
            # fake CPU devices of a test (production-scale meshes come
            # from a topology preset, as in the dry-run)
            n_dev = jax.device_count()
            model = self.geo.model_ranks
            pods = self.spec.pods or 1
            data = self._data or max(1, n_dev // (pods * model))
            self._data = data
            need = pods * data * model
            if need > n_dev:
                raise SessionError(
                    f"mesh ({'pods×' if pods > 1 else ''}data×model = "
                    f"{need}) exceeds the {n_dev} available devices; "
                    f"call repro.api.ensure_host_devices({need}) "
                    f"before any other JAX use, or shrink data=/pods=")
            if pods > 1:
                self._mesh = jax.make_mesh(
                    (pods, data, model), ("pod", "data", "model"))
            else:
                self._mesh = jax.make_mesh((data, model), ("data", "model"))
        return self._mesh

    @property
    def data_size(self) -> int:
        if self._data is None:
            self._data = dict(self.mesh.shape)["data"]
        return self._data

    @property
    def shape_cfg(self) -> ShapeConfig:
        if self._shape_cfg is None:
            sp = self.spec
            if sp.mode == "serve":
                gb = sp.global_batch or sp.max_slots or 8
                self._shape_cfg = ShapeConfig("serve", sp.max_seq, gb,
                                              "decode")
            else:
                gb = sp.global_batch or (
                    self.pods_size * self.data_size * self.rc.groups
                    * self.rc.microbatches * sp.microbatch_size)
                self._shape_cfg = ShapeConfig(sp.mode, sp.seq_len or 32,
                                              gb, "train")
        return self._shape_cfg

    @property
    def rt(self) -> Runtime:
        """The underlying pipeline Runtime (built on first use). An
        auto-selected plan is injected so the Runtime executes exactly
        the table the selection simulated."""
        if self._rt is None:
            self._rt = Runtime(
                self.cfg, self.rc, self.mesh, multi_pod=self.multi_pod,
                plan=(self.plan_selection.selected
                      if self.plan_selection is not None else None))
        return self._rt

    # ------------------------------------------------------------------ #
    # Schedule-plan selection (schedule="auto")
    # ------------------------------------------------------------------ #

    def _cost_shape(self) -> tuple[int, int, int]:
        """(seq, mbs, dp) for the cost model — device-free: prefers the
        explicit shape/spec values, never forces a mesh build."""
        if self._shape_cfg is not None:
            seq = self._shape_cfg.seq_len
        else:
            seq = self.spec.seq_len or self.spec.max_seq or 32
        if self._data is not None:
            dp = self._data
        elif self.spec.mesh is not None:
            dp = dict(self.spec.mesh.shape).get("data", 1)
        else:
            # data axis not yet known and we must stay device-free: a
            # dp=1 guess would cost every FSDP gather/reduce at zero
            # ((dp-1)/dp = 0) and bias the selection toward
            # collective-heavy schedules, so assume the demo/CI mesh
            # width instead ((dp-1)/dp is within 15% of its asymptote
            # from dp=8 on, so the exact guess barely matters).
            dp = 8
        return seq, self.spec.microbatch_size, dp

    def _coll_counts(self, seg, moe_mode: str | None = None
                     ) -> tuple[int, int]:
        """(per-gather-tick, per-reduce-tick) collective counts for the
        α–β cost model — 1 each under the flat-segment layout, the
        gatherable tensor count under per-tensor collectives. Device-free:
        divisibility is judged against the cost-shape dp guess."""
        if self.rc.serve_resident:
            return 0, 0  # weight-resident: no FSDP collectives at all
        _, _, dp = self._cost_shape()
        mode = moe_mode if moe_mode is not None else self.rc.moe_mode
        ep = mode == "ep" and self.cfg.moe is not None
        specs = M.stage_specs(self.cfg, seg)
        n_gath = n_repl = 0
        for n, sp in specs.items():
            if sp.ep and ep:
                continue  # EP tensors never enter the FSDP collectives
            if sp.shape and sp.shape[sp.fsdp_dim] % dp == 0:
                n_gath += 1
            else:
                n_repl += 1  # replicated: psum'd per tensor on reduce
        if n_gath == 0:
            return 0, n_repl
        if self.rc.coalesce == "flat":
            return 1, 1 + n_repl
        return n_gath, n_gath + n_repl

    def _moe_layers_per_stage(self, seg) -> float:
        """Mean MoE layers per pipeline stage of ``seg`` (0 without MoE)."""
        if self.cfg.moe is None:
            return 0.0
        n_moe = sum(1 for i in range(self.cfg.n_layers)
                    if self.cfg.layer_kind(i).endswith(":moe"))
        n_stages = max(self.geo.seg_stages(seg), 1)
        return n_moe / n_stages

    def _a2a_workload(self, seg, moe_mode: str | None = None
                      ) -> tuple[int, int, float]:
        """(n_a2a_f, n_a2a_b, a2a_bytes) of one stage tick under EP.

        dispatch + combine per MoE layer in F; B re-runs the forward
        pair under remat and pays the backward pair, so 4 (2 without
        remat). Bytes = one event's wire traffic: the [E, capacity, d]
        dispatch buffer's off-rank fraction (dp-1)/dp at the compute
        dtype. All zeros unless EP MoE is active — gathered MoE moves
        tokens locally and pays the FSDP gathers instead."""
        mode = moe_mode if moe_mode is not None else self.rc.moe_mode
        mo = self.cfg.moe
        if mo is None or mode != "ep":
            return 0, 0, 0.0
        from repro.models.blocks import _capacity

        seq, mbs, dp = self._cost_shape()
        m = self._moe_layers_per_stage(seg)
        if m <= 0:
            return 0, 0, 0.0
        cap = _capacity(seq * mbs, mo)
        dtype_bytes = 2 if "16" in self.rc.compute_dtype else 4
        a2a_bytes = (mo.n_experts * cap * self.cfg.d_model * dtype_bytes
                     * (dp - 1) / max(dp, 1))
        n_f = max(1, round(2 * m))
        n_b = max(1, round((4 if self.rc.remat else 2) * m))
        return n_f, n_b, a2a_bytes

    def _moe_gather_bytes(self, seg, moe_mode: str | None = None) -> float:
        """Extra per-tick FSDP gather/reduce bytes the *gathered* MoE
        mode pays for expert tensors (EP never gathers them)."""
        mode = moe_mode if moe_mode is not None else self.rc.moe_mode
        mo = self.cfg.moe
        if mo is None or mode == "ep":
            return 0.0
        m = self._moe_layers_per_stage(seg)
        dtype_bytes = 2 if "16" in self.rc.param_dtype else 4
        return 3 * mo.n_experts * self.cfg.d_model * mo.d_ff_expert \
            * m * dtype_bytes

    def _cost_model(self, vpp: int, moe_mode: str | None = None):
        seq, mbs, dp = self._cost_shape()
        seg = self.geo.segments[-1]
        n_g, n_r = self._coll_counts(seg, moe_mode)
        n_a2a_f, n_a2a_b, a2a_bytes = self._a2a_workload(seg, moe_mode)
        return preset_cost_model(
            self.spec.cost_preset, self.cfg, P=self.rc.pp, V=vpp,
            seq=seq, mbs=mbs, dp=dp,
            n_coll_gather=n_g, n_coll_reduce=n_r,
            n_a2a_f=n_a2a_f, n_a2a_b=n_a2a_b, a2a_bytes=a2a_bytes,
            extra_stage_param_bytes=self._moe_gather_bytes(seg, moe_mode))

    def _auto_select(self, profiled: bool = False,
                     moe_mode: str | None = None):
        """Simulate every registered schedule (+ the §4 autogen heuristic)
        for this (arch × shape × mesh) and pick the minimum-makespan plan
        — or, ``profiled``, the minimum *measured* us/call among the
        top-K simulated survivors. Selections are cached process-wide on
        the key below AND persisted on disk (``core/plan_cache.py``), so
        an identical later session — this process or the next — pays
        zero simulate and zero measure calls."""
        from repro.core.plan import plan_cache_info

        rc = self.rc
        seg = self.geo.segments[-1]
        seq, mbs, dp = self._cost_shape()
        preset = self.spec.cost_preset
        mode = moe_mode if moe_mode is not None else rc.moe_mode
        # component order mirrors plan.SELECT_KEY_SCHEMA (part of the
        # persisted-cache fingerprint)
        cache_key = (
            self.cfg.name, rc.pp, seg.vpp, rc.groups, rc.microbatches,
            rc.unit_size, rc.gather_prefetch, seq, mbs, dp,
            self.spec.pods or 1, preset, rc.coalesce, rc.grad_compress,
            mode, self.spec.mem_budget, rc.schedule,
            self.spec.profile_top_k if profiled else None,
        )
        self._plan_key = cache_key
        before = plan_cache_info()
        sel = select_plan(
            rc.pp, seg.vpp, rc.microbatches, rc.unit_size,
            self._cost_model(seg.vpp, mode), preset=preset,
            prefetch=rc.gather_prefetch, cache_key=cache_key,
            mem_budget=self.spec.mem_budget,
            measure_fn=self._build_measure_fn(mode) if profiled else None,
            top_k=self.spec.profile_top_k,
            profile_budget_s=self.spec.profile_budget_s,
            persist=True)
        after = plan_cache_info()
        if after["hits"].get(cache_key, 0) > \
                before["hits"].get(cache_key, 0):
            self._plan_source = "memory-hit"
        elif after["disk_hits"].get(cache_key, 0) > \
                before["disk_hits"].get(cache_key, 0):
            self._plan_source = "persisted-hit"
        else:
            self._plan_source = sel.provenance
        return sel

    def _auto_select_moe(self, profiled: bool = False):
        """``moe_mode="auto"`` × ``schedule="auto"``: run the §4 plan
        selection once per MoE mode (each with its own mode-bearing
        cache key and a2a/gather cost model) and let the better selected
        makespan — measured us/call when profiled — pick the mode.
        Returns ``(selection, mode)`` with the loser's candidates merged
        in under ``"<mode>:<schedule>"`` keys so describe()/launch can
        rank EP vs gathered rows side by side."""
        if self.cfg.moe is None:
            return self._auto_select(profiled, "gathered"), "gathered"
        sels: dict[str, Any] = {}
        keys: dict[str, Any] = {}
        for mode in ("gathered", "ep"):
            sels[mode] = self._auto_select(profiled, mode)
            keys[mode] = self._plan_key

        def _score(sel):
            if sel.measured:
                return min(sel.measured.values())
            return sel.analysis.makespan

        mode = min(sels, key=lambda m: _score(sels[m]))
        self._plan_key = keys[mode]
        self._moe_mode_auto = {
            "resolved": mode,
            "scores": {m: _score(s) for m, s in sels.items()},
            "selected": {m: s.selected.name for m, s in sels.items()},
        }
        merged = dataclasses.replace(
            sels[mode],
            candidates={f"{m}:{n}": a
                        for m in ("gathered", "ep")
                        for n, a in sels[m].candidates.items()})
        return merged, mode

    def _resolve_moe_mode_fixed(self) -> str:
        """``moe_mode="auto"`` under a *fixed* schedule: analyze that one
        schedule's table under each mode's cost model (EP pays costed
        a2a ticks, gathered pays the expert tensors' FSDP collective
        bytes) and keep the smaller simulated makespan."""
        if self.cfg.moe is None:
            return "gathered"
        rc = self.rc
        seg = self.geo.segments[-1]
        unit = (rc.unit_size if rc.schedule in UNIT_GATED_SCHEDULES
                else rc.microbatches)
        plan = SchedulePlan.build(
            rc.schedule,
            SchedParams(P=rc.pp, V=seg.vpp, n_mb=rc.microbatches,
                        unit=unit),
            prefetch=rc.gather_prefetch)
        scores = {}
        for mode in ("gathered", "ep"):
            cm = self._cost_model(seg.vpp, mode)
            ana = plan.analyze(cm if plan.has_w else fused_cost_model(cm),
                               preset=self.spec.cost_preset)
            scores[mode] = ana.makespan
        mode = min(scores, key=scores.get)
        self._moe_mode_auto = {"resolved": mode, "scores": scores,
                               "selected": {m: rc.schedule for m in scores}}
        return mode

    def _build_measure_fn(self, moe_mode: str | None = None):
        """The auto_profiled fine pass: ``measure_fn(plan) -> us/call``.

        Each candidate gets its own Runtime (same mesh, same params —
        parameter layout does not depend on the schedule) with the plan
        injected, its train step jitted, and one warmup + median-of-3
        timed steps through ``repro.timing``. Only *called* on a cache
        miss, so warm sessions never compile a step during selection.
        """
        from repro.timing import measure_us

        state: dict[str, Any] = {}

        def _measure(plan: SchedulePlan) -> float:
            rc = dataclasses.replace(
                self.rc, schedule=plan.name,
                **({"moe_mode": moe_mode} if moe_mode else {}))
            rt = Runtime(self.cfg, rc, self.mesh,
                         multi_pod=self.multi_pod, plan=plan)
            step = make_train_step(rt, self.shape_cfg)
            if "params" not in state:
                state["params"] = rt.init_params(jax.random.PRNGKey(0))
                state["batch"] = self.stream(seed=0).batch(0)
            return measure_us(
                lambda: step(state["params"], state["batch"]),
                warmup=1, iters=3)

        return _measure

    # ------------------------------------------------------------------ #
    # Parameters / optimizer
    # ------------------------------------------------------------------ #

    def init_params(self, key=None):
        return self.rt.init_params(key)

    def param_shapes(self):
        return self.rt.param_shapes()

    def input_specs(self, max_seq=None):
        return self.rt.input_specs(self.shape_cfg, max_seq=max_seq)

    def opt_config(self):
        """(AdamWConfig, use_lr_schedule, warmup, total) from spec.optim."""
        kw = dict(self.spec.optim)
        use_sched = "warmup" in kw or "total" in kw
        warmup = kw.pop("warmup", 100)
        total = kw.pop("total", 10_000)
        bad = sorted(set(kw) - _OPT_FIELDS)
        if bad:
            raise SessionError(
                f"unknown optim option(s) {bad}; valid: warmup, total, "
                f"{', '.join(sorted(_OPT_FIELDS))}")
        kw.setdefault("moment_dtype", self.rc.opt_moment_dtype)
        return adamw.AdamWConfig(**kw), use_sched, warmup, total

    def init_opt_state(self, params):
        return adamw.init_state(params, self.opt_config()[0])

    def opt_step_fn(self):
        if "opt" not in self._steps:
            opt_cfg, use_sched, warmup, total = self.opt_config()

            # params and opt state are consumed and replaced every step:
            # donate both so the updated trees reuse their buffers (no
            # transient 2× params + 2× moments residency). Callers follow
            # the rebind pattern (``params, opt, om = sess.opt_step(...)``).
            @partial(jax.jit, donate_argnums=(0, 2))
            def _opt(params, grads, opt_state):
                scale = adamw.lr_schedule(
                    opt_state["step"], base_lr=1.0, warmup=warmup,
                    total=total) if use_sched else 1.0
                return adamw.apply_updates(params, grads, opt_state,
                                           opt_cfg, scale)

            self._steps["opt"] = _opt
        return self._steps["opt"]

    def opt_step(self, params, grads, opt_state):
        """One AdamW update; returns (params, opt_state, metrics)."""
        return self.opt_step_fn()(params, grads, opt_state)

    # ------------------------------------------------------------------ #
    # Training
    # ------------------------------------------------------------------ #

    def train_step_fn(self):
        if "train" not in self._steps:
            if self.shape_cfg.kind != "train":
                raise SessionError(
                    f"train_step needs a 'train' shape; this session is "
                    f"{self.shape_cfg.kind!r} ({self.shape_cfg.name})")
            self._steps["train"] = make_train_step(self.rt, self.shape_cfg)
        return self._steps["train"]

    def train_step(self, params, batch):
        """One pipeline step; returns (grads, metrics)."""
        return self.train_step_fn()(params, batch)

    # ------------------------------------------------------------------ #
    # Serving
    # ------------------------------------------------------------------ #

    def _max_seq(self) -> int:
        return self.spec.max_seq or self.shape_cfg.seq_len

    # ---- paged-KV geometry ------------------------------------------- #

    @property
    def paged(self) -> bool:
        """True when the serve caches are paged (spec.page_size set)."""
        return self.spec.page_size is not None

    @property
    def page_size(self) -> int:
        return self.spec.page_size or 0

    @property
    def pages_per_slot(self) -> int:
        """Worst-case pages one request can span (max_seq / page_size)."""
        return self._max_seq() // self.spec.page_size

    @property
    def n_pages(self) -> int:
        """Total page count (spec.max_pages, else the contiguous-cache
        footprint max_slots × max_seq/page_size — same bytes, but pages
        only *fill* with tokens actually written)."""
        if self.spec.max_pages is not None:
            return self.spec.max_pages
        return self.max_slots * self.pages_per_slot

    def serve_step_fn(self, prompt_len: int, want_logits: bool = False):
        key = ("serve", prompt_len, want_logits)
        if key not in self._steps:
            self._steps[key] = make_serve_step(
                self.rt, self.shape_cfg, prompt_len=prompt_len,
                max_seq=self._max_seq(), page_size=self.page_size,
                want_logits=want_logits)
        return self._steps[key]

    def init_caches(self, abstract: bool = False):
        if self.paged and self.cfg.encdec is not None:
            raise SessionError(
                "paged KV serving does not cover encoder-decoder "
                "sessions (enc_memory has no page layout) — drop "
                "page_size")
        return init_serve_caches(self.rt, self.shape_cfg,
                                 max_seq=self._max_seq(),
                                 abstract=abstract,
                                 page_size=self.page_size,
                                 n_pages=self.n_pages if self.paged
                                 else 0)

    def serve_prefill(self, params, caches, batch):
        """Run the prompt through the pipeline; returns (tokens, caches)."""
        if self.paged:
            raise SessionError(
                "paged sessions serve through the slotted path "
                "(serve_step_batched / serve_engine); the scalar-pos "
                "serve_prefill has no page tables")
        prompt = batch["tokens"].shape[1]
        return self.serve_step_fn(prompt)(params, caches, batch)

    def serve_decode(self, params, caches, batch):
        """One cached decode step; returns (tokens, caches)."""
        if self.paged:
            raise SessionError(
                "paged sessions serve through the slotted path "
                "(serve_step_batched / serve_engine); the scalar-pos "
                "serve_decode has no page tables")
        return self.serve_step_fn(1)(params, caches, batch)

    # ---- slot-aware (continuous-batching) serving -------------------- #

    @property
    def max_slots(self) -> int:
        """Serving slot count == the serve-mode global batch."""
        return self.shape_cfg.global_batch

    def serve_step_batched(self, params, caches, batch,
                           want_logits: bool = False):
        """One slot-aware step (prefill chunk s>=1 or decode s==1).

        Unlike :meth:`serve_prefill`/:meth:`serve_decode`, ``batch`` is
        per-slot: ``pos`` is an int32 ``[max_slots]`` vector (each slot's
        first absolute position) and the optional ``slot_mask`` bool
        ``[max_slots]`` gates cache writes so a prefill into one slot
        cannot clobber a neighbouring in-flight request. Paged sessions
        additionally carry ``page_tables`` (int32
        ``[max_slots, pages_per_slot]`` shard-local page ids). Returns
        ``(tokens[max_slots], caches)`` — or, with ``want_logits``,
        ``(tokens, logits[max_slots, vocab], caches)`` for the host-side
        sampling layer. Rows outside ``slot_mask`` carry garbage samples
        the caller ignores. With ``RunConfig.moe_stats`` on an MoE
        segment, one extra trailing ``{"load", "dropped"}`` dict is
        appended (per-layer-row expert-load histogram + capacity drops).
        """
        pos = batch.get("pos")
        if getattr(pos, "ndim", 0) != 1:
            raise SessionError(
                "serve_step_batched needs batch['pos'] as a per-slot "
                f"[{self.max_slots}] int32 vector (got "
                f"{getattr(pos, 'shape', None)}); use serve_prefill/"
                "serve_decode for the scalar-pos path")
        if self.paged and batch.get("page_tables") is None:
            raise SessionError(
                "paged sessions need batch['page_tables'] (int32 "
                f"[{self.max_slots}, {self.pages_per_slot}] shard-local "
                "page ids; see PagedSlotPool.page_table_matrix)")
        self.check_slot_sharding()
        s = batch["tokens"].shape[1]
        return self.serve_step_fn(s, want_logits)(params, caches, batch)

    def check_slot_sharding(self) -> None:
        """The slotted (per-slot pos) path needs a batch-sharded cache
        AND a micro-batch tiling that covers every slot row — rows
        beyond the tiling would silently never be computed. The
        spec-level check only fires when ``data=`` is explicit, so
        re-check against the materialized mesh (covers derived axes).
        Session-invariant, so the result is cached."""
        if self._steps.get("slot_sharding_ok"):
            return
        from repro.core.pipeline import serve_tiling

        shards = self.pods_size * self.data_size
        if self.max_slots % shards != 0:
            raise SessionError(
                f"max_slots ({self.max_slots}) must divide evenly over "
                f"the pods×data axes ({shards}) for the slotted serve "
                "path — round max_slots up or shrink data=/pods=")
        b_loc, Btot, mbs = serve_tiling(self.rt, self.max_slots,
                                        seq_shard=False)
        covered = self.rt.G * Btot * mbs
        if covered != b_loc:
            raise SessionError(
                f"max_slots ({self.max_slots}) gives {b_loc} slot rows "
                f"per data shard, but the serve step tiles them as "
                f"groups×microbatches×mbs = {self.rt.G}×{Btot}×{mbs}, "
                f"covering only {covered} — pick max_slots so "
                f"slots/(pods·data) is a multiple of "
                f"groups·min(microbatches, slots/(pods·data)), or "
                "adjust the microbatches override")
        self._steps["slot_sharding_ok"] = True

    def reset_slot_caches(self, caches, slot_mask):
        """Zero the cache rows of the slots flagged in ``slot_mask``
        (slot reclaim: recurrent state and stale bytes must not leak
        into the next request)."""
        if "slot_reset" not in self._steps:
            from repro.core.pipeline import reset_slot_caches
            self._steps["slot_reset"] = jax.jit(reset_slot_caches,
                                                donate_argnums=(0,))
        return self._steps["slot_reset"](caches, slot_mask)

    def reset_pages(self, caches, page_mask):
        """Zero the pages flagged in ``page_mask`` [n_pages] (paged
        analogue of :meth:`reset_slot_caches`: a request's *fresh* pages
        must read as zeros; shared prefix pages keep their contents)."""
        if "page_reset" not in self._steps:
            from repro.core.pipeline import reset_pages
            self._steps["page_reset"] = jax.jit(reset_pages,
                                                donate_argnums=(0,))
        return self._steps["page_reset"](caches, page_mask)

    def copy_pages(self, caches, src, dst):
        """Copy page ``src[i]`` -> ``dst[i]`` (int32 [w] global ids) in
        every paged leaf — cross-partition prefix reuse. Callers keep
        ``w`` fixed (pad by repeating the first pair) so this compiles
        once."""
        if "page_copy" not in self._steps:
            from repro.core.pipeline import copy_pages
            self._steps["page_copy"] = jax.jit(copy_pages,
                                               donate_argnums=(0,))
        return self._steps["page_copy"](caches, src, dst)

    def sampling_unsupported_reason(self) -> str | None:
        """None when the serve step can return full next-token logits
        (the host-side sampling layer's input); otherwise why it cannot.
        The engine checks this once and rejects ``temperature > 0``
        submissions up front, so the ``make_serve_step`` layout guards
        never fire mid-tick against an already-admitted request."""
        if self.rt.multi_pod:
            reason = "logits return is not wired for multi-pod meshes"
            if self._topology is not None:
                reason += f" (topology: {self._topology.label()})"
            elif self.spec.pods:
                reason += f" (pods={self.spec.pods})"
            return reason
        _, seq_shard, _ = serve_cache_pspecs(self.rt, self.shape_cfg)
        if seq_shard:
            return ("the sequence-sharded serve layout cannot return "
                    "per-slot logits (needs a slot count divisible by "
                    "the pods×data axes)")
        return None

    def serve_engine(self, params, **kw):
        """A continuous-batching :class:`repro.serving.ServeEngine` over
        this session (serve mode only)."""
        from repro.serving import ServeEngine
        return ServeEngine(self, params, **kw)

    # ------------------------------------------------------------------ #
    # Data / checkpointing / dry-run
    # ------------------------------------------------------------------ #

    def stream(self, seed: int = 0) -> SyntheticStream:
        cfg, sc = self.cfg, self.shape_cfg
        return SyntheticStream(DataConfig(
            seq_len=sc.seq_len, global_batch=sc.global_batch,
            vocab=cfg.vocab, seed=seed,
            kind=("enc_dec" if cfg.encdec else
                  "vision" if cfg.frontend == "vision" else "lm"),
            d_model=cfg.d_model,
            enc_ctx=cfg.encdec.enc_ctx if cfg.encdec else 0))

    def checkpointing(self, ckpt_dir: str, *, every: int = 50, **kw):
        """A fault-tolerance TrainController over this checkpoint dir."""
        from repro.runtime.fault_tolerance import (
            FaultToleranceConfig,
            TrainController,
        )
        return TrainController(ckpt_dir,
                               FaultToleranceConfig(ckpt_every=every, **kw))

    def restore_params(self, ckpt_dir: str, *, step: int | None = None):
        """Boot this session's params from a train checkpoint
        (train→serve handoff).

        Accepts checkpoints whose tree either *is* the params tree
        (``{"io": ..., "segments": ...}``) or nests it under a ``params``
        key (the fault-tolerance controller's usual state layout). The
        restored arrays are re-laid-out onto THIS session's mesh and
        shardings — a serve session may use a different data axis, dtype
        or schedule than the trainer that wrote the checkpoint; only the
        pipeline geometry (pp × vpp × groups stacking) must match, and a
        mismatch raises with the offending leaf.
        """
        from repro.ckpt.checkpoint import CheckpointManager

        mgr = CheckpointManager(ckpt_dir)
        tree, manifest = mgr.restore(step)
        if tree is None:
            raise SessionError(
                f"no checkpoint found under {ckpt_dir!r} "
                f"(steps: {mgr.list_steps()})")
        return self.adopt_params(tree)

    def adopt_params(self, tree):
        """Re-lay-out a host-side (or foreign-mesh) params tree onto THIS
        session's mesh and shardings — the relayout half of
        :meth:`restore_params`, also the elastic path: a reshard/restart
        pulls the old session's params to host and adopts them here.
        Accepts the params tree directly or nested under ``"params"``;
        leaf shapes must match (geometry mismatch raises with the leaf).
        """
        if "params" in tree and "io" not in tree:
            tree = tree["params"]
        if not ("io" in tree and "segments" in tree):
            raise SessionError(
                f"params tree has keys {sorted(tree)}; expected 'io' and "
                "'segments' (or a tree nested under 'params')")
        shapes = self.param_shapes()
        flat_want = dict(jax.tree_util.tree_flatten_with_path(shapes)[0])
        flat_got = dict(jax.tree_util.tree_flatten_with_path(
            {"io": tree["io"], "segments": tree["segments"]})[0])
        missing = sorted(set(map(jax.tree_util.keystr, flat_want))
                         - set(map(jax.tree_util.keystr, flat_got)))
        if missing:
            raise SessionError(
                f"checkpoint is missing param leaves {missing[:5]}"
                f"{'...' if len(missing) > 5 else ''} — was it written by "
                "a different architecture?")
        out_flat = {}
        for kp, want in flat_want.items():
            got = flat_got[kp]
            if tuple(got.shape) != tuple(want.shape):
                raise SessionError(
                    f"param {jax.tree_util.keystr(kp)} has shape "
                    f"{tuple(got.shape)} in the checkpoint but this "
                    f"session needs {tuple(want.shape)} — the pipeline "
                    "geometry (pp/vpp/groups) must match the trainer's")
            # host -> sharded directly; never commit a full leaf to one
            # device (large train checkpoints exceed a single device)
            out_flat[kp] = jax.device_put(
                np.asarray(got, want.dtype), want.sharding)
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(shapes), [
                out_flat[kp] for kp, _ in
                jax.tree_util.tree_flatten_with_path(shapes)[0]])

    def with_topology(self, topology) -> "Session":
        """A fresh Session of this spec bound to ``topology`` — the
        elastic rebuild (train restart on a shrunk mesh, serve reshard).
        The explicit axis knobs reset: the new topology owns the layout.
        Heavy state (mesh, Runtime, jitted steps) is rebuilt lazily; use
        :meth:`adopt_params` to carry params across."""
        spec = dataclasses.replace(
            self.spec, topology=topology, data=None, pods=None,
            devices=None, mesh=None)
        return Session(spec)

    def lower(self):
        """Lower the step for this shape (dry-run: inspect, then compile)."""
        rt, sc = self.rt, self.shape_cfg
        params = rt.param_shapes()
        batch = rt.input_specs(sc)
        if sc.kind == "train":
            return self.train_step_fn().lower(params, batch)
        prompt = 1 if sc.kind == "decode" else (
            min(sc.seq_len, 448) if self.cfg.encdec else sc.seq_len)
        caches = self.init_caches(abstract=True)
        return self.serve_step_fn(prompt).lower(params, caches, batch)

    # ------------------------------------------------------------------ #
    # Introspection
    # ------------------------------------------------------------------ #

    def describe(self) -> dict:
        """Geometry, schedule-plan and simulated-cost summary.

        Device-free: the schedule numbers come from the discrete-event
        simulator (``core/simulator.py``) under the session's hardware
        cost preset — bubble ratio and gathers/rank are the *timed*
        quantities, not static tick counts. For ``schedule="auto"``
        sessions the dict describes the *selected* plan and lists every
        candidate's simulated makespan under ``schedule.auto``.
        """
        cfg, rc, geo = self.cfg, self.rc, self.geo
        seg = geo.segments[-1]  # "main", or "dec" for enc-dec families
        unit = (rc.unit_size if rc.schedule in UNIT_GATED_SCHEDULES
                else rc.microbatches)
        if self.plan_selection is not None:
            plan = self.plan_selection.selected
            ana = self.plan_selection.analysis
        else:
            plan = SchedulePlan.build(
                rc.schedule,
                SchedParams(P=rc.pp, V=seg.vpp, n_mb=rc.microbatches,
                            unit=unit),
                prefetch=rc.gather_prefetch)
            cm = self._cost_model(seg.vpp)
            ana = plan.analyze(cm if plan.has_w else fused_cost_model(cm),
                               preset=self.spec.cost_preset)
        n_params = sum(int(np.prod(s.shape))
                       for s in M.io_specs(cfg).values())
        for sg in geo.segments:
            n_params += geo.seg_stages(sg) * sum(
                int(np.prod(s.shape))
                for s in M.stage_specs(cfg, sg).values())
        from repro.core.plan import COLLECTIVE_ALPHA_BETA
        alpha, beta = COLLECTIVE_ALPHA_BETA[self.spec.cost_preset]
        a2a_alpha, a2a_beta = COLLECTIVE_ALPHA_BETA.get(
            f"{self.spec.cost_preset}:a2a", (2 * alpha, beta))
        n_g, n_r = self._coll_counts(seg)
        n_a2a_f, n_a2a_b, a2a_bytes = self._a2a_workload(seg)
        sched: dict[str, Any] = {
            "name": rc.schedule,
            "microbatches": rc.microbatches,
            "unit": unit,
            "ticks": plan.table.T,
            "preset": ana.preset,
            "makespan": ana.makespan,
            "bubble_ratio": ana.bubble_frac,
            "peak_mem": ana.peak_mem,
            "gathers_per_rank": ana.gathers_per_rank,
            "reduces": ana.n_reduce,
            "comm_frac": ana.comm_frac,
            "prefetch": rc.gather_prefetch,
            # unit-gated executor buffers: the stash depth this plan's
            # tables actually claim (U for zeropp/autogen_gated, n_mb
            # for full-depth schedules).
            "stash_depth": plan.table.unit,
            # reduce-scatter overlap accounting: exposed = critical-path
            # reduce time; saved = the worst rank's reduce time hidden
            # under the next unit's B/W compute.
            "rs_overlap": {
                "exposed_s": ana.rs_exposed,
                "saved_s": ana.rs_overlap_saved,
            },
            # α–β collective profile: per-tick counts under the session's
            # coalesce mode, with the calibrated preset constants.
            "collectives": {
                "coalesce": rc.coalesce,
                "per_gather_tick": n_g,
                "per_reduce_tick": n_r,
                "alpha_s": alpha,
                "beta_s_per_byte": beta,
                # EP MoE all-to-all profile: events per F/B tick (0 in
                # gathered mode), one event's wire bytes, the a2a α–β
                # constants, and the plan's simulated a2a totals.
                "moe_mode": rc.moe_mode,
                "a2a_per_f_tick": n_a2a_f,
                "a2a_per_b_tick": n_a2a_b,
                "a2a_bytes": a2a_bytes,
                "a2a_alpha_s": a2a_alpha,
                "a2a_beta_s_per_byte": a2a_beta,
                "a2a_t_event_s": ana.t_a2a,
                "a2a_total_s": ana.a2a_total,
            },
        }
        if self._moe_mode_auto is not None:
            sched["moe_mode_auto"] = dict(self._moe_mode_auto)
        if self.plan_selection is not None:
            sel = self.plan_selection

            def _cand(a):
                if not isinstance(a, PlanAnalysis):
                    return str(a)
                d = {"makespan": a.makespan,
                     "peak_mem": a.peak_mem,
                     "stash_depth": a.stash_depth,
                     "rs_overlap_saved": a.rs_overlap_saved}
                # measured us/call rides along only for the profiled
                # survivors — simulated-only candidates keep the
                # established 4-key shape.
                if a.measured_us is not None:
                    d["measured_us"] = a.measured_us
                # EP candidates carry their simulated a2a share (0-cost
                # candidates — gathered/dense — keep the base shape)
                if a.a2a_total > 0:
                    d["a2a_total"] = a.a2a_total
                return d

            sched["auto"] = {
                "selected": sel.selected.name,
                "mem_budget": sel.mem_budget,
                # hit/miss/refine provenance: how the *selection object*
                # came to be (search | search+measured | cache:disk) and
                # what THIS construction's lookup did (memory-hit |
                # persisted-hit | a fresh search).
                "provenance": {"selection": sel.provenance,
                               "this_session": self._plan_source},
                # per-candidate memory/makespan trade-off: stash depth,
                # simulated peak memory and reduce-overlap savings ride
                # along with the makespan each candidate was ranked on.
                "candidates": {n: _cand(a)
                               for n, a in sel.candidates.items()},
            }
            if sel.measured:
                sched["auto"]["measured"] = dict(sel.measured)
            if sel.profile:
                sched["auto"]["profile"] = dict(sel.profile)
            # persisted + in-memory plan-cache state (per-key hit counts,
            # simulate/measure work counters) for this session's key
            from repro.core.plan import plan_cache_info
            info = plan_cache_info()
            key = getattr(self, "_plan_key", None)
            sched["cache"] = {
                "key": repr(key),
                "hits": info["hits"].get(key, 0),
                "disk_hits": info["disk_hits"].get(key, 0),
                "misses": info["misses"],
                "simulate_calls": info["simulate_calls"],
                "measure_calls": info["measure_calls"],
                "entries": info["entries"],
                "persisted": info["persisted"],
            }
        out = {
            "arch": cfg.name,
            "mode": self.spec.mode,
            # jit buffer-donation audit: which step inputs alias their
            # outputs (no spurious full-size copies). The train step's
            # carry lives inside its scan; params are reused by opt_step
            # and must NOT be donated there.
            "donation": {
                "opt_step": ["params", "opt_state"],
                "serve_step": ["caches"],
                "reset_slot_caches": ["caches"],
                "reset_pages": ["caches"],
                "copy_pages": ["caches"],
                "train_step": [],
            },
            "geometry": {
                "pp": rc.pp, "vpp": seg.vpp, "groups": rc.groups,
                "model_ranks": geo.model_ranks,
                "segments": [
                    {"name": sg.name, "layers": sg.n_layers,
                     "stages": geo.seg_stages(sg), "k": sg.k}
                    for sg in geo.segments],
            },
            "schedule": sched,
            "kernels": self._kernel_report(),
            "n_params": n_params,
            "topology": self._topology_report(),
        }
        if self._engine_stats is not None:
            out["serving"] = self._serving_report()
        if self._fault_tolerance is not None:
            out["fault_tolerance"] = self._fault_tolerance.summary()
        return out

    def _topology_report(self) -> dict:
        """``describe()["topology"]`` — the resolved hardware + axis
        layout. Device-free: the topology path derives the layout from
        the hardware description; the legacy-knob path reports what the
        spec pinned (data may be None until a mesh materializes)."""
        if self._topology is not None:
            return self._topology.describe(self.geo.model_ranks,
                                           self.spec.cost_preset)
        return {
            "kind": None,
            "name": None,
            "layout": {"pods": self.spec.pods or 1,
                       "data": self._data,
                       "model": self.geo.model_ranks},
        }

    def _serving_report(self) -> dict:
        """Engine-side counters for ``describe()["serving"]`` — present
        once a :meth:`serve_engine` has attached its stats: throughput
        counters plus the capacity-admission (deferral / projected
        hot-expert overflow) and dispatch-observability (per-layer
        expert-load histogram, dropped-token) counters."""
        st = self._engine_stats
        out = dataclasses.asdict(st)
        moe = getattr(st, "moe", None)
        if moe is not None:
            out["moe"] = moe.as_dict()
        return out

    def _kernel_report(self) -> dict:
        """Kernel-dispatch summary for ``describe()["kernels"]``.

        ``counters`` are per-session deltas of the trace-time dispatch
        counters (one count per traced call site, not per executed
        step); ``fallbacks`` isolates the calls where Pallas was
        selected but the shape/backend combination still forced the
        reference path — after the slot-aware kernel this should stay
        empty on the serving hot path.
        """
        from repro.kernels import ops as _ops
        now = _ops.kernel_counters()
        base = self._kernel_counter_base
        delta = {k: v - base.get(k, 0) for k, v in now.items()
                 if v - base.get(k, 0) > 0}
        return {
            "impl": self.rc.kernel_impl or "auto",
            "kv_cache_dtype": self.rc.kv_cache_dtype or "compute",
            "counters": delta,
            "fallbacks": {k: v for k, v in delta.items()
                          if k.startswith("fallback_")},
        }

    def __repr__(self):
        return (f"Session({self.cfg.name!r}, mode={self.spec.mode!r}, "
                f"schedule={self.rc.schedule!r}, P={self.rc.pp} "
                f"V={self.rc.vpp} G={self.rc.groups} "
                f"B={self.rc.microbatches} U={self.rc.unit_size})")
