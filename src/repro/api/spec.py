"""SessionSpec: the validated builder behind ``repro.api.session``.

Owns everything the old entry points assembled by hand — architecture
resolution, RunConfig overrides, shape selection, mesh sizing — and
turns bad inputs into actionable errors *before* any device work starts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

from repro.api.registry import (
    ARCH_REGISTRY,
    RegistryError,
    SCHEDULE_REGISTRY,
)
from repro.models.common import RunConfig, SHAPES, ShapeConfig


class SessionError(ValueError):
    """Invalid session specification (message says how to fix it)."""


MODES = ("train", "serve", "dry-run")
_MODE_ALIASES = {"dry_run": "dry-run", "dryrun": "dry-run"}

_RC_FIELDS = {f.name for f in dataclasses.fields(RunConfig)}


@dataclasses.dataclass(frozen=True)
class SessionSpec:
    """Everything needed to build a Session. Validated, not yet built."""

    arch: str
    mode: str = "train"
    # named ShapeConfig ("train_4k", ...), an explicit ShapeConfig, or
    # None to derive one from seq_len / global_batch / the RunConfig.
    shape: str | ShapeConfig | None = None
    # True: the arch's tiny reduced() smoke config. False: published
    # widths — the arch's chip_share() cut (one chip's share of a stated
    # deployment) on the attached devices, or in mode="dry-run" the full
    # config() with production_run(shape) on a topology preset.
    reduced: bool = True
    # pipeline schedule: a registered name, "auto" to run the §4
    # selection (every registered schedule + the autogen heuristic,
    # simulated under `cost_preset`; minimum makespan wins), or
    # "auto_profiled" for the coarse→fine search (same simulated screen,
    # then the top-K survivors are compiled and *timed* on the live mesh
    # and the minimum measured us/call wins — train mode only, needs
    # devices at construction). Shorthand for overrides["schedule"].
    schedule: str | None = None
    cost_preset: str = "a800"       # simulator preset: a800 | tpu_v5e
    # auto_profiled knobs: how many simulated survivors get a real
    # measurement, and a wall-clock cap on the measuring phase (the
    # simulated-best survivor is always measured, budget or not).
    profile_top_k: int = 3
    profile_budget_s: float | None = None
    # schedule="auto" memory cap (simulated peak bytes under the preset
    # cost model): candidates over budget lose to any that fits — the
    # knob that makes the unit-gated autogen (O(U) activation memory)
    # win over full-depth candidates when the whole batch can't stay
    # live. None ranks purely on makespan.
    mem_budget: float | None = None
    # collective coalescing: "flat" (default via RunConfig) packs each
    # stage's gatherable params into one flat buffer so every FSDP
    # gather/reduce tick issues ONE collective; "none" is the per-tensor
    # escape hatch (debugging / bitwise A-B). Shorthand for
    # overrides["coalesce"].
    coalesce: str | None = None
    # MoE expert placement: "gathered" (experts ride the FSDP
    # gather/reduce path like any tensor), "ep" (experts stay sharded
    # over the data axis; tokens move via all-to-all dispatch/combine),
    # or "auto" (cost both under the a2a-aware α–β model and keep the
    # smaller simulated makespan — with schedule="auto" the §4 search
    # runs once per mode). Shorthand for overrides["moe_mode"].
    moe_mode: str | None = None
    overrides: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    optim: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    data: int | None = None         # data-axis size (None -> derived)
    pods: int | None = None         # hybrid-sharded DP axis (reduced runs)
    devices: int | None = None      # ensure this many host devices first
    seq_len: int | None = None      # derived-shape sequence length
    global_batch: int | None = None  # derived-shape global batch
    microbatch_size: int = 1        # samples per micro-batch (derived gb)
    max_seq: int | None = None      # serving cache length
    max_slots: int | None = None    # continuous-batching slot count
    #                                 (serve-mode global batch; each slot
    #                                 holds one in-flight request)
    prefill_chunk: int | None = None  # split prompts into chunks of this
    #                                   width (bounds the number of
    #                                   distinct prefill compilations)
    page_size: int | None = None    # paged KV cache: tokens per page
    #                                 (None -> contiguous per-slot rows)
    max_pages: int | None = None    # paged KV cache: total page count
    #                                 (None -> max_slots * max_seq/page)
    prefix_sharing: str = "on"      # radix prefix sharing across
    #                                 requests ("off": escape hatch —
    #                                 pages stay private per request)
    kv_cache_dtype: str | None = None  # KV-cache storage dtype: "fp32" |
    #                                    "bf16" | "int8" (int8 = quantized
    #                                    pages, needs page_size). Shorthand
    #                                    for overrides["kv_cache_dtype"].
    mesh: Any = None                # pre-built jax Mesh (advanced)
    # hardware topology: a preset name ("fake_cpu", "gpu_cluster",
    # "tpu_pod", "tpu_pod_x2"), a repro.runtime.topology.Topology, or a
    # kwargs dict. Subsumes the data=/pods=/devices=/mesh=
    # knobs: the DP×FSDP×PP axis layout is derived from the hardware
    # under cost_preset and Session.mesh is built from it.
    topology: Any = None

    def __post_init__(self):
        object.__setattr__(self, "mode",
                           _MODE_ALIASES.get(self.mode, self.mode))
        object.__setattr__(self, "overrides", dict(self.overrides or {}))
        object.__setattr__(self, "optim", dict(self.optim or {}))
        if self.schedule is not None:
            prev = self.overrides.get("schedule")
            if prev is not None and prev != self.schedule:
                raise SessionError(
                    f"schedule given twice and inconsistently: "
                    f"schedule={self.schedule!r} vs "
                    f"overrides['schedule']={prev!r}")
            self.overrides["schedule"] = self.schedule
        if self.coalesce is not None:
            prev = self.overrides.get("coalesce")
            if prev is not None and prev != self.coalesce:
                raise SessionError(
                    f"coalesce given twice and inconsistently: "
                    f"coalesce={self.coalesce!r} vs "
                    f"overrides['coalesce']={prev!r}")
            self.overrides["coalesce"] = self.coalesce
        if self.moe_mode is not None:
            prev = self.overrides.get("moe_mode")
            if prev is not None and prev != self.moe_mode:
                raise SessionError(
                    f"moe_mode given twice and inconsistently: "
                    f"moe_mode={self.moe_mode!r} vs "
                    f"overrides['moe_mode']={prev!r}")
            self.overrides["moe_mode"] = self.moe_mode
        if self.kv_cache_dtype is not None:
            prev = self.overrides.get("kv_cache_dtype")
            if prev is not None and prev != self.kv_cache_dtype:
                raise SessionError(
                    f"kv_cache_dtype given twice and inconsistently: "
                    f"kv_cache_dtype={self.kv_cache_dtype!r} vs "
                    f"overrides['kv_cache_dtype']={prev!r}")
            self.overrides["kv_cache_dtype"] = self.kv_cache_dtype

    # ------------------------------------------------------------------ #
    def validate(self) -> "SessionSpec":
        if self.mode not in MODES:
            raise SessionError(
                f"unknown mode {self.mode!r}; pick one of {MODES}")
        try:
            ARCH_REGISTRY.get(self.arch)
        except RegistryError as e:
            raise SessionError(str(e)) from e

        bad = sorted(set(self.overrides) - _RC_FIELDS)
        if bad:
            raise SessionError(
                f"unknown RunConfig override(s) {bad}; valid fields: "
                f"{', '.join(sorted(_RC_FIELDS))}")
        sched = self.overrides.get("schedule")
        auto_modes = ("auto", "auto_profiled")
        if sched is not None and sched not in auto_modes \
                and sched not in SCHEDULE_REGISTRY:
            try:
                SCHEDULE_REGISTRY.get(sched)  # raises with the full hint
            except RegistryError as e:
                raise SessionError(
                    str(e) + " (or pass schedule='auto' to search the "
                    "registered schedules, 'auto_profiled' to also time "
                    "the finalists on the live mesh)") from e
        if sched == "auto_profiled" and self.mode != "train":
            raise SessionError(
                "schedule='auto_profiled' measures real *train* steps "
                f"during selection; this session is mode={self.mode!r} — "
                "use schedule='auto' (simulated-only) here, or tune in a "
                "train session and pass the winning schedule explicitly")
        if self.profile_top_k < 1:
            raise SessionError(
                f"profile_top_k must be >= 1 (at least the simulated-best "
                f"candidate gets measured), got {self.profile_top_k}")
        if self.profile_budget_s is not None and self.profile_budget_s < 0:
            raise SessionError(
                f"profile_budget_s must be >= 0 (0 still measures the "
                f"simulated-best candidate), got {self.profile_budget_s}")
        if sched != "auto_profiled" and (
                self.profile_top_k != 3 or self.profile_budget_s
                is not None):
            raise SessionError(
                "profile_top_k/profile_budget_s only steer the "
                "schedule='auto_profiled' measured refinement; pass "
                "schedule='auto_profiled' (or drop them)")
        co = self.overrides.get("coalesce")
        if co is not None and co not in ("flat", "none"):
            raise SessionError(
                f"unknown coalesce mode {co!r}; pick 'flat' (one "
                "collective per stage segment per tick) or 'none' "
                "(per-tensor collectives)")
        mm = self.overrides.get("moe_mode")
        if mm is not None and mm not in ("gathered", "ep", "auto"):
            raise SessionError(
                f"unknown moe_mode {mm!r}; pick 'gathered' (experts ride "
                "the FSDP collectives), 'ep' (expert-parallel: experts "
                "sharded over data, tokens all-to-all'd), or 'auto' "
                "(cost both and keep the smaller simulated makespan)")
        ki = self.overrides.get("kernel_impl")
        if ki not in (None, "ref", "pallas"):
            raise SessionError(
                f"unknown kernel_impl {ki!r}; pick 'pallas' (force the "
                "Pallas kernels; interpret mode off-TPU), 'ref' (jnp "
                "references), or None (backend default)")
        kvd = self.overrides.get("kv_cache_dtype")
        if kvd is not None:
            if kvd not in ("fp32", "bf16", "int8"):
                raise SessionError(
                    f"unknown kv_cache_dtype {kvd!r}; pick 'fp32', "
                    "'bf16', or 'int8' (quantized pages)")
            if self.mode != "serve":
                raise SessionError(
                    "kv_cache_dtype is a serving knob (KV-cache storage "
                    f"dtype); this session is mode={self.mode!r}")
            if kvd == "int8" and self.page_size is None:
                raise SessionError(
                    "kv_cache_dtype='int8' quantizes *pages* (per-page "
                    "scales live beside the page pool); pass "
                    "page_size=<tokens per page> — contiguous slot rows "
                    "have no scale storage")
        if self.topology is not None:
            clash = [k for k, v in (("data", self.data),
                                    ("pods", self.pods),
                                    ("devices", self.devices),
                                    ("mesh", self.mesh)) if v is not None]
            if clash:
                raise SessionError(
                    f"topology= subsumes {', '.join(clash)}: the axis "
                    "layout (and device bootstrap) is derived from the "
                    "topology under cost_preset — drop the explicit "
                    "knob(s) or pin the axis via "
                    "Topology(..., data=<width>)")
            from repro.runtime.topology import (TopologyError,
                                                resolve_topology)
            try:
                resolve_topology(self.topology)
            except TopologyError as e:
                raise SessionError(str(e)) from e

        from repro.core.plan import PRESETS
        if self.cost_preset not in PRESETS:
            raise SessionError(
                f"unknown cost_preset {self.cost_preset!r}; known "
                f"presets: {', '.join(sorted(PRESETS))}")
        if self.mem_budget is not None:
            if self.mem_budget <= 0:
                raise SessionError(
                    f"mem_budget must be a positive simulated-peak-memory "
                    f"cap (bytes under the {self.cost_preset!r} preset), "
                    f"got {self.mem_budget}")
            if sched not in auto_modes:
                raise SessionError(
                    "mem_budget only steers the schedule='auto'/"
                    "'auto_profiled' selection; pass one of those (or "
                    "drop mem_budget)")

        if isinstance(self.shape, str) and self.shape not in SHAPES:
            raise SessionError(
                f"unknown shape {self.shape!r}; named shapes: "
                f"{', '.join(sorted(SHAPES))} (or pass a ShapeConfig)")
        if not self.reduced and self.mode == "dry-run" \
                and not isinstance(self.shape, str):
            raise SessionError(
                "production dry-runs (reduced=False) need a named shape "
                f"from {sorted(SHAPES)} so production_run(shape) can pick "
                "the RunConfig")
        if self.shape is None and self.mode == "serve" \
                and self.max_seq is None:
            raise SessionError(
                "serve sessions need max_seq=<prompt+gen+slack> (the KV "
                "cache length) or an explicit shape")
        if self.max_slots is not None:
            if self.mode != "serve":
                raise SessionError(
                    "max_slots is a serving knob (the continuous-batching "
                    f"slot count); this session is mode={self.mode!r}")
            if self.max_slots < 1:
                raise SessionError(
                    f"max_slots must be >= 1, got {self.max_slots}")
            if self.global_batch is not None \
                    and self.global_batch != self.max_slots:
                raise SessionError(
                    f"max_slots ({self.max_slots}) and global_batch "
                    f"({self.global_batch}) disagree; in serve mode they "
                    "are the same quantity — pass one of them")
            shards = (self.pods or 1) * (self.data or 1)
            if self.max_slots % shards != 0:
                raise SessionError(
                    f"max_slots ({self.max_slots}) must divide evenly "
                    f"over the pods×data axes ({shards}): the slotted "
                    "(per-slot pos) serve path needs a batch-sharded "
                    "cache — round max_slots up or shrink data=/pods=")
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise SessionError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        if self.prefix_sharing not in ("on", "off"):
            raise SessionError(
                f"prefix_sharing must be 'on' or 'off', got "
                f"{self.prefix_sharing!r}")
        if self.page_size is not None:
            if self.mode != "serve":
                raise SessionError(
                    "page_size is a serving knob (the paged-KV page "
                    f"width); this session is mode={self.mode!r}")
            if self.page_size < 1:
                raise SessionError(
                    f"page_size must be >= 1, got {self.page_size}")
            if self.max_seq is not None \
                    and self.max_seq % self.page_size != 0:
                raise SessionError(
                    f"page_size ({self.page_size}) must divide max_seq "
                    f"({self.max_seq}) so page tables have a fixed "
                    "width")
        if self.max_pages is not None:
            if self.page_size is None:
                raise SessionError(
                    "max_pages needs page_size=<tokens per page> (it "
                    "sizes the paged KV cache)")
            if self.max_pages < 1:
                raise SessionError(
                    f"max_pages must be >= 1, got {self.max_pages}")
            shards = (self.pods or 1) * (self.data or 1)
            if self.max_pages % shards != 0:
                raise SessionError(
                    f"max_pages ({self.max_pages}) must divide evenly "
                    f"over the pods×data axes ({shards}): the page axis "
                    "shards exactly like the slot batch axis")
        if self.page_size is not None:
            # the page arena and the slot rows partition over pods×data
            # × FSDP groups (cache leaves shard over the stage axis, so
            # a page exists only in the group replica that wrote it) —
            # catch a bad count here with the full partition count, not
            # deep in PagePool at engine construction.
            try:
                groups = self.resolve_configs()[2].groups
            except Exception:   # resolution errors surface on their own
                groups = None
            if groups is not None and groups > 1:
                shards = (self.pods or 1) * (self.data or 1)
                parts = shards * groups
                if self.max_pages is not None \
                        and self.max_pages % parts != 0:
                    raise SessionError(
                        f"max_pages ({self.max_pages}) must divide "
                        f"evenly over the {parts} cache partitions "
                        f"(pods×data ({shards}) × FSDP groups "
                        f"({groups})): a page lives only in the stage "
                        "replica of the group that wrote it — round "
                        f"max_pages to a multiple of {parts}")
                if self.max_slots is not None \
                        and self.max_slots % parts != 0:
                    raise SessionError(
                        f"max_slots ({self.max_slots}) must divide "
                        f"evenly over the {parts} cache partitions "
                        f"(pods×data ({shards}) × FSDP groups "
                        f"({groups})) for the paged serve path")
        return self

    # ------------------------------------------------------------------ #
    def resolve_configs(self):
        """Returns (arch_module, ModelConfig, RunConfig) post-overrides."""
        mod = ARCH_REGISTRY.get(self.arch)
        if self.reduced:
            if not hasattr(mod, "reduced"):
                raise SessionError(
                    f"architecture {self.arch!r} has no reduced() config; "
                    "pass reduced=False with a named shape")
            cfg, rc = mod.reduced()
        elif self.mode == "dry-run":
            cfg = mod.config()
            rc = mod.production_run(self.shape)
        else:
            if not hasattr(mod, "chip_share"):
                raise SessionError(
                    f"architecture {self.arch!r} has no chip_share() cut "
                    "(published widths sized to one chip's share of a "
                    "deployment); run it reduced, or compile it at full "
                    "size with mode='dry-run'")
            cfg, rc = mod.chip_share()
        if self.overrides:
            rc = dataclasses.replace(rc, **self.overrides)
        return mod, cfg, rc
