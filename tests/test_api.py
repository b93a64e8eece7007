"""Tests for the repro.api facade: registries, spec validation, parity.

Registry/spec/describe tests run in-process (device-free by design —
Session defers all mesh/device work). The numerical parity check runs in
a subprocess with 8 fake devices, like the other SPMD cases.
"""

import pytest

from repro.api import (
    SessionError,
    RegistryError,
    generate_schedule,
    get_arch,
    greedy_schedule,
    list_archs,
    list_schedules,
    register_arch,
    register_schedule,
    session,
)
from repro.models.common import ModelConfig, RunConfig
from tests.spmd_run import run_case


# --------------------------------------------------------------------------- #
# Registries
# --------------------------------------------------------------------------- #


def test_builtin_registries_populated():
    archs = list_archs()
    assert "llama3p2_1b" in archs and len(archs) >= 11
    scheds = list_schedules()
    for s in ("zeropp", "gpipe", "1f1b", "interleaved", "bfs",
              "fwd_only"):
        assert s in scheds
    # aliases resolve to the same module as canonical names
    assert get_arch("llama3.2-1b") is get_arch("llama3p2_1b")


def test_arch_registry_round_trip():
    @register_arch("toy-arch-rt", aliases=("toy_arch_rt_alias",))
    class ToyArch:
        @staticmethod
        def reduced():
            cfg = ModelConfig(name="toy", n_layers=2, d_model=32,
                              n_heads=2, n_kv_heads=2, d_ff=64,
                              vocab=64, tie_embeddings=True)
            return cfg, RunConfig(pp=2, vpp=1, microbatches=2,
                                  param_dtype="float32",
                                  compute_dtype="float32")

    assert get_arch("toy-arch-rt") is ToyArch
    assert get_arch("toy_arch_rt_alias") is ToyArch
    assert "toy-arch-rt" in list_archs()
    # duplicate registration is refused unless overwrite=True
    with pytest.raises(RegistryError, match="already registered"):
        register_arch("toy-arch-rt", ToyArch)
    register_arch("toy-arch-rt", ToyArch, overwrite=True)
    # a Session builds from the registered arch without edits to core
    sess = session("toy-arch-rt", overrides=dict(unit=2))
    assert sess.cfg.name == "toy"
    assert sess.describe()["geometry"]["model_ranks"] == 2


def test_arch_registry_rejects_bad_entry():
    with pytest.raises(RegistryError, match="config\\(\\)/reduced\\(\\)"):
        register_arch("bad-arch", object())


def test_schedule_registry_round_trip():
    # custom schedule = greedy scheduler with a custom priority
    @register_schedule("gpipe-clone-rt")
    def gpipe_clone(sp):
        def prio(sp_, kind, u, s):
            return (kind, u, s)
        return greedy_schedule(sp, prio, name="gpipe-clone-rt")

    tt = generate_schedule("gpipe-clone-rt", P=2, V=2, n_mb=4)
    tt.validate()
    assert tt.counts()["F"] == 4 * 2 * 2
    assert "gpipe-clone-rt" in list_schedules()
    with pytest.raises(RegistryError, match="already registered"):
        register_schedule("gpipe-clone-rt", gpipe_clone)


def test_unknown_names_raise_actionable_errors():
    with pytest.raises(RegistryError, match="known:"):
        get_arch("no-such-arch")
    with pytest.raises(RegistryError, match="did you mean"):
        generate_schedule("zropp", P=2, V=1, n_mb=2)


# --------------------------------------------------------------------------- #
# Spec validation (device-free: errors fire before any mesh is built)
# --------------------------------------------------------------------------- #


def test_spec_rejects_unknown_arch():
    with pytest.raises(SessionError, match="unknown architecture"):
        session("no-such-arch")


def test_spec_rejects_bad_override_key():
    with pytest.raises(SessionError, match="valid fields"):
        session("llama3.2-1b", overrides={"microbatch": 4})


def test_spec_rejects_unknown_schedule():
    with pytest.raises(SessionError, match="unknown schedule"):
        session("llama3.2-1b", overrides={"schedule": "zig-zag"})


def test_spec_rejects_bad_mode_and_shape():
    with pytest.raises(SessionError, match="unknown mode"):
        session("llama3.2-1b", mode="training")
    with pytest.raises(SessionError, match="named shapes"):
        session("llama3.2-1b", shape="train_9000k")
    with pytest.raises(SessionError, match="reduced=False"):
        # a production dry-run needs a named shape
        session("llama3.2-1b", mode="dry-run", reduced=False)
    with pytest.raises(SessionError, match="chip_share"):
        session("llama3.2-1b", reduced=False)  # no one-chip cut
    with pytest.raises(SessionError, match="max_seq"):
        session("llama3.2-1b", mode="serve")


def test_spec_rejects_bad_geometry():
    # jamba's hybrid layer pattern is not static per slot at vpp=2 —
    # the facade surfaces the geometry error with context
    with pytest.raises(SessionError, match="invalid geometry"):
        session("jamba-v0.1-52b", overrides={"vpp": 2})


def test_describe_is_device_free():
    sess = session("llama3.2-1b", overrides=dict(microbatches=4, unit=2))
    d = sess.describe()
    assert d["geometry"]["model_ranks"] == 2
    assert d["schedule"]["name"] == "zeropp"
    assert 0.0 <= d["schedule"]["bubble_ratio"] < 1.0
    assert d["schedule"]["unit"] == 2
    assert d["n_params"] > 0
    assert "Session(" in repr(sess)


def test_opt_config_rejects_unknown_keys():
    sess = session("llama3.2-1b", optim=dict(lr=1e-3, momentum=0.9))
    with pytest.raises(SessionError, match="unknown optim option"):
        sess.opt_config()


def test_mem_budget_steers_auto_selection():
    """mem_budget is validated (auto-only, positive) and the selection
    honours it: the winner's simulated peak memory fits the cap, and
    describe() reports the per-candidate memory/makespan trade-off."""
    with pytest.raises(SessionError, match="schedule='auto'"):
        session("llama3.2-1b", mem_budget=1e9)
    with pytest.raises(SessionError, match="positive"):
        session("llama3.2-1b", schedule="auto", mem_budget=0)

    sess = session("llama3.2-1b", schedule="auto",
                   overrides=dict(microbatches=4, unit=2))
    cands = {n: a for n, a in sess.plan_selection.candidates.items()
             if not isinstance(a, str)}
    assert "autogen_gated" in cands
    assert cands["autogen_gated"].stash_depth == 2
    assert cands["autogen"].stash_depth == 4
    # cap below the biggest candidate: the winner must fit
    mems = sorted(a.peak_mem for a in cands.values())
    budget = (mems[0] + mems[-1]) / 2
    sess_b = session("llama3.2-1b", schedule="auto", mem_budget=budget,
                     overrides=dict(microbatches=4, unit=2))
    assert sess_b.plan_selection.analysis.peak_mem <= budget
    assert sess_b.plan_selection is not sess.plan_selection  # own cache
    d = sess_b.describe()
    assert d["schedule"]["auto"]["mem_budget"] == budget
    c = d["schedule"]["auto"]["candidates"]["autogen_gated"]
    assert set(c) == {"makespan", "peak_mem", "stash_depth",
                      "rs_overlap_saved"}
    assert "rs_overlap" in d["schedule"] and "stash_depth" in d["schedule"]


# --------------------------------------------------------------------------- #
# schedule="auto" (device-free selection + describe)
# --------------------------------------------------------------------------- #


def test_schedule_auto_is_device_free_and_optimal():
    sess = session("llama3.2-1b", schedule="auto",
                   overrides=dict(microbatches=4, unit=2))
    sel = sess.plan_selection
    assert sel is not None
    assert sess.rc.schedule == sel.selected.name != "auto"
    spans = {n: a.makespan for n, a in sel.candidates.items()
             if not isinstance(a, str)}
    assert len(spans) >= 5  # every registered built-in simulated
    assert all(sel.analysis.makespan <= m + 1e-12 for m in spans.values())
    d = sess.describe()
    assert d["schedule"]["name"] == sel.selected.name
    assert d["schedule"]["auto"]["selected"] == sel.selected.name
    assert set(spans) <= set(d["schedule"]["auto"]["candidates"])
    assert d["schedule"]["preset"] == "a800"
    assert d["schedule"]["makespan"] > 0


def test_schedule_auto_selection_is_cached():
    kw = dict(schedule="auto", overrides=dict(microbatches=4, unit=2))
    s1 = session("llama3.2-1b", **kw)
    s2 = session("llama3.2-1b", **kw)
    assert s1.plan_selection is s2.plan_selection  # same cache entry
    s3 = session("llama3.2-1b", schedule="auto", cost_preset="tpu_v5e",
                 overrides=dict(microbatches=4, unit=2))
    assert s3.plan_selection is not s1.plan_selection
    assert s3.describe()["schedule"]["preset"] == "tpu_v5e"


# --------------------------------------------------------------------------- #
# schedule="auto_profiled" (spec validation + full cache lifecycle,
# device-free: the measure_fn is monkeypatched so nothing compiles)
# --------------------------------------------------------------------------- #


def test_spec_validates_profile_knobs():
    with pytest.raises(SessionError, match="profile_top_k"):
        session("llama3.2-1b", schedule="auto_profiled", profile_top_k=0)
    with pytest.raises(SessionError, match="profile_budget_s"):
        session("llama3.2-1b", schedule="auto_profiled",
                profile_budget_s=-1.0)
    with pytest.raises(SessionError, match="train"):
        session("llama3.2-1b", mode="serve", schedule="auto_profiled")
    # the profile knobs only steer auto_profiled; anything else rejects
    with pytest.raises(SessionError, match="auto_profiled"):
        session("llama3.2-1b", schedule="auto", profile_top_k=5)
    with pytest.raises(SessionError, match="auto_profiled"):
        session("llama3.2-1b", schedule="zeropp", profile_budget_s=10.0)


def test_schedule_auto_profiled_full_cache_lifecycle(monkeypatch):
    """search+measured -> memory hit -> persisted hit, with the work
    counters proving the warm paths do zero simulate/measure calls."""
    from repro.api.session import Session
    from repro.core.plan import clear_plan_cache, plan_cache_info

    clear_plan_cache(persisted=True)
    calls = []

    def fake_build(self, moe_mode=None):
        # later measurements come back *faster*, so the measured winner
        # differs from the simulated-best (the re-ranking must matter)
        def measure(plan):
            calls.append(plan.name)
            return float(200 - len(calls))
        return measure

    monkeypatch.setattr(Session, "_build_measure_fn", fake_build)
    kw = dict(schedule="auto_profiled",
              overrides=dict(microbatches=4, unit=2))

    s1 = session("llama3.2-1b", **kw)
    sel = s1.plan_selection
    assert s1._plan_source == "search+measured"
    assert sel.provenance == "search+measured"
    assert len(calls) == 3                     # profile_top_k default
    assert calls[0] == sel.profile["simulated_best"]
    assert s1.rc.schedule == sel.selected.name == calls[-1]
    # acceptance inequality: winner measured <= simulated-best measured
    assert sel.measured[sel.selected.name] <= \
        sel.profile["simulated_best_us"]
    d = s1.describe()["schedule"]
    assert d["auto"]["provenance"] == {
        "selection": "search+measured", "this_session": "search+measured"}
    assert d["auto"]["measured"] == sel.measured
    assert d["auto"]["candidates"][sel.selected.name]["measured_us"] == \
        sel.measured[sel.selected.name]
    assert d["cache"]["measure_calls"] == 3

    # second identical session: in-memory hit, zero extra work
    before = plan_cache_info()
    s2 = session("llama3.2-1b", **kw)
    after = plan_cache_info()
    assert s2._plan_source == "memory-hit"
    assert s2.plan_selection is sel
    assert after["simulate_calls"] == before["simulate_calls"]
    assert after["measure_calls"] == before["measure_calls"]
    assert len(calls) == 3

    # wipe memory: third session reloads from disk — still zero work
    clear_plan_cache()
    s3 = session("llama3.2-1b", **kw)
    info = plan_cache_info()
    assert s3._plan_source == "persisted-hit"
    assert info["simulate_calls"] == 0 and info["measure_calls"] == 0
    assert s3.plan_selection.provenance == "cache:disk"
    assert s3.plan_selection.selected.name == sel.selected.name
    assert s3.plan_selection.measured == sel.measured
    d3 = s3.describe()["schedule"]
    assert d3["auto"]["provenance"]["this_session"] == "persisted-hit"
    assert d3["cache"]["disk_hits"] == 1
    assert len(calls) == 3
    clear_plan_cache(persisted=True)


def test_schedule_kw_and_override_consistency():
    # schedule= kw is shorthand for overrides["schedule"]
    s = session("llama3.2-1b", schedule="gpipe")
    assert s.rc.schedule == "gpipe"
    with pytest.raises(SessionError, match="twice and inconsistently"):
        session("llama3.2-1b", schedule="gpipe",
                overrides=dict(schedule="1f1b"))
    with pytest.raises(SessionError, match="unknown cost_preset"):
        session("llama3.2-1b", cost_preset="h100")


def test_describe_uses_simulator_not_tick_counts():
    sess = session("llama3.2-1b", overrides=dict(microbatches=4, unit=2))
    d = sess.describe()["schedule"]
    for k in ("preset", "makespan", "peak_mem", "bubble_ratio",
              "gathers_per_rank", "comm_frac"):
        assert k in d, k
    # simulated bubble fraction, not the tick-quantized ratio
    from repro.api import SchedParams, generate_schedule
    tt = generate_schedule("zeropp", SchedParams(P=2, V=1, n_mb=4, unit=2))
    assert d["ticks"] == tt.T


# --------------------------------------------------------------------------- #
# Numerical parity facade vs hand-assembled path (subprocess, 8 devices)
# --------------------------------------------------------------------------- #


def test_session_matches_hand_assembled_path():
    run_case("api_parity", "llama3.2-1b")


# --------------------------------------------------------------------------- #
# Topology (device-free: layout derivation never touches jax)
# --------------------------------------------------------------------------- #


def test_topology_preset_layouts():
    from repro.api import TOPOLOGY_PRESETS

    # a800 NVLink confinement: 256 devices / model=16 would give data=16
    # spanning two 8-GPU hosts; the rule folds the excess into pods
    lay = TOPOLOGY_PRESETS["gpu_cluster"].axis_layout(
        16, cost_preset="a800")
    assert (lay["pods"], lay["data"], lay["model"]) == (2, 8, 16)
    assert lay["devices_used"] == 256
    # tpu_v5e keeps the full-pod data axis
    lay = TOPOLOGY_PRESETS["tpu_pod"].axis_layout(
        16, cost_preset="tpu_v5e")
    assert (lay["pods"], lay["data"], lay["model"]) == (1, 16, 16)
    lay = TOPOLOGY_PRESETS["tpu_pod_x2"].axis_layout(
        16, cost_preset="tpu_v5e")
    assert (lay["pods"], lay["data"], lay["model"]) == (2, 16, 16)


def test_topology_explicit_data_and_shrink():
    from repro.api import Topology
    from repro.runtime.topology import TopologyError

    t = Topology(kind="fake_cpu", data=4)
    lay = t.axis_layout(2)
    assert (lay["data"], lay["model"]) == (4, 2)
    s = t.shrink(model_ranks=2)
    assert s.data == 2
    assert s.shrink(model_ranks=2).data == 1
    with pytest.raises(TopologyError, match="nothing left to shrink"):
        s.shrink(model_ranks=2).shrink(model_ranks=2)


def test_topology_validation_errors():
    from repro.api import Topology
    from repro.runtime.topology import TopologyError, resolve_topology

    with pytest.raises(TopologyError, match="unknown topology kind"):
        Topology(kind="warp_drive").validate()
    with pytest.raises(TopologyError, match="devices_per_host"):
        Topology(kind="gpu_cluster", hosts=4).validate()
    with pytest.raises(TopologyError, match="partition"):
        Topology(kind="gpu_cluster", hosts=5, devices_per_host=8,
                 pods=2).validate()
    with pytest.raises(TopologyError, match="unknown topology preset"):
        resolve_topology("no-such-preset")


def test_spec_topology_knob():
    from repro.api import Topology

    # topology= subsumes the legacy placement knobs — clash is an error
    with pytest.raises(SessionError, match="subsumes"):
        session("llama3.2-1b", topology="fake_cpu", data=2)
    with pytest.raises(SessionError, match="unknown topology preset"):
        session("llama3.2-1b", topology="no-such-preset")
    # describe()["topology"] resolves the layout without devices
    sess = session("llama3.2-1b",
                   topology=Topology(kind="fake_cpu", data=2),
                   overrides=dict(microbatches=4, unit=2))
    topo = sess.describe()["topology"]
    assert topo["kind"] == "fake_cpu"
    assert topo["layout"] == {"pods": 1, "data": 2, "model": 2,
                              "devices_used": 4, "devices_total": 8}
    # without topology= the report still carries the resolved layout
    sess = session("llama3.2-1b", data=2,
                   overrides=dict(microbatches=4, unit=2))
    topo = sess.describe()["topology"]
    assert topo["kind"] is None and topo["layout"]["data"] == 2


def test_topology_production_mesh_presets_agree():
    """launch.mesh's production builders are now topology presets; the
    derived layouts must match the former hard-coded 16x16 pod."""
    from repro.api import TOPOLOGY_PRESETS

    lay = TOPOLOGY_PRESETS["tpu_pod"].axis_layout(
        16, cost_preset="tpu_v5e")
    assert lay["devices_total"] == 256 == 16 * 16
    lay2 = TOPOLOGY_PRESETS["tpu_pod_x2"].axis_layout(
        16, cost_preset="tpu_v5e")
    assert lay2["devices_total"] == 512 and lay2["pods"] == 2


def test_published_widths_resolve_to_the_chip_share_cut():
    """reduced=False on a live mode is the arch's chip_share() cut: the
    published widths, depth cut to one pipeline stage, device-free."""
    from repro.configs import gpt_paper

    sess = session("gpt_paper", reduced=False, seq_len=1024)
    cfg, rc = sess.cfg, sess.rc
    full = gpt_paper.config("1.5B")
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == (
        full.d_model, full.n_heads, full.d_ff, full.vocab) == (
        2304, 24, 9216, 50304)
    cut = gpt_paper.CHIP_SHARE["reduced"]["n_layers"]
    assert (full.n_layers, cfg.n_layers) == (cut["published"], cut["here"])
    assert (rc.pp, rc.schedule) == (1, "zeropp")
    assert sess.describe()["geometry"]["model_ranks"] == 1


def test_attached_topology_reads_the_live_host():
    import jax

    from repro.runtime.topology import attached_topology

    t = attached_topology()
    dev = jax.devices()
    assert t.kind == {"cpu": "fake_cpu", "tpu": "tpu_host"}[dev[0].platform]
    assert t.total_devices == len(dev)
    assert t.describe()["device_kind"] == dev[0].device_kind
    assert t.axis_layout(1)["data"] == len(dev)


@pytest.mark.parametrize("outside", [True, False])
def test_compile_cache_placement(monkeypatch, outside):
    """$JAX_COMPILATION_CACHE_DIR (or a directory already configured)
    wins; otherwise the cache goes to the fixed in-checkout path."""
    import jax

    from repro.api import devices

    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if outside:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        devices._place_compile_cache()
        placed = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert placed == (None if outside else str(devices.CACHE_DIR))
    assert devices.CACHE_DIR.parent.joinpath("src", "repro").is_dir()
