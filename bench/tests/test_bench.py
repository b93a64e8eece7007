"""Fast CPU checks of the benchmark itself: every file BENCHMARK.json
names is found, the trace reduction and the FLOP arithmetic give known
numbers, traffic is a function of the seed, the reference reads what it
read, a second architecture enters by new files alone, and a run
without a TPU fails without a result."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import common  # noqa: E402
import flops  # noqa: E402
import generator  # noqa: E402
import trace_reduce  # noqa: E402

SPEC = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in SPEC["workloads"]]


def with_held(spec: dict) -> dict:
    """``spec`` plus the entries held back under bench/held/: their files
    are kept and checked, so adding such a cell back is data alone."""
    out = dict(spec)
    held = os.path.join(BENCH, "held")
    for f in sorted(os.listdir(held)):
        frag = common.load_json(os.path.join(held, f))
        for k in ("configs", "workloads", "end_to_end", "per_layer"):
            out[k] = out[k] + frag[k]
    return out


ALL = with_held(SPEC)
GPT = common.load_arch("gpt", "serve")
READINGS = {"train": {"loss_gap", "grad_gap", "delta_gap"},
            "serve": {"served_gap"}}
TINY = dict(d_model=4, n_heads=2, n_kv_heads=2, d_head=8, d_ff=16,
            vocab=16, n_layers=1)


@pytest.mark.parametrize("name", [w["name"] for w in ALL["workloads"]])
def test_cell_files_are_found_by_name(name):
    cell = common.load_cell(name, ALL)
    kind = cell.traffic["kind"]
    assert os.path.isfile(os.path.join(BENCH, kind + ".py"))
    assert cell.arch is common.load_arch(cell.config["arch"], kind)
    assert set(cell.limits) == READINGS[kind]
    assert cell.config["name"] == cell.config["model"]["name"] \
        == next(w["config"] for w in ALL["workloads"] if w["name"] == name)
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        mod = common.load_module(
            common.bench_file("metrics", m["name"] + ".py"), "m")
        assert callable(mod.read)


def test_configs_state_their_cuts():
    for c in ALL["configs"]:
        cfg = common.load_json(os.path.join(ROOT, c["file"]))
        changed = sorted(k for k, v in cfg["published"].items()
                         if cfg["model"][k] != v)
        assert changed == sorted(c["reduced"]) == sorted(cfg["reduced"])
        # each names an architecture module that exports what its cells'
        # kinds need
        assert os.path.isfile(os.path.join(BENCH, "arch",
                                           cfg["arch"] + ".py"))
        for w in ALL["workloads"]:
            if w["config"] == c["name"]:
                kind = common.load_json(common.bench_file(
                    "traffic", w["traffic"] + ".json"))["kind"]
                mod = common.load_arch(cfg["arch"], kind)
                assert all(hasattr(mod, x)
                           for x in common.ARCH_EXPORTS[kind])


@pytest.mark.parametrize("arch", [None, "no_such_arch", "../gpt"])
def test_load_cell_needs_a_known_arch(tmp_path, arch):
    spec = json.loads(json.dumps(SPEC))
    cfg = common.load_json(os.path.join(ROOT, spec["configs"][0]["file"]))
    cfg.pop("arch")
    if arch is not None:
        cfg["arch"] = arch
    path = tmp_path / "config.json"
    path.write_text(json.dumps(cfg))
    spec["configs"][0]["file"] = str(path)
    with pytest.raises(common.BenchError):
        common.load_cell(CELLS[0], spec)


def test_trace_reduction_known_numbers():
    ev = [
        ["/host:CPU", "python", "bench.window", 0, 1000],
        ["/host:CPU", "python", "bench.engine_step", 0, 650],
        ["/host:CPU", "python", "bench.idle", 650, 350],
        ["/device:TPU:0", "XLA Ops", "fusion.1", 100, 220],
        ["/device:TPU:0", "XLA Ops", "%_fwd_kernel.2 = bf16[1,4] custom-call("
         "%fusion.1)", 250, 150],
        ["/device:TPU:0", "XLA Ops", "all-gather.3", 380, 120],
        ["/device:TPU:0", "XLA Ops", "_fwd_kernel", 700, 50],
        ["/device:TPU:0", "XLA Ops", "fusion.2", 1500, 100],
        ["/device:TPU:0", "XLA Ops", "%while.1 = (s32[]) while(%t)", 690, 70],
    ]
    r = trace_reduce.reduce(ev, {"flash": ["_fwd_kernel"]})
    assert r["window_s"] == pytest.approx(1e-6)
    # busy: [100, 500) and [690, 760); the op past the window is cut
    assert r["busy_s"] == pytest.approx(470e-9)
    # the all-gather runs alone over [400, 500)
    assert r["collective_exposed_s"] == pytest.approx(100e-9)
    assert r["kernels"]["flash"] == {"seconds": pytest.approx(200e-9),
                                     "calls": 1 + 1}
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.idle", pytest.approx(240e-9)]
    assert ["bench.engine_step", pytest.approx(190e-9)] in gaps
    ops = dict(r["breakdown"]["device_ops"])
    assert r["breakdown"]["device_ops"][0] == ["fusion",
                                               pytest.approx(220e-9)]
    assert "while" not in ops  # it only encloses the second kernel call


def test_recorded_chip_trace():
    path = os.path.join(BENCH, "tests", "data", "trace_small.json")
    with open(path) as f:
        rec = json.load(f)
    r = trace_reduce.reduce(rec["events"], rec["kernels"])
    for key, want in rec["expect"].items():
        got = r["kernels"][key[8:]] if key.startswith("kernels.") else r[key]
        assert got == pytest.approx(want, rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]


def test_flash_fwd_hand_count():
    # b 1, s 4, 2 heads of 8: 10 causal pairs; QK^T and PV each 2*8 a pair
    f, b = flops.flash_fwd(TINY, 1, 4)
    assert f == 2 * (10 * 2 * 8 * 2)
    assert b == 4 * (4 * 2 * 8 * 2)  # q, k, v, o in bf16


def test_fused_xent_hand_count():
    f, b = flops.fused_xent(TINY, 4)  # 4 rows, d 4, vocab 16, float32
    assert f == 3 * (2 * 4 * 4 * 16)
    assert b == 4 * (4 * 4) * 2 + 4 * (4 * 16) * 2 + 4 * 4


def test_paged_attn_hand_count():
    # one decode token per row, rows at positions 3 and 0
    f, b = flops.paged_attn_call(TINY, 1, [3, 0])
    assert f == 2 * (2 * 8 * 2) * (4 + 1)
    kv_read = (4 + 1) * 2 * 2 * 8 * 2
    kv_write = 2 * (2 * 2 * 8 * 2)
    q_o = 2 * (2 * 2 * 8 * 2)
    assert b == kv_read + kv_write + q_o


def test_train_flops_per_token_hand_count():
    m = dict(TINY, d_model=4, n_layers=1, vocab=12)
    assert GPT.train_flops_per_token(m, 6) == 72 * 16 * (1 + 0.25 + 0.25)


def test_serve_flops_hand_count():
    # one row, one token at position 0
    per_token = 2 * (2 * 4 * 16 + 2 * 4 * 16 + 2 * 4 * 16)
    attn = 4 * 2 * 8 * 1
    head = 2 * 4 * 16
    assert GPT.serve_call_flops(TINY, 1, [0]) == per_token + attn + head


def _serve_mix():
    return common.load_json(os.path.join(BENCH, "traffic", "chat.json"))


def test_traffic_is_a_function_of_the_seed():
    t = _serve_mix()
    a = generator.serve_requests(t, 50304, 2**33 + 7, 60.0)
    b = generator.serve_requests(t, 50304, 2**33 + 7, 60.0)
    c = generator.serve_requests(t, 50304, 2**33 + 8, 60.0)
    key = lambda rs: [(r.due_s, r.max_gen, r.prompt.tolist()) for r in rs]
    assert key(a) == key(b) and key(a) != key(c)
    # the seed draws the schedule too: other sizes, gaps and order ...
    sched = lambda rs: [(r.due_s, len(r.prompt), r.max_gen) for r in rs]
    assert sched(a) != sched(c)
    # ... stratified, so every seed offers nearly the same work
    for f in (lambda r: len(r.prompt), lambda r: r.max_gen):
        x, y = sum(map(f, a)), sum(map(f, c))
        assert abs(x - y) < 0.02 * x
    assert abs(a[-1].due_s - c[-1].due_s) < 0.05 * a[-1].due_s
    lengths = {len(r.prompt) for r in a}
    assert len(lengths) > len(a) // 2          # whole sizes, not rounded
    for r in a:
        assert t["prompt"]["min"] <= len(r.prompt) <= t["prompt"]["max"]
        assert len(r.prompt) + r.max_gen <= t["context"]
    tr = common.load_json(os.path.join(BENCH, "traffic", "train.json"))
    x = generator.train_batch(tr, 50304, 8, 5, 3)
    assert np.array_equal(x["tokens"],
                          generator.train_batch(tr, 50304, 8, 5, 3)["tokens"])
    assert not np.array_equal(
        x["tokens"], generator.train_batch(tr, 50304, 8, 6, 3)["tokens"])
    assert np.array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])


@pytest.mark.parametrize("change", [
    {"arrivals": "bursty"}, {"shape": "chat"},
    {"prompt": {"median": 256, "sigma": 0.6, "min": 64, "max": 768,
                "multiple": 16}}])
def test_mix_refuses_what_nothing_reads(change):
    t = _serve_mix()
    generator.check_mix(t)
    with pytest.raises(ValueError):
        generator.check_mix(dict(t, **change))


def test_warm_widths_cover_the_mix():
    t = _serve_mix()
    chunk = t["prefill_chunk"]
    widths = {n % chunk or chunk for n in generator.warm_prompt_lengths(t)}
    seen = {len(r.prompt) % chunk or chunk
            for seed in (3, 2**31 + 5)
            for r in generator.serve_requests(t, 50304, seed, 60.0)}
    # whole prompt lengths over more than a chunk: every tail width
    assert widths == set(range(1, chunk + 1)) and seen <= widths


def _run(cwd):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result(monkeypatch, capsys):
    # in this process (JAX is on the CPU here), with the compile-cache
    # placement stubbed so the suite's JAX settings stay as they are
    import run

    monkeypatch.setattr(run, "place_compile_cache", lambda: None)
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc != 0 and capsys.readouterr().out.strip() == ""


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""


# --------------------------------------------------------------------------- #
# Architectures: the GPT reads what it read; layers may differ; a second
# architecture enters by new files alone
# --------------------------------------------------------------------------- #

PINNED = common.load_json(os.path.join(BENCH, "tests", "data",
                                       "gpt_reference.json"))
SEED = 2**33 + 21


def _keyed(norms: dict) -> dict:
    return {f"{part}/{name}": np.asarray(v).tolist()
            for (part, name), v in norms.items()}


def test_gpt_reference_reads_what_it_read():
    import model

    cfg = common.load_json(os.path.join(BENCH, "configs",
                                        "gpt-1.5B-stage.json"))
    m = dict(cfg["model"], name="tiny", n_layers=2, d_model=64, n_heads=4,
             n_kv_heads=4, d_head=16, d_ff=256, vocab=256, max_seq=64)
    t = dict(common.load_json(os.path.join(BENCH, "traffic", "train.json")),
             seq=16)
    batches = [generator.train_batch(t, m["vocab"], 2, SEED, k)
               for k in range(3)]
    ref = model.train_reference(GPT, m, SEED, batches, t["optim"])
    assert ref["losses"] == PINNED["losses"]
    assert _keyed(ref["grad1"]) == PINNED["grad1"]
    assert _keyed(ref["delta"]) == PINNED["delta"]


def test_train_readers_read_what_they_read():
    rec = {"config": common.load_json(os.path.join(
               BENCH, "configs", "gpt-1.5B-stage.json"))["model"],
           "arch": GPT,
           "traffic": common.load_json(os.path.join(BENCH, "traffic",
                                                    "train.json")),
           "peaks": common.peaks_for("TPU v5 lite"), "chips": 1,
           "train_tokens_per_s": 17098.0,
           "trace": {"kernels": {
               "xent": {"seconds": 0.253907701, "calls": 96},
               "flash_fwd": {"seconds": 0.0151, "calls": 192}}}}
    for name, want in PINNED["readers"].items():
        mod = common.load_module(common.bench_file("metrics", name + ".py"),
                                 "m")
        assert mod.read(rec) == want, name


MOE_TINY = {"n_experts": 4, "top_k": 2, "d_ff_expert": 32, "first_dense": 1}


class _MixedStack:
    """Leaf tables of a stack whose first layer is dense and the others
    sparse (the program's RMSNorm / SwiGLU / GQA decoder with MoE);
    ``swap`` gives each layer the other kind's table."""

    def __init__(self, swap: bool = False):
        self.swap = swap

    def io_leaves(self, m):
        d, v = m["d_model"], m["vocab"]
        return {"embed.table": ((v, d), "normal", 1),
                "final_norm.scale": ((d,), "ones", 0),
                "head.w": ((d, v), "normal", d)}

    def layer_leaves(self, m, layer):
        d, h, g, e, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["d_head"], m["d_ff"])
        x, fe = m["moe"]["n_experts"], m["moe"]["d_ff_expert"]
        out = {"ln1.scale": ((d,), "ones", 0),
               "mix.wq": ((d, h, e), "normal", d),
               "mix.wk": ((d, g, e), "normal", d),
               "mix.wv": ((d, g, e), "normal", d),
               "mix.wo": ((h, e, d), "normal", h * e),
               "ln2.scale": ((d,), "ones", 0)}
        if (layer < m["moe"]["first_dense"]) != self.swap:
            out.update({"ffn.wg": ((d, f), "normal", d),
                        "ffn.wu": ((d, f), "normal", d),
                        "ffn.wd": ((f, d), "normal", f)})
        else:
            out.update({"ffn.router": ((d, x), "normal", d),
                        "ffn.e_wg": ((x, d, fe), "normal", d),
                        "ffn.e_wu": ((x, d, fe), "normal", d),
                        "ffn.e_wd": ((x, fe, d), "normal", fe)})
        return out


def test_per_layer_leaf_tables_on_a_mixed_stack():
    import model
    import program
    from repro.api import session

    cfg = common.load_json(os.path.join(
        BENCH, "tests", "data", "second_arch", "configs", "llama_tiny.json"))
    m = dict(cfg["model"], name="moe_tiny", n_layers=3, moe=MOE_TINY)
    sess = session(program.register(dict(cfg, name="moe_tiny", model=m)),
                   reduced=False, seq_len=16, microbatch_size=1, data=1)
    shapes, arch = sess.param_shapes(), _MixedStack()
    model.check_layout(arch, m, sess.geo, shapes)
    with pytest.raises(ValueError):
        model.check_layout(_MixedStack(swap=True), m, sess.geo, shapes)
    # the reference stacks the dense layer and the two sparse ones apart
    ref = model.reference_params(arch, SEED, m)
    assert [len(g["mix.wq"]) for g in ref["layers"]] == [1, 2]
    want = {"ffn.wg": 1, "ffn.router": 2, "ffn.e_wd": 2, "mix.wq": 3,
            "ln2.scale": 3}
    ref_n = model.norms(ref)
    prog_n = program.leaf_norms(program.row_norms(
        model.program_maker(arch, m, sess.geo, shapes)(
            model.seed_key(SEED))), sess.geo, arch, m)
    for name, layers in want.items():
        assert len(ref_n[("layers", name)]) == layers
        assert len(prog_n[("layers", name)]) == layers
    assert set(ref_n) == set(prog_n)
    # the same weights on both sides: the program's bfloat16 numbers are
    # the reference's, so every norm agrees to float32 summation and no
    # gap is NaN
    gaps = program.leaf_gaps(prog_n, {k: np.asarray(v, np.float64)
                                      for k, v in ref_n.items()})
    assert len(gaps) == sum(len(v) for v in ref_n.values())
    assert all(np.isfinite(g[0]) and g[0] < 1e-5 for g in gaps)


_SECOND = os.path.join(BENCH, "tests", "data", "second_arch")
_DRIVE = r"""
import json, sys, time
sys.path.insert(0, "bench")
import jax, common, faults, run, train
cell = common.load_cell("llama_tiny.train_tiny")
dev = {"platform": "cpu", "kind": "cpu", "count": 1}
kw = dict(seed=2**33 + 5, seconds=0.5, trace_dir=None,
          devices=jax.devices()[:1])
out = train.run(cell, t_start=time.perf_counter(),
                clock=common.CompileClock(), **kw)
sound = run.result(cell, out, False, dev, {})
rec = dict(out["rec"], config=cell.config["model"], arch=cell.arch,
           traffic=cell.traffic, chips=1, peaks={"bf16_flops_per_s": 1e12})
mfu = run.readers(cell)["mfu.train"].read(rec)
bad = train.run(cell, t_start=time.perf_counter(),
                clock=common.CompileClock(), patch=faults.half_batch,
                sess=out["sess"], **kw)
print(json.dumps({"sound": sound["correct"], "checks": sound["checks"],
                  "metrics": sorted(sound["metrics"]), "mfu": mfu,
                  "loss_sum": float(out["rec"]["step_metrics"]["loss_sum"]),
                  "half_batch": run.result(cell, bad, False, dev,
                                           {})["correct"],
                  "half_batch_checks": run.checks_of(bad["readings"],
                                                     cell.limits)}))
"""


def _digests(root) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = hashlib.sha256(fh.read()).digest()
    return out


def test_second_architecture_enters_by_new_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path / "bench")
    for d, _, files in os.walk(_SECOND):
        rel = os.path.relpath(d, _SECOND)
        for f in files:
            if f == "entries.json":
                continue
            dst = tmp_path / "bench" / rel / f
            assert not dst.exists(), dst
            shutil.copy(os.path.join(d, f), dst)
    # the entries a new cell adds to BENCHMARK.json
    spec = json.loads(json.dumps(SPEC))
    new = common.load_json(os.path.join(_SECOND, "entries.json"))
    spec["configs"] += new["configs"]
    spec["workloads"] += new["workloads"]
    cell = new["workloads"][0]["name"]
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if metric["name"] in new["reports"]:
            metric["workloads"] = metric["workloads"] + [cell]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", _DRIVE], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["sound"], got
    assert not got["half_batch"], got
    assert got["metrics"] == ["setup_s", "train_tokens_per_s"]
    assert got["mfu"] > 0 and np.isfinite(got["loss_sum"])
    after = _digests(tmp_path / "bench")
    assert {k: after[k] for k in before} == before
