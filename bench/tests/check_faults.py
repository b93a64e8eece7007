"""The comparison that decides ``correct``, shown to fail: a run of each
cell kind at a tiny size on the CPU, driven past the harness's look for
a chip, with the timed path broken underneath, and the control (the
reference in float8 in the program's place). Slower than the suite's
budget allows (about a minute), so pytest does not collect it by name;
run it with

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests/check_faults.py
"""

from __future__ import annotations

import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import common  # noqa: E402
import faults  # noqa: E402

SEED = 2**33 + 21
_TINY_MODEL = dict(name="tiny", n_layers=2, d_model=64, n_heads=4,
                   n_kv_heads=4, d_head=16, d_ff=256, vocab=256, max_seq=64)


def _cell(kind: str):
    from test_bench import with_held

    spec = with_held(common.load_json(os.path.join(os.path.dirname(BENCH),
                                                   "BENCHMARK.json")))
    name = next(w["name"] for w in spec["workloads"]
                if common.load_cell(w["name"], spec).traffic["kind"] == kind)
    real = common.load_cell(name, spec)
    config = dict(real.config, name="tiny",
                  model=dict(real.config["model"], **_TINY_MODEL),
                  run=dict(real.config["run"], microbatches=2))
    t = dict(real.traffic)
    if kind == "train":
        t.update(seq=32)
    else:
        t.update(slots=4, page=8, prefill_chunk=16, context=64, rate=20.0,
                 prompt={"median": 16, "sigma": 0.5, "min": 8, "max": 40},
                 output={"median": 8, "sigma": 0.5, "min": 6, "max": 20},
                 check={"min_tokens": 60, "max_requests": 8})
    return common.Cell(real.name, 1, config, real.arch, t, real.limits,
                       real.end_to_end, real.per_layer)


def _correct(cell, patch=None) -> tuple[bool, dict]:
    import jax

    import run
    runner = common.load_module(
        os.path.join(BENCH, cell.traffic["kind"] + ".py"), "r")
    out = runner.run(cell, seed=SEED, seconds=1.0, trace_dir=None,
                     t_start=time.perf_counter(),
                     clock=common.CompileClock(), devices=jax.devices()[:1],
                     patch=patch)
    res = run.result(cell, out, False, {"platform": "cpu", "kind": "cpu",
                                        "count": 1}, {})
    return res["correct"], out


def test_train_sound_run_is_correct():
    ok, out = _correct(_cell("train"))
    assert ok, out["readings"]


@pytest.mark.parametrize("fault", faults.TRAIN)
def test_train_fault_is_not_correct(fault):
    ok, out = _correct(_cell("train"), fault)
    assert not ok, out["readings"]


def test_train_control_is_not_correct():
    import model
    import train

    cell = _cell("train")
    _, out = _correct(cell)
    got = model.train_reference(cell.arch, cell.config["model"], SEED,
                                out["batches"], cell.traffic["optim"],
                                precision="fp8")
    readings = train.compare(got["losses"], got["grad1"], got["delta"],
                             out["reference"])
    assert any(readings[k] > lim for k, lim in cell.limits.items()), readings


def test_serve_sound_run_is_correct():
    ok, out = _correct(_cell("serve"))
    assert ok, out["readings"]


def test_serve_token_altered_is_not_correct():
    ok, out = _correct(_cell("serve"), faults.token_altered)
    assert not ok, out["readings"]


def test_serve_control_is_not_correct():
    import model

    cell = _cell("serve")
    _, out = _correct(cell)
    got = model.serve_reference(cell.arch, cell.config["model"], SEED,
                                out["sample"], cell.traffic["context"],
                                control="fp8")
    assert got["control_gap"] > cell.limits["served_gap"], got
