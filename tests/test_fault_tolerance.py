"""Fault-tolerance and elasticity tests.

Fast cases exercise the host-side controller pieces in-process — the
corrupt-checkpoint fallback *chain*, max_failures exhaustion, the
async-save wait() on the failure path, and the describe() surfacing.
The end-to-end elastic cases (train shrink 4→2 data ranks bit-exact,
ServeEngine.reshard, EngineRouter failover) run in subprocesses with
fake host devices via tests/spmd_case.py.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.fault_tolerance import (
    FaultToleranceConfig,
    TrainController,
)
from tests.spmd_run import run_case


# --------------------------------------------------------------------------- #
# Checkpoint fallback chain (no devices)
# --------------------------------------------------------------------------- #


def test_restore_latest_falls_back_through_corruption_chain(tmp_path):
    """Both of the two newest checkpoints corrupt (one truncated leaf,
    one missing manifest) -> restore_latest walks back to the oldest
    intact step instead of failing or loading garbage."""
    ctl = TrainController(str(tmp_path), FaultToleranceConfig(keep=3))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    for step in (2, 4, 6):
        ctl.mgr.save(step, {"w": tree["w"] + step},
                     extra={"step": step}, blocking=True)
    # newest: truncated npy payload
    with open(os.path.join(str(tmp_path), "step_000000006", "w.npy"),
              "wb") as f:
        f.write(b"\x93NUMPY")
    # second newest: manifest gone entirely
    os.remove(os.path.join(str(tmp_path), "step_000000004",
                           "manifest.json"))
    got, manifest = ctl.restore_latest()
    assert manifest["extra"]["step"] == 2
    np.testing.assert_array_equal(got["w"], np.asarray(tree["w"]) + 2)


def test_restore_latest_with_every_step_corrupt_returns_none(tmp_path):
    ctl = TrainController(str(tmp_path), FaultToleranceConfig())
    ctl.mgr.save(1, {"w": jnp.ones(3)}, blocking=True)
    os.remove(os.path.join(str(tmp_path), "step_000000001",
                           "manifest.json"))
    assert ctl.restore_latest() == (None, None)


# --------------------------------------------------------------------------- #
# Controller failure paths (no devices)
# --------------------------------------------------------------------------- #


def _counting_build(calls, fail_steps=(), fail_once=False):
    armed = set(fail_steps)

    def build(restored, manifest):
        calls["builds"] += 1
        state = {"x": jnp.asarray(restored["x"]) if restored
                 else jnp.zeros(())}

        def run_one(state, step):
            if step in armed:
                if fail_once:
                    armed.discard(step)
                raise RuntimeError(f"boom at {step}")
            return {"x": state["x"] + 1.0}, {"x": float(state["x"])}

        return state, run_one, lambda s: s

    return build


def test_max_failures_exhaustion_reraises(tmp_path):
    """A step that fails on every attempt burns through max_failures and
    then re-raises the real error instead of looping forever."""
    ctl = TrainController(str(tmp_path), FaultToleranceConfig(
        ckpt_every=2, max_failures=3, async_save=False))
    calls = {"builds": 0}
    with pytest.raises(RuntimeError, match="boom at 3"):
        ctl.run(_counting_build(calls, fail_steps={3}), total_steps=6)
    assert ctl.failures == 3
    assert calls["builds"] == 3           # original + 2 restarts
    # every restart resumed from the last good checkpoint
    assert ctl.resume_steps == [2, 2]
    assert ctl.summary()["resume_steps"] == [2, 2]


def test_failure_path_waits_for_async_saves(tmp_path):
    """With async_save on, a failure right after a checkpoint was queued
    must wait() for the background save before restoring — the restart
    resumes from the freshest step, not a stale one."""
    ctl = TrainController(str(tmp_path), FaultToleranceConfig(
        ckpt_every=1, max_failures=3, async_save=True))
    calls = {"builds": 0}
    state, hist = ctl.run(_counting_build(calls, fail_steps={4},
                                          fail_once=True),
                          total_steps=6)
    assert calls["builds"] == 2 and ctl.failures == 1
    # the step-4 save was in flight when step 4 failed; wait() made it
    # durable, so the restart resumed at 4 (no recompute of 0..3)
    assert ctl.resume_steps == [4]
    assert [s for s, _ in hist] == [0, 1, 2, 3, 4, 5]
    assert float(state["x"]) == 6.0


def test_summary_and_attach_surface_in_describe():
    """attach() hooks the controller into Session.describe() without
    touching devices; summary() carries the counters."""
    import tempfile

    from repro.api import session

    ctl = TrainController(tempfile.mkdtemp(), FaultToleranceConfig(
        ckpt_every=5, max_failures=2))
    sess = session("llama3.2-1b", topology="fake_cpu",
                   overrides=dict(microbatches=4, unit=2))
    assert "fault_tolerance" not in sess.describe()
    assert ctl.attach(sess) is ctl
    ft = sess.describe()["fault_tolerance"]
    assert ft["failures"] == 0 and ft["max_failures"] == 2
    assert ft["ckpt_every"] == 5 and ft["ckpt_steps"] == []
    assert ft["straggler_flags"] == 0 and ft["resume_steps"] == []


# --------------------------------------------------------------------------- #
# End-to-end elastic cases (subprocess, 8 fake devices)
# --------------------------------------------------------------------------- #


def test_elastic_train_shrinks_topology_bit_exact():
    """Injected failure mid-run -> restart on a data-halved topology;
    the post-restore loss trajectory is bit-exact vs a clean restore."""
    run_case("elastic_train", "llama3.2-1b")


def test_serve_reshard_zero_drops_token_identical():
    """ServeEngine.reshard parks a staggered in-flight workload and
    re-admits it on the shrunk mesh with identical token streams."""
    run_case("serve_reshard", "llama3.2-1b")


def test_router_two_replicas_token_identical_with_failover():
    """EngineRouter: 2 replicas ≡ 1 engine; replica kill moves work."""
    run_case("router_equiv", "llama3.2-1b")
