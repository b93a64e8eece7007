"""Schedule equivalence: every baseline schedule, both §4 autogen tables
and the auto-selected schedule run through the same tick engine, with the
reference's gradients, as cases of tests/spmd_case.py."""

import pytest

from tests.spmd_run import run_case


@pytest.mark.parametrize("schedule", ["bfs", "gpipe", "1f1b", "autogen",
                                      "autogen_gated"])
def test_baseline_schedules_equivalence(schedule):
    """Every baseline (and both §4 autogen tables) runs through the same
    tick engine, exactly."""
    run_case("train_equiv", "llama3.2-1b", f"schedule={schedule}")


def test_gated_autogen_bitwise_parity_and_memory():
    """ISSUE-5 acceptance: "autogen_gated" keeps unit-depth stash buffers,
    its gradients are bit-identical to the zeropp baseline on the smoke
    config, and its simulated peak memory is strictly below full-depth
    autogen."""
    run_case("gated_autogen_parity", "llama3.2-1b")


def test_executor_matches_seed_bit_for_bit():
    """The extracted tick engine must reproduce the recorded seed
    executor's train grads/metrics and served tokens bit-for-bit.
    PYTHONHASHSEED is pinned: trace-time set iteration order is the only
    run-to-run variance in this fully-deterministic CPU setup."""
    run_case("golden_parity", "llama3.2-1b",
             env_extra={"PYTHONHASHSEED": "0"})


def test_auto_schedule_trains_and_serves():
    """session(arch, schedule="auto"): picks the min-makespan plan among
    every registered schedule, then trains and serves with it."""
    run_case("auto_schedule", "llama3.2-1b")
