"""The one runner for the cases in tests/spmd_case.py.

Each case runs in its own subprocess, so it can set its own fake-device
count while the pytest process keeps a single CPU device. A case passes
when it prints ``CASE_OK <case>``.

Each case has its own time limit, ``CASE_TIMEOUT_S``: more than twice the
slowest case (about 200 s on 8 CPU cores shared by 6 pytest workers) and
under a third of the tier-1 run's 1470 s limit. A case that hangs then
fails alone, with its name, arguments and captured output, instead of
cutting the whole run.
"""

import os
import subprocess
import sys

import pytest

CASE_TIMEOUT_S = 450
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _report(what: str, out, err) -> str:
    # TimeoutExpired carries the output read so far as bytes, even under
    # text=True.
    out, err = (x.decode(errors="replace") if isinstance(x, bytes)
                else x or "" for x in (out, err))
    return (f"{what}\n--- stdout ---\n{out[-3000:]}"
            f"\n--- stderr ---\n{err[-3000:]}")


def run_case(case: str, *args: str, env_extra: dict | None = None):
    """Run ``python -m tests.spmd_case <case> <args>`` and fail the calling
    test unless it prints ``CASE_OK <case>`` within ``CASE_TIMEOUT_S``."""
    cmd = [sys.executable, "-m", "tests.spmd_case", case, *args]
    env = {**os.environ, "PYTHONPATH": "src", **(env_extra or {})}
    try:
        p = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=CASE_TIMEOUT_S, env=env, cwd=_ROOT)
    except subprocess.TimeoutExpired as e:
        raise pytest.fail.Exception(_report(
            f"{case} {args} ran past its {CASE_TIMEOUT_S} s limit",
            e.stdout, e.stderr), pytrace=False) from None
    if f"CASE_OK {case}" not in p.stdout:
        pytest.fail(_report(f"{case} {args} failed (exit {p.returncode})",
                            p.stdout, p.stderr), pytrace=False)
