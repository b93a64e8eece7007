"""Training equivalence of the dense architectures: pipeline gradients
equal jax.grad of the reference, as cases of tests/spmd_case.py."""

import pytest

from tests.spmd_run import run_case


@pytest.mark.parametrize("arch", [
    "llama3.2-1b", "yi-9b", "minitron-4b", "phi4-mini-3.8b",
    "phi-3-vision-4.2b", "whisper-large-v3", "gpt_paper",
])
def test_train_equivalence(arch):
    """Pipeline gradients == jax.grad(reference) for every architecture."""
    run_case("train_equiv", arch)


def test_multi_pod_equivalence():
    run_case("train_equiv", "llama3.2-1b", "pod=2", "data=2")
