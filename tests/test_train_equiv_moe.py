"""Training equivalence of the MoE architectures, gathered and
expert-parallel: pipeline gradients equal jax.grad of the reference, as
cases of tests/spmd_case.py."""

import pytest

from tests.spmd_run import run_case


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "deepseek-v3-671b"])
def test_train_equivalence(arch):
    """Pipeline gradients == jax.grad(reference) for every architecture."""
    run_case("train_equiv", arch)


def test_ep_moe_equivalence():
    run_case("train_equiv", "deepseek-v3-671b", "moe_mode=ep")
