"""Training equivalence of the recurrent hybrids (Mamba, mLSTM/sLSTM):
pipeline gradients equal jax.grad of the reference, as cases of
tests/spmd_case.py."""

import pytest

from tests.spmd_run import run_case


@pytest.mark.parametrize("arch", ["jamba-v0.1-52b", "xlstm-1.3b"])
def test_train_equivalence(arch):
    """Pipeline gradients == jax.grad(reference) for every architecture."""
    run_case("train_equiv", arch)
