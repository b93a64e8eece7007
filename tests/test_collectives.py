"""Collectives and training state: the compiled gathers and
reduce-scatters, their flat and int8 forms, buffer donation, elastic
resharding and falling loss, as cases of tests/spmd_case.py."""

import pytest

from tests.spmd_run import run_case


def test_hlo_collective_structure():
    """§3.3 comm counts realized in the compiled HLO."""
    run_case("hlo_gather_count", "llama3.2-1b")


def test_gather_prefetch_is_numerically_neutral():
    run_case("prefetch_equiv", "llama3.2-1b")


def test_flat_coalesce_bitwise_parity():
    """coalesce="flat" (ONE all-gather / reduce-scatter per tick) must be
    bit-identical to per-tensor collectives: train grads + serve tokens."""
    run_case("flat_parity", "llama3.2-1b")


def test_flat_int8_error_feedback_reduce():
    """grad_compress="int8" through the flat reduce: one int32
    psum_scatter with a segment-wide shared scale + error feedback."""
    run_case("flat_int8", "llama3.2-1b")


def test_flat_fallback_mixed_divisibility():
    """Replicated (non-divisible) tensors fall back to per-tensor
    collectives bit-identically, incl. an ld != 0 flat-pack member."""
    run_case("flat_fallback", "llama3.2-1b")


def test_buffer_donation_audit():
    """Serve step donates caches, opt step donates params + opt state —
    input/output aliasing visible in the lowered modules."""
    run_case("donation", "llama3.2-1b")


def test_int8_grad_reduction():
    run_case("int8_grads", "llama3.2-1b")


def test_elastic_reshard_resume():
    """Checkpoint at D=4, restore and continue at D=2."""
    run_case("elastic_reshard", "llama3.2-1b")


@pytest.mark.parametrize("arch", ["llama3.2-1b", "qwen2-moe-a2.7b"])
def test_pipeline_loss_decreases(arch):
    run_case("loss_decreases", arch)
