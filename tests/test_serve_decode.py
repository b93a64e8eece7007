"""Serving decode: greedy continuation through the cached serving pipeline
equals the reference model's, as cases of tests/spmd_case.py."""

import pytest

from tests.spmd_run import run_case


@pytest.mark.parametrize("arch", ["llama3.2-1b", "xlstm-1.3b",
                                  "qwen2-moe-a2.7b", "jamba-v0.1-52b",
                                  "deepseek-v3-671b"])
def test_serve_decode_matches_reference(arch):
    """Greedy continuation through the cached serving pipeline equals the
    reference model's — covers GQA, mLSTM/sLSTM, gathered MoE, Mamba
    hybrid and MLA compressed-KV decode paths."""
    run_case("serve_decode", arch)
