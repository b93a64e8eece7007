"""The Pallas kernels of the GPT path compile for a TPU v5e at gpt_paper
1.5B widths (d_model 2304, 24 heads of 96, vocab 50304, seq 1024), and
those of the Moonlight-16B-A3B cell at its widths (latent attention at
16 heads of 192 / 128 over 4096 rows; 8 held experts of 2048 x 1408).

Nothing runs: each kernel is lowered and compiled for a *described* v5e
chip (``jax.experimental.topologies``), which raises what the chip's
compiler would raise — block layouts Mosaic refuses, VMEM overruns,
missing differentiation rules — and interpret-mode tests cannot see.
Each compiled program must contain the kernel (``tpu_custom_call``).

The topology is described inside a module fixture (only the worker that
runs this file loads the TPU compiler) and the persistent compilation
cache is off around the compiles: an entry written for a described chip
cannot be read back on a CPU host.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import expert_gmm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_xent import softmax_xent
from repro.kernels.paged_attention import (
    decode_attention,
    flash_attention_slotted,
    paged_attention,
)

SEQ, H, E, D, VOCAB = 1024, 24, 96, 2304, 50304
SLOTS, PAGE, CHUNK = 8, 16, 256
N_PAGES = SLOTS * SEQ // PAGE


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


@pytest.mark.parametrize("shape,causal", [
    (((1, SEQ, H, E), (1, SEQ, H, E), (1, SEQ, H, E)), True),
    (((1, SEQ, 32, 64), (1, SEQ, 8, 64), (1, SEQ, 8, 64)), True),  # GQA
    (((1, SEQ, 16, 192), (1, SEQ, 16, 192), (1, SEQ, 16, 128)), True),  # MLA
    (((1, 4096, 16, 192), (1, 4096, 16, 192), (1, 4096, 16, 128)), True),
    (((1, 448, 20, 64), (1, 1500, 20, 64), (1, 1500, 20, 64)), False),
], ids=["gpt", "gqa", "mla", "mla_4k", "whisper_cross"])
def test_flash_attention_fwd_and_custom_vjp_bwd(chip, shape, causal):
    qkv = [(s, jnp.bfloat16) for s in shape]

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=causal), q, k, v)
        return out, vjp(out)

    _compile(fwd_bwd, chip, *qkv)


def test_expert_gmm_fwd_and_bwd(chip):
    """The grouped expert product, forward, dx and dW, over a micro-batch
    of 4096 tokens x 6 pairs, 8 held experts of 2048 x 1408."""
    def fwd_bwd(lhs, rhs, sizes):
        out, vjp = jax.vjp(
            lambda a, b: expert_gmm.expert_gmm(a, b, sizes), lhs, rhs)
        return out, vjp(out)

    hlo = _compile(fwd_bwd, chip, ((4096 * 6, 2048), jnp.bfloat16),
                   ((8, 2048, 1408), jnp.bfloat16), ((8,), jnp.int32))
    assert "_expert_gmm" in hlo and "_expert_tgmm" in hlo


def test_fused_xent_at_vocab_50304(chip):
    _compile(lambda h, w, lab: softmax_xent(h, w, lab), chip,
             ((SEQ, D), jnp.bfloat16), ((D, VOCAB), jnp.bfloat16),
             ((SEQ,), jnp.int32))


@pytest.mark.parametrize("sq", [1, CHUNK])
def test_slotted_and_decode_attention(chip, sq):
    def fn(q, k, v, pos):
        if sq == 1:
            return decode_attention(q, k, v, pos)
        return flash_attention_slotted(q, k, v, pos=pos)

    _compile(fn, chip, ((SLOTS, sq, H, E), jnp.bfloat16),
             ((SLOTS, SEQ, H, E), jnp.bfloat16),
             ((SLOTS, SEQ, H, E), jnp.bfloat16), ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("sq", [1, CHUNK])
def test_paged_attention(chip, pool, sq):
    quant = pool == "int8"
    dt = jnp.int8 if quant else jnp.bfloat16
    shapes = [((SLOTS, sq, H, E), jnp.bfloat16),
              ((N_PAGES, PAGE, H, E), dt), ((N_PAGES, PAGE, H, E), dt),
              ((SLOTS, SEQ // PAGE), jnp.int32), ((SLOTS,), jnp.int32)]
    if quant:
        shapes += [((N_PAGES, H), jnp.float32)] * 2

    def fn(q, kp, vp, pt, pos, *scales):
        ks, vs = scales if quant else (None, None)
        return paged_attention(q, kp, vp, page_tables=pt, pos=pos,
                               k_scale=ks, v_scale=vs)

    _compile(fn, chip, *shapes)
