"""The paper's GPT models (Table 4): 1.5B / 6.2B / 14.6B, seq 1024.

Used by the Table-3/5 and Fig-5/6/7 benchmark reproductions (simulator cost
model), by the end-to-end training example at reduced width, and — through
``chip_share()`` — at the 1.5B model's published widths on one chip.
"""

from repro.models.common import ModelConfig, RunConfig

SIZES = {
    "1.5B": dict(n_layers=22, n_heads=24, d_model=2304),
    "6.2B": dict(n_layers=30, n_heads=32, d_model=4096),
    "14.6B": dict(n_layers=46, n_heads=40, d_model=5120),
}


def config(size: str = "1.5B") -> ModelConfig:
    s = SIZES[size]
    return ModelConfig(
        name=f"gpt-{size}", n_layers=s["n_layers"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_kv_heads=s["n_heads"],
        d_ff=4 * s["d_model"], vocab=50304,
        norm="layernorm", act="gelu_mlp", max_seq=1024,
    )


def paper_run(n_micro: int = 8, unit: int = 0, schedule="zeropp") -> RunConfig:
    """The paper's setup: PP=4, DP(FSDP)=4 per node group."""
    return RunConfig(pp=4, vpp=2, microbatches=n_micro, unit=unit,
                     schedule=schedule)


def production_run(shape: str) -> RunConfig:
    from repro.configs._base import make_run
    return make_run(config("6.2B"), shape, pp=16, vpp=2)


def reduced():
    cfg = ModelConfig(
        name="gpt-smoke", n_layers=4, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=256, vocab=256, d_head=16, norm="layernorm", act="gelu_mlp",
    )
    rc = RunConfig(pp=2, vpp=2, microbatches=2, param_dtype="float32",
                   compute_dtype="float32")
    return cfg, rc


# One chip's share of the paper's 1.5B deployment (``paper_run``: PP=4 ×
# FSDP=4). Each pipeline stage holds ceil(22 / 4) = 6 layers; this chip
# holds one stage's layers whole plus the untied embedding and head (in
# the deployment they sit on the first and last stage). No width is cut.
CHIP_SHARE = {
    "source": "ZeroPP (arXiv 2402.03791) Table 4, GPT 1.5B: 22 layers, "
              "24 heads, d_model 2304, seq 1024",
    "deployment": "PP=4 x FSDP=4; one stage's layers on this chip, "
                  "embedding and head both held here",
    "reduced": {"n_layers": {"published": 22, "here": 6}},
    "assumed": {"vocab": "50304 (GPT-2's 50257 padded to a multiple of 64)",
                "d_ff": "4 x d_model", "d_head": "d_model / n_heads = 96"},
}


def chip_share() -> tuple[ModelConfig, RunConfig]:
    """1.5B at published widths, cut to one stage (see ``CHIP_SHARE``):
    ≈614M parameters, ≈9.8 GB of bf16 weights + f32 grads and Adam state
    at 16 B/param, leaving the rest of a 16 GB v5e for activations at seq
    1024 over 8 micro-batches (the paper's ``n_micro``). pp=1: the whole
    stage runs on one device; a four-chip host overrides pp/data."""
    import dataclasses

    cfg = dataclasses.replace(
        config("1.5B"), name="gpt-1.5B-stage",
        n_layers=CHIP_SHARE["reduced"]["n_layers"]["here"])
    return cfg, RunConfig(pp=1, vpp=1, microbatches=8, unit=1,
                          schedule="zeropp")
