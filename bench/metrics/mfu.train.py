"""mfu.train: model FLOPs per token (flops.train_flops_per_token, no
recomputation counted) times the window's train tokens per second, over
the chips' bf16 peak. Moves train_tokens_per_s."""

import flops


def read(rec):
    per_token = flops.train_flops_per_token(rec["config"],
                                            rec["traffic"]["seq"])
    return 100.0 * per_token * rec["train_tokens_per_s"] / (
        rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
