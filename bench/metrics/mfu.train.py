"""mfu.train: model FLOPs per token (the architecture's
train_flops_per_token, no recomputation counted) times the window's
train tokens per second, over the chips' bf16 peak. Moves
train_tokens_per_s."""


def read(rec):
    per_token = rec["arch"].train_flops_per_token(rec["config"],
                                                  rec["traffic"]["seq"])
    return 100.0 * per_token * rec["train_tokens_per_s"] / (
        rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
