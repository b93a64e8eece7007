"""xent_roofline.train: the fused cross-entropy's three kernels
(_p1_kernel, _p2_dh_kernel, _p2_dw_kernel; on the chip all three carry
the name of their jitted wrapper, "softmax_xent") together. Each call,
over one micro-batch's tokens, runs the three once, so calls are the
events over three; their roofline time (flops.fused_xent) over their
device time. Moves train_tokens_per_s."""

import flops

KERNELS = {"xent": ["_p1_kernel", "_p2_dh_kernel", "_p2_dw_kernel",
                    "softmax_xent"]}


def read(rec):
    k = rec["trace"]["kernels"]["xent"]
    calls = k["calls"] / 3
    if not calls or k["seconds"] <= 0:
        return None
    t = rec["traffic"]
    f, b = flops.fused_xent(rec["config"], t["microbatch_size"] * t["seq"])
    return 100.0 * calls * flops.roofline_s(f, b, rec["peaks"]) \
        / k["seconds"]
