"""flash_fwd_roofline.train: the traced window's calls of the flash
forward kernel (_fwd_kernel; "_flash_fwd" on the chip, the name of its
jitted wrapper), each at the cell's micro-batch shape, at
their roofline time (flops.flash_fwd), over their device time. Moves
train_tokens_per_s. Reads nothing where no such kernel ran."""

import flops

KERNELS = {"flash_fwd": ["_fwd_kernel", "_flash_fwd", "flash_attention"]}


def read(rec):
    k = rec["trace"]["kernels"]["flash_fwd"]
    if not k["calls"] or k["seconds"] <= 0:
        return None
    t = rec["traffic"]
    f, b = flops.flash_fwd(rec["config"], t["microbatch_size"], t["seq"])
    return 100.0 * k["calls"] * flops.roofline_s(f, b, rec["peaks"]) \
        / k["seconds"]
