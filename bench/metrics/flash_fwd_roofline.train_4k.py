"""flash_fwd_roofline.train_4k: the traced window's calls of the flash
forward kernel ("_flash_fwd" on the chip, the name of its jitted
wrapper) at the cell's micro-batch shape, with q/k and v of their own
widths (latent attention: e = qk_nope + rope, ev = v_head), at their
roofline time (the architecture's ``flash_fwd``) over their device time.
Moves train_tokens_per_s. Reads nothing where no such kernel ran."""

import flops

KERNELS = {"flash_fwd": ["_fwd_kernel", "_flash_fwd", "flash_attention"]}


def read(rec):
    k = rec["trace"]["kernels"]["flash_fwd"]
    count = getattr(rec["arch"], "flash_fwd", None)
    if count is None or not k["calls"] or k["seconds"] <= 0:
        return None
    t = rec["traffic"]
    f, b = count(rec["config"], t["microbatch_size"], t["seq"])
    return 100.0 * k["calls"] * flops.roofline_s(f, b, rec["peaks"]) \
        / k["seconds"]
