"""expert_load_max.train_4k: in the window's last step, the rows of the
busiest expert held here over the mean held expert's rows, in the layer
where that ratio is worst (1 is even; the program's ``moe_load``, a
histogram over every routed expert, read at the held experts). Lower is
better; moves train_tokens_per_s (the grouped product runs as long as
its groups' tiles). Reads nothing where the program counts no load."""

import numpy as np


def read(rec):
    load = (rec.get("step_metrics") or {}).get("moe_load")
    if load is None:
        return None
    mo = rec["config"]["moe"]
    lo = mo["held_offset"]
    held = np.asarray(load, np.float64)[:, lo:lo + mo["n_experts"]]
    held = held[held.sum(axis=1) > 0]
    if not len(held):
        return None
    return float((held.max(axis=1) / held.mean(axis=1)).max())
