"""expert_gmm_roofline.train_4k: the grouped expert products of the
traced window, forward and dx ("_expert_gmm", and "jvp_jit__expert_gmm__"
in the B tick's re-forward) and dW ("_expert_tgmm": the names of their
jitted wrappers in kernels/expert_gmm.py), at their roofline time over
their device time. Their operations and bytes are the architecture's
(``expert_gmm_step``), from the window's last step's held rows a layer
(the program's ``moe_rows_held``) and rows an expert (``moe_load``).
Moves train_tokens_per_s. Reads nothing where no such kernel ran or the
program counts no held rows."""

KERNELS = {"expert_gmm": ["_expert_gmm", "jvp_jit__expert_gmm__"],
           "expert_tgmm": ["_expert_tgmm"]}


def read(rec):
    met = rec.get("step_metrics") or {}
    step = getattr(rec["arch"], "expert_gmm_step", None)
    traced = {k: rec["trace"]["kernels"][k] for k in KERNELS}
    seconds = sum(k["seconds"] for k in traced.values())
    if step is None or seconds <= 0 or met.get("moe_rows_held") is None \
            or met.get("moe_load") is None:
        return None
    t = rec["traffic"]
    microbatches = round(rec["tokens"] / rec["steps"] / t["seq"]
                         / t["microbatch_size"])
    counted = step(rec["config"], met["moe_rows_held"], met["moe_load"],
                   microbatches, t["seq"] * t["microbatch_size"],
                   rec["peaks"])
    least = {"gmm": traced["expert_gmm"], "tgmm": traced["expert_tgmm"]}
    roof = sum(least[k]["calls"] * secs / calls
               for k, (calls, secs) in counted.items() if calls)
    return 100.0 * roof / seconds if roof else None
