"""mfu.serve: model FLOPs of every serve step in the window (each row's
tokens through the matrices and attention, the head once per row;
flops.serve_call_flops) over the time of the engine ticks that did
work, times the bf16 peak. The whole step's share of the peak, which
bounds what any kernel of the step can gain. Moves itl_p95_ms."""

import flops


def read(rec):
    busy = sum(rec["tick_s"])
    if not rec["calls"] or busy <= 0:
        return None
    total = sum(flops.serve_call_flops(rec["config"], w, pos)
                for w, pos in rec["calls"])
    return 100.0 * total / (busy * rec["chips"]
                            * rec["peaks"]["bf16_flops_per_s"])
