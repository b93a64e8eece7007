"""paged_attn_roofline.serve: the paged attention kernel (_paged_kernel,
named "paged_attention" in the trace)
in the traced window: every serve step there runs it once a layer over
the rows it was called for; their roofline time (flops.paged_attn_call,
each row's own context) over the kernel's device time. Moves
itl_p95_ms."""

import flops

KERNELS = {"paged_attn": ["paged_attention", "_paged_kernel"]}


def read(rec):
    k = rec["trace"]["kernels"]["paged_attn"]
    calls = rec.get("trace_calls") or []
    if not k["calls"] or k["seconds"] <= 0 or not calls:
        return None
    m = rec["config"]
    ideal = sum(flops.roofline_s(*flops.paged_attn_call(m, w, pos),
                                 rec["peaks"]) for w, pos in calls)
    return 100.0 * m["n_layers"] * ideal / k["seconds"]
