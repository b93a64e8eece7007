"""Operations and bytes of the kernels from their shapes: the yardstick
of every roofline share the benchmark reports. ``m`` is a configuration
file's "model" block. A whole step's model FLOPs belong to its
architecture (``bench/arch/<name>.py``)."""

from __future__ import annotations


def dims(m: dict):
    d, h, g = m["d_model"], m["n_heads"], m["n_kv_heads"]
    return d, h, g, m["d_head"] or d // h, m["d_ff"], m["vocab"], \
        m["n_layers"]


def flash_fwd(m: dict, batch: int, seq: int, elem: int = 2):
    """One causal flash-attention forward over [batch, seq] (all heads):
    QK^T and PV over the causal pairs; q, k, v read and o written."""
    _, h, g, e, _, _, _ = dims(m)
    pairs = seq * (seq + 1) / 2
    flops = 4.0 * batch * h * e * pairs
    nbytes = elem * batch * seq * e * (2 * h + 2 * g)
    return flops, nbytes


def fused_xent(m: dict, rows: int, elem: int = 4):
    """One fused softmax cross-entropy call, forward and backward, over
    ``rows`` tokens: logits, dh and dW are 2 n d V each; h and W read
    once, dh and dW written once, labels read (int32). ``elem`` is the
    width of h and W as the training step passes them (float32)."""
    d, _, _, _, _, v, _ = dims(m)
    flops = 6.0 * rows * d * v
    nbytes = elem * (2 * rows * d + 2 * d * v) + 4 * rows
    return flops, nbytes


def paged_attn_call(m: dict, width: int, positions, elem: int = 2):
    """One layer's paged attention for a serve step of ``width`` tokens
    per row, rows starting at ``positions``: causal QK^T and PV over each
    row's context, its K/V read from the pool and written for the new
    tokens, q read and o written."""
    _, h, g, e, _, _, _ = dims(m)
    flops = nbytes = 0.0
    for p in positions:
        flops += 4.0 * h * e * (width * p + width * (width + 1) / 2)
        nbytes += elem * ((p + width) * 2 * g * e + width * 2 * g * e
                          + 2 * width * h * e)
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: compute or memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
