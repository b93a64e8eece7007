"""Operations and bytes from shapes: the yardstick of every utilisation
and roofline share the benchmark reports. ``m`` is a configuration
file's "model" block."""

from __future__ import annotations


def dims(m: dict):
    d, h, g = m["d_model"], m["n_heads"], m["n_kv_heads"]
    return d, h, g, m["d_head"] or d // h, m["d_ff"], m["vocab"], \
        m["n_layers"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Model FLOPs of one trained token, forward and backward, with no
    recomputation counted (Narayanan et al. 2021, eq. 3 without the
    recompute pass): 72 L h^2 (1 + s / 6h + V / 12 h L)."""
    d, _, _, _, _, v, n = dims(m)
    return 72.0 * n * d * d * (1 + seq / (6.0 * d) + v / (12.0 * d * n))


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


def serve_call_flops(m: dict, width: int, positions) -> float:
    """Model FLOPs of one serve step over its rows: every token through
    every layer's matrices and attention, and the head once per row."""
    d, h, g, e, f, v, n = dims(m)
    per_token = 2.0 * n * (2 * d * h * e + 2 * d * g * e + 2 * d * f)
    attn, _ = paged_attn_call(m, width, positions)
    return len(positions) * (width * per_token + 2.0 * d * v) + n * attn


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: compute or memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
