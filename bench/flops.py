"""Operations and bytes a step needs, from the configuration's shapes.

This counts the work the algorithm needs, not the work the current code
does: causal attention counts only the keys at or before each query, decode
reads the K/V only up to the token's position, and the K/V projection of a
prompt counts once.  A later change that stops doing needless work is then
judged against the same count, and no share of a roofline or a peak can
read above 100%.

FLOPs are the matrix products (2 per multiply-add); norms, rotary
embedding, softmax and biases are left out (under 1% at these widths).
Bytes are HBM traffic at the configuration's parameter type: every weight
read once per step, the embedding rows gathered, the K/V read and written.
"""
from __future__ import annotations

from dataclasses import dataclass

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


@dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(self.flops + other.flops, self.bytes + other.bytes)


def _sizes(m: dict):
    d, h, kv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * m["d_ff"]
    norms = 2 * d + (h + 2 * kv) * hd * bool(m["qkv_bias"])
    layers = m["n_layers"] * (per_layer + norms) + d
    head = d * m["vocab_size"]
    return per_layer, layers, head, DTYPE_BYTES[m["dtype"]]


def kv_bytes_per_position(m: dict) -> float:
    """K and V of one position, over all layers."""
    return (m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"]
            * DTYPE_BYTES[m["dtype"]])


def prefill(m: dict, t: int) -> Cost:
    """A prompt of ``t`` tokens: every layer over every position, causal
    attention over the lower triangle, and logits at the last position
    only (the program samples from that one)."""
    per_layer, layers, head, b = _sizes(m)
    n_l, h, hd = m["n_layers"], m["n_heads"], m["head_dim"]
    pairs = t * (t + 1) // 2                       # query-key pairs, causal
    flops = 2 * t * n_l * per_layer + n_l * 4 * h * hd * pairs + 2 * head
    nbytes = (layers + head) * b + t * m["d_model"] * b \
        + t * kv_bytes_per_position(m)             # K/V written once
    return Cost(float(flops), float(nbytes))


def decode(m: dict, pos: int) -> Cost:
    """One token at absolute position ``pos`` (0-based), attending to the
    ``pos`` cached positions and itself."""
    per_layer, layers, head, b = _sizes(m)
    n_l, h, hd = m["n_layers"], m["n_heads"], m["head_dim"]
    flops = 2 * n_l * per_layer + n_l * 4 * h * hd * (pos + 1) + 2 * head
    nbytes = (layers + head) * b + m["d_model"] * b \
        + (pos + 1) * kv_bytes_per_position(m)     # K/V read, new K/V written
    return Cost(float(flops), float(nbytes))


def least_seconds(cost: Cost, peak_flops: float, peak_bytes_s: float) -> float:
    """The roofline bound: the larger of compute time and memory time."""
    return max(cost.flops / peak_flops, cost.bytes / peak_bytes_s)
