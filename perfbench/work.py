"""Operations and bytes that a GQA transformer's serving work needs.

Counted from the configuration's shapes, for the algorithm and not for
any implementation of it:

- a decode token at context ``c`` (the new token is the ``c``-th
  position) reads every weight once, except the embedding table, of which
  it reads one row; it reads the K/V of ``min(c, window)`` positions in
  each layer and writes one position.  Its operations are 2 per matmul
  parameter plus attention over those positions (QK^T and AV, 2 each per
  query head, head dimension and position).
- a prompt of ``P`` tokens reads the same weights once and ``P`` embedding
  rows, does ``P`` times the operations of the layers' matmuls, the output
  head once (only the last position's logits are needed), causal attention
  over each position's window, and writes ``P`` positions of K/V.

Not counted: the allocated ``max_len`` of a cache, the copy a non-donated
cache update makes, or one weight read per prompt token.  Those belong to
an implementation, and removing them shows as a higher share.

The least time for a piece of work is the larger of its operations over
the peak rate and its bytes over the peak bandwidth (``least_seconds``).
"""
from __future__ import annotations

from typing import Dict

LOCAL = "local"          # sliding-window layers; "global" attends to all


def _layer_kinds(c: Dict) -> list:
    pattern = list(c["layer_pattern"])
    reps = -(-c["n_layers"] // len(pattern))
    return (pattern * reps)[: c["n_layers"]]


def _span(c: Dict, kind: str, ctx: int) -> int:
    """Positions a query at context ``ctx`` attends to in a layer."""
    if kind == LOCAL and c.get("window_size"):
        return min(ctx, c["window_size"])
    return ctx


def layer_matmul_params(c: Dict) -> int:
    d, h, k, hd, f = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                      c["head_dim"], c["d_ff"])
    attn = d * h * hd + 2 * d * k * hd + h * hd * d
    mlp = 3 * d * f                      # gated: gate, up, down
    return attn + mlp


def head_params(c: Dict) -> int:
    return c["vocab_size"] * c["d_model"]


def weight_bytes(c: Dict, dtype_bytes: int = 2) -> int:
    """Every weight but the embedding table: the layers' matmuls and the
    output head in the served dtype, the norm scales in float32."""
    matmul = c["n_layers"] * layer_matmul_params(c) + head_params(c)
    norms = (2 * c["n_layers"] + 1) * c["d_model"]
    return matmul * dtype_bytes + norms * 4


def kv_bytes_per_position(c: Dict, dtype_bytes: int = 2) -> int:
    """K and V of one position in one layer."""
    return 2 * c["n_kv_heads"] * c["head_dim"] * dtype_bytes


def _attn_flops_per_position(c: Dict) -> int:
    return 4 * c["n_heads"] * c["head_dim"]


def decode_token(c: Dict, ctx: int) -> dict:
    """One decode step whose new token is position ``ctx - 1``."""
    kinds = _layer_kinds(c)
    spans = [_span(c, k, ctx) for k in kinds]
    flops = 2 * (c["n_layers"] * layer_matmul_params(c) + head_params(c))
    flops += _attn_flops_per_position(c) * sum(spans)
    kv = kv_bytes_per_position(c)
    nbytes = weight_bytes(c) + c["d_model"] * 2
    nbytes += kv * sum(spans) + kv * len(kinds)
    return {"flops": flops, "bytes": nbytes}


def _causal_sum(c: Dict, kind: str, P: int) -> int:
    """Sum over positions 1..P of the positions each attends to."""
    if kind == LOCAL and c.get("window_size") and P > c["window_size"]:
        W = c["window_size"]
        return W * (W + 1) // 2 + (P - W) * W
    return P * (P + 1) // 2


def prompt(c: Dict, P: int) -> dict:
    """A whole prompt of ``P`` tokens, up to its last position's logits."""
    kinds = _layer_kinds(c)
    flops = 2 * P * c["n_layers"] * layer_matmul_params(c)
    flops += 2 * head_params(c)
    flops += _attn_flops_per_position(c) * sum(
        _causal_sum(c, k, P) for k in kinds)
    nbytes = weight_bytes(c) + P * c["d_model"] * 2
    nbytes += P * len(kinds) * kv_bytes_per_position(c)
    return {"flops": flops, "bytes": nbytes}


def least_seconds(work: dict, peak: dict) -> float:
    return max(work["flops"] / peak["flops_per_s"],
               work["bytes"] / peak["bytes_per_s"])
