"""Operations and bytes of serving work for a decoder whose every FFN is
a layer of sparse experts, of which this chip holds a share (a
configuration with ``moe``: ``n_experts`` routed, ``n_held`` of them
here, ``experts_per_token`` chosen per token).

Attention, K/V, embedding rows, norms and the output head are counted as
``perfbench.work`` counts them.  The expert layer adds:

- the router: 2·d·E operations per token (E the router's width), its
  float32 weights read once per pass;
- the experts: each token's k choices fall on a held expert
  k·held/E times, the expectation under routing that spreads tokens
  evenly (4 for 8 of 64 with 32 held); each such row is 2·3·d·f
  operations (gate, up and down of width f);
- decode bytes: each expected row reads one expert's weights (3·d·f in
  the served dtype), however many experts the program reads;
- prompt bytes: every held expert's weights once per chunk of ``CHUNK``
  tokens, since a chunk's rows reach every held expert.

Not counted, as in ``perfbench.work``: the allocated length of a cache
and copies an implementation makes.
"""
from __future__ import annotations

from typing import Dict

from perfbench import work

CHUNK = 512              # prompt tokens a pass: the serving path's chunk


def attention_params(c: Dict) -> int:
    """Matmul parameters of one layer's attention."""
    return work.layer_matmul_params({**c, "d_ff": 0})


def expert_params(c: Dict) -> int:
    return 3 * c["d_model"] * c["moe"]["d_ff_expert"]


def router_params(c: Dict) -> int:
    return c["d_model"] * c["moe"]["n_experts"]


def held_rows_per_token(c: Dict) -> float:
    m = c["moe"]
    held = m.get("n_held") or m["n_experts"]
    return m["experts_per_token"] * held / m["n_experts"]


def _per_token_params(c: Dict) -> float:
    """Matmul parameters one token meets in one layer."""
    return (attention_params(c) + router_params(c)
            + held_rows_per_token(c) * expert_params(c))


def _fixed_bytes(c: Dict, dtype_bytes: int = 2) -> int:
    """Attention and head weights in the served dtype, router and norm
    scales in float32."""
    L, d = c["n_layers"], c["d_model"]
    norms = (2 * L + 1) * d
    if c.get("qk_norm"):
        norms += 2 * L * c["head_dim"]
    return ((L * attention_params(c) + work.head_params(c)) * dtype_bytes
            + (L * router_params(c) + norms) * 4)


def decode_token(c: Dict, ctx: int) -> dict:
    """One decode step whose new token is position ``ctx - 1``."""
    kinds = work._layer_kinds(c)
    span = sum(work._span(c, k, ctx) for k in kinds)
    L = c["n_layers"]
    flops = 2 * (L * _per_token_params(c) + work.head_params(c))
    flops += work._attn_flops_per_position(c) * span
    kv = work.kv_bytes_per_position(c)
    nbytes = _fixed_bytes(c) + c["d_model"] * 2
    nbytes += L * held_rows_per_token(c) * expert_params(c) * 2
    nbytes += kv * span + kv * L
    return {"flops": flops, "bytes": nbytes}


def prompt(c: Dict, P: int) -> dict:
    """A whole prompt of ``P`` tokens, up to its last position's logits."""
    kinds = work._layer_kinds(c)
    L = c["n_layers"]
    held = c["moe"].get("n_held") or c["moe"]["n_experts"]
    flops = 2 * P * L * _per_token_params(c) + 2 * work.head_params(c)
    flops += work._attn_flops_per_position(c) * sum(
        work._causal_sum(c, k, P) for k in kinds)
    chunks = -(-P // CHUNK)
    nbytes = _fixed_bytes(c) + P * c["d_model"] * 2
    nbytes += chunks * L * held * expert_params(c) * 2
    nbytes += P * L * work.kv_bytes_per_position(c)
    return {"flops": flops, "bytes": nbytes}
