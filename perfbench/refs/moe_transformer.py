"""Plain reference of a decoder with sparse experts, sliding and full
attention layers, and YaRN on the full ones (the Mellum 2 form).

    x = E[tokens]
    per layer:  h = RMSNorm(x)
                q = rot(RMSNorm_q(W_q h)),  k = rot(RMSNorm_k(W_k h)),  v = W_v h
                x += W_o Attn(q, k, v)
                h = RMSNorm(x)
                x += sum over held e of g_e(h) W_down_e(SiLU(W_gate_e h) * W_up_e h)
    logits = W_head RMSNorm(x)

RMSNorm_q and RMSNorm_k normalise each head's vector (``qk_norm``).
Attention is causal; a sliding layer ("local") sees its own position and
the ``window_size - 1`` before it.  ``rot`` is the rotate-half rotary
embedding at ``rope_theta``; on full layers ("global") with
``rope_yarn`` set, its frequencies are YaRN's (arXiv 2309.00071, as HF's
``rope_type: yarn``): theta^(-2i/d) below the ramp, that over ``factor``
above it, blended between the dimensions that turn ``beta_fast`` and
``beta_slow`` times over ``original_max_position_embeddings``, and cos
and sin are scaled by ``attention_factor``.

Routing: p = softmax(h W_router) over all ``moe.n_experts``; the top
``experts_per_token`` of p, divided by their sum, are the weights g, and
g_e is 0 for an expert that is not among them.  Only the experts held
here, ``n_held`` from ``first_held`` (all where ``n_held`` is 0), add to
x; their weights come stacked in that order.  Every expert is computed
for every position and weighted by g_e, so no token is dropped.

Everything runs in float32 with every dot at ``Precision.HIGHEST``, one
layer at a time and one expert at a time.  Nothing of the program is
imported; the norm, embedding, output head, layer slicing and rounding
are those of ``refs/transformer.py``.  ``quant`` is its control: every
weight matrix, the router's among them, rounded per output channel.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.refs.transformer import (HI, _embed, _Frozen, _head,
                                        _layer_params, _norm, _quantize)


def _inv_freq(hd: int, theta: float, yarn: Optional[Dict]):
    """(inverse frequencies (hd/2,), scale of cos and sin)."""
    i = np.arange(hd // 2, dtype=np.float64)
    base = theta ** (-2.0 * i / hd)
    if not yarn:
        return base, 1.0

    def turning_dim(turns):
        return hd * math.log(yarn["original_max_position_embeddings"]
                             / (2 * math.pi * turns)) / (2 * math.log(theta))
    low = max(math.floor(turning_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(turning_dim(yarn["beta_slow"])), hd - 1)
    keep = 1 - np.clip((i - low) / max(high - low, 1e-3), 0, 1)
    freq = base / yarn["factor"] * (1 - keep) + base * keep
    return freq, yarn["attention_factor"]


def _rope(x, pos, theta, yarn):
    """x: (S, heads, hd); rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    freq, scale = _inv_freq(x.shape[-1], theta, yarn)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freq, jnp.float32)
    cos = (jnp.cos(ang) * scale)[:, None]
    sin = (jnp.sin(ang) * scale)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _experts(p, h, c, quant):
    """The held experts' part of the layer for every position of h, and
    the number of held experts among each position's top k."""
    m = c["moe"]
    f32 = lambda a: a.astype(jnp.float32)
    k, first = m["experts_per_token"], m.get("first_held", 0)
    held = m.get("n_held") or m["n_experts"]
    router = _quantize(f32(p["router"]), quant, axis=0)
    probs = jax.nn.softmax(jnp.dot(h, router, precision=HI), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    g = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(
        top)[:, first:first + held]

    def one(y, e):
        w = {n: _quantize(f32(p[n][e]), quant, axis=0)
             for n in ("w_gate", "w_up", "w_down")}
        a = jax.nn.silu(jnp.dot(h, w["w_gate"], precision=HI)) \
            * jnp.dot(h, w["w_up"], precision=HI)
        return y + g[:, e, None] * jnp.dot(a, w["w_down"], precision=HI), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    return y, jnp.sum(g > 0, axis=-1)


@partial(jax.jit, static_argnames=("c", "local", "quant"))
def _layer(p, x, *, c, local: bool, quant):
    S = x.shape[0]
    H, K, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    f32 = lambda a: a.astype(jnp.float32)
    w = {n: _quantize(f32(v), quant, axis=0)
         for n, v in p["attn"].items() if n.startswith("w")}
    pos = jnp.arange(S)
    h = _norm(x, f32(p["ln1"]), c["norm_eps"])
    q = jnp.dot(h, w["wq"], precision=HI).reshape(S, H, hd)
    k = jnp.dot(h, w["wk"], precision=HI).reshape(S, K, hd)
    v = jnp.dot(h, w["wv"], precision=HI).reshape(S, K, hd)
    if c.get("qk_norm"):
        q = _norm(q, f32(p["attn"]["q_norm"]), c["norm_eps"])
        k = _norm(k, f32(p["attn"]["k_norm"]), c["norm_eps"])
    theta, yarn = c["rope_theta"], None
    if not local:
        theta = c.get("rope_theta_global") or theta
        yarn = c.get("rope_yarn")
    q, k = _rope(q, pos, theta, yarn), _rope(k, pos, theta, yarn)
    q = q.reshape(S, K, H // K, hd)
    s = jnp.einsum("skgd,tkd->kgst", q, k, precision=HI) * hd ** -0.5
    allow = pos[None, :] <= pos[:, None]
    if local and c.get("window_size"):
        allow &= pos[None, :] > pos[:, None] - c["window_size"]
    s = jnp.where(allow, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", a, v, precision=HI).reshape(S, H * hd)
    x = x + jnp.dot(o, w["wo"], precision=HI)
    h = _norm(x, f32(p["ln2"]), c["norm_eps"])
    y, held = _experts(p["moe"], h, c, quant)
    return x + y, held


def _forward(params: Dict, c: Dict, tokens, quant: Optional[str]):
    cfg = _Frozen(c)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _embed(params["embed"], tokens, c=cfg, quant=quant)
    pattern = list(c["layer_pattern"])
    held = jnp.zeros(tokens.shape, jnp.int32)
    for l in range(c["n_layers"]):
        p = _layer_params(params, l, len(pattern))
        x, n = _layer(p, x, c=cfg, local=pattern[l % len(pattern)] == "local",
                      quant=quant)
        held = held + n
    head = params["embed"] if c.get("tie_embeddings") else params["lm_head"]
    return _head(head, params["final_norm"], x, c=cfg, quant=quant), held


def logits(params: Dict, c: Dict, tokens, quant: Optional[str] = None):
    """float32 logits (S, vocab) at every position of ``tokens``."""
    return _forward(params, c, tokens, quant)[0]


def held_pairs(params: Dict, c: Dict, tokens) -> np.ndarray:
    """(S,) the (position, held expert) pairs of each position's routing,
    summed over the layers."""
    return np.asarray(_forward(params, c, tokens, None)[1])
