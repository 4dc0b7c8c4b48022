"""Plain reference of a decoder-only GQA transformer (Llama/Mistral form).

    x = E[tokens]
    per layer:  h = RMSNorm(x);  x += Attn(h);  x += W_down(SiLU(W_gate h) * W_up h)
    logits = W_head RMSNorm(x)

Attention is causal, with rotary embeddings in the rotate-half form
(frequencies theta^(-i/half)); a sliding-window layer sees its own
position and the ``window - 1`` before it.  RMSNorm is
``x / sqrt(mean(x^2) + eps) * g``; the served weights keep ``g - 1``, so
the reference adds the 1 back.

Everything runs in float32 with every dot at ``Precision.HIGHEST``, one
layer at a time, so that a long sequence fits beside the served weights.
Nothing of the program is imported: the weights come in the program's
layout (stacked per layer) and are read as plain arrays.

``quant="int8"`` / ``"fp8"`` is the control: the same pass with every
weight matrix rounded per output channel to int8 or float8_e4m3 first.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0


def _quantize(w, quant: Optional[str], axis: int):
    """Round ``w`` (float32) per slice along ``axis`` (the reduced axis)."""
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if quant == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.maximum(amax, 1e-30) / _FP8_MAX
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"unknown quant {quant!r}")


def _norm(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g)


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq            # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("c", "local", "quant"))
def _layer(p, x, *, c, local: bool, quant):
    S = x.shape[0]
    H, K, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    f32 = lambda a: a.astype(jnp.float32)
    w = {k: _quantize(f32(v), quant, axis=0)
         for k, v in {**p["attn"], **p["mlp"]}.items()}
    pos = jnp.arange(S)
    h = _norm(x, f32(p["ln1"]), c["norm_eps"])
    q = jnp.dot(h, w["wq"], precision=HI).reshape(S, H, hd)
    k = jnp.dot(h, w["wk"], precision=HI).reshape(S, K, hd)
    v = jnp.dot(h, w["wv"], precision=HI).reshape(S, K, hd)
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
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
    g = jax.nn.silu(jnp.dot(h, w["w_gate"], precision=HI))
    u = jnp.dot(h, w["w_up"], precision=HI)
    return x + jnp.dot(g * u, w["w_down"], precision=HI)


@partial(jax.jit, static_argnames=("c", "quant"))
def _embed(table, tokens, *, c, quant):
    rows = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    return _quantize(rows, quant, axis=1)


@partial(jax.jit, static_argnames=("c", "quant"))
def _head(params_head, g, x, *, c, quant):
    w = _quantize(params_head.astype(jnp.float32), quant, axis=1)
    h = _norm(x, g.astype(jnp.float32), c["norm_eps"])
    return jnp.einsum("sd,vd->sv", h, w, precision=HI)


def _layer_params(params: Dict, l: int, plen: int):
    R = jax.tree.leaves(params["blocks"][0])[0].shape[0] \
        if params["blocks"] else 0
    if l < R * plen:
        return jax.tree.map(lambda a: a[l // plen], params["blocks"][l % plen])
    return params["tail"][l - R * plen]


def logits(params: Dict, c: Dict, tokens, quant: Optional[str] = None):
    """float32 logits (S, vocab) at every position of ``tokens``."""
    cfg = _Frozen(c)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _embed(params["embed"], tokens, c=cfg, quant=quant)
    pattern = list(c["layer_pattern"])
    for l in range(c["n_layers"]):
        p = _layer_params(params, l, len(pattern))
        x = _layer(p, x, c=cfg, local=pattern[l % len(pattern)] == "local",
                   quant=quant)
    head = params["embed"] if c.get("tie_embeddings") else params["lm_head"]
    return _head(head, params["final_norm"], x, c=cfg, quant=quant)


class _Frozen(dict):
    """A configuration dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))

    def __eq__(self, other):
        return dict.__eq__(self, other)
