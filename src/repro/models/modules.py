"""Shared neural-net building blocks (pure JAX, pytree params)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def group_norm_heads(x, scale, bias, n_heads: int, eps: float = 1e-5):
    """GroupNorm with one group per head over the last dim (RWKV ln_x)."""
    dt = x.dtype
    *lead, d = x.shape
    x = x.astype(jnp.float32).reshape(*lead, n_heads, d // n_heads)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    x = x.reshape(*lead, d)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------
def rope_freqs(hd: int, theta: float, yarn=None):
    """(inverse frequencies (hd/2,), scale of cos and sin) of a rotary
    table; with ``yarn`` (a ``YarnConfig``) the YaRN blend of HF's
    ``rope_type: yarn``: dimension i keeps theta's frequency below the
    ramp that runs from ``low`` to ``high``, takes it over ``factor``
    above, and a blend between."""
    half = hd // 2
    freq = theta ** (-np.arange(0, half, dtype=np.float32) / half)
    if yarn is None:
        return freq, 1.0

    def dim(turns):      # the dimension that turns ``turns`` times
        return hd * math.log(yarn.original_max_position_embeddings
                             / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(dim(yarn.beta_fast)), 0)
    high = min(math.ceil(dim(yarn.beta_slow)), hd - 1)
    if high == low:
        high += 1e-3
    m = 1 - np.clip((np.arange(half, dtype=np.float32) - low)
                    / (high - low), 0, 1)   # 1 keeps theta's frequency
    freq = freq / yarn.factor * (1 - m) + freq * m
    return freq.astype(np.float32), yarn.attention_factor


def rope(x, positions, theta: float, yarn=None):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq, scale = rope_freqs(hd, theta, yarn)
    ang = positions[..., None].astype(jnp.float32) * freq          # (..., S, half)
    ang = ang[..., None, :]                                        # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_emb(seq_len: int, d: int, dtype=jnp.float32):
    pos = np.arange(seq_len)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / np.power(10_000.0, 2 * i / d)
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb, dtype=dtype)


# ---------------------------------------------------------------------------
# activations / MLP
# ---------------------------------------------------------------------------
def activation(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "gelu_plain": lambda x: jax.nn.gelu(x, approximate=True),
        "relu_sq": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = pdtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = d ** -0.5, f ** -0.5
    if cfg.act == "gelu_plain":  # non-gated (whisper)
        return {
            "w_up": jax.random.normal(k1, (d, f), dt) * s_in,
            "w_down": jax.random.normal(k2, (f, d), dt) * s_out,
        }
    return {
        "w_gate": jax.random.normal(k1, (d, f), dt) * s_in,
        "w_up": jax.random.normal(k2, (d, f), dt) * s_in,
        "w_down": jax.random.normal(k3, (f, d), dt) * s_out,
    }


def mlp(params, x, act_name: str):
    from repro.distributed.context import constrain
    act = activation(act_name)
    if "w_gate" in params:
        h = act(x @ params["w_gate"]) * (x @ params["w_up"])
    else:
        h = act(x @ params["w_up"])
    h = constrain(h, ("batch", "seq", "ff"))
    return h @ params["w_down"]


def init_norm(key, d: int):
    del key
    return jnp.zeros((d,), jnp.float32)
