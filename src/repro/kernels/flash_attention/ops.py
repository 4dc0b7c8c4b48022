"""Jitted model-facing wrapper: adapts (B,S,K,G,hd) GQA tensors to the
kernel layout and plugs into ``repro.models.attention.set_attention_impl``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bkg


def flash_attention(q, k, v, *, window: int = 0, softcap: float = 0.0,
                    scale: float, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: Optional[bool] = None):
    """q: (B,S,K,G,hd); k,v: (B,Skv,K,hd) -> (B,S,K,G,hd)."""
    B, Sq, K, G, hd = q.shape
    Skv = k.shape[1]
    qf = q.transpose(0, 2, 1, 3, 4).reshape(B * K, Sq, G, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, Skv, hd)
    o = flash_attention_bkg(qf, kf, vf, scale=scale, softcap=softcap,
                            window=window, causal=causal, bq=bq, bk=bk,
                            interpret=interpret)
    return o.reshape(B, K, Sq, G, hd).transpose(0, 2, 1, 3, 4)
