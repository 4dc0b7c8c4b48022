"""Model-facing wrapper for the wkv6 kernel."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.rwkv6_chunk.kernel import wkv6_chunked


def wkv6(r, k, v, logw, u, *, chunk: int = 64,
         interpret: Optional[bool] = None):
    """r,k,v,logw: (B,S,H,hd); u: (H,hd) -> (B,S,H,hd)."""
    B, S, H, hd = r.shape
    fold = lambda t: t.astype(jnp.float32).transpose(0, 2, 1, 3) \
        .reshape(B * H, S, hd)
    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, hd) \
        .astype(jnp.float32)
    y = wkv6_chunked(fold(r), fold(k), fold(v), fold(logw), uf,
                     chunk=chunk, interpret=interpret)
    return y.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
