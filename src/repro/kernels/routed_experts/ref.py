"""Pure-jnp oracle for the routed-experts kernel: every (token, choice)
pair on its own, in float32."""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def routed_experts_ref(x, gate, expert, w_gate, w_up, w_down, *,
                       act: Callable):
    """Arguments as ``ops.routed_experts`` with unstacked weights."""
    E = w_gate.shape[0]
    held = (expert >= 0) & (expert < E)
    e = jnp.where(held, expert, 0)
    f32 = lambda w: w.astype(jnp.float32)[e]          # (T, k, ...)
    hi = jax.lax.Precision.HIGHEST
    xf = x.astype(jnp.float32)
    h = act(jnp.einsum("td,tkdf->tkf", xf, f32(w_gate), precision=hi)) \
        * jnp.einsum("td,tkdf->tkf", xf, f32(w_up), precision=hi)
    h = h * jnp.where(held, gate, 0.0)[..., None]
    return jnp.einsum("tkf,tkfd->td", h, f32(w_down),
                      precision=hi).astype(x.dtype)
