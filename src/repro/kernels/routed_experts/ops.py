"""Model-facing wrapper for the routed-experts kernel: sorts a few
tokens' (token, choice) pairs by held expert and builds the kernel's
scalar and gate operands."""
from __future__ import annotations

from typing import Callable, Optional

import jax.numpy as jnp

from repro.kernels.routed_experts.kernel import routed_experts_sorted


def routed_experts(x, gate, expert, w_gate, w_up, w_down, *, act: Callable,
                   layer=None, interpret: Optional[bool] = None):
    """x (T, D); gate (T, k) f32; expert (T, k) int32, the choice's index
    among the held experts, any value outside [0, E) for a choice held
    elsewhere.  Weights (E, D, F), (E, D, F), (E, F, D), or stacked over
    layers with a leading L axis and ``layer`` (an int32 scalar) picking
    one.  Returns sum over held choices of gate · expert(x), (T, D) in
    x's dtype."""
    if layer is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    E = w_gate.shape[1]
    T, k = expert.shape
    flat = expert.reshape(-1)
    key = jnp.where((flat >= 0) & (flat < E), flat, E)
    order = jnp.argsort(key, stable=True)
    ids = key[order].astype(jnp.int32)
    held = ids < E
    # a pair held elsewhere repeats the last held pair's block: no DMA
    blk = jnp.where(held, ids, jnp.max(jnp.where(held, ids, 0)))
    Tp = -(-T // 8) * 8
    rows = jnp.arange(Tp)[None, :] == (order // k)[:, None]
    gates = jnp.where(rows, gate.reshape(-1)[order][:, None], 0.0)
    y = routed_experts_sorted(
        ids, blk, jnp.reshape(layer, (1,)).astype(jnp.int32),
        jnp.pad(x, ((0, Tp - T), (0, 0))),
        gates.astype(jnp.float32)[..., None], w_gate, w_up, w_down,
        act=act, interpret=interpret)
    return y[:T]


def blocks_read(expert, n_held: int):
    """The distinct held experts among ``expert``'s choices (int32): the
    experts whose weights ``routed_experts`` reads."""
    hit = jnp.zeros((n_held + 1,), jnp.int32).at[
        jnp.where((expert >= 0) & (expert < n_held), expert, n_held)].set(1)
    return jnp.sum(hit[:n_held])
