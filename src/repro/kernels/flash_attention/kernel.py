"""Pallas TPU flash attention: GQA, causal/sliding-window masks, logit
softcap — the compute hot spot of every attention arch in the pool.

TPU adaptation (vs the CUDA flash algorithm): the online-softmax loop runs
over the *grid's* minor dimension with VMEM scratch carrying (m, l, acc)
between grid steps — the MXU sees (bq*G, hd) x (hd, bk) matmuls with
hardware-aligned tiles; fully-masked KV blocks are skipped with ``pl.when``
(block-sparse causality/window, no wasted MXU work).

Layout: q (BK, Sq, G, hd); k,v (BK, Skv, hd) — one grid row per (batch x
kv-head), GQA group folded into the q-block rows.

Precision: blocks are upcast to f32, and the two dots run at the MXU's
default precision, as XLA's f32 dots on a TPU do: operands rounded to
bf16, products accumulated in f32.  On a TPU v5e at gemma3-1b widths the
output is within about 0.4% of max |out| of an f32 reference
(``chip_smoke.py``, kernels phase).  The interpreter on the CPU computes
the dots in full f32, so CPU tests cannot see this.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, softcap: float, window: int, causal: bool,
                  bq: int, bk: int, nk: int, g: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    # block-level reachability (skip fully masked blocks)
    needed = True
    if causal:
        needed = k_start <= q_start + bq - 1
        if window:
            needed = jnp.logical_and(
                needed, k_start + bk - 1 > q_start - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32).reshape(bq * g, -1)   # (bq*G, hd)
        k = k_ref[0].astype(jnp.float32)                        # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq * g, bk), 0) // g \
            + q_start
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq * g, bk), 1) + k_start
        allow = jnp.ones((bq * g, bk), jnp.bool_)
        if causal:
            allow = cols <= rows
            if window:
                allow &= cols > rows - window
        s = jnp.where(allow, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        out = (acc_scr[...] / l[:, None]).reshape(bq, g, -1)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "window", "causal", "bq", "bk",
                     "interpret"))
def flash_attention_bkg(q, k, v, *, scale: float, softcap: float = 0.0,
                        window: int = 0, causal: bool = True, bq: int = 128,
                        bk: int = 128, interpret: Optional[bool] = None):
    """q: (BK, Sq, G, hd); k,v: (BK, Skv, hd) -> (BK, Sq, G, hd)."""
    BK, Sq, G, hd = q.shape
    Skv = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nq, nk = Sq // bq, Skv // bk

    kernel = functools.partial(
        _flash_kernel, scale=scale, softcap=softcap, window=window,
        causal=causal, bq=bq, bk=bk, nk=nk, g=G)

    return pl.pallas_call(
        kernel,
        grid=(BK, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, G, hd), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, G, hd), lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BK, Sq, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq * G,), jnp.float32),
            pltpu.VMEM((bq * G,), jnp.float32),
            pltpu.VMEM((bq * G, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
