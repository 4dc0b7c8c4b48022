"""Pallas TPU kernel for the RWKV-6 (Finch) wkv recurrence, chunkwise.

TPU adaptation of the CUDA wkv kernel: instead of one thread per channel
scanning time steps, the recurrence is reformulated as chunk-local matmuls
(MXU work) with the (hd x hd) state carried across the chunk-grid dimension
in VMEM scratch.  Intra-chunk pairwise decays are factored in log space
around one reference per row tile (``_intra``) so f32 never overflows.

Layout: r,k,v,logw: (BH, S, hd) f32; u: (BH, 1, hd); grid (BH, S/c); state
scratch (hd, hd), transposed.

Precision: inputs, state and accumulation are f32, but the dots run at the
MXU's default precision, as XLA's f32 dots on a TPU do: operands rounded
to bf16.  On a TPU v5e at rwkv6-7b head width the output is within about
0.4% of max |y| of the exact scan (``chip_smoke.py``, kernels phase).  The
interpreter on the CPU computes the dots in full f32, so CPU tests cannot
see this.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _cumsum_rows(x):
    """Inclusive prefix sum over rows: a log-depth ladder of sublane rolls
    (Mosaic has no lowering for ``cumsum``)."""
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    shift = 1
    while shift < x.shape[0]:
        x = x + jnp.where(row >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _intra(rc, kc, vc, lp, lp_prev, u, c: int, tile: int):
    """Intra-chunk output.  Row tile T takes ``lp`` at its first row as the
    reference: r-side factors ``exp(lp_{t-1} - ref)`` and k-side factors
    ``exp(ref - lp_s)``, the latter masked in log space for s past the
    tile, so no factor exceeds ``exp(tile*|logw|_max)`` (see
    models/rwkv.py).  One (tile, hd) x (hd, c) matmul per row tile."""
    hd = rc.shape[-1]
    s_row = jax.lax.broadcasted_iota(jnp.int32, (c, hd), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tile, c), 1)
    t_row = jax.lax.broadcasted_iota(jnp.int32, (tile, c), 0)
    rows = []
    for lo in range(0, c, tile):
        hi = lo + tile
        ref = lp[lo:lo + 1]                                    # (1, hd)
        r_f = rc[lo:hi] * jnp.exp(lp_prev[lo:hi] - ref)       # (tile, hd)
        k_f = kc * jnp.exp(jnp.where(s_row < hi, ref - lp, -jnp.inf))
        a = jax.lax.dot_general(r_f, k_f, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        rows.append(jnp.where(t_row + lo > col, a, 0.0))      # strictly s < t
    A = jnp.concatenate(rows, axis=0)                          # (c, c)
    y = jnp.dot(A, vc, preferred_element_type=jnp.float32)
    diag_bonus = jnp.sum(rc * u * kc, axis=1, keepdims=True)   # (c, 1)
    return y + diag_bonus * vc


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, st_scr, *,
                c: int, tile: int, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)

    rc = r_ref[0]
    kc = k_ref[0]
    vc = v_ref[0]
    wc = w_ref[0]
    u = u_ref[0]                       # (1, hd)
    lp = _cumsum_rows(wc)
    lp_prev = lp - wc
    y = _intra(rc, kc, vc, lp, lp_prev, u, c, tile)
    # the scratch holds the state transposed, S^T (value x key), so the
    # per-key decay is a row broadcast and no (1, hd) row needs a transpose
    st_t = st_scr[...]
    y = y + jax.lax.dot_general(rc * jnp.exp(lp_prev), st_t,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    lp_last = lp[c - 1:]                                       # (1, hd)
    k_out = kc * jnp.exp(lp_last - lp)
    st_scr[...] = st_t * jnp.exp(lp_last) + jax.lax.dot_general(
        vc, k_out, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "tile", "interpret"))
def wkv6_chunked(r, k, v, logw, u, *, chunk: int = 64, tile: int = 8,
                 interpret: Optional[bool] = None):
    """r,k,v,logw: (BH, S, hd) f32; u: (BH, hd) -> y (BH, S, hd)."""
    BH, S, hd = r.shape
    # u travels as (BH, 1, hd) so its block's last two dims are whole
    # array dims, as Mosaic's (8, 128) tiling rule requires
    u = u.reshape(BH, 1, hd)
    c = min(chunk, S)
    assert S % c == 0 and c % tile == 0, (S, c, tile)
    nc = S // c
    kernel = functools.partial(_wkv_kernel, c=c, tile=tile, nc=nc)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, c, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, c, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, c, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, c, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(r, k, v, logw, u)
