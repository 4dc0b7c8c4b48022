"""Pallas TPU kernel for the RG-LRU diagonal linear recurrence.

h_t = a_t * h_{t-1} + b_t, elementwise over channels.  The channel dimension
is tiled over the grid (VPU lanes saturated per block); time is blocked with
the running state carried in VMEM scratch between time-block grid steps, and
each block runs a short unrolled ladder (log-steps of the Blelloch-style
scan) in registers.  This is the memory-bound kernel Griffin's authors
describe: the win over a naive XLA scan is one HBM round-trip per element.

Layout: a, b: (B, S, C) f32 -> h: (B, S, C) f32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _rglru_kernel(a_ref, b_ref, h_ref, carry_scr, *, bt: int, bc: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        carry_scr[...] = jnp.zeros_like(carry_scr)

    a = a_ref[0]                       # (bt, bc)
    b = b_ref[0]
    # inclusive blocked scan via log-depth ladder (associative combine).
    # Row shifts are sublane rolls with the wrapped rows masked to the
    # identity, and the carry is read back with a static slice: Mosaic
    # has no lowering for the dynamic_slice that h[-1] or an unaligned
    # concatenate would need.
    row = jax.lax.broadcasted_iota(jnp.int32, (bt, bc), 0)
    A, Bv = a, b
    shift = 1
    while shift < bt:
        keep = row >= shift
        A_prev = jnp.where(keep, pltpu.roll(A, shift, 0), 1.0)
        B_prev = jnp.where(keep, pltpu.roll(Bv, shift, 0), 0.0)
        Bv = A * B_prev + Bv
        A = A * A_prev
        shift *= 2
    h = A * carry_scr[...] + Bv        # carry (1, bc) broadcasts over rows
    carry_scr[...] = h[bt - 1:]
    h_ref[0] = h.astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "bc", "interpret"))
def rglru_scan_blocked(a, b, *, bt: int = 128, bc: int = 256,
                       interpret: Optional[bool] = None):
    """a,b: (B, S, C) f32 -> inclusive scan h (B, S, C)."""
    B, S, C = a.shape
    bt = min(bt, S)
    bc = min(bc, C)
    assert S % bt == 0 and C % bc == 0, (S, bt, C, bc)
    nt, nc = S // bt, C // bc
    kernel = functools.partial(_rglru_kernel, bt=bt, bc=bc)
    return pl.pallas_call(
        kernel,
        grid=(B, nc, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bc), lambda bi, ci, ti: (bi, ti, ci)),
            pl.BlockSpec((1, bt, bc), lambda bi, ci, ti: (bi, ti, ci)),
        ],
        out_specs=pl.BlockSpec((1, bt, bc), lambda bi, ci, ti: (bi, ti, ci)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a, b)
