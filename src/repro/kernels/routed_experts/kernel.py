"""Pallas TPU kernel: the held experts that a few tokens routed to, and
no others.

A decode step routes each token to k of the router's experts; at one
token (k = 8 of 64) those fall on about 4 of the 32 experts a chip
holds.  The expert weights stay where they are, stacked over layers as
``(L, E, D, F)`` (gate, up) and ``(L, E, F, D)`` (down), and the kernel
DMAs only the tiles of the experts that the (token, choice) pairs routed
to: the pairs' expert ids and the layer's index are scalar-prefetched,
and each weight's ``index_map`` reads ``[layer, block]`` from them.

Grid (pair,), a step's blocks one whole expert: its gate, up and down
weights, three contiguous DMAs (12.4 MB at Mellum's widths, double
buffered under a raised VMEM limit; on a TPU v5e this beat 128-wide
f-tiles, PERF.md section 6).  The pairs come sorted, the held ones first
in ascending expert id; a pair that is not held repeats the previous
pair's block, so Pallas issues no DMA for it, and ``pl.when`` skips its
compute.  Consecutive pairs on one expert share its blocks: one DMA, so
each routed expert is fetched once however many tokens share it.  Each
step computes act(x·Wg)·(x·Wu) in f32 for all the rows (few: this form
is taken only where T·k < E), weights each row by the pair's gate (0
off the pair's token), casts to the served dtype and adds h·Wd into an
f32 accumulator; the output is cast once, after the last step.

Layout: ids, blk (P,) int32; layer (1,) int32; x (Tp, D); gates
(P, Tp, 1) f32 -> (Tp, D) in x's dtype.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _routed_kernel(ids_ref, blk_ref, layer_ref, x_ref, g_ref, wg_ref, wu_ref,
                   wd_ref, o_ref, acc_scr, *, act: Callable, held: int):
    p = pl.program_id(0)

    @pl.when(p == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ids_ref[p] < held)
    def _compute():
        x = x_ref[...]
        f32 = jnp.float32
        h = act(jnp.dot(x, wg_ref[0, 0], preferred_element_type=f32)) \
            * jnp.dot(x, wu_ref[0, 0], preferred_element_type=f32)
        h = (h * g_ref[p]).astype(x.dtype)
        acc_scr[...] += jnp.dot(h, wd_ref[0, 0], preferred_element_type=f32)

    @pl.when(p == pl.num_programs(0) - 1)
    def _finish():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def routed_experts_sorted(ids, blk, layer, x, gates, w_gate, w_up, w_down, *,
                          act: Callable, interpret: Optional[bool] = None):
    """Sorted pairs (``ops.routed_experts`` builds them) -> (Tp, D)."""
    _, E, D, F = w_gate.shape
    Tp = x.shape[0]
    P = ids.shape[0]
    # double-buffered gate, up and down blocks, beside the small ones
    vmem = 2 * 3 * D * F * w_gate.dtype.itemsize + (8 << 20)
    expert = lambda p, ids, blk, layer: (layer[0], blk[p], 0, 0)
    return pl.pallas_call(
        functools.partial(_routed_kernel, act=act, held=E),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(P,),
            in_specs=[
                pl.BlockSpec((Tp, D), lambda p, *_: (0, 0)),
                pl.BlockSpec((P, Tp, 1), lambda p, *_: (0, 0, 0)),
                pl.BlockSpec((1, 1, D, F), expert),
                pl.BlockSpec((1, 1, D, F), expert),
                pl.BlockSpec((1, 1, F, D), expert),
            ],
            out_specs=pl.BlockSpec((Tp, D), lambda p, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((Tp, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Tp, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(vmem, 32 << 20)),
        interpret=resolve_interpret(interpret),
    )(ids, blk, layer, x, gates, w_gate, w_up, w_down)
