"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Stages live on consecutive ranks of a 1D ``pp`` mesh axis; microbatches
stream through with the classic (P + M - 1)-tick schedule.  Activations hop
stage-to-stage with ``ppermute`` — the Databelt Offload phase verbatim: the
producer pushes its output state to the node that will run the consumer,
ahead of the consumer's turn.

``pipeline_apply`` is generic over the stage function; ``pipeline_stages``
splits a scanned-superblock parameter tree into contiguous stage groups.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_params, x, stage_fn: Callable, mesh: Mesh,
                   axis: str = "pp", microbatches: int = 0):
    """Run ``stage_fn(params_p, x_mb)`` through P pipeline stages.

    stage_params: pytree with leading stage dim P on every leaf (sharded
    over ``axis``); x: (B, ...) batch, split into M microbatches along dim 0.
    Returns f(x) with the same layout as a sequential stack would produce.
    """
    pp = mesh.shape[axis]
    B = x.shape[0]
    M = microbatches or pp
    assert B % M == 0, (B, M)
    mb = B // M

    def body(params_p, xl):
        # params_p: this rank's stage params (leading dim 1); xl: (B, ...)
        rank = jax.lax.axis_index(axis)
        p_local = jax.tree.map(lambda t: t[0], params_p)
        mbs = xl.reshape(M, mb, *xl.shape[1:])
        state = jnp.zeros_like(mbs[0])          # activation in flight
        out = jnp.zeros_like(mbs)
        fwd = [(i, (i + 1) % pp) for i in range(pp)]

        def tick(t, carry):
            state, out = carry
            # stage 0 ingests microbatch t (when in window)
            take = jnp.clip(t, 0, M - 1)
            state = jnp.where(rank == 0,
                              jnp.where(t < M, mbs[take], state), state)
            live = (t - rank >= 0) & (t - rank < M)
            y = stage_fn(p_local, state)
            state = jnp.where(live, y, state)
            # last stage banks its finished microbatch
            done_idx = jnp.clip(t - (pp - 1), 0, M - 1)
            bank = (rank == pp - 1) & live
            out = jnp.where(bank, out.at[done_idx].set(state), out)
            # Offload: push the activation to the next stage's rank
            state = jax.lax.ppermute(state, axis, fwd)
            return state, out

        state, out = jax.lax.fori_loop(0, pp + M - 1, tick, (state, out))
        # results live on the last rank; broadcast so every rank returns them
        out = jax.lax.psum(jnp.where(rank == pp - 1, out, 0.0), axis)
        return out.reshape(B, *xl.shape[1:])

    pspec = jax.tree.map(lambda t: P(axis), stage_params)
    return jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(pspec, P()),
        out_specs=P(),
    )(stage_params, x)


def pipeline_stages(stacked_params, n_stages: int):
    """Split (R, ...) scanned-superblock params into ``n_stages`` contiguous
    groups: returns params with leading dims (n_stages, R//n_stages, ...)."""
    def split(t):
        R = t.shape[0]
        assert R % n_stages == 0, (R, n_stages)
        return t.reshape(n_stages, R // n_stages, *t.shape[1:])
    return jax.tree.map(split, stacked_params)
