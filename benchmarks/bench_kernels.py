"""Kernel micro-benchmarks: Pallas in interpret mode vs jnp oracle.

On CPU the interpret-mode kernel is slower than fused XLA — the number that
matters here is the ORACLE column (the jnp path the dry-run lowers) and the
derived flops estimate; the Pallas timings become meaningful on real TPU.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.kernels.flash_attention.kernel import flash_attention_bkg
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.rglru_scan.kernel import rglru_scan_blocked
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.rwkv6_chunk.kernel import wkv6_chunked
from repro.kernels.rwkv6_chunk.ref import wkv6_ref


def _time(fn, *args, reps: int = 3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    key = jax.random.PRNGKey(0)
    rows = {}

    BK, S, G, hd = 4, 512, 4, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (BK, S, G, hd), jnp.float32)
    k = jax.random.normal(ks[1], (BK, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (BK, S, hd), jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v, scale=0.125))
    rows["flash_ref_us"] = round(_time(f, q, k, v), 1)
    g = jax.jit(lambda q, k, v: flash_attention_bkg(
        q, k, v, scale=0.125, bq=128, bk=128, interpret=True))
    rows["flash_pallas_interp_us"] = round(_time(g, q, k, v), 1)
    rows["flash_gflops"] = round(
        4 * BK * G * S * S * hd / 1e9, 2)

    BH, hd2 = 8, 64
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (BH, S, hd2), jnp.float32)
    kk = jax.random.normal(ks[1], (BH, S, hd2), jnp.float32)
    vv = jax.random.normal(ks[2], (BH, S, hd2), jnp.float32)
    lw = jnp.clip(-jnp.exp(jax.random.normal(ks[3], (BH, S, hd2)) * 0.5),
                  -5.0, -1e-4)
    u = jax.random.normal(ks[4], (BH, hd2), jnp.float32) * 0.1
    f = jax.jit(wkv6_ref)
    rows["wkv6_ref_us"] = round(_time(f, r, kk, vv, lw, u), 1)
    g = jax.jit(lambda *a: wkv6_chunked(*a, chunk=64, interpret=True))
    rows["wkv6_pallas_interp_us"] = round(_time(g, r, kk, vv, lw, u), 1)

    B, C = 4, 512
    ks = jax.random.split(key, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, C)))
    b = jax.random.normal(ks[1], (B, S, C))
    f = jax.jit(rglru_scan_ref)
    rows["rglru_ref_us"] = round(_time(f, a, b), 1)
    g = jax.jit(lambda a, b: rglru_scan_blocked(a, b, bt=128, bc=256,
                                                interpret=True))
    rows["rglru_pallas_interp_us"] = round(_time(g, a, b), 1)

    emit("bench_kernels", rows["flash_ref_us"], rows)
    return rows


if __name__ == "__main__":
    run()
