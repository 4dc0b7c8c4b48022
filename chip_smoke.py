"""Smoke run of the system's two JAX paths on one TPU chip.

    python chip_smoke.py

Everything runs in this one process, which holds the chip:

1. device: JAX must see a TPU.  There is no CPU fallback.
2. serving: gemma3-1b at its published widths, with random bf16 weights
   made from a seed on the chip, served by ``ServingEngine`` (4 slots,
   max_len 128) for 8 requests built as ``repro.launch.serve`` builds them.
   Every request must complete with token ids in the vocabulary, every
   logit must be finite and the decode step must compile once.  What the
   batched requests compute is not checked: requests of different
   lengths share one decode position, which is wrong (ROADMAP queue 2).
   Then one request is served alone: the logits that chose each of its
   tokens must match one forward pass over its prompt and served tokens
   within ``LOGITS_TOL``, and each served token must be that pass's
   greedy choice up to a near-tie inside the same bound.
3. flood workflow: ``WorkflowEngine(real_compute=True)`` runs the paper's
   flood chain at the largest payload ``make_payload`` builds (4096
   frames), for the databelt and the stateless strategy; the function
   bodies' array outputs must live on the chip.
4. kernels: each Pallas kernel, compiled by Mosaic at a real width, must
   match its jnp reference within ``DOT_TOL`` or ``SCAN_TOL``.

No timing is printed: this checks that the paths run and compute the
right thing, it measures nothing.  A failed check raises, so the process
exits non-zero.  The last line of stdout is one JSON object naming the
device.
"""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import ModelConfig, get_config  # noqa: E402
from repro.continuum.network import ContinuumNetwork  # noqa: E402
from repro.continuum.orbits import Constellation  # noqa: E402
from repro.kernels.flash_attention.kernel import (  # noqa: E402
    flash_attention_bkg)
from repro.kernels.flash_attention.ref import (  # noqa: E402
    flash_attention_ref)
from repro.kernels.rglru_scan.kernel import rglru_scan_blocked  # noqa: E402
from repro.kernels.rglru_scan.ref import rglru_scan_ref  # noqa: E402
from repro.kernels.rwkv6_chunk.kernel import wkv6_chunked  # noqa: E402
from repro.kernels.rwkv6_chunk.ref import wkv6_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import make_requests  # noqa: E402
from repro.models import forward_train, init_params  # noqa: E402
from repro.serverless.engine import WorkflowEngine  # noqa: E402
from repro.serverless.workflow import (flood_workflow,  # noqa: E402
                                       make_payload)

ARCH = "gemma3-1b"            # launch/serve.py's default arch
SEED = 0
N_REQUESTS, N_SLOTS, MAX_LEN, MAX_NEW = 8, 4, 128, 16
# Largest payload make_payload builds: 4096 frames of 32x32 float32.
FLOOD_BYTES = 4096 * 32 * 32 * 4
FLOOD_RUNS = 3
# Bound on max |served - reference| over the vocabulary, as a fraction of
# max |reference|, set from CPU runs with random weights: the served logits
# and the forward pass differ by 0.0 at the smoke config and at gemma3-1b
# widths cut to 6 layers, and decode after the prompt differed from the
# prefill by 0.0013 there (one bf16 step of a logit near 3).  The same bf16 model
# differs from its float32 twin by 0.009-0.010 at 6 layers and 0.013-0.018
# at 12, so bf16 rounding alone stays far below the bound at 26 layers,
# while a wrong position or cache entry moves logits by their own size.
LOGITS_TOL = 0.05


# The kernels phase: each Pallas kernel compiled by Mosaic at a real width
# against its jnp reference run at the highest matmul precision.  The bound
# is on max |err| as a fraction of max |ref|.  The kernels' dots run at the
# MXU's default precision (f32 operands rounded to bf16, f32 accumulation,
# as XLA's own f32 dots on a TPU), which stays near one bf16 step (2**-8 of
# the largest value); a wrong mask, block or carry is off by the values'
# own size.  The RG-LRU scan has no dot and is exact to f32 rounding.
DOT_TOL, SCAN_TOL = 2e-2, 1e-5


def _flash(key, dtype, BK, S, G, hd, window):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (BK, S, G, hd), dtype)
    k = jax.random.normal(kk, (BK, S, hd), dtype)
    v = jax.random.normal(kv, (BK, S, hd), dtype)
    kw = {"scale": hd ** -0.5, "window": window}
    return (lambda: flash_attention_bkg(q, k, v, **kw),
            lambda: flash_attention_ref(q, k, v, **kw))


def _rglru(key, B, S, C):
    ka, kb = jax.random.split(key)
    a = jax.nn.sigmoid(jax.random.normal(ka, (B, S, C)))
    b = jax.random.normal(kb, (B, S, C))
    return lambda: rglru_scan_blocked(a, b), lambda: rglru_scan_ref(a, b)


def _wkv6(key, BH, S, hd):
    ks = jax.random.split(key, 5)
    r, k, v = (jax.random.normal(kk, (BH, S, hd)) for kk in ks[:3])
    logw = jnp.clip(-jnp.exp(jax.random.normal(ks[3], (BH, S, hd)) * 0.5),
                    -5.0, -1e-4)
    u = jax.random.normal(ks[4], (BH, hd)) * 0.1
    return (lambda: wkv6_chunked(r, k, v, logw, u),
            lambda: wkv6_ref(r, k, v, logw, u))


# name -> (inputs and calls, real-width arguments, bound)
KERNEL_CASES = {
    # gemma3-1b: 4 query heads over 1 kv head, head_dim 256, window 512
    "flash_attention_f32": (_flash, dict(dtype=jnp.float32, BK=2, S=1024,
                                         G=4, hd=256, window=512), DOT_TOL),
    "flash_attention_bf16": (_flash, dict(dtype=jnp.bfloat16, BK=2, S=1024,
                                          G=4, hd=256, window=512), DOT_TOL),
    # recurrentgemma-2b: d_rnn 2560
    "rglru_scan": (_rglru, dict(B=2, S=1024, C=2560), SCAN_TOL),
    # rwkv6-7b: 64 heads of 64, one sequence
    "wkv6": (_wkv6, dict(BH=64, S=512, hd=64), DOT_TOL),
}


def check_device():
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX sees "
                         f"{dev.platform!r}); this check has no CPU "
                         "fallback")
    return dev, len(devs)


def _check(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def serve_phase(cfg: ModelConfig, dev) -> dict:
    """Serve N_REQUESTS seeded requests, then one alone against a forward
    pass over its prompt and served tokens.  Returns what was checked."""
    from repro.serving.engine import ServingEngine

    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    _check(all(w.devices() == {dev} for w in jax.tree.leaves(params)),
           "parameters are not on the device")
    eng = ServingEngine(cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN)

    # every decode call's logits are checked for finiteness on the device;
    # lane 0's are kept for the lone request
    decode, finite, lane0 = eng._decode, [], []

    def checked_decode(*args):
        logits, cache = decode(*args)
        finite.append(jnp.isfinite(logits).all())
        lane0.append(logits[0, -1])
        return logits, cache

    eng._decode = checked_decode
    reqs = make_requests(cfg.vocab_size, N_REQUESTS + 1, MAX_NEW)
    for r in reqs[:N_REQUESTS]:
        eng.submit(r)
    done = list(eng.run_until_done())
    _check(len(done) == N_REQUESTS and all(r.done for r in done),
           f"{len(done)}/{N_REQUESTS} requests completed")
    _check(all(1 <= len(r.tokens_out) <= MAX_NEW for r in done),
           "a request produced no tokens or more than max_new")
    _check(all(0 <= t < cfg.vocab_size for r in done for t in r.tokens_out),
           "token id out of range")

    # one request alone: every slot is free, so it is admitted to slot 0
    # and decodes at its own positions.  Its first token comes from the
    # logits of the last prompt feed, token j from decode call P-1+j.
    _check(all(s is None for s in eng.slots), "a slot is still busy")
    alone = reqs[N_REQUESTS]
    lane0.clear()
    eng.submit(alone)
    eng.run_until_done()
    _check(alone.done, "the lone request did not complete")
    P, served = len(alone.prompt), alone.tokens_out
    K = len(served)
    _check(len(lane0) == P + K - 1,
           f"{len(lane0)} decode calls for {P} prompt and {K} served tokens")
    got = np.stack([np.asarray(x, np.float32) for x in lane0[P - 1:]])
    # the reference sees the prompt and the served tokens at once: its
    # logits at position P-1+j are the forward pass's next-token
    # distribution given everything served before token j
    seq = np.concatenate([alone.prompt, np.asarray(served[:-1], np.int32)])
    ref, _ = jax.jit(forward_train, static_argnums=1)(
        params, cfg, {"tokens": jnp.asarray(seq[None])})
    ref = np.asarray(ref[0, P - 1:], np.float32)                # (K, vocab)
    _check(all(bool(f) for f in finite), "non-finite logits in a decode step")
    _check(np.isfinite(ref).all(), "non-finite reference logits")
    diff = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    _check(diff <= LOGITS_TOL * scale,
           f"served vs reference logits: max diff {diff} > "
           f"{LOGITS_TOL} * {scale}")
    # what was served: each token is the reference's greedy choice, up to
    # a near-tie within the same bound
    picked = ref[np.arange(K), served]
    _check(bool(np.all(picked >= ref.max(axis=1) - LOGITS_TOL * scale)),
           "a served token is not the reference's greedy choice")

    compiles = decode._cache_size()
    _check(compiles == 1, f"decode compiled {compiles} times")
    return {"requests": len(eng.completed),
            "tokens": sum(len(r.tokens_out) for r in eng.completed),
            "decode_calls": len(finite), "decode_compiles": compiles,
            "alone_tokens": K,
            "alone_argmax_agree": int(np.sum(ref.argmax(axis=1) == served)),
            "logits_max_abs_diff": diff, "logits_max_abs": scale,
            "logits_diff_frac": diff / scale}


def _record_outputs(wf, outputs: dict):
    """Wrap each function body so its output is kept for checking."""
    for fn in wf.functions:
        body = fn.compute

        def rec(payload, body=body, name=fn.name):
            out = body(payload)
            outputs.setdefault(name, []).append(out)
            return out
        fn.compute = rec
    return wf


def flood_phase(dev, payload_bytes: float = FLOOD_BYTES,
                runs: int = FLOOD_RUNS) -> dict:
    """The flood chain with real JAX bodies under both strategies."""
    n_frames = make_payload(payload_bytes)["frames"].shape[0]
    net = ContinuumNetwork(Constellation(n_planes=8, sats_per_plane=8))
    report = {"frames": n_frames}
    for strategy in ("databelt", "stateless"):
        eng = WorkflowEngine(net, strategy=strategy, real_compute=True,
                             seed=SEED)
        outputs: dict = {}
        for i in range(runs):
            wf = _record_outputs(flood_workflow(f"smoke-{strategy}-{i}"),
                                 outputs)
            m = eng.run_instance(wf, payload_bytes, t0=i * 90.0)
            _check(m.latency > 0 and m.compute_time > 0,
                   f"{strategy} instance {i} reported no latency or compute")
        _check(sorted(outputs) == ["alarm", "detect", "ingest", "map"],
               f"function bodies that ran: {sorted(outputs)}")
        arrays = 0
        for name in ("ingest", "detect", "map"):
            _check(len(outputs[name]) == runs, f"{name} ran "
                   f"{len(outputs[name])} times in {runs} instances")
            for out in outputs[name]:
                for leaf in jax.tree.leaves(out):
                    _check(isinstance(leaf, jax.Array)
                           and leaf.devices() == {dev},
                           f"{name} output is not on {dev}")
                    _check(bool(jnp.isfinite(leaf).all()),
                           f"{name} output is not finite")
                    arrays += 1
        _check(all(math.isfinite(out["score"]) for out in outputs["alarm"]),
               "alarm score is not finite")
        _check(outputs["detect"][0]["detections"].shape == (n_frames,),
               "detect scored the wrong number of frames")
        report[strategy] = {"instances": runs, "device_arrays": arrays}
    return report


def kernel_phase(dev, cases: dict = KERNEL_CASES) -> dict:
    """Each kernel, compiled for the backend, against its reference."""
    report = {}
    for name, (build, kw, tol) in cases.items():
        kernel, reference = build(jax.random.PRNGKey(SEED), **kw)
        out = kernel()
        _check(out.devices() == {dev}, f"{name} output is not on {dev}")
        # the kernel is traced outside this context: only the reference
        # runs its dots at full f32 precision
        with jax.default_matmul_precision("highest"):
            ref = reference()
        out = np.asarray(out, np.float32)
        ref = np.asarray(ref, np.float32)
        _check(np.isfinite(out).all(), f"{name} output is not finite")
        err = float(np.max(np.abs(out - ref)))
        scale = float(np.max(np.abs(ref)))
        _check(err <= tol * scale,
               f"{name}: max |err| {err} > {tol} * {scale}")
        report[name] = {"max_abs_err": err, "max_abs_ref": scale,
                        "frac": err / scale, "tol": tol}
    return report

def main() -> int:
    enable_compile_cache()
    dev, count = check_device()

    cfg = get_config(ARCH)
    serve = serve_phase(cfg, dev)
    stats = dev.memory_stats() or {}
    print(f"serving: arch={ARCH} (full width, {cfg.n_layers} layers) "
          f"requests={serve['requests']} tokens={serve['tokens']} "
          f"decode_calls={serve['decode_calls']} "
          f"decode_compiles={serve['decode_compiles']} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)
    print(f"serving: lone request tokens={serve['alone_tokens']} "
          f"argmax_agree={serve['alone_argmax_agree']}; served vs forward "
          f"logits max_abs_diff={serve['logits_max_abs_diff']} "
          f"max_abs={serve['logits_max_abs']} "
          f"frac={serve['logits_diff_frac']} (tol {LOGITS_TOL})", flush=True)

    flood = flood_phase(dev)
    for strategy in ("databelt", "stateless"):
        print(f"flood: strategy={strategy} frames={flood['frames']} "
              f"instances={flood[strategy]['instances']} "
              f"outputs_on_{dev.platform}={flood[strategy]['device_arrays']}",
              flush=True)

    for name, k in kernel_phase(dev).items():
        print(f"kernels: {name} max_abs_err={k['max_abs_err']} "
              f"max_abs_ref={k['max_abs_ref']} frac={k['frac']} "
              f"(tol {k['tol']})", flush=True)

    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
