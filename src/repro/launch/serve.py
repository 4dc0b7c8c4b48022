"""Serving launcher: ``PYTHONPATH=src python -m repro.launch.serve
--arch <id> [--requests N] [--slots K] [--full]`` — continuous-batching
engine over the arch's smoke config, or with ``--full`` its published
config (gemma3-1b at full width fits one TPU v5e chip).  Runs on whatever
backend JAX picks; the printed line names that device.  It prints no
timing: a clock around this loop would count compilation and set-up.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import get_config, get_smoke_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving.engine import Request, ServingEngine


def make_requests(vocab_size: int, n: int, max_new: int) -> list:
    """``n`` requests with prompts of 3-8 token ids in ``[2, vocab_size)``
    drawn from seed 0 (0 and 1 are left out: 1 is the engine's EOS)."""
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(n):
        prompt = rng.integers(2, vocab_size, size=int(rng.integers(3, 9)))
        reqs.append(Request(i, prompt.astype(np.int32), max_new=max_new))
    return reqs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=list_archs())
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, n_slots=args.slots,
                        max_len=args.max_len)
    for req in make_requests(cfg.vocab_size, args.requests, args.max_new):
        eng.submit(req)
    done = eng.run_until_done()
    toks = sum(len(r.tokens_out) for r in done)
    dev = jax.devices()[0]
    print(f"{len(done)} requests, {toks} tokens on {dev.platform} "
          f"{dev.device_kind} x{len(jax.devices())}")


if __name__ == "__main__":
    main()
