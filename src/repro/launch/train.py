"""Training launcher: ``PYTHONPATH=src python -m repro.launch.train
--arch <id> [--steps N] [--smoke] [--ckpt DIR]``.

``--smoke`` (default on CPU) uses the reduced config of the same family;
the full configs are for real accelerator fleets (the dry-run proves they
lower and compile on the production meshes).
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs.base import get_config, get_smoke_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import make_optimizer
from repro.train.loop import TrainLoop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b",
                    choices=list_archs())
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--full", action="store_true",
                    help="use the full production config (needs a real "
                         "accelerator fleet)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    opt = make_optimizer(cfg.optimizer)
    print(f"arch={args.arch} params={cfg.param_count()/1e6:.1f}M "
          f"optimizer={cfg.optimizer} steps={args.steps}")
    loop = TrainLoop(cfg, opt, batch=args.batch, seq=args.seq, lr=args.lr,
                     ckpt_dir=args.ckpt or None,
                     grad_accum=args.grad_accum)
    m = loop.run(args.steps, log_every=max(args.steps // 10, 1))
    print(f"final loss {np.mean(m.losses[-5:]):.4f} "
          f"({np.mean(m.step_times)*1e3:.0f} ms/step)")


if __name__ == "__main__":
    main()
