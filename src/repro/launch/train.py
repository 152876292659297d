"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch minimalist-lm-360m \
        --steps 300 --batch 8 --seq 256

Runs on whatever devices exist (CPU here, TPU pods in production — the
same code path; only the mesh constructor differs).  Uses the synthetic
structured-token pipeline, AdamW + cosine, checkpoint/restart, straggler
monitoring, and optional int8 gradient compression.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.common.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.data import SyntheticLMDataset, ShardedLoader
from repro.models import build_model
from repro.optim import AdamW, cosine_schedule
from repro.train import Trainer, TrainConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minimalist-lm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config variant")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch + ("-smoke" if args.smoke else ""))
    model = build_model(cfg)
    ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=args.seq)
    loader = ShardedLoader(ds, global_batch=args.batch)
    opt = AdamW(lr=cosine_schedule(args.lr, warmup=args.steps // 20,
                                   total=args.steps))
    tcfg = TrainConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                       ckpt_dir=args.ckpt_dir, microbatch=args.microbatch,
                       grad_compress=args.grad_compress, log_every=10)
    trainer = Trainer(model, opt, tcfg, loader=loader)
    params, step = trainer.run()
    losses = [h["loss"] for h in trainer.history]
    if losses:
        k = max(1, len(losses) // 10)
        print(f"done at step {step}; loss first-{k}-mean "
              f"{sum(losses[:k])/k:.4f} -> last-{k}-mean "
              f"{sum(losses[-k:])/k:.4f}")
    return trainer


if __name__ == "__main__":
    main()
