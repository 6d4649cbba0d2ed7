"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

On CPU this trains reduced/smoke configs (the end-to-end example path);
on a real pod the same driver takes the full config + production mesh.
Checkpoint/restart, LR schedule, watchdog and best-model restore come
from the Trainer.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.arch import ShapeConfig
from repro.data.synthetic import lm_batches, token_stream
from repro.launch import compile_cache
from repro.models.params import init_params, param_count
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def build(arch: str, *, smoke: bool, batch: int, seq: int, n_micro: int,
          lr: float, grad_compression: str | None, remat: str):
    cfg = configs.get_smoke(arch) if smoke else configs.get(arch)
    params = init_params(cfg, jax.random.key(0))
    opt_state = adamw_init(params)
    step = make_train_step(
        cfg, n_microbatch=n_micro, remat=remat,
        opt=AdamWConfig(lr=lr),
        grad_compression=grad_compression)
    return cfg, params, opt_state, jax.jit(step, donate_argnums=(0, 1))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--grad-compression", default=None)
    ap.add_argument("--ckpt-dir", default="checkpoints/train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    compile_cache.enable()

    cfg, params, opt_state, step = build(
        args.arch, smoke=args.smoke, batch=args.batch, seq=args.seq,
        n_micro=args.micro, lr=args.lr,
        grad_compression=args.grad_compression, remat=args.remat)
    print(f"arch={cfg.name} params={param_count(cfg):,}")

    tokens = token_stream(200_000, cfg.vocab_size, seed=1)
    batches = lm_batches(tokens, args.batch, args.seq)

    trainer = Trainer(step, params, opt_state,
                      ckpt_dir=Path(args.ckpt_dir),
                      config=TrainerConfig(total_steps=args.steps,
                                           checkpoint_every=args.ckpt_every,
                                           log_every=10))
    if args.resume:
        resumed = trainer.maybe_resume()
        print("resumed from checkpoint" if resumed else "fresh start")
    result = trainer.run(iter(batches))
    print(f"final loss {result['final_loss']:.4f} "
          f"(best {result['best']['loss']:.4f} @ {result['best']['step']})")
    if args.out:
        Path(args.out).write_text(json.dumps(
            {"arch": cfg.name, "final": result["final_loss"],
             "best": result["best"], "steps": args.steps,
             "history_tail": result["history"][-5:]}, indent=1))


if __name__ == "__main__":
    main()
