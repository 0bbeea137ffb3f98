"""Training driver CLI.

Container scale (tiny smoke config, real training):
    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \\
        --steps 100

Production lowering check (no execution, 512 fake devices):
    handled by repro.launch.dryrun; this driver runs REAL steps on
    whatever devices exist, with checkpoint/restart fault tolerance.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.data.tokens import TokenLoader
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.train.optimizer import AdamWConfig, adamw_update
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.smoke:
        cfg = cfg.replace(dtype="float32")
    model = build_model(cfg)
    print(f"{cfg.name}: {model.n_params():,} params on "
          f"{len(jax.devices())} device(s)")
    hp = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                     total_steps=args.steps)

    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt, gn = adamw_update(grads, opt, params, hp)
        return params, opt, {"loss": loss, "grad_norm": gn,
                             "step": opt.count}

    loader = TokenLoader(cfg.vocab_size, batch=args.batch, seq_len=args.seq)
    tc = TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                       ckpt_dir=args.ckpt_dir, log_every=10)
    trainer = Trainer(model, jax.jit(step), loader, tc)
    trainer.run()


if __name__ == "__main__":
    main()
