"""Training launcher: ``python -m repro.launch.train --arch stablelm_1_6b``.

On this CPU container it trains the reduced (smoke) configs; pass
``--full`` on real hardware to use the production config, mesh, and
sharding rules (the (16, 16) mesh of ``launch/mesh.make_production_mesh``).
"""
from __future__ import annotations

import argparse

import jax

from repro.configs.registry import get_config, lm_archs
from repro.data.pipeline import DataConfig
from repro.dist import sharding as shd
from repro.models.transformer import LM
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_1_6b", choices=lm_archs())
    ap.add_argument("--full", action="store_true",
                    help="production config + mesh (real hardware)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compression", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.smoke()
    else:
        from repro.launch.mesh import make_production_mesh

        shd.set_mesh(make_production_mesh())

    model = LM(cfg, attn_impl="chunked", remat="full" if args.full else None)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      batch_per_shard=args.batch)
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                       total_steps=args.steps)
    tcfg = TrainerConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=f"{args.ckpt_dir}/{args.arch}", log_every=10,
        accum_steps=args.accum, grad_compression=args.grad_compression,
    )
    out = Trainer(model, data, ocfg, tcfg).run()
    losses = [m["loss"] for _, m in out["history"]]
    print(f"[train] {args.arch}: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({len(losses)} steps)")


if __name__ == "__main__":
    main()
