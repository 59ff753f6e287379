"""End-to-end serving driver: continuous batching over the PUMA paged KV
pool, comparing placement policies — the TPU adaptation of the paper's
experiment (block-table contiguity is the '% executable in PUD' analogue).

    PYTHONPATH=src python examples/serve_paged.py [--policy puma|first_fit|random]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.core.kv_pool import KVPoolConfig
from repro.models.transformer import LM
from repro.serve.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default=None, help="run one policy (default: all)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    cfg = get_config("stablelm_1_6b").smoke()
    model = LM(cfg, attn_impl="naive", remat=None)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(0, cfg.vocab_size, int(rng.integers(8, 48))))
        for _ in range(args.requests)
    ]

    policies = [args.policy] if args.policy else ["puma", "first_fit", "random"]
    for policy in policies:
        pool_cfg = KVPoolConfig(
            num_blocks=256, block_size=8, kv_heads=cfg.n_kv_heads,
            head_dim=cfg.hd, n_layers=cfg.n_layers, max_seqs=6,
            max_blocks_per_seq=16, blocks_per_arena=32,
            policy=policy, dtype="float32",
        )
        eng = ServeEngine(model, params, pool_cfg)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new=args.max_new))
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        m = eng.metrics()
        print(
            f"{policy:10s} served {len(done):3d} reqs, "
            f"{int(m['tokens'])} tokens in {dt:5.1f}s | "
            f"contiguity={m['mean_contiguous_fraction']:.3f} "
            f"descriptors/tile={m['descriptors_per_tile']:.3f} "
            f"align_hits={int(m['align_hits'])} misses={int(m['align_misses'])}"
        )


if __name__ == "__main__":
    main()
