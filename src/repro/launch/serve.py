"""Serving launcher: ``python -m repro.launch.serve --arch stablelm_1_6b``.

Continuous batching over the PUMA paged KV pool at the architecture's
published widths; ``--smoke`` serves the reduced config instead (CPU runs).
``--policy`` compares placement policies.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.registry import get_config, lm_archs
from repro.core.kv_pool import KVPoolConfig, lane_dim
from repro.models import mamba2 as M2
from repro.models.transformer import LM
from repro.serve.engine import Request, ServeEngine

#: root of the checkout (``src/repro/launch/serve.py`` -> three levels up)
REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache
    lives at the fixed ``<checkout>/.jax_cache``.  The directory is part of
    the cache key, so it must not move between runs.  Call before the first
    compile: JAX decides once per process whether the cache is in use.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pool_config(
    cfg: ModelConfig, *, max_seqs: int, policy: str = "puma"
) -> KVPoolConfig:
    """The serving pool for ``cfg``: 512 pages of 16 tokens (a page fills
    bf16 sublanes) in ``cfg.kv_cache_dtype``, up to 512 tokens a sequence;
    a hybrid's pages hold its shared-block uses and its Mamba2 layers get a
    state slot per sequence."""
    hybrid = cfg.family == "hybrid"
    states = {"state_layers": cfg.n_layers, **M2.slot_widths(cfg)} if hybrid else {}
    return KVPoolConfig(
        num_blocks=512, block_size=16, kv_heads=cfg.n_kv_heads,
        head_dim=lane_dim(cfg.hd) if hybrid else cfg.hd, n_layers=cfg.kv_layers,
        max_seqs=max_seqs,
        max_blocks_per_seq=32, blocks_per_arena=64, policy=policy,
        dtype=cfg.kv_cache_dtype, **states,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_1_6b", choices=lm_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced config (d_model 128, float32)")
    ap.add_argument("--policy", default="puma",
                    choices=["puma", "first_fit", "random"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seqs", type=int, default=8)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if cfg.family in ("ssm", "encdec"):
        raise SystemExit(
            f"{args.arch}: paged serving applies to attention-KV and Mamba2-hybrid "
            "archs; RWKV and encoder-decoder serving use the dense decode path"
        )
    model = LM(cfg, attn_impl="naive", remat=None)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(
        model, params,
        pool_config(cfg, max_seqs=args.max_seqs, policy=args.policy),
    )
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        eng.submit(Request(
            rid=i,
            prompt=list(rng.integers(0, cfg.vocab_size, int(rng.integers(8, 64)))),
            max_new=args.max_new,
        ))
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    m = eng.metrics()
    print(
        f"[serve] {args.arch} policy={args.policy}: {len(done)} requests, "
        f"{int(m['tokens'])} tokens, {m['tokens']/dt:.1f} tok/s | "
        f"contiguity={m['mean_contiguous_fraction']:.3f} "
        f"descriptors/tile={m['descriptors_per_tile']:.3f}"
    )


if __name__ == "__main__":
    main()
