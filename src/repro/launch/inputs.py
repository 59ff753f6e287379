"""Input builders for a (config x run shape) batch.

``batch_shapes(cfg, shape)`` names each input's shape and dtype;
``make_batch`` builds concrete arrays for smoke tests / examples.  Modality
frontends are stubs: [audio] provides precomputed frame embeddings, [vlm]
precomputed patch embeddings (spliced over the first positions).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunShape

N_PATCHES = 256  # vlm stub: patch embeddings replace the first 256 positions


def _pos_shape(cfg: ModelConfig, B: int, S: int) -> Tuple[int, ...]:
    return (B, S, 3) if cfg.rope == "mrope" else (B, S)


def batch_shapes(cfg: ModelConfig, shape: RunShape) -> Dict[str, Any]:
    """Name -> (shape, dtype) for the step-function ``batch`` argument."""
    B = shape.global_batch
    S = 1 if shape.is_decode else shape.seq_len
    out: Dict[str, Any] = {
        "tokens": ((B, S), jnp.int32),
        "positions": (_pos_shape(cfg, B, S), jnp.int32),
    }
    if shape.mode == "train":
        out["targets"] = ((B, S), jnp.int32)
        out["loss_mask"] = ((B, S), jnp.float32)
    if cfg.frontend == "vision" and not shape.is_decode:
        out["patch_embeds"] = ((B, min(N_PATCHES, S), cfg.d_model), jnp.bfloat16)
    if cfg.is_encdec and not shape.is_decode:
        out["enc_embeds"] = ((B, shape.seq_len, cfg.d_model), jnp.bfloat16)
    return out


def make_batch(
    cfg: ModelConfig, shape: RunShape, seed: int = 0
) -> Dict[str, jax.Array]:
    """Concrete random batch (smoke tests, examples)."""
    rng = np.random.default_rng(seed)
    out = {}
    for k, (s, d) in batch_shapes(cfg, shape).items():
        if k in ("tokens", "targets"):
            out[k] = jnp.asarray(rng.integers(0, cfg.vocab_size, size=s), d)
        elif k == "positions":
            B, S = s[0], s[1]
            base = np.broadcast_to(np.arange(S)[None], (B, S))
            if len(s) == 3:
                base = np.broadcast_to(base[..., None], (B, S, 3))
            out[k] = jnp.asarray(base.copy(), d)
        elif k == "loss_mask":
            out[k] = jnp.ones(s, d)
        else:  # frontend embeddings
            out[k] = jnp.asarray(rng.normal(size=s) * 0.02, d)
    return out
