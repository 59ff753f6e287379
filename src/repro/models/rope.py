"""Rotary position embeddings: standard (interleaved pairs), rotate-half
(GPT-NeoX / Zamba2), partial (ChatGLM-style 2D), and
M-RoPE (Qwen2-VL: separate temporal/height/width sections)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def _rot_half_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate consecutive (even, odd) channel pairs (computed f32, cast back)."""
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def _angles(positions: jax.Array, dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin (..., dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(
    cfg: ModelConfig,
    x: jax.Array,            # (B, S, H, hd)
    positions: jax.Array,    # (B, S) or (B, S, 3) for mrope
) -> jax.Array:
    hd = x.shape[-1]
    if cfg.rope == "none":
        return x

    if cfg.rope == "rope":
        cos, sin = _angles(positions, hd, cfg.rope_theta)      # (B,S,hd/2)
        return _rot_half_pairs(x, cos[:, :, None, :], sin[:, :, None, :])

    if cfg.rope == "rope_half":
        # rotate-half (GPT-NeoX / Zamba2): channel i pairs with i + hd/2
        cos, sin = _angles(positions, hd, cfg.rope_theta)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., : hd // 2], xf[..., hd // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.astype(x.dtype)

    if cfg.rope == "rope2d":
        # ChatGLM: rotary over the first half of channels only.
        rd = hd // 2
        cos, sin = _angles(positions, rd, cfg.rope_theta)
        rot = _rot_half_pairs(x[..., :rd], cos[:, :, None, :], sin[:, :, None, :])
        return jnp.concatenate([rot, x[..., rd:]], axis=-1)

    if cfg.rope == "mrope":
        # positions (B, S, 3): (t, h, w); channel sections per stream.
        st, sh, sw = cfg.mrope_sections
        assert (st + sh + sw) * 2 == hd, (cfg.mrope_sections, hd)
        inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang_all = positions[..., None, :].astype(jnp.float32) * inv[None, None, :, None]
        # pick stream per channel section: [0:st]->t, [st:st+sh]->h, rest->w
        sec = jnp.concatenate(
            [
                jnp.zeros((st,), jnp.int32),
                jnp.ones((sh,), jnp.int32),
                jnp.full((sw,), 2, jnp.int32),
            ]
        )
        ang = jnp.take_along_axis(
            ang_all, sec[None, None, :, None].astype(jnp.int32), axis=-1
        )[..., 0]                                               # (B,S,hd/2)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        return _rot_half_pairs(x, cos[:, :, None, :], sin[:, :, None, :])

    raise ValueError(cfg.rope)
