"""Pure-jnp oracle for paged decode attention: gathers each sequence's KV
stream out of the pool and runs dense masked attention."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_attention_ref(
    q: jax.Array,             # (B, Hkv, group, D)
    k_pool: jax.Array,        # (num_blocks, block_size, Hkv, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32, -1 padded
    seq_lens: jax.Array,      # (B,) int32
    *,
    scale: float,
) -> jax.Array:
    B, Hkv, group, D = q.shape
    _, block_size, _, _ = k_pool.shape
    max_blocks = block_tables.shape[1]
    S = max_blocks * block_size

    idx = jnp.maximum(block_tables, 0)                      # (B, nb)
    k = k_pool[idx]                                         # (B, nb, bs, Hkv, D)
    v = v_pool[idx]
    k = k.reshape(B, S, Hkv, D).transpose(0, 2, 1, 3)       # (B, Hkv, S, D)
    v = v.reshape(B, S, Hkv, D).transpose(0, 2, 1, 3)

    s = jnp.einsum(
        "bhgd,bhsd->bhgs", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    pos = jnp.arange(S)[None, None, None, :]
    mask = pos < seq_lens[:, None, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32)).astype(q.dtype)


def paged_attention_layer_ref(q, k_pool, v_pool, block_tables, seq_lens, layer, *, scale):
    """Layer ``layer[0]`` of every-layer pools: (out, log-sum-exp (B, Hkv,
    group), -inf where no token is live)."""
    B, Hkv, group, D = q.shape
    k_pool, v_pool = k_pool[layer[0]], v_pool[layer[0]]
    block_size = k_pool.shape[1]
    S = block_tables.shape[1] * block_size
    idx = jnp.maximum(block_tables, 0)
    k = k_pool[idx].reshape(B, S, Hkv, D)
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    s = jnp.where(jnp.arange(S)[None, None, None, :] < seq_lens[:, None, None, None], s, -jnp.inf)
    return (paged_attention_ref(q, k_pool, v_pool, block_tables, seq_lens, scale=scale),
            jax.nn.logsumexp(s, axis=-1))
