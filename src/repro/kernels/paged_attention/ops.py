"""Wrapper: (B, Hq, D) query layout -> grouped (B, Hkv, group, D) layout."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import kernel as _k
from repro.kernels.paged_attention import ref as _ref


def paged_attention(
    q: jax.Array,             # (B, Hq, D)
    k_pool: jax.Array,        # (num_blocks, block_size, Hkv, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks)
    seq_lens: jax.Array,      # (B,)
    *,
    scale: float | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    B, Hq, D = q.shape
    Hkv = k_pool.shape[2]
    assert Hq % Hkv == 0
    scale = (D ** -0.5) if scale is None else scale
    qg = q.reshape(B, Hkv, Hq // Hkv, D)
    fn = _k.paged_attention if use_kernel else _ref.paged_attention_ref
    out = fn(
        qg, k_pool, v_pool,
        block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
        scale=scale,
    )
    return out.reshape(B, Hq, D)


def paged_attention_layer(
    q: jax.Array,             # (B, Hq, D)
    k_pool: jax.Array,        # (L, num_blocks, block_size, Hkv, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks)
    seq_lens: jax.Array,      # (B,)
    layer: int,
    *,
    scale: float,
    use_kernel: bool = True,
):
    """Attention over layer ``layer`` of every-layer pools, read in place:
    (out (B, Hq, D), log-sum-exp (B, Hkv, Hq // Hkv) float32)."""
    B, Hq, D = q.shape
    Hkv = k_pool.shape[3]
    qg = q.reshape(B, Hkv, Hq // Hkv, D)
    fn = _k.paged_attention if use_kernel else _ref.paged_attention_layer_ref
    out, lse = fn(
        qg, k_pool, v_pool, block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
        jnp.full((1,), layer, jnp.int32), scale=scale,
    )
    return out.reshape(B, Hq, D), lse
