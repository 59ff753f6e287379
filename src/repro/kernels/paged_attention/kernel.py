"""Paged decode attention over the PUMA KV pool (Pallas TPU kernel).

One query token per sequence attends to its KV stream, which lives as
``block_size``-token pages scattered through the pool and addressed by a
scalar-prefetched *block table* — the TPU replacement for the paper's
re-mmap.  PUMA placement makes consecutive table entries contiguous, which
turns consecutive grid steps' DMAs into sequential HBM streams (the
hardware prefetcher's fast path); the kernel itself is placement-agnostic.

Layout: the grid is (batch, max_blocks) and each step stages one whole
page — every KV head of it, block ``(1, block_size, Hkv, D)`` — so the
block's two minor dims equal the pool's and the tiling is legal for any
head count.  Queries come in group-major, ``(group, Hkv, D)``, so that
each page token's ``(Hkv, D)`` K/V tile lines up with every query group
by broadcasting over leading dims only.  Scores are lane reductions on the
VPU: a decode step has one query row per head, too little to fill the MXU.

Called with ``layer``, the pools hold every layer, ``(L, num_blocks, ...)``,
and the kernel reads that layer's pages in place (the layer is one more
scalar-prefetched index, so no slice of the pool is made) and also returns
the log-sum-exp of each head's scores, which a caller needs to merge a
token the pool does not hold yet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    tbl_ref, lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, block_size, n_blocks,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_valid = lens_ref[b] - j * block_size      # tokens of this page in use

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n_valid > 0)
    def _page():
        q = q_ref[0].astype(jnp.float32)[:, None]          # (G, 1, KV, D)
        k = k_ref[0].astype(jnp.float32)[None]             # (1, bs, KV, D)
        v = v_ref[0].astype(jnp.float32)[None]
        s = jnp.sum(q * k, axis=-1, keepdims=True) * scale  # (G, bs, KV, 1)
        tok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = tok < n_valid
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :, :1]                           # (G, KV, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
        l_cur = alpha * l_scr[:, :, :1] + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha + (p * v).sum(axis=1)
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)

    @pl.when(j == n_blocks - 1)
    def _fin():
        l = l_scr[:, :, :1]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _paged_kernel_lse(tbl_ref, lens_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *, scale, block_size, n_blocks):
    _paged_kernel(tbl_ref, lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  scale=scale, block_size=block_size, n_blocks=n_blocks)

    @pl.when(pl.program_id(1) == n_blocks - 1)
    def _lse():
        l = l_scr[...]
        lse_ref[0] = jnp.where(l == 0.0, NEG_INF, m_scr[...] + jnp.log(jnp.where(l == 0.0, 1.0, l)))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(
    q: jax.Array,            # (B, Hkv, group, D)
    k_pool: jax.Array,       # (num_blocks, block_size, Hkv, D), or (L, ...) with layer
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32, -1 padded
    seq_lens: jax.Array,      # (B,) int32
    layer: jax.Array | None = None,   # (1,) int32
    *,
    scale: float,
    interpret: bool | None = None,
):
    """(B, Hkv, group, D); with ``layer``, also the log-sum-exp (B, Hkv,
    group) float32, NEG_INF where no token is live."""
    if layer is not None:
        return _paged_attention_layer(q, k_pool, v_pool, block_tables, seq_lens, layer,
                                      scale=scale, interpret=interpret)
    B, Hkv, group, D = q.shape
    _, block_size, _, _ = k_pool.shape
    max_blocks = block_tables.shape[1]

    def kv_index(b, j, tbl, lens):
        # -1 (pad) entries clamp to block 0; masking zeroes their weight.
        return (jnp.maximum(tbl[b, j], 0), 0, 0, 0)

    def q_index(b, j, tbl, lens):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_blocks),
        in_specs=[
            pl.BlockSpec((1, group, Hkv, D), q_index),
            pl.BlockSpec((1, block_size, Hkv, D), kv_index),
            pl.BlockSpec((1, block_size, Hkv, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, group, Hkv, D), q_index),
        scratch_shapes=[
            pltpu.VMEM((group, Hkv, 128), jnp.float32),
            pltpu.VMEM((group, Hkv, 128), jnp.float32),
            pltpu.VMEM((group, Hkv, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, block_size=block_size, n_blocks=max_blocks
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, group, Hkv, D), q.dtype),
        interpret=jax.default_backend() != "tpu" if interpret is None else interpret,
    )(block_tables, seq_lens, q.transpose(0, 2, 1, 3), k_pool, v_pool)
    return out.transpose(0, 2, 1, 3)


def _paged_attention_layer(
    q: jax.Array,            # (B, Hkv, group, D)
    k_pool: jax.Array,       # (L, num_blocks, block_size, Hkv, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32, -1 padded
    seq_lens: jax.Array,      # (B,) int32
    layer: jax.Array,         # (1,) int32
    *,
    scale: float,
    interpret: bool | None = None,
):
    B, Hkv, group, D = q.shape
    block_size = k_pool.shape[2]
    max_blocks = block_tables.shape[1]

    def kv_index(b, j, tbl, lens, lay):
        return (lay[0], jnp.maximum(tbl[b, j], 0), 0, 0, 0)

    def q_index(b, j, tbl, lens, lay):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_blocks),
        in_specs=[
            pl.BlockSpec((1, group, Hkv, D), q_index),
            pl.BlockSpec((None, 1, block_size, Hkv, D), kv_index),
            pl.BlockSpec((None, 1, block_size, Hkv, D), kv_index),
        ],
        out_specs=[pl.BlockSpec((1, group, Hkv, D), q_index),
                   pl.BlockSpec((1, group, Hkv, 128), q_index)],
        scratch_shapes=[
            pltpu.VMEM((group, Hkv, 128), jnp.float32),
            pltpu.VMEM((group, Hkv, 128), jnp.float32),
            pltpu.VMEM((group, Hkv, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel_lse, scale=scale, block_size=block_size, n_blocks=max_blocks
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, group, Hkv, D), q.dtype),
                   jax.ShapeDtypeStruct((B, group, Hkv, 128), jnp.float32)],
        interpret=jax.default_backend() != "tpu" if interpret is None else interpret,
    )(block_tables, seq_lens, layer, q.transpose(0, 2, 1, 3), k_pool, v_pool)
    return out.transpose(0, 2, 1, 3), lse[..., 0].transpose(0, 2, 1)
