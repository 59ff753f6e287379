"""Tiled (flash) attention Pallas kernel for TPU — prefill / training path.

Online-softmax attention with GQA support.  Grid: (batch, q_heads,
q_blocks, k_blocks) with the K dimension innermost; running max / sum /
accumulator live in VMEM scratch across the K sweep.

Tiling notes (TPU):
  * q/k/v blocks are (block_q|block_k, head_dim) staged via BlockSpec; with
    the default 128x128 blocks and head_dim<=256, the working set is
    ~(2*128*256*4B)*3 < 1 MB — comfortably inside the ~16 MB/core VMEM, and
    all matmul dims are MXU-aligned (128 multiples).
  * masking (causal + KV-length) is value-based (-1e30 + multiplicative
    renorm guard) so padded and fully-masked blocks are numerically inert;
    the grid still visits the blocks above the causal diagonal (no skipping).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, block_q, block_k, kv_len, n_kblocks,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                     # (bq, bk)

    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = kpos < kv_len
    if causal:
        qi = pl.program_id(2)
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        mask = mask & (qpos >= kpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, 0]                          # (bq,)
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    # `where` (not just exp) so fully-masked sweeps stay exactly zero.
    p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
    l_cur = alpha * l_scr[:, 0] + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_scr[...] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)

    @pl.when(ki == n_kblocks - 1)
    def _fin():
        l = l_scr[:, :1]
        o_ref[0, 0, :, :] = (
            acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "kv_len", "interpret"),
)
def flash_attention(
    q: jax.Array,   # (B, Hq, Sq, D) — Sq padded to block_q multiple
    k: jax.Array,   # (B, Hkv, Sk, D) — Sk padded to block_k multiple
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    kv_len: int | None = None,   # true (unpadded) KV length
    interpret: bool | None = None,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    scale = (D ** -0.5) if scale is None else scale
    kv_len = Sk if kv_len is None else kv_len
    nq, nk = Sq // block_q, Sk // block_k

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_len=kv_len,
        n_kblocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, h, qi, ki, g=group: (b, h // g, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, h, qi, ki, g=group: (b, h // g, ki, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
            pltpu.VMEM((block_q, D), jnp.float32),     # output accumulator
        ],
        interpret=jax.default_backend() != "tpu" if interpret is None else interpret,
    )(q, k, v)
