"""Multi-head attention (MHA/GQA/MQA) with selectable inner implementation.

``impl``:
  * "naive"   — materializes the (S, S) score matrix (the un-fused XLA
                baseline; what you get without a flash kernel),
  * "chunked" — XLA-visible online-softmax over KV blocks via lax.scan
                (flash-style memory behaviour, analyzable by cost_analysis),
  * "pallas"  — the repro.kernels.flash_attention TPU kernel (used on real
                hardware and in kernel tests; opaque to HLO cost analysis).

Decode mode consumes/produces an explicit KV cache
``(k, v): (B, S_max, KV, hd)`` plus the current length, updating in place
with dynamic_update_slice — the dense-cache path used by the dry-run; the
serving engine swaps in the PUMA paged pool on-line.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constraint
from repro.models.params import ParamDef
from repro.models.rope import apply_rope

Cache = Tuple[jax.Array, jax.Array]  # (k, v) each (B, S_max, KV, hd)

NEG_INF = -1e30


def attn_defs(cfg: ModelConfig, d_model: Optional[int] = None) -> Dict:
    d = d_model or cfg.d_model
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    return {
        "wq": ParamDef((d, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((H, hd, d), ("heads", "head_dim", "embed")),
    }


def _repeat_kv(k: jax.Array, group: int) -> jax.Array:
    if group == 1:
        return k
    return jnp.repeat(k, group, axis=2)


def _naive_attention(q, k, v, *, causal, kv_len, scale, q_offset=0):
    """q (B,Sq,H,hd), k/v (B,Sk,H,hd) — full score matrix."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    kpos = jnp.arange(Sk)[None, None, None, :]
    mask = kpos < kv_len
    if causal:
        qpos = (q_offset + jnp.arange(Sq))[None, None, :, None]
        mask = mask & (kpos <= qpos)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _attention_with_lse(q, k, v, *, kv_len, kv_offset, scale, q_pos):
    """Partial attention over one KV segment, returning (out_f32, lse).

    q (B,Sq,H,hd); k/v (B,Sk,KV,hd).  GQA-native grouped einsums: KV is
    never repeated ``group`` times and never materialized in f32 — the dots
    accumulate in f32 via preferred_element_type (quantized fp8 pages are
    widened to the compute dtype elementwise, which fuses into the dot).
    Segment tokens occupy absolute positions [kv_offset, kv_offset+kv_len);
    causal masking uses absolute query positions ``q_pos`` (B, Sq).
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    Sk = k.shape[1]
    cd = q.dtype  # compute dtype (bf16 in production)
    # barrier: keeps the (quantized) page widening *inside* the layer loop —
    # XLA otherwise hoists the convert and materializes the whole stacked
    # cache in compute dtype (a 2x cache-sized temp).
    k, v = jax.lax.optimization_barrier((k, v))
    qg = q.reshape(B, Sq, KV, group, hd)
    s = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k.astype(cd),
        preferred_element_type=jnp.float32,
    ) * scale                                              # (B,KV,g,Sq,Sk)
    kpos = kv_offset + jnp.arange(Sk)
    mask = (jnp.arange(Sk)[None, None, None, None, :] < kv_len) & (
        kpos[None, None, None, None, :] <= q_pos[:, None, None, :, None]
    )
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(-1)                                          # (B,KV,g,Sq)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    l = p.sum(-1)
    out = jnp.einsum(
        "bkgqs,bskd->bkgqd", p.astype(cd), v.astype(cd),
        preferred_element_type=jnp.float32,
    )
    out = out / jnp.where(l == 0.0, 1.0, l)[..., None]
    lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(jnp.where(l == 0.0, 1.0, l)))
    # -> (B, Sq, H, hd), (B, Sq, H)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    lse = lse.transpose(0, 3, 1, 2).reshape(B, Sq, H)
    return out, lse


def merge_segments(parts):
    """Exactly combine [(out_normalized, lse), ...] partial attentions."""
    m = parts[0][1]
    for _, lse in parts[1:]:
        m = jnp.maximum(m, lse)
    m = jnp.maximum(m, NEG_INF)  # keep finite when all segments are empty
    num = 0.0
    den = 0.0
    for out, lse in parts:
        w = jnp.exp(lse - m)                                # (B,Sq,H)
        num = num + out * w[..., None]
        den = den + w
    den = jnp.where(den == 0.0, 1.0, den)
    return num / den[..., None]


def _chunked_attention(q, k, v, *, causal, kv_len, scale, q_offset=0, block_k=512):
    """Online-softmax over KV chunks (XLA flash): O(Sq*bk) live memory.

    GQA-native: q is grouped per KV head ("bqkgd,bskd" einsums), so KV is
    never materialized repeated ``group`` times — at 72B-decode scale that's
    the difference between a 268 MB and a 2 GB per-device working set.  The
    chunk body is rematerialized (jax.checkpoint) so the backward pass
    re-derives the (Sq, block_k) score tile instead of saving one per chunk.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    Sk = k.shape[1]
    pad = (-Sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkb = k.shape[1] // block_k
    kb = k.reshape(B, nkb, block_k, KV, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nkb, block_k, KV, hd).transpose(1, 0, 2, 3, 4)

    qf = q.reshape(B, Sq, KV, group, hd).astype(jnp.float32)
    qpos = (q_offset + jnp.arange(Sq))[None, None, None, :, None]

    @jax.checkpoint
    def body(carry, blk):
        m, l, acc = carry
        kc, vc, ki = blk
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qf, kc.astype(jnp.float32)
        ) * scale                                      # (B, KV, g, Sq, bk)
        kpos = (ki * block_k + jnp.arange(block_k))[None, None, None, None, :]
        mask = kpos < kv_len
        if causal:
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        l_new = alpha * l + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, group, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, group, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, group, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(nkb))
    )
    out = acc / jnp.where(l[..., None] == 0, 1.0, l[..., None])
    # (B, KV, g, Sq, hd) -> (B, Sq, H, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def _inner_attention(q, k, v, *, impl, causal, kv_len, scale, q_offset=0):
    group = q.shape[2] // k.shape[2]
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fl

        o = fl.flash_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=causal,
            scale=scale,
        )
        return o.transpose(0, 2, 1, 3)
    if impl == "chunked":
        return _chunked_attention(
            q, k, v, causal=causal, kv_len=kv_len, scale=scale, q_offset=q_offset
        )
    k = _repeat_kv(k, group)
    v = _repeat_kv(v, group)
    return _naive_attention(
        q, k, v, causal=causal, kv_len=kv_len, scale=scale, q_offset=q_offset
    )


def apply_attention(
    p: Dict,
    cfg: ModelConfig,
    x: jax.Array,                       # (B, S, d)
    positions: jax.Array,               # (B, S) or (B, S, 3)
    *,
    impl: str = "naive",
    causal: bool = True,
    cache: Optional[Cache] = None,
    cache_len: Optional[jax.Array] = None,   # scalar int32: tokens already cached
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn
    scale: Optional[float] = None,           # default 1/sqrt(head_dim)
) -> Tuple[jax.Array, Optional[Cache]]:
    B, S, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    scale = 1.0 / math.sqrt(hd) if scale is None else scale

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if cache is not None and S == 1:
        # decode: q is tiny — replicate heads so the attention contractions
        # stay aligned with the kv_seq-sharded cache (split-K pattern: the
        # softmax/PV reductions become small partial-sum all-reduces instead
        # of a full cache re-shard to a heads-sharded layout).
        q = constraint(q, "batch", "seq", None, None)
    else:
        q = constraint(q, "batch", "seq", "heads", None)
    q = apply_rope(cfg, q, positions)

    if kv_override is not None:
        k, v = kv_override
        out = _inner_attention(
            q, k, v, impl=impl, causal=False,
            kv_len=cache_len if cache_len is not None else k.shape[1],
            scale=scale,
        )
        new_cache = cache
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
        k = constraint(k, "batch", "seq", "kv_heads", None)
        v = constraint(v, "batch", "seq", "kv_heads", None)
        k = apply_rope(cfg, k, positions)

        if cache is None:
            out = _inner_attention(
                q, k, v, impl=impl, causal=causal, kv_len=S, scale=scale
            )
            new_cache = None
        elif isinstance(cache, dict):
            # Split KV cache: "main" is the big kv_seq-sharded store
            # (READ-ONLY within a decode step — never DUS'd on its sharded
            # dim, which would force a full-cache reshard), "recent" is a
            # small batch-sharded ring the new tokens append to; a separate
            # amortized flush moves recent -> main every R steps.  The two
            # segments merge exactly via logsumexp weights.
            mk, mv = cache["main"]
            rk, rv = cache["recent"]
            len_main, len_rec = cache_len  # (tokens in main, tokens in recent)
            rk = jax.lax.dynamic_update_slice(
                rk, k.astype(rk.dtype), (0, len_rec, 0, 0)
            )
            rv = jax.lax.dynamic_update_slice(
                rv, v.astype(rv.dtype), (0, len_rec, 0, 0)
            )
            q_pos = positions[:, :, 0] if positions.ndim == 3 else positions
            out_m, lse_m = _attention_with_lse(
                q, mk, mv, kv_len=len_main, kv_offset=0, scale=scale,
                q_pos=q_pos,
            )
            out_r, lse_r = _attention_with_lse(
                q, rk, rv, kv_len=len_rec + S, kv_offset=len_main,
                scale=scale, q_pos=q_pos,
            )
            out = merge_segments([(out_m, lse_m), (out_r, lse_r)]).astype(q.dtype)
            # main is read-only: return ONLY the recent ring so a scanned
            # layer stack never double-buffers the big store as scan ys
            new_cache = {"recent": (rk, rv)}
        else:
            ck, cv = cache
            ck = jax.lax.dynamic_update_slice(
                ck, k.astype(ck.dtype), (0, cache_len, 0, 0)
            )
            cv = jax.lax.dynamic_update_slice(
                cv, v.astype(cv.dtype), (0, cache_len, 0, 0)
            )
            # Decode (S==1) always takes the score-materializing path: the
            # score tile is (B, H, 1, Sk) — linear, not quadratic — and its
            # softmax/PV contractions partition cleanly over the kv_seq-
            # sharded cache (GSPMD turns them into partial sums), whereas a
            # scan over KV chunks would slice the sharded dim per step.
            decode_impl = "naive" if S == 1 else impl
            out = _inner_attention(
                q, ck, cv,
                impl=decode_impl, causal=causal, kv_len=cache_len + S,
                scale=scale, q_offset=cache_len,
            )
            new_cache = (ck, cv)

    if cache is not None and S == 1:
        out = constraint(out, "batch", "seq", None, None)
    else:
        out = constraint(out, "batch", "seq", "heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return constraint(y, "batch", "seq_res", None), new_cache
