"""Decode-step over the PUMA paged KV pool (dense/moe/vlm and hybrid families).

This is where the paper's technique meets the serving path: attention reads
KV through the *block table* (re-mmap analogue) with the
``repro.kernels.paged_attention`` kernel, and the new token's K/V is written
back into pool blocks placed by the PUMA policy.

The runner mirrors ``LM.decode_step`` exactly (same params, same math) with
the dense cache swapped for (k_pool, v_pool, block_table, seq_lens); the
layer loop is unrolled (serving configs are small).

Every weight matrix is cast to ``cfg.dtype`` where it is used, so the step
takes parameters in f32 or with the matrices already in the compute dtype,
as ``ServeEngine`` holds them where its copy fits; on those the casts are
no-ops and the matmuls see the same values.

A hybrid (zamba2) runs its Mamba2 layers in order, each on its sequences'
state slots (``state``: the pool's conv and SSD arrays and the batch's
slots) for one token, and before the layers ``hybrid_uses`` names one use of
a shared block, whose attention reads that use's pages of the pool.  It
returns the new states beside the new K/V, for the caller to write back.

Each layer's parts carry a ``jax.named_scope`` — ``attn_proj`` (norm, q/k/v
projections, rope), ``paged_attention`` (the kernel and the merge of the
current token), ``paged_lse`` (the second pass for the log-sum-exp),
``attn_out`` and ``mlp`` (a shared block's MLP with its use's adapter and
linear) — a Mamba2 layer's ``mamba_in`` (norm, input projection, conv),
``ssm_state`` (the recurrence) and ``mamba_out`` (D skip, gated norm, output
projection), and the final norm and head carry ``logits``.  Scopes only
name the operations in the program's metadata, so a profiler trace can
split the step's device time by part.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import ops as paged_ops
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.models import transformer as TF
from repro.models.rope import apply_rope

__all__ = ["paged_decode_step", "paged_decode_step_jit"]


def paged_decode_step(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,        # (B, 1)
    positions: jax.Array,     # (B, 1)
    k_pool: jax.Array,        # (L, nb, bs, KV, hd)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks)
    seq_lens: jax.Array,      # (B,) length INCLUDING the current token
    state: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
    *,
    use_kernel: bool = True,
) -> Tuple[jax.Array, ...]:
    """Returns (logits (B, V), new_k (L, B, KV, hd), new_v (L, B, KV, hd)),
    L the layers that keep KV; a hybrid also returns its new states
    ``(conv (L_m, B, (K-1)·conv_dim), ssd (L_m, B, H, P·N))`` after them,
    from ``state`` = (conv pool, ssd pool, slots (B,)).

    The caller writes new_k/new_v into pool blocks, every layer and
    sequence in one ``PagedKVPool.write_token_kv`` call (host-side PUMA
    bookkeeping decides *which* blocks — that's the paper's policy layer).
    Attention masks to ``seq_lens`` which already counts the current token,
    whose K/V is injected via a one-slot overlay so the kernel sees it
    before the host writes it back.
    """
    if cfg.family == "hybrid":
        return _hybrid_step(params, cfg, tokens, positions, k_pool, v_pool, block_tables,
                            seq_lens, state, use_kernel=use_kernel)
    B = tokens.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dtype = jnp.dtype(cfg.dtype)

    x = L.embed_tokens(params["embed"], tokens, dtype)   # (B, 1, d)

    new_ks, new_vs = [], []
    n_layers = cfg.n_layers
    for li in range(n_layers):
        lp = jax.tree.map(lambda a: a[li], params["layers"])
        with jax.named_scope("attn_proj"):
            h = L.apply_norm(lp["ln1"], x)
            q = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wq"].astype(dtype))
            k1 = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wk"].astype(dtype))
            v1 = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wv"].astype(dtype))
            q = apply_rope(cfg, q, positions)
            k1 = apply_rope(cfg, k1, positions)

        # overlay: extend each sequence's KV stream with the current token by
        # appending a virtual block holding it at position seq_len-1.
        attn_out = _paged_attention_with_current(
            q[:, 0], k_pool[li], v_pool[li], block_tables, seq_lens,
            k1[:, 0].astype(k_pool.dtype), v1[:, 0].astype(v_pool.dtype),
            use_kernel=use_kernel,
        )
        with jax.named_scope("attn_out"):
            a = jnp.einsum("bhk,hkd->bd", attn_out, lp["attn"]["wo"].astype(dtype))
            x = x + a[:, None]
        with jax.named_scope("mlp"):
            h = L.apply_norm(lp["ln2"], x)
            if cfg.n_experts:
                m, _ = MOE.apply_moe(lp["moe"], cfg, h)
            else:
                m = L.apply_mlp(lp["mlp"], h)
            x = x + m
        new_ks.append(k1[:, 0])
        new_vs.append(v1[:, 0])

    with jax.named_scope("logits"):
        x = L.apply_norm(params["final_ln"], x)
        logits = L.logits_from(params["embed"], x)[:, 0]
    return logits, jnp.stack(new_ks), jnp.stack(new_vs)


def _hybrid_step(params, cfg, tokens, positions, k_pool, v_pool, block_tables, seq_lens,
                 state, *, use_kernel):
    conv_pool, ssd_pool, slots = state
    B = tokens.shape[0]
    d_in, H, P, N, G, conv_dim = M2.dims(cfg)
    dtype = jnp.dtype(cfg.dtype)
    uses = cfg.hybrid_uses
    x = L.embed_tokens(params["embed"], tokens, dtype)   # (B, 1, d)
    e = x
    zero = jnp.minimum(seq_lens[0], 0)
    # the pool's head dimension fills whole lane tiles (``kv_pool.lane_dim``)
    lanes = lambda a: jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, k_pool.shape[-1] - cfg.hd),))  # noqa: E731
    new_ks, new_vs, new_conv, new_ssd = [], [], [], []
    for li in range(cfg.n_layers):
        T = None
        if li in uses:
            u = uses.index(li)
            sp = jax.tree.map(lambda a: a[u % cfg.num_mem_blocks], params["shared"])
            up = jax.tree.map(lambda a: a[u], params["uses"])
            with jax.named_scope("attn_proj"):
                h = TF.shared_block_input(sp, cfg, x, e)
                q = apply_rope(cfg, jnp.einsum("bsd,dhk->bshk", h, sp["wq"].astype(dtype)),
                               positions)
                k1 = apply_rope(cfg, jnp.einsum("bsd,dhk->bshk", h, sp["wk"].astype(dtype)),
                                positions)
                v1 = jnp.einsum("bsd,dhk->bshk", h, sp["wv"].astype(dtype))
                q, k1, v1 = lanes(q), lanes(k1), lanes(v1)
            o = _paged_attention_with_current(
                q[:, 0], k_pool, v_pool, block_tables, seq_lens,
                k1[:, 0].astype(k_pool.dtype), v1[:, 0].astype(v_pool.dtype),
                use_kernel=use_kernel, scale=(cfg.hd / 2) ** -0.5, layer=u,
            )[..., :cfg.hd]
            with jax.named_scope("attn_out"):
                a = jnp.einsum("bhk,hkd->bd", o, sp["wo"].astype(dtype))[:, None]
            with jax.named_scope("mlp"):
                T = TF.shared_block_mlp(sp, up, cfg, a)
            new_ks.append(k1[:, 0])
            new_vs.append(v1[:, 0])
        # an index the compiler cannot fold keeps each layer's weight cast to
        # that layer (folded, the casts merge into one of the whole stack)
        at = li + zero
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, at, keepdims=False),
                          params["layers"])
        with jax.named_scope("mamba_in"):
            h = L.apply_norm(lp["ln"], x if T is None else x + T, cfg.norm_eps)
            conv = conv_pool[slots, at].reshape(B, cfg.ssm_conv - 1, conv_dim)
            z, xs, Bm, Cm, dt, conv = M2.mamba_in(lp["mamba"], cfg, h, conv)
        with jax.named_scope("ssm_state"):
            A = -jnp.exp(lp["mamba"]["A_log"].astype(jnp.float32))
            ssd = ssd_pool[slots, at].reshape(B, H, P, N)
            y, ssd = M2.ssd_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], ssd)
        with jax.named_scope("mamba_out"):
            x = x + M2.mamba_out(lp["mamba"], cfg, y[:, None], xs, z)
        new_conv.append(conv.reshape(B, -1))
        new_ssd.append(ssd.reshape(B, H, P * N))
    with jax.named_scope("logits"):
        x = L.apply_norm(params["final_ln"], x, cfg.norm_eps)
        logits = L.logits_from(params["embed"], x)[:, 0]
    return (logits, jnp.stack(new_ks), jnp.stack(new_vs), jnp.stack(new_conv),
            jnp.stack(new_ssd))


def _paged_attention_with_current(
    q, k_pool, v_pool, block_tables, seq_lens, k_cur, v_cur, *, use_kernel, scale=None,
    layer=None,
):
    """Attention over pooled KV plus the in-flight token.

    We append one per-sequence "current" block to the pool view and extend
    each block table with its index; masking is handled by seq_lens.  The
    current token sits at position ceil: we place it in a dedicated block at
    offset (seq_len-1) % block_size of a scratch block filled at that slot.
    For simplicity and exactness, scratch blocks hold ONLY the current token
    at slot 0 and the table entry is appended with an adjusted... — instead
    we take the simpler exact route: compute attention over pool (lengths
    seq_len-1) and merge the current token analytically.

    With ``layer`` the pools hold every layer and the kernel reads that
    layer's pages in place and returns the past log-sum-exp itself, so the
    second pass (``paged_lse``) is not made.
    """
    B, H, hd = q.shape
    KV = k_pool.shape[-2]
    scale = hd ** -0.5 if scale is None else scale
    group = H // KV

    # past contribution (lengths exclude the current token)
    past_len = seq_lens - 1
    with jax.named_scope("paged_attention"):
        if layer is None:
            out_past = paged_ops.paged_attention(
                q, k_pool, v_pool, block_tables, past_len,
                scale=scale, use_kernel=use_kernel,
            )                                             # (B, H, hd)
        else:
            out_past, lse_past = paged_ops.paged_attention_layer(
                q, k_pool, v_pool, block_tables, past_len, layer,
                scale=scale, use_kernel=use_kernel,
            )

        # merge current token: softmax over [past, current] decomposes into
        # weighted average of past attention output and v_cur.
        qg = q.reshape(B, KV, group, hd).astype(jnp.float32)
        s_cur = jnp.einsum("bkgd,bkd->bkg", qg, k_cur.astype(jnp.float32)) * scale

    # recompute the past logsumexp (cheap second pass over logits only)
    if layer is None:
        with jax.named_scope("paged_lse"):
            lse_past = _paged_lse(q, k_pool, block_tables, past_len, scale)  # (B,KV,group)
    with jax.named_scope("paged_attention"):
        has_past = (past_len > 0)[:, None, None]
        m = jnp.maximum(jnp.where(has_past, lse_past, -jnp.inf), s_cur)
        w_past = jnp.where(has_past, jnp.exp(lse_past - m), 0.0)
        w_cur = jnp.exp(s_cur - m)
        denom = w_past + w_cur
        out = (
            out_past.reshape(B, KV, group, hd).astype(jnp.float32) * w_past[..., None]
            + v_cur.astype(jnp.float32)[:, :, None, :] * w_cur[..., None]
        ) / denom[..., None]
        return out.reshape(B, H, hd).astype(q.dtype)


def _paged_lse(q, k_pool, block_tables, seq_lens, scale):
    """log-sum-exp of past attention logits, via the jnp gather path."""
    B, H, hd = q.shape
    nb, bs, KV, _ = k_pool.shape
    group = H // KV
    idx = jnp.maximum(block_tables, 0)
    k = k_pool[idx].reshape(B, -1, KV, hd)                 # (B, S, KV, hd)
    qg = q.reshape(B, KV, group, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32)) * scale
    pos = jnp.arange(s.shape[-1])[None, None, None, :]
    s = jnp.where(pos < seq_lens[:, None, None, None], s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1)                    # (B, KV, group)


#: process-wide jitted variant (cfg and use_kernel are static): the serving
#: engine's decode hot path.  One shared wrapper — not one per engine — so
#: the XLA cache survives across scenario/engine instances and a load run
#: compiles each (batch, pool) shape exactly once.
paged_decode_step_jit = jax.jit(
    paged_decode_step, static_argnums=(1,), static_argnames=("use_kernel",)
)
