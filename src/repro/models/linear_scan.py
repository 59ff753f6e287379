"""Chunked linear attention with per-step decay — RWKV6's (Finch,
per-channel data-dependent decay + bonus); the ``bonus=None`` form is the
decay kernel's oracle.  Mamba2 has its own SSD chunks (``models/mamba2.py``),
which need no clamp of the decay.

Recurrence (state S: (dk, dv) per head):

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    y_t = q_t . S_t                         (mamba-style, ``bonus=None``)
    y_t = q_t . S_{t-1} + (q_t*u).k_t v_t   (rwkv-style, ``bonus=u``)

The chunk-parallel form turns the intra-chunk part into two matmuls with a
causal mask and the inter-chunk part into a scan over chunk states — the
standard SSD/GLA decomposition, which keeps HLO cost analysis meaningful
(FLOPs live in einsums, not a length-S while loop).

Numerics: pairwise weights exp(cum_i - cum_j) are computed factored
(q*exp(cum)) . (k*exp(-cum)); with per-step log-decay clamped to
``MIN_LOG_DECAY`` and ``chunk`` = 32, |cum| <= 57.6 so both factors stay
inside float32 range while every *product* is <= 1.  Faster-than-0.165/step
decays are indistinguishable from zero-memory anyway.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

MIN_LOG_DECAY = -1.8
CHUNK = 32


def chunked_decay_attention(
    q: jax.Array,          # (B, S, H, dk)
    k: jax.Array,          # (B, S, H, dk)
    v: jax.Array,          # (B, S, H, dv)
    log_w: jax.Array,      # (B, S, H, dk) per-step log decay (<= 0)
    *,
    bonus: Optional[jax.Array] = None,   # (H, dk) rwkv "u"
    initial_state: Optional[jax.Array] = None,  # (B, H, dk, dv)
    chunk: int = CHUNK,
    return_state: bool = False,
):
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    orig_S = S
    pad = (-S) % chunk
    if pad:
        zpad = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q, k, v, log_w = zpad(q), zpad(k), zpad(v), zpad(log_w)
        S = q.shape[1]
    nc = S // chunk

    f32 = jnp.float32
    qc = q.astype(f32).reshape(B, nc, chunk, H, dk).transpose(1, 0, 2, 3, 4)
    kc = k.astype(f32).reshape(B, nc, chunk, H, dk).transpose(1, 0, 2, 3, 4)
    vc = v.astype(f32).reshape(B, nc, chunk, H, dv).transpose(1, 0, 2, 3, 4)
    lw = (
        jnp.clip(log_w.astype(f32), MIN_LOG_DECAY, 0.0)
        .reshape(B, nc, chunk, H, dk)
        .transpose(1, 0, 2, 3, 4)
    )

    i_idx = jnp.arange(chunk)[:, None]
    j_idx = jnp.arange(chunk)[None, :]
    mask = (j_idx <= i_idx) if bonus is None else (j_idx < i_idx)

    S0 = (
        initial_state.astype(f32)
        if initial_state is not None
        else jnp.zeros((B, H, dk, dv), f32)
    )

    def body(Sprev, blk):
        qb, kb, vb, lwb = blk                         # (B, Q, H, dk/dv)
        cum = jnp.cumsum(lwb, axis=1)                 # inclusive
        ecum = cum - lwb                              # exclusive
        total = cum[:, -1]                            # (B, H, dk)

        q_out = qb * jnp.exp(cum if bonus is None else ecum)
        qs = qb * jnp.exp(cum if bonus is None else ecum)
        ks = kb * jnp.exp(-cum)
        A = jnp.einsum("bihk,bjhk->bhij", qs, ks)
        A = jnp.where(mask[None, None], A, 0.0)
        y = jnp.einsum("bhij,bjhv->bihv", A, vb)
        if bonus is not None:
            diag = ((qb * bonus[None, None]) * kb).sum(-1)  # (B, Q, H)
            y = y + diag[..., None] * vb
        y = y + jnp.einsum("bihk,bhkv->bihv", q_out, Sprev)

        ks_end = kb * jnp.exp(total[:, None] - cum)   # <= 1
        Snew = Sprev * jnp.exp(total)[..., None] + jnp.einsum(
            "bihk,bihv->bhkv", ks_end, vb
        )
        return Snew, y

    S_final, ys = jax.lax.scan(body, S0, (qc, kc, vc, lw))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, H, dv)[:, :orig_S]
    y = y.astype(q.dtype)
    if return_state:
        return y, S_final
    return y


def decay_attention_step(
    q1: jax.Array,         # (B, H, dk)
    k1: jax.Array,
    v1: jax.Array,         # (B, H, dv)
    log_w1: jax.Array,     # (B, H, dk)
    state: jax.Array,      # (B, H, dk, dv)
    *,
    bonus: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Single decode step of the same recurrence (serve path, O(1) memory)."""
    f32 = jnp.float32
    w = jnp.exp(jnp.clip(log_w1.astype(f32), MIN_LOG_DECAY, 0.0))
    kv = jnp.einsum("bhk,bhv->bhkv", k1.astype(f32), v1.astype(f32))
    if bonus is None:
        new_state = state * w[..., None] + kv
        y = jnp.einsum("bhk,bhkv->bhv", q1.astype(f32), new_state)
    else:
        y = jnp.einsum("bhk,bhkv->bhv", q1.astype(f32), state) + (
            (q1.astype(f32) * bonus[None]) * k1.astype(f32)
        ).sum(-1)[..., None] * v1.astype(f32)
        new_state = state * w[..., None] + kv
    return y.astype(q1.dtype), new_state


def decay_attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array, log_w: jax.Array,
    *, bonus: Optional[jax.Array] = None,
    initial_state: Optional[jax.Array] = None,
    return_state: bool = False,
):
    """Sequential oracle (scan over time steps) for tests."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    S0 = (
        initial_state.astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((B, H, dk, dv), jnp.float32)
    )

    def body(state, xs):
        q1, k1, v1, w1 = xs
        y, ns = decay_attention_step(q1, k1, v1, w1, state, bonus=bonus)
        return ns, y

    tr = lambda x: x.transpose(1, 0, 2, 3)
    Sf, ys = jax.lax.scan(body, S0, (tr(q), tr(k), tr(v), tr(log_w)))
    y = ys.transpose(1, 0, 2, 3).astype(q.dtype)
    if return_state:
        return y, Sf
    return y
