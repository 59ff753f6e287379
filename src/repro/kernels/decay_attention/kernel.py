"""Chunked decay linear attention Pallas kernel (RWKV6 / Mamba2 hot loop).

TPU-native SSD/GLA chunk recurrence: the grid walks (batch, head, chunk)
with the chunk axis innermost; the (dk, dv) state lives in VMEM scratch and
persists across grid steps for a fixed (batch, head) — TPU grids execute
sequentially, which is exactly the dependency the recurrence needs.  Each
chunk does three MXU matmuls (A = qs ks^T, y_intra = A v, state update
ks_end^T v) plus VPU exp/cumsum work; numerics follow
repro.models.linear_scan (clamped per-step log decay keeps the factored
exp(cum_i - cum_j) inside f32 range).

Layout: operands come in as (B, H, nc, Q, d) so the per-step block
(1, 1, 1, Q, d) is a clean (Q, d) VMEM tile (Q = 32 sublane-aligned,
d padded to 128 lanes by ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MIN_LOG_DECAY = -1.8
CHUNK = 32


def _decay_kernel(
    q_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_scr,
    *, chunk, use_bonus,
):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    q = q_ref[0, 0, 0].astype(jnp.float32)        # (Q, dk)
    k = k_ref[0, 0, 0].astype(jnp.float32)
    v = v_ref[0, 0, 0].astype(jnp.float32)        # (Q, dv)
    lw = jnp.clip(lw_ref[0, 0, 0].astype(jnp.float32), MIN_LOG_DECAY, 0.0)

    cum = jnp.cumsum(lw, axis=0)                  # inclusive (Q, dk)
    ecum = cum - lw                               # exclusive
    total = cum[-1]                               # (dk,)

    q_out_scale = jnp.exp(ecum if use_bonus else cum)
    qs = q * q_out_scale
    ks = k * jnp.exp(-cum)
    A = jax.lax.dot_general(
        qs, ks, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                             # (Q, Q)
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = (j_idx < i_idx) if use_bonus else (j_idx <= i_idx)
    A = jnp.where(mask, A, 0.0)

    y = jax.lax.dot(A, v, preferred_element_type=jnp.float32)
    if use_bonus:
        u = u_ref[0].astype(jnp.float32)          # (dk,)
        diag = ((q * u[None, :]) * k).sum(-1)     # (Q,)
        y = y + diag[:, None] * v
    # inter-chunk: qs carries the same exp(cum/ecum) scaling the state needs
    y = y + jax.lax.dot(qs, state_scr[...], preferred_element_type=jnp.float32)

    ks_end = k * jnp.exp(total[None, :] - cum)    # <= 1
    state_scr[...] = state_scr[...] * jnp.exp(total)[:, None] + jax.lax.dot_general(
        ks_end, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[0, 0, 0] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "use_bonus", "interpret")
)
def decay_attention(
    q: jax.Array,    # (B, H, nc, Q, dk)
    k: jax.Array,
    v: jax.Array,    # (B, H, nc, Q, dv)
    log_w: jax.Array,
    u: jax.Array,    # (H, dk) — ignored unless use_bonus
    *,
    chunk: int = CHUNK,
    use_bonus: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    B, H, nc, Q, dk = q.shape
    dv = v.shape[-1]
    spec_k = pl.BlockSpec((1, 1, 1, Q, dk), lambda b, h, c: (b, h, c, 0, 0))
    spec_v = pl.BlockSpec((1, 1, 1, Q, dv), lambda b, h, c: (b, h, c, 0, 0))
    kernel = functools.partial(_decay_kernel, chunk=Q, use_bonus=use_bonus)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            spec_k, spec_k, spec_v, spec_k,
            pl.BlockSpec((1, dk), lambda b, h, c: (h, 0)),
        ],
        out_specs=spec_v,
        out_shape=jax.ShapeDtypeStruct((B, H, nc, Q, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=jax.default_backend() != "tpu" if interpret is None else interpret,
    )(q, k, v, log_w, u)
