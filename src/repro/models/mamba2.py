"""Mamba2 (SSD) mixer of zamba2's backbone, as ``Zamba2MambaMixer`` computes it.

One fused input projection gives the gate ``z`` (d_in), the conv input
``xBC`` (d_in + 2·G·N) and ``dt`` (one per head).  A depthwise causal conv
with bias and a silu run over ``xBC``, which splits into ``x`` (H heads of
P), ``B`` and ``C`` (G groups of N, group g serving heads
[g·H/G, (g+1)·H/G)).  With ``dt = softplus(dt + dt_bias)`` (no clamp:
``time_step_limit`` is null) and ``A = -exp(A_log)``, every head keeps a
(P, N) state

    h_t = exp(dt_t·A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t

and ``y`` goes through the gated group norm (``y·silu(z)`` first, then an
RMS norm over each group of d_in/G channels, eps 1e-5) into the output
projection.  The SSD form runs a sequence in chunks: within a chunk the
pairwise decays exp(cum_i - cum_j), i >= j, are at most 1, so no decay is
clamped; across chunks a scan carries the state.  A decode step is the
recurrence itself.  The state of a sequence is ``MambaState``: the last
K-1 conv inputs and the (H, P, N) SSD state, both float32.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constraint
from repro.models.params import ParamDef

HIGHEST = jax.lax.Precision.HIGHEST
#: tokens per SSD chunk of a prefill or a training sequence
CHUNK = 128


class MambaState(NamedTuple):
    conv: jax.Array   # (B, K-1, conv_dim) float32
    ssd: jax.Array    # (B, H, P, N) float32


def dims(cfg: ModelConfig) -> Tuple[int, int, int, int, int, int]:
    """(d_in, heads H, head dim P, state N, groups G, conv_dim)."""
    d_in = cfg.ssm_expand * cfg.d_model
    G, N = cfg.ssm_ngroups, cfg.ssm_state
    return d_in, d_in // cfg.ssm_head_dim, cfg.ssm_head_dim, N, G, d_in + 2 * G * N


def slot_widths(cfg: ModelConfig) -> Dict[str, int]:
    """One sequence's state per layer as the pool stores it, lane-dense:
    the conv inputs flattened to (K-1)·conv_dim, the SSD state as H rows
    of P·N."""
    d_in, H, P, N, G, conv_dim = dims(cfg)
    return {"conv_width": (cfg.ssm_conv - 1) * conv_dim, "ssd_heads": H, "ssd_width": P * N}


def mamba_defs(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    d_in, H, P, N, G, conv_dim = dims(cfg)
    defs = {
        "in_proj": ParamDef((d, d_in + conv_dim + H), ("embed", "mlp")),
        "conv_w": ParamDef((cfg.ssm_conv, conv_dim), ("conv", "mlp"), init="embed", scale=0.5),
        "dt_bias": ParamDef((H,), ("heads",), init="zeros"),
        "A_log": ParamDef((H,), ("heads",), init="zeros"),
        "D": ParamDef((H,), ("heads",), init="ones"),
        "norm": ParamDef((d_in,), ("mlp",), init="ones"),
        "out_proj": ParamDef((d_in, d), ("mlp", "embed")),
    }
    if cfg.ssm_conv_bias:
        defs["conv_b"] = ParamDef((conv_dim,), ("mlp",), init="zeros")
    return defs


def mamba_in(p: Dict, cfg: ModelConfig, u: jax.Array, conv_prev: Optional[jax.Array]):
    """Input projection and causal conv of the normed input ``u`` (B, S, d).
    Returns ``(z, x, B, C, dt)`` — ``x`` (B, S, H, P), ``B`` and ``C``
    (B, S, G, N) and ``dt`` (B, S, H) in float32 — and the new conv state
    (B, K-1, conv_dim)."""
    Bsz, S, _ = u.shape
    d_in, H, P, N, G, conv_dim = dims(cfg)
    zxbcdt = jnp.einsum("bsd,de->bse", u, p["in_proj"].astype(u.dtype))
    z, xBC, dt = jnp.split(zxbcdt, [d_in, d_in + conv_dim], axis=-1)
    xBC = constraint(xBC, "batch", "seq", "mlp")
    K = cfg.ssm_conv
    if conv_prev is None:
        conv_prev = jnp.zeros((Bsz, K - 1, conv_dim), jnp.float32)
    xp = jnp.concatenate([conv_prev.astype(jnp.float32), xBC.astype(jnp.float32)], axis=1)
    w = p["conv_w"].astype(jnp.float32)
    out = sum(xp[:, i:i + S] * w[i] for i in range(K))
    if "conv_b" in p:
        out = out + p["conv_b"].astype(jnp.float32)
    xBC = jax.nn.silu(out)
    x, Bm, Cm = jnp.split(xBC, [d_in, d_in + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    return (z, x.reshape(Bsz, S, H, P), Bm.reshape(Bsz, S, G, N), Cm.reshape(Bsz, S, G, N),
            dt, xp[:, S:])


def _heads(a: jax.Array, H: int) -> jax.Array:
    """(..., G, N) per group -> (..., H, N) per head (group g serves H/G
    consecutive heads)."""
    return jnp.repeat(a, H // a.shape[-2], axis=-2)


def ssd_step(x, dt, A, Bm, Cm, h):
    """One token: x (B, H, P), dt (B, H), A (H,), B/C (B, G, N), h (B, H, P, N)
    float32.  Returns (y (B, H, P) without the D skip, new h)."""
    H = x.shape[1]
    Bh, Ch = _heads(Bm, H), _heads(Cm, H)
    h = (h * jnp.exp(dt * A)[..., None, None]
         + (dt[..., None] * x)[..., None] * Bh[:, :, None, :])
    return jnp.sum(h * Ch[:, :, None, :], axis=-1), h


def ssd_chunked(x, dt, A, Bm, Cm, h0=None, chunk: int = CHUNK):
    """A sequence: x (B, S, H, P), dt (B, S, H), A (H,), B/C (B, S, G, N),
    h0 (B, H, P, N) or None, all float32.  Returns (y (B, S, H, P) without
    the D skip, final state)."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2:]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:     # dt 0 past the end: decay 1 and no input, the state passes
        zp = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        x, dt, Bm, Cm = zp(x), zp(dt), zp(Bm), zp(Cm)
    nc = x.shape[1] // Q
    chunks = lambda a: a.reshape((Bsz, nc, Q) + a.shape[2:]).swapaxes(0, 1)  # noqa: E731
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    ein = lambda eq, *a: jnp.einsum(eq, *a, precision=HIGHEST)  # noqa: E731

    def body(h, blk):
        xq, dtq, Bq, Cq = blk                                 # (B, Q, ...)
        cum = jnp.cumsum(dtq * A, axis=1)                     # (B, Q, H), <= 0
        seg = cum[:, :, None, :] - cum[:, None, :, :]          # (B, Qi, Qj, H)
        decay = jnp.where(causal[None, :, :, None], jnp.exp(jnp.where(
            causal[None, :, :, None], seg, 0.0)), 0.0)
        Bh, Ch = _heads(Bq, H), _heads(Cq, H)                  # (B, Q, H, N)
        scores = ein("bihn,bjhn->bijh", Ch, Bh) * decay * dtq[:, None, :, :]
        y = ein("bijh,bjhp->bihp", scores, xq)
        y = y + ein("bihn,bhpn->bihp", Ch, h) * jnp.exp(cum)[..., None]
        to_end = jnp.exp(cum[:, -1:, :] - cum) * dtq           # (B, Q, H)
        h = (h * jnp.exp(cum[:, -1])[..., None, None]
             + ein("bjh,bjhp,bjhn->bhpn", to_end, xq, Bh))
        return h, y

    h, ys = jax.lax.scan(body, h0.astype(jnp.float32), (chunks(x), chunks(dt),
                                                        chunks(Bm), chunks(Cm)))
    return ys.swapaxes(0, 1).reshape(Bsz, nc * Q, H, P)[:, :S], h


def mamba_out(p: Dict, cfg: ModelConfig, y, x, z) -> jax.Array:
    """The D skip, the gated group norm and the output projection:
    y, x (B, S, H, P) float32, z (B, S, d_in) -> (B, S, d)."""
    Bsz, S, H, P = y.shape
    d_in, _, _, _, G, _ = dims(cfg)
    y = (y + p["D"][:, None] * x).reshape(Bsz, S, G, d_in // G)
    y = y * jax.nn.silu(z.astype(jnp.float32)).reshape(y.shape)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    y = (y.reshape(Bsz, S, d_in) * p["norm"]).astype(z.dtype)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(z.dtype))
    return constraint(out, "batch", "seq_res", None)


def apply_mamba(
    p: Dict,
    cfg: ModelConfig,
    u: jax.Array,                 # (B, S, d), normed
    state: Optional[MambaState] = None,
) -> Tuple[jax.Array, Optional[MambaState]]:
    """The mixer over ``u``; with ``state`` it continues that state and
    returns the new one (one token: the recurrence; more: the chunks)."""
    z, x, Bm, Cm, dt, conv = mamba_in(p, cfg, u, None if state is None else state.conv)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if u.shape[1] == 1 and state is not None:
        y, h = ssd_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], state.ssd)
        y = y[:, None]
    else:
        y, h = ssd_chunked(x, dt, A, Bm, Cm, None if state is None else state.ssd)
    out = mamba_out(p, cfg, y, x, z)
    return out, (None if state is None else MambaState(conv=conv, ssd=h))


def init_mamba_state(cfg: ModelConfig, batch: int) -> MambaState:
    d_in, H, P, N, G, conv_dim = dims(cfg)
    return MambaState(
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), jnp.float32),
        ssd=jnp.zeros((batch, H, P, N), jnp.float32),
    )
