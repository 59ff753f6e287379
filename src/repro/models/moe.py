"""Mixture-of-Experts block (granite-MoE style: top-k routed SwiGLU experts).

Two execution paths:

* **single-device** (unit tests / smoke, no mesh set): straightforward
  scatter/gather against a global capacity buffer.
* **distributed** (mesh set): GSPMD cannot partition a data-dependent
  scatter, so dispatch runs inside ``shard_map`` — every device routes its
  *local* tokens into a *local* (E, C_local, d) capacity buffer (exactly how
  production EP systems bound the dispatch memory), FSDP-gathers the expert
  weights over "data", computes with the f-dim sharded over "model"
  (expert-TP, granite's d_ff=512 / 16 = 32), and all-reduces the partial
  expert outputs over "model".  Capacity dropping is per-device local
  (documented deviation from global capacity; same capacity_factor).

Position-in-expert uses a double argsort over (T*K,) ids — O(TK) int32 —
instead of a (T*K, E) one-hot cumsum; scatter and combine loop over the K
routed slots so the largest float intermediate is (T_local, d).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.sharding import get_mesh
from repro.models.params import ParamDef


def moe_defs(cfg: ModelConfig) -> Dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamDef((d, E), ("embed", "experts")),
        "wg": ParamDef((E, d, f), ("experts", "embed", "mlp")),
        "wu": ParamDef((E, d, f), ("experts", "embed", "mlp")),
        "wo": ParamDef((E, f, d), ("experts", "mlp", "embed")),
    }


def _positions_in_expert(flat_e: jax.Array, E: int) -> jax.Array:
    """pos[i] = rank of slot i among slots routed to the same expert.

    Double argsort gives each slot's rank in expert-sorted order; subtracting
    the expert's first rank (via searchsorted) yields the within-expert
    position.  O(TK log TK) compute, O(TK) int32 memory.
    """
    order = jnp.argsort(flat_e)                  # slots sorted by expert
    rank = jnp.argsort(order)                    # rank of each slot
    sorted_e = flat_e[order]
    first_rank = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    return rank - first_rank[flat_e]


def _moe_math(
    xt: jax.Array,          # (T, d) local tokens
    router: jax.Array,      # (d, E)
    wg: jax.Array,          # (E, d, f_local)
    wu: jax.Array,
    wo: jax.Array,          # (E, f_local, d)
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array]:
    """Route + dispatch + expert compute for one shard's tokens.

    Returns (out_partial, aux): ``out_partial`` is a PARTIAL sum over the
    f dim if wo is f-sharded (caller psums over "model").
    """
    T, d = xt.shape
    E, K = cfg.n_experts, cfg.experts_per_tok
    C = max(8, int(cfg.moe_capacity_factor * T * K / E))

    logits = jnp.einsum("td,de->te", xt, router.astype(xt.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)       # (T, E)
    gate, eidx = jax.lax.top_k(probs, K)                              # (T, K)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux loss (local; caller averages).
    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    pos = _positions_in_expert(eidx.reshape(-1), E).reshape(T, K)
    keep = pos < C
    dst = jnp.where(keep, eidx * C + pos, E * C)                      # (T, K)

    # single-pass scatter: all T*K updates in one in-place pass over the
    # buffer (vs K full passes — 8x less HBM traffic at K=8)
    upd = jnp.broadcast_to(xt[:, None, :], (T, K, d)).reshape(T * K, d)
    buf = jnp.zeros((E * C + 1, d), xt.dtype).at[dst.reshape(-1)].add(upd)
    buf = buf[: E * C].reshape(E, C, d)

    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(xt.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(xt.dtype))
    h = jax.nn.silu(g) * u
    eo = jnp.einsum("ecf,efd->ecd", h, wo.astype(xt.dtype))           # (E, C, d)

    # single-pass gather-combine: one gather of (T*K, d), weighted-reduced
    eo_flat = jnp.concatenate([eo.reshape(E * C, d), jnp.zeros((1, d), xt.dtype)])
    picked = eo_flat[dst.reshape(-1)].reshape(T, K, d)
    out = jnp.einsum("tkd,tk->td", picked, gate.astype(xt.dtype))
    return out, aux.astype(jnp.float32)


def apply_moe(
    p: Dict, cfg: ModelConfig, x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss)."""
    B, S, d = x.shape
    mesh = get_mesh()
    if mesh is None:
        out, aux = _moe_math(
            x.reshape(B * S, d), p["router"], p["wg"], p["wu"], p["wo"], cfg
        )
        return out.reshape(B, S, d), aux

    from repro.dist.sharding import ACT_RULES, PARAM_RULES, filter_spec

    batch_rule = ACT_RULES.get("batch", "data")
    batch_axes = batch_rule if isinstance(batch_rule, tuple) else (batch_rule,)
    if "pod" in mesh.axis_names:
        batch_axes = ("pod",) + tuple(a for a in batch_axes if a != "pod")
    emb_ax, mlp_ax = PARAM_RULES.get("embed"), PARAM_RULES.get("mlp")
    # divisibility-aware specs (decode has S=1; small smoke meshes vary)
    seq_entry = "model" if "model" not in batch_axes else None
    x_spec = filter_spec(P(batch_axes, seq_entry, None), x.shape, mesh)
    router_spec = filter_spec(P(emb_ax, None), p["router"].shape, mesh)
    w_in_spec = filter_spec(P(None, emb_ax, mlp_ax), p["wg"].shape, mesh)
    w_out_spec = filter_spec(P(None, mlp_ax, emb_ax), p["wo"].shape, mesh)

    f_sharded = w_in_spec[2] is not None
    if f_sharded:
        # expert-TP partial sums over "model" are only correct when every
        # model shard sees the SAME tokens — keep seq unsharded here.
        x_spec = P(x_spec[0], None, None)

    def local_fn(xb, router, wg, wu, wo):
        # FSDP-gather the d dim of weights (transpose = reduce-scatter
        # grads).  Cast to the compute dtype FIRST: gathering f32 master
        # weights would double the bytes on the wire for no benefit — the
        # expert matmuls run in bf16 anyway (grads still reduce in f32 via
        # the convert's transpose).
        cd = xb.dtype

        def gather(w, spec_entry, axis):
            if spec_entry is None:
                return w.astype(cd)
            names = spec_entry if isinstance(spec_entry, tuple) else (spec_entry,)
            w = w.astype(cd)
            for name in reversed(names):
                w = jax.lax.all_gather(w, name, axis=axis, tiled=True)
            return w

        router = gather(router, router_spec[0], 0)
        wg = gather(wg, w_in_spec[1], 1)
        wu = gather(wu, w_in_spec[1], 1)
        wo = gather(wo, w_out_spec[2], 2)
        Bl, Sl, _ = xb.shape
        out, aux = _moe_math(xb.reshape(Bl * Sl, d), router, wg, wu, wo, cfg)
        if f_sharded:
            # expert-TP: wo's f dim is model-sharded -> partial sums
            out = jax.lax.psum(out, w_in_spec[2])
            aux = jax.lax.pmean(aux, w_in_spec[2])
        for ax in batch_axes:
            aux = jax.lax.pmean(aux, ax)
        if x_spec[1] == "model" and not f_sharded:
            aux = jax.lax.pmean(aux, "model")
        return out.reshape(Bl, Sl, d), aux

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(x_spec, router_spec, w_in_spec, w_in_spec, w_out_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    out, aux = fn(x, p["router"], p["wg"], p["wu"], p["wo"])
    return out, aux
