"""Model assembly for every assigned architecture family.

One :class:`LM` object per config exposes pure functions:

  * ``init(key) -> params``           (stacked-layer pytree, scan-ready)
  * ``param_specs(mesh) -> pytree[PartitionSpec]``
  * ``train_loss(params, batch)``     (teacher-forced CE + MoE aux)
  * ``prefill_logits(params, batch)`` (last-position logits)
  * ``decode_step(params, batch, cache) -> (logits, cache)``
  * ``init_cache(batch, max_len) / cache_specs(mesh)``

Layers are *stacked* (leading "layers" axis) and driven by ``lax.scan`` so
an 88-layer model lowers its block exactly once — the difference between a
40 s and a 40 min dry-run compile.  Remat wraps the scan body.

Families:
  dense   — [norm-attn-res, norm-mlp-res] x L (GQA/MQA, RoPE variants)
  moe     — dense attention + top-k routed experts (aux loss carried)
  ssm     — RWKV6 time-mix + channel-mix
  hybrid  — Mamba2 backbone with shared-weight attention blocks used in
            turn before the layers ``hybrid_layer_ids`` names (zamba2)
  encdec  — bidirectional encoder + causal decoder with cross-attention
  vlm     — dense + M-RoPE, patch embeddings spliced into the token stream
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import constraint, get_mesh, logical_spec
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import mamba2 as M2
from repro.models import rwkv6 as R6
from repro.models.attention import apply_attention, attn_defs
from repro.models.params import ParamDef, init_params, spec_tree

__all__ = ["LM", "shared_block_input", "shared_block_mlp"]

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def stack_defs(defs: Any, n: int) -> Any:
    """Add a leading "layers" axis to every ParamDef (for lax.scan)."""

    def one(d: ParamDef) -> ParamDef:
        return dataclasses.replace(
            d, shape=(n,) + d.shape, axes=("layers",) + d.axes
        )

    return jax.tree.map(one, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def scan_or_loop(body, carry, xs, use_scan: bool):
    """lax.scan, or an unrolled python loop over the leading axis.

    The unrolled form exists for HLO cost accounting: XLA's cost analysis
    counts a while-loop body *once*, so the roofline pipeline compiles small
    unrolled variants to recover exact per-layer costs (launch/roofline.py).
    """
    if use_scan:
        return jax.lax.scan(body, carry, xs)
    leaves = [x for x in jax.tree.leaves(xs) if hasattr(x, "shape")]
    n = leaves[0].shape[0]
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if all(jax.tree.structure(y).num_leaves == 0 for y in ys):
        return carry, ys[0]
    stacked = jax.tree.map(lambda *zs: jnp.stack(zs, axis=0), *ys)
    return carry, stacked


def _remat(fn, policy: Optional[str]):
    if policy is None or policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    raise ValueError(policy)


def cross_entropy(
    logits: jax.Array, targets: jax.Array, mask: Optional[jax.Array]
) -> jax.Array:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return (nll * mask).sum() / jnp.clip(mask.sum(), 1.0)
    return nll.mean()


# ---------------------------------------------------------------------------
# per-family layer bodies.  Signature: (lp, cfg, impl, x, pos, cache_slice,
# cache_len, extra) -> (x, new_cache_slice, aux)
# ---------------------------------------------------------------------------

def _dense_block(lp, cfg, impl, x, pos, cache, cache_len, kv_override=None):
    h = L.apply_norm(lp["ln1"], x)
    a, new_cache = apply_attention(
        lp["attn"], cfg, h, pos,
        impl=impl, causal=True, cache=cache, cache_len=cache_len,
    )
    x = x + a
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(lp["ln2"], x)
    if cfg.n_experts:
        m, aux = MOE.apply_moe(lp["moe"], cfg, h)
    else:
        m = L.apply_mlp(lp["mlp"], h)
    return x + m, new_cache, aux


def _rwkv_block(lp, cfg, impl, x, pos, state, cache_len):
    st = state  # RwkvState or None
    h = L.apply_norm(lp["ln1"], x)
    a, tm_new = R6.apply_time_mix(lp["tm"], cfg, h, st)
    x = x + a
    h = L.apply_norm(lp["ln2"], x)
    m, cm_shift = R6.apply_channel_mix(
        lp["cm"], cfg, h, st.shift_cm if st is not None else None
    )
    x = x + m
    new_state = None
    if st is not None:
        new_state = R6.RwkvState(
            shift_tm=tm_new[0], shift_cm=cm_shift, wkv=tm_new[1]
        )
    return x, new_state, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# hybrid (zamba2): Mamba2 layers, shared attention blocks used between them
# ---------------------------------------------------------------------------

def _mamba_block(lp, cfg, x, state, T=None):
    """One Mamba2 decoder layer.  ``T``, a shared-block use's output, joins
    the mixer's input only: the residual is taken before it is added."""
    h = L.apply_norm(lp["ln"], x if T is None else x + T, cfg.norm_eps)
    a, new_state = M2.apply_mamba(lp["mamba"], cfg, h, state)
    return x + a, new_state


def shared_block_defs(cfg: ModelConfig) -> Dict:
    """One shared block: attention over the 2·d concat (no residual),
    then the pre-MLP norm and a gelu MLP with a fused gate_up."""
    d, H, KV, hd, f = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff
    return {
        "ln1": L.norm_defs(cfg, 2 * d),
        "wq": ParamDef((2 * d, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamDef((2 * d, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((2 * d, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((H, hd, d), ("heads", "head_dim", "embed")),
        "ln2": L.norm_defs(cfg),
        "w_gate_up": ParamDef((d, 2 * f), ("embed", "mlp")),
        "w_down": ParamDef((f, d), ("mlp", "embed")),
    }


def use_defs(cfg: ModelConfig) -> Dict:
    """What one use of a shared block has of its own: the LoRA on gate_up
    and the linear into the next Mamba layer's input."""
    d, f, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    return {
        "lora_a": ParamDef((d, r), ("embed", None)),
        "lora_b": ParamDef((r, 2 * f), (None, "mlp")),
        "linear": ParamDef((d, d), ("embed", None)),
    }


def shared_block_input(sp: Dict, cfg: ModelConfig, x: jax.Array, e: jax.Array) -> jax.Array:
    """A shared block's normed input: the residual stream ``x`` beside the
    embedding output ``e``, 2·d wide."""
    return L.apply_norm(sp["ln1"], jnp.concatenate([x, e], axis=-1), cfg.norm_eps)


def shared_block_mlp(sp: Dict, up: Dict, cfg: ModelConfig, a: jax.Array) -> jax.Array:
    """A use after its attention output ``a`` (B, S, d): the pre-MLP norm,
    gate_up plus the use's LoRA, gelu(gate)·up, down, and the use's
    linear.  Returns T, which joins the next Mamba layer's input."""
    dt = a.dtype
    h = L.apply_norm(sp["ln2"], a, cfg.norm_eps)
    lora = jnp.einsum("bsd,dr->bsr", h, up["lora_a"].astype(dt))
    gu = (jnp.einsum("bsd,df->bsf", h, sp["w_gate_up"].astype(dt))
          + jnp.einsum("bsr,rf->bsf", lora, up["lora_b"].astype(dt)))
    g, u = jnp.split(gu, 2, axis=-1)
    m = jnp.einsum("bsf,fd->bsd", jax.nn.gelu(g, approximate=False) * u,
                   sp["w_down"].astype(dt))
    return jnp.einsum("bsd,de->bse", m, up["linear"].astype(dt))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class LM:
    def __init__(
        self,
        cfg: ModelConfig,
        *,
        attn_impl: str = "naive",
        remat: Optional[str] = "full",
        rules: Optional[Dict] = None,
        scan_layers: bool = True,
    ):
        self.cfg = cfg
        self.attn_impl = attn_impl
        self.remat = remat
        self.rules = rules or {}
        self.scan_layers = scan_layers
        self.dtype = jnp.dtype(cfg.dtype)

    # -- parameter definitions ------------------------------------------------
    def _layer_defs(self) -> Dict:
        cfg = self.cfg
        if cfg.family == "ssm":
            return {
                "ln1": L.norm_defs(cfg),
                "tm": R6.time_mix_defs(cfg),
                "ln2": L.norm_defs(cfg),
                "cm": R6.channel_mix_defs(cfg),
            }
        if cfg.family == "hybrid":
            return {"ln": L.norm_defs(cfg), "mamba": M2.mamba_defs(cfg)}
        out = {
            "ln1": L.norm_defs(cfg),
            "attn": attn_defs(cfg),
            "ln2": L.norm_defs(cfg),
        }
        if cfg.n_experts:
            out["moe"] = MOE.moe_defs(cfg)
        else:
            out["mlp"] = L.mlp_defs(cfg)
        return out

    def param_defs(self) -> Dict:
        cfg = self.cfg
        defs: Dict[str, Any] = {"embed": L.embed_defs(cfg)}
        defs["final_ln"] = L.norm_defs(cfg)
        if cfg.is_encdec:
            enc_layer = {
                "ln1": L.norm_defs(cfg),
                "attn": attn_defs(cfg),
                "ln2": L.norm_defs(cfg),
                "mlp": L.mlp_defs(cfg),
            }
            dec_layer = {
                "ln1": L.norm_defs(cfg),
                "attn": attn_defs(cfg),
                "lnx": L.norm_defs(cfg),
                "xattn": attn_defs(cfg),
                "ln2": L.norm_defs(cfg),
                "mlp": L.mlp_defs(cfg),
            }
            defs["encoder"] = stack_defs(enc_layer, cfg.enc_layers)
            defs["enc_ln"] = L.norm_defs(cfg)
            defs["decoder"] = stack_defs(dec_layer, cfg.n_layers)
            return defs
        defs["layers"] = stack_defs(self._layer_defs(), cfg.n_layers)
        if cfg.family == "hybrid":
            defs["shared"] = stack_defs(shared_block_defs(cfg), cfg.num_mem_blocks)
            defs["uses"] = stack_defs(use_defs(cfg), len(cfg.hybrid_uses))
        return defs

    def init(self, key: jax.Array) -> Dict:
        params = init_params(key, self.param_defs())
        return jax.tree.map(lambda x: x.astype(jnp.float32), params)

    def param_specs(self) -> Any:
        return spec_tree(self.param_defs(), self.rules)

    # -- forward helpers --------------------------------------------------------
    def _embed_inputs(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        tokens = batch["tokens"]
        x = L.embed_tokens(params["embed"], tokens, self.dtype)
        if cfg.frontend == "vision" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(self.dtype)
            x = jax.lax.dynamic_update_slice(x, pe, (0, 0, 0))
        pos = batch["positions"]
        return constraint(x, "batch", "seq_res", None), pos

    def _run_decoder_stack(
        self, params, x, pos, caches, cache_len, enc_out=None, enc_len=None
    ):
        """Scan the (stacked) layer params; returns (x, new_caches, aux)."""
        cfg = self.cfg
        impl = self.attn_impl

        if cfg.is_encdec:
            def body(carry, xs):
                xc, aux = carry
                lp, cache = xs
                h = L.apply_norm(lp["ln1"], xc)
                a, c_self = apply_attention(
                    lp["attn"], cfg, h, pos,
                    impl=impl, causal=True,
                    cache=None if cache is None else cache["self"],
                    cache_len=cache_len,
                )
                xc = xc + a
                h = L.apply_norm(lp["lnx"], xc)
                kv = cache["cross"] if cache is not None else enc_out
                if cache is not None:
                    a, _ = apply_attention(
                        lp["xattn"], cfg, h, pos,
                        impl=impl, kv_override=kv, cache_len=enc_len,
                    )
                else:
                    ek, ev = self._encoder_kv(lp["xattn"], enc_out)
                    a, _ = apply_attention(
                        lp["xattn"], cfg, h, pos,
                        impl=impl, kv_override=(ek, ev), cache_len=enc_len,
                    )
                xc = xc + a
                h = L.apply_norm(lp["ln2"], xc)
                xc = xc + L.apply_mlp(lp["mlp"], h)
                new_cache = None if cache is None else {"self": c_self}
                return (xc, aux), new_cache

            body = _remat(body, self.remat)
            (x, aux), new_caches = scan_or_loop(
                body, (x, jnp.zeros((), jnp.float32)), (params["decoder"], caches),
                self.scan_layers,
            )
            return x, new_caches, aux

        if cfg.family == "ssm":
            def body(carry, xs):
                xc, aux = carry
                lp, st = xs
                xc, new_st, a = _rwkv_block(lp, cfg, impl, xc, pos, st, cache_len)
                return (xc, aux + a), new_st

            body = _remat(body, self.remat)
            (x, aux), new_caches = scan_or_loop(
                body, (x, jnp.zeros((), jnp.float32)), (params["layers"], caches),
                self.scan_layers,
            )
            return x, new_caches, aux

        if cfg.family == "hybrid":
            return self._run_hybrid(params, x, pos, caches, cache_len)

        def body(carry, xs):
            xc, aux = carry
            lp, cache = xs
            xc, new_cache, a = _dense_block(lp, cfg, impl, xc, pos, cache, cache_len)
            return (xc, aux + a), new_cache

        body = _remat(body, self.remat)
        (x, aux), new_caches = scan_or_loop(
            body, (x, jnp.zeros((), jnp.float32)), (params["layers"], caches),
            self.scan_layers,
        )
        return x, new_caches, aux

    def _encoder_kv(self, attn_params, enc_out):
        cfg = self.cfg
        k = jnp.einsum("bsd,dhk->bshk", enc_out, attn_params["wk"].astype(enc_out.dtype))
        v = jnp.einsum("bsd,dhk->bshk", enc_out, attn_params["wv"].astype(enc_out.dtype))
        return k, v

    def _run_hybrid(self, params, x, pos, caches, cache_len):
        """Zamba2: the Mamba2 layers in order; before each layer of
        ``hybrid_uses`` one use of a shared block reads the residual stream
        beside the embedding output ``x`` enters with, and its output joins
        that layer's input.  The layers are unrolled, each taking its
        weights from the stacks at an index the compiler cannot fold: a
        scan's, or a folded index's, weight casts become one cast of the
        whole stack, which a chip-sized prefill has no memory for."""
        cfg = self.cfg
        e = x
        uses = cfg.hybrid_uses
        zero = jnp.minimum(jnp.min(pos), 0)
        at = lambda tree, i: jax.tree.map(  # noqa: E731
            lambda a: jax.lax.dynamic_index_in_dim(a, i + zero, keepdims=False), tree)
        mstates = caches["mamba"] if caches is not None else None
        acaches = caches["attn"] if caches is not None else None

        def layer(xc, i, T):
            st = None if mstates is None else jax.tree.map(lambda a: a[i], mstates)
            return _mamba_block(at(params["layers"], i), cfg, xc, st, T)

        layer = _remat(layer, self.remat)
        new_states, new_recent = [], []
        for i in range(cfg.n_layers):
            T = None
            if i in uses:
                u = uses.index(i)
                cache = None if acaches is None else jax.tree.map(lambda a: a[u], acaches)
                sp = at(params["shared"], u % cfg.num_mem_blocks)
                T, new_cache = self._shared_use(sp, at(params["uses"], u), x, e, pos, cache,
                                                cache_len)
                new_recent.append(new_cache)
            x, st = layer(x, i, T)
            new_states.append(st)
        if caches is None:
            return x, None, jnp.zeros((), jnp.float32)
        recent = (jax.tree.map(lambda *a: jnp.stack(a), *[c["recent"] for c in new_recent])
                  if new_recent else acaches["recent"])
        merged = jax.tree.map(lambda *a: jnp.stack(a), *new_states)
        return x, {"mamba": merged, "attn": {"recent": recent}}, jnp.zeros((), jnp.float32)

    def _shared_use(self, sp, up, x, e, pos, cache, cache_len):
        """One use of a shared block (``sp``) with its own weights ``up``:
        its T and its KV cache (attention scale (hd/2)^-0.5, as Zamba2 has
        it)."""
        cfg = self.cfg
        a, new_cache = apply_attention(
            sp, cfg, shared_block_input(sp, cfg, x, e), pos, impl=self.attn_impl,
            causal=True, cache=cache, cache_len=cache_len, scale=(cfg.hd / 2) ** -0.5,
        )
        return shared_block_mlp(sp, up, cfg, a), new_cache

    def _run_encoder(self, params, enc_embeds):
        cfg = self.cfg
        impl = self.attn_impl
        x = constraint(enc_embeds.astype(self.dtype), "batch", "seq", None)
        Se = x.shape[1]
        pos = jnp.broadcast_to(jnp.arange(Se)[None], x.shape[:2])

        def body(carry, lp):
            xc = carry
            h = L.apply_norm(lp["ln1"], xc)
            a, _ = apply_attention(lp["attn"], cfg, h, pos, impl=impl, causal=False)
            xc = xc + a
            h = L.apply_norm(lp["ln2"], xc)
            return xc + L.apply_mlp(lp["mlp"], h), None

        body = _remat(body, self.remat)
        x, _ = scan_or_loop(body, x, params["encoder"], self.scan_layers)
        return L.apply_norm(params["enc_ln"], x)

    # -- public entry points ------------------------------------------------------
    def train_loss(self, params, batch) -> jax.Array:
        cfg = self.cfg
        x, pos = self._embed_inputs(params, batch)
        enc_out = None
        if cfg.is_encdec:
            enc_out = self._run_encoder(params, batch["enc_embeds"])
        x, _, aux = self._run_decoder_stack(
            params, x, pos, None, None,
            enc_out=enc_out,
            enc_len=enc_out.shape[1] if enc_out is not None else None,
        )
        x = L.apply_norm(params["final_ln"], x, cfg.norm_eps)
        logits = L.logits_from(params["embed"], x)
        logits = constraint(logits, "batch", None, "vocab")
        loss = cross_entropy(logits, batch["targets"], batch.get("loss_mask"))
        if cfg.n_experts:
            loss = loss + 0.01 * aux / cfg.n_layers
        return loss

    def prefill_logits(self, params, batch) -> jax.Array:
        cfg = self.cfg
        x, pos = self._embed_inputs(params, batch)
        enc_out = None
        if cfg.is_encdec:
            enc_out = self._run_encoder(params, batch["enc_embeds"])
        x, _, _ = self._run_decoder_stack(
            params, x, pos, None, None,
            enc_out=enc_out,
            enc_len=enc_out.shape[1] if enc_out is not None else None,
        )
        x = L.apply_norm(params["final_ln"], x[:, -1:], cfg.norm_eps)
        logits = L.logits_from(params["embed"], x)[:, 0]
        return constraint(logits, "batch", "vocab")

    def decode_step(self, params, batch, cache) -> Tuple[jax.Array, Any]:
        """One token for every sequence; cache carries KV / recurrent state.

        Attention caches are split (main, recent): appends land in the small
        batch-sharded recent ring (see attention.apply_attention), so the
        big kv_seq-sharded main store is never re-sharded per step."""
        cfg = self.cfg
        x, pos = self._embed_inputs(params, batch)
        split = "len_rec" in cache
        cache_len = (cache["len"], cache["len_rec"]) if split else cache["len"]
        x, new_layer_caches, _ = self._run_decoder_stack(
            params, x, pos, cache["layers"], cache_len,
            enc_len=cache.get("enc_len"),
        )
        x = L.apply_norm(params["final_ln"], x[:, -1:], cfg.norm_eps)
        logits = L.logits_from(params["embed"], x)[:, 0]
        new_cache = dict(cache)
        new_cache["layers"] = self._merge_layer_caches(
            cache["layers"], new_layer_caches
        )
        S = batch["tokens"].shape[1]
        if split:
            new_cache["len_rec"] = cache["len_rec"] + S
        else:
            new_cache["len"] = cache["len"] + S
        return constraint(logits, "batch", "vocab"), new_cache

    @staticmethod
    def _merge_layer_caches(old, new):
        """Scan ys carry only what changed (recent rings, recurrent
        states); graft them back onto the read-only parts (main KV stores,
        cross KV)."""
        if new is None:
            return old
        if isinstance(old, dict) and "main" in old:
            return {"main": old["main"], "recent": new["recent"]}
        if isinstance(old, dict):  # encdec {"self","cross"} / hybrid {"mamba","attn"}
            out = {}
            for k, v in old.items():
                if isinstance(new, dict) and k in new:
                    out[k] = LM._merge_layer_caches(v, new[k])
                else:
                    out[k] = v
            return out
        return new

    def flush_cache(self, cache):
        """Amortized recent->main flush: one dynamic-update-slice of the
        whole recent ring per attention cache (call every ~R decode steps;
        this is the only op that touches the kv_seq-sharded dim)."""
        if "len_rec" not in cache:
            return cache
        len_main, len_rec = cache["len"], cache["len_rec"]

        def flush(node):
            if isinstance(node, dict) and "main" in node:
                mk, mv = node["main"]
                rk, rv = node["recent"]
                ndim = mk.ndim
                idx = (0, 0, len_main) + (0,) * (ndim - 3)
                mk = jax.lax.dynamic_update_slice(mk, rk.astype(mk.dtype), idx)
                mv = jax.lax.dynamic_update_slice(mv, rv.astype(mv.dtype), idx)
                return {
                    "main": (mk, mv),
                    "recent": (jnp.zeros_like(rk), jnp.zeros_like(rv)),
                }
            return node

        new_cache = dict(cache)
        layers = cache["layers"]
        if isinstance(layers, dict) and "main" in layers:
            layers = flush(layers)
        elif isinstance(layers, dict):
            layers = {k: flush(v) for k, v in layers.items()}
        new_cache["layers"] = layers
        new_cache["len"] = len_main + len_rec
        new_cache["len_rec"] = jnp.zeros((), jnp.int32)
        return new_cache

    # -- caches ---------------------------------------------------------------------
    def init_cache(
        self, batch_size: int, max_len: int, enc_len: int = 0,
        recent_size: int = 256,
    ) -> Dict:
        cfg = self.cfg
        KV, hd, Lr = cfg.n_kv_heads, cfg.hd, cfg.n_layers
        kv_shape = (Lr, batch_size, max_len, KV, hd)
        kv_dt = jnp.dtype(cfg.kv_cache_dtype)
        R = recent_size

        def split_kv(n_stack, length):
            return {
                "main": (
                    jnp.zeros((n_stack, batch_size, length, KV, hd), kv_dt),
                    jnp.zeros((n_stack, batch_size, length, KV, hd), kv_dt),
                ),
                "recent": (
                    jnp.zeros((n_stack, batch_size, R, KV, hd), kv_dt),
                    jnp.zeros((n_stack, batch_size, R, KV, hd), kv_dt),
                ),
            }
        if cfg.is_encdec:
            cache = {
                "layers": {
                    "self": split_kv(Lr, max_len),
                    "cross": (
                        jnp.zeros((Lr, batch_size, enc_len, KV, hd), kv_dt),
                        jnp.zeros((Lr, batch_size, enc_len, KV, hd), kv_dt),
                    ),
                },
                "len": jnp.zeros((), jnp.int32),
                "len_rec": jnp.zeros((), jnp.int32),
                "enc_len": jnp.asarray(enc_len, jnp.int32),
            }
            return cache
        if cfg.family == "ssm":
            st = R6.init_rwkv_state(cfg, batch_size, self.dtype)
            stacked = R6.RwkvState(
                shift_tm=jnp.zeros((Lr,) + st.shift_tm.shape, st.shift_tm.dtype),
                shift_cm=jnp.zeros((Lr,) + st.shift_cm.shape, st.shift_cm.dtype),
                wkv=jnp.zeros((Lr,) + st.wkv.shape, st.wkv.dtype),
            )
            return {"layers": stacked, "len": jnp.zeros((), jnp.int32)}
        if cfg.family == "hybrid":
            st = M2.init_mamba_state(cfg, batch_size)
            return {
                "layers": {
                    "mamba": jax.tree.map(lambda a: jnp.zeros((Lr,) + a.shape, a.dtype), st),
                    "attn": split_kv(cfg.kv_layers, max_len),
                },
                "len": jnp.zeros((), jnp.int32),
                "len_rec": jnp.zeros((), jnp.int32),
            }
        return {
            "layers": split_kv(Lr, max_len),
            "len": jnp.zeros((), jnp.int32),
            "len_rec": jnp.zeros((), jnp.int32),
        }

    def cache_spec_axes(self):
        """Logical axis names per cache leaf (for sharding the dry-run)."""
        cfg = self.cfg

        def kv_axes(leaf_ndim):
            # kv_seq carries the "model" axis; heads stay unsharded in the
            # cache (sharding both would double-book "model").
            return (None, "batch", "kv_seq", None, None)

        def split_axes():
            return {
                "main": (kv_axes(5), kv_axes(5)),
                # recent ring is batch-sharded only: its appends must never
                # touch a sharded dim
                "recent": (
                    (None, "batch", None, None, None),
                    (None, "batch", None, None, None),
                ),
            }

        if cfg.is_encdec:
            return {
                "layers": {
                    "self": split_axes(),
                    "cross": (kv_axes(5), kv_axes(5)),
                },
                "len": (),
                "len_rec": (),
                "enc_len": (),
            }
        if cfg.family == "ssm":
            return {
                "layers": R6.RwkvState(
                    shift_tm=(None, "batch", None),
                    shift_cm=(None, "batch", None),
                    wkv=(None, "batch", "heads", None, None),
                ),
                "len": (),
            }
        if cfg.family == "hybrid":
            return {
                "layers": {
                    "mamba": M2.MambaState(
                        conv=(None, "batch", None, "mlp"),
                        ssd=(None, "batch", "heads", None, None),
                    ),
                    "attn": split_axes(),
                },
                "len": (),
                "len_rec": (),
            }
        return {"layers": split_axes(), "len": (), "len_rec": ()}
