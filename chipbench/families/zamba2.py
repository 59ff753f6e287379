"""The Zamba2 hybrid family: Mamba2 layers, and shared transformer blocks
used in turn before the layers ``hybrid_layer_ids`` names.

The published model (transformers' ``modeling_zamba2``), which the
reference below follows:

* a Mamba2 layer: ``x + mixer(rms(x + T))``, where T is the output of the
  shared-block use before it (0 elsewhere), so T never joins the residual
  stream.  The mixer's fused input projection gives z, xBC and dt; a
  depthwise causal conv with bias and a silu run over xBC, which splits
  into x (H heads of P), B and C (G groups of N); dt = softplus(dt +
  dt_bias), unclamped; every head's (P, N) state follows
  h_t = exp(dt_t·A) h_{t-1} + dt_t x_t B_t^T and y_t = h_t C_t + D x_t,
  A = -exp(A_log); then y·silu(z), an RMS norm over each group of d_in/G
  channels, and the output projection.
* use u of block u mod ``num_mem_blocks``: rms(concat(x, e)) over 2·d,
  e the embedding output; MHA with rotate-half rotary over all head_dim
  channels and scale (head_dim/2)^-0.5; no residual; the pre-MLP norm;
  gate_up plus the use's LoRA, gelu(gate)·up, down; then the use's linear.
* RMS norms with ``rms_norm_eps``, a final norm and the head tied to the
  embedding.

``forward_logits`` is that model in float32 at ``Precision.HIGHEST``,
with the state recurrence run token by token; it imports nothing of the
program.  The interface is the one ``families/dense.py`` documents.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench.families.dense import paged_attention
from chipbench.reference import _mm, _norm

# ---------------------------------------------------------------------------
# sizes


def _sizes(spec: Dict) -> Dict[str, int]:
    """Derived sizes: Mamba2's inner width, conv channels, the uses kept."""
    d_in = spec["ssm_expand"] * spec["d_model"]
    conv_dim = d_in + 2 * spec["ssm_groups"] * spec["ssm_state"]
    uses = [i for i in spec["hybrid_layer_ids"] if i < spec["layers"]]
    return {"d_in": d_in, "conv_dim": conv_dim, "proj": d_in + conv_dim + spec["ssm_heads"],
            "uses": len(uses)}


def program_config(cfg_file: dict, spec: dict):
    """The program's ModelConfig for this configuration file, checked
    against every published size in ``spec``; exits naming each mismatch."""
    from repro.configs.registry import get_config

    overrides = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in cfg_file["overrides"].items()}
    pcfg = dataclasses.replace(get_config(cfg_file["arch"]), **overrides)
    g = lambda name: getattr(pcfg, name, None)  # noqa: E731
    have = {
        "layers": pcfg.n_layers, "d_model": pcfg.d_model, "heads": pcfg.n_heads,
        "kv_heads": pcfg.n_kv_heads, "head_dim": pcfg.hd, "d_ff": pcfg.d_ff,
        "vocab": pcfg.vocab_size, "norm_eps": g("norm_eps"), "ssm_state": pcfg.ssm_state,
        "ssm_heads": pcfg.ssm_expand * pcfg.d_model // pcfg.ssm_head_dim,
        "ssm_head_dim": pcfg.ssm_head_dim, "ssm_expand": pcfg.ssm_expand,
        "ssm_groups": g("ssm_ngroups"), "conv": pcfg.ssm_conv, "conv_bias": g("ssm_conv_bias"),
        "hybrid_layer_ids": list(g("hybrid_uses") or ()), "mem_blocks": g("num_mem_blocks"),
        "adapter_rank": g("adapter_rank"), "rope_theta": pcfg.rope_theta,
        "activation": pcfg.activation, "compute_dtype": pcfg.dtype,
        "kv_dtype": pcfg.kv_cache_dtype, "tie_head": pcfg.tie_embeddings,
        "rotary": {"rope_half": "rotate_half"}.get(pcfg.rope, pcfg.rope),
    }
    want = dict(spec, hybrid_layer_ids=[i for i in spec["hybrid_layer_ids"]
                                        if i < spec["layers"]])
    bad = {k: (v, want.get(k)) for k, v in have.items() if want.get(k) != v}
    if bad or pcfg.family != "hybrid":
        raise SystemExit(f"chipbench: program config departs from the file "
                         f"(program, file): {bad}, family {pcfg.family!r}")
    return pcfg


def pool_config(pcfg, mix: Dict):
    """KV pages for the shared-block uses (head dimension padded to whole
    lane tiles, ``lane_dim``) and a state slot per session for every Mamba2
    layer, the pages sized by the mix (``traffic.pool_sizing``)."""
    from repro.core.kv_pool import KVPoolConfig, lane_dim
    from repro.models.mamba2 import slot_widths

    num_blocks, per_seq = traffic.pool_sizing(mix)
    return KVPoolConfig(
        num_blocks=num_blocks, block_size=mix["block_size"], kv_heads=pcfg.n_kv_heads,
        head_dim=lane_dim(pcfg.hd), n_layers=pcfg.kv_layers, max_seqs=mix["sessions"],
        max_blocks_per_seq=per_seq, blocks_per_arena=mix["blocks_per_arena"],
        dtype=pcfg.kv_cache_dtype, state_layers=pcfg.n_layers, **slot_widths(pcfg))


# ---------------------------------------------------------------------------
# weights

def weight_shapes(spec: Dict, padded_vocab: int) -> Dict:
    """The benchmark's weight layout: Mamba2 leaves stacked on a layer axis,
    shared-block leaves on a block axis, per-use leaves on a use axis; the
    embedding (also the head) over the padded vocabulary.  ``in_proj``'s
    columns are [z | xBC | dt]; the conv ``kernel[K-1]`` multiplies the
    current token."""
    L, d, H, KV, hd, f = (spec[k] for k in
                          ("layers", "d_model", "heads", "kv_heads", "head_dim", "d_ff"))
    s = _sizes(spec)
    NB, U, r, Hm = spec["mem_blocks"], s["uses"], spec["adapter_rank"], spec["ssm_heads"]
    return {
        "embed": (padded_vocab, d),
        "final_norm": {"scale": (d,)},
        "mamba": {
            "norm": {"scale": (L, d)},
            "in_proj": (L, d, s["proj"]),
            "conv": {"kernel": (L, spec["conv"], s["conv_dim"]), "bias": (L, s["conv_dim"])},
            "dt_bias": (L, Hm), "A_log": (L, Hm), "D": (L, Hm),
            "gate_norm": {"scale": (L, s["d_in"])},
            "out_proj": (L, s["d_in"], d),
        },
        "shared": {
            "norm1": {"scale": (NB, 2 * d)},
            "wq": (NB, 2 * d, H, hd), "wk": (NB, 2 * d, KV, hd), "wv": (NB, 2 * d, KV, hd),
            "wo": (NB, H, hd, d),
            "norm2": {"scale": (NB, d)},
            "w_gate_up": (NB, d, 2 * f), "w_down": (NB, f, d),
        },
        "uses": {"lora_a": (U, d, r), "lora_b": (U, r, 2 * f), "linear": (U, d, d)},
    }


#: fan-in of each matrix (std 1/sqrt(fan_in)): the axes its input contracts.
#: The per-head scalars (dt_bias, A_log, D) are drawn at std 1, and so are
#: the two matrices that write from a normed input into the residual stream
#: or a Mamba input: ``out_proj`` and a use's ``linear`` add about
#: sqrt(fan-in) per channel.  So the residual outgrows the N(0, 1)
#: embedding, as a trained model's does (with the head tied to the
#: embedding, a residual of the embedding's size would make every step
#: repeat its input token), and a use's T is of the residual's size.
FAN_IN = {"in_proj": (1,), "kernel": (1,), "out_proj": (), "dt_bias": (), "A_log": (),
          "D": (), "wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2), "w_gate_up": (1,),
          "w_down": (1,), "lora_a": (1,), "lora_b": (1,), "linear": ()}


def program_params(w: Dict, model) -> Dict:
    """The program's parameter tree over the same device arrays, checked
    against the tree ``model.init`` would build (structure, shapes, dtypes)."""
    m, s = w["mamba"], w["shared"]
    tree = {
        "embed": {"tok": w["embed"]},
        "final_ln": dict(w["final_norm"]),
        "layers": {
            "ln": dict(m["norm"]),
            "mamba": {"in_proj": m["in_proj"], "conv_w": m["conv"]["kernel"],
                      "conv_b": m["conv"]["bias"], "dt_bias": m["dt_bias"], "A_log": m["A_log"],
                      "D": m["D"], "norm": m["gate_norm"]["scale"], "out_proj": m["out_proj"]},
        },
        "shared": {"ln1": dict(s["norm1"]), "wq": s["wq"], "wk": s["wk"], "wv": s["wv"],
                   "wo": s["wo"], "ln2": dict(s["norm2"]), "w_gate_up": s["w_gate_up"],
                   "w_down": s["w_down"]},
        "uses": dict(w["uses"]),
    }
    want = jax.eval_shape(model.init, jax.random.key(0))
    sig = lambda t: [(a.shape, a.dtype) for a in jax.tree.leaves(t)]  # noqa: E731
    if jax.tree.structure(want) != jax.tree.structure(tree) or sig(want) != sig(tree):
        raise ValueError(f"weights do not fit the program's parameter tree:\n{sig(want)}\n"
                         f"vs\n{sig(tree)}")
    return tree


# ---------------------------------------------------------------------------
# reference

def _rope_half(x, theta):
    """Rotate-half rotary over all channels of x (T, H, hd): channel i
    pairs with i + hd/2 at angle position * theta^(-2i/hd)."""
    T, hd = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _spec_items(spec: Dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in spec.items()))


@functools.partial(jax.jit, static_argnames=("spec_items", "fp8"))
def _mamba_layer(x, T, mamba, li, *, spec_items, fp8):
    """One Mamba2 layer over x (T_len, d); ``T`` joins the mixer's input."""
    spec = dict(spec_items)
    s = _sizes(spec)
    lw = jax.tree.map(lambda a: a[li], mamba)
    Hm, P, N, G, K = (spec[k] for k in ("ssm_heads", "ssm_head_dim", "ssm_state",
                                         "ssm_groups", "conv"))
    n = x.shape[0]
    h = _norm(x + T, lw["norm"], spec)
    zxbcdt = _mm("td,de->te", h, lw["in_proj"], fp8)
    z, xbc, dt = jnp.split(zxbcdt, [s["d_in"], s["d_in"] + s["conv_dim"]], axis=-1)
    xpad = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xpad[k:k + n] * lw["conv"]["kernel"][k] for k in range(K))
                      + lw["conv"]["bias"])
    xs, Bm, Cm = jnp.split(xbc, [s["d_in"], s["d_in"] + G * N], axis=-1)
    xs = xs.reshape(n, Hm, P)
    Bh = jnp.repeat(Bm.reshape(n, G, N), Hm // G, axis=1)       # (n, Hm, N)
    Ch = jnp.repeat(Cm.reshape(n, G, N), Hm // G, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                      # (n, Hm)
    A = -jnp.exp(lw["A_log"])

    def token(state, inp):                                        # state (Hm, P, N)
        x_t, dt_t, B_t, C_t = inp
        state = (state * jnp.exp(dt_t * A)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return state, jnp.sum(state * C_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((Hm, P, N), jnp.float32), (xs, dt, Bh, Ch), unroll=8)
    y = (y + lw["D"][:, None] * xs).reshape(n, G, s["d_in"] // G)
    y = y * jax.nn.silu(z).reshape(y.shape)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + spec["norm_eps"])
    y = y.reshape(n, s["d_in"]) * lw["gate_norm"]["scale"]
    return x + _mm("te,ed->td", y, lw["out_proj"], fp8)


@functools.partial(jax.jit, static_argnames=("spec_items", "fp8"))
def _shared_use(x, e, shared, uses, b, u, *, spec_items, fp8):
    """Use ``u`` of shared block ``b`` over x (T_len, d): its T."""
    spec = dict(spec_items)
    sw = jax.tree.map(lambda a: a[b], shared)
    uw = jax.tree.map(lambda a: a[u], uses)
    n, hd = x.shape[0], spec["head_dim"]
    h = _norm(jnp.concatenate([x, e], axis=-1), sw["norm1"], spec)
    q = _rope_half(_mm("td,dhk->thk", h, sw["wq"], fp8), spec["rope_theta"])
    k = _rope_half(_mm("td,dhk->thk", h, sw["wk"], fp8), spec["rope_theta"])
    v = _mm("td,dhk->thk", h, sw["wv"], fp8)
    k = jnp.repeat(k, spec["heads"] // spec["kv_heads"], axis=1)
    v = jnp.repeat(v, spec["heads"] // spec["kv_heads"], axis=1)
    sc = _mm("qhd,khd->hqk", q, k, fp8) * (hd / 2) ** -0.5
    causal = jnp.arange(n)[None, :, None] >= jnp.arange(n)[None, None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    a = _mm("thk,hkd->td", _mm("hqk,khd->qhd", p, v, fp8), sw["wo"], fp8)
    h = _norm(a, sw["norm2"], spec)
    gu = (_mm("td,df->tf", h, sw["w_gate_up"], fp8)
          + _mm("tr,rf->tf", _mm("td,dr->tr", h, uw["lora_a"], fp8), uw["lora_b"], fp8))
    g, up = jnp.split(gu, 2, axis=-1)
    m = _mm("tf,fd->td", jax.nn.gelu(g, approximate=False) * up, sw["w_down"], fp8)
    return _mm("td,de->te", m, uw["linear"], fp8)


@functools.partial(jax.jit, static_argnames=("spec_items", "span", "fp8"))
def _logits(x, final_norm, embed, start, *, spec_items, span, fp8):
    """Logits over the published vocabulary at positions [start, start+span),
    the head tied to the embedding."""
    spec = dict(spec_items)
    xs = jax.lax.dynamic_slice_in_dim(x, start, span, axis=0)
    xs = _norm(xs, final_norm, spec)
    return _mm("td,vd->tv", xs, embed[:spec["vocab"]], fp8)


def forward_logits(w: Dict, spec: Dict, ids: Sequence[int], start: int, span: int,
                   bucket: int, fp8: bool = False) -> jax.Array:
    """Reference logits (span, vocab) of ``ids`` at positions start.., with
    the sequence padded to ``bucket`` tokens (every layer is causal)."""
    items = _spec_items(spec)
    toks = np.zeros((bucket,), np.int32)
    toks[:len(ids)] = ids
    e = w["embed"][jnp.asarray(toks)]
    x, zero = e, jnp.zeros_like(e)
    uses = [i for i in spec["hybrid_layer_ids"] if i < spec["layers"]]
    for li in range(spec["layers"]):
        T = zero
        if li in uses:
            u = uses.index(li)
            T = _shared_use(x, e, w["shared"], w["uses"], u % spec["mem_blocks"], u,
                            spec_items=items, fp8=fp8)
        x = _mamba_layer(x, T, w["mamba"], li, spec_items=items, fp8=fp8)
    return _logits(x, w["final_norm"], w["embed"], start, spec_items=items, span=span,
                   fp8=fp8)


# ---------------------------------------------------------------------------
# counts: what the algorithm needs, from shapes alone; pads, grid steps and
# re-reads a kernel makes do not count

def paged_attention_model(spec: Dict, past_lens: Iterable[int]) -> Dict[str, float]:
    """One decode step's paged attention over the model: one call (the
    dense family's count, ``dense.paged_attention``) per shared-block use."""
    c = paged_attention(spec, past_lens)
    return {k: _sizes(spec)["uses"] * v for k, v in c.items()}


def kv_bytes_per_token(spec: Dict, kv_bytes: int = 2) -> int:
    """Pool bytes one token keeps: K and V of every use."""
    return _sizes(spec)["uses"] * 2 * spec["kv_heads"] * spec["head_dim"] * kv_bytes


def mamba_params(spec: Dict) -> int:
    """Parameters of one Mamba2 layer (norms, conv and per-head scalars
    included)."""
    s, d, Hm = _sizes(spec), spec["d_model"], spec["ssm_heads"]
    return (d + d * s["proj"] + spec["conv"] * s["conv_dim"] + s["conv_dim"] * spec["conv_bias"]
            + 3 * Hm + s["d_in"] + s["d_in"] * d)


def shared_block_params(spec: Dict) -> int:
    """Parameters of one shared block (its two norms included)."""
    d, H, KV, hd, f = (spec[k] for k in ("d_model", "heads", "kv_heads", "head_dim", "d_ff"))
    return 2 * d + 2 * d * (H + 2 * KV) * hd + H * hd * d + d + 2 * d * f + f * d


def use_params(spec: Dict) -> int:
    """Parameters one use has of its own: the LoRA on gate_up and the linear."""
    d, f, r = spec["d_model"], spec["d_ff"], spec["adapter_rank"]
    return r * (d + 2 * f) + d * d


def matmul_params_per_token(spec: Dict) -> int:
    """Matrix weights one token multiplies: every Mamba2 layer's two
    projections and, per use, its block's and its own matrices."""
    s = _sizes(spec)
    d, H, KV, hd, f = (spec[k] for k in ("d_model", "heads", "kv_heads", "head_dim", "d_ff"))
    mamba = d * s["proj"] + s["d_in"] * d
    block = 2 * d * (H + 2 * KV) * hd + H * hd * d + 3 * d * f
    return spec["layers"] * mamba + s["uses"] * (block + use_params(spec))


def ssd_flops_per_token(spec: Dict) -> int:
    """The state recurrence of one token over every Mamba2 layer, counted
    as 5·H·P·N per layer: decay and input outer product (3) and the read
    against C (2).  The conv, norms and gates are not counted."""
    return 5 * spec["layers"] * spec["ssm_heads"] * spec["ssm_head_dim"] * spec["ssm_state"]


def decode_token_flops(spec: Dict, context: int) -> float:
    """Model FLOPs of one generated token that attends to ``context`` tokens
    (itself included): matmuls, every use's attention, the SSD recurrence
    and the tied head."""
    att = 4 * _sizes(spec)["uses"] * spec["heads"] * spec["head_dim"] * context
    return float(2 * matmul_params_per_token(spec) + att + ssd_flops_per_token(spec)
                 + 2 * spec["d_model"] * spec["vocab"])


def prefill_flops(spec: Dict, prompt: int) -> float:
    """Model FLOPs of a causal prefill of ``prompt`` tokens: matmuls and the
    SSD recurrence for every token, causal attention in every use (token i
    attends to i tokens), and the head for the last position only."""
    att = 4 * _sizes(spec)["uses"] * spec["heads"] * spec["head_dim"] * prompt * (prompt + 1) // 2
    return float((2 * matmul_params_per_token(spec) + ssd_flops_per_token(spec)) * prompt
                 + att + 2 * spec["d_model"] * spec["vocab"])
