"""The dense decoder family: pre-norm attention (MHA or GQA, rotary) and a
SwiGLU MLP in every layer, an untied head.

A configuration file names its family with ``"family"``; ``run.py`` finds
this module as ``<benchmark dir>/families/<family>.py``.  A family module
provides:

    program_config(cfg_file, spec)        the program's ModelConfig, checked
                                          against the reference's sizes
    pool_config(pcfg, mix)                the program's pool for the mix
    weight_shapes(spec, padded_vocab)     the benchmark's weight layout
    FAN_IN                                fan-in axes of each matrix leaf
    program_params(w, model)              the same arrays in the program's tree
    forward_logits(w, spec, ids, start, span, bucket, fp8=False)
                                          plain reference logits
    decode_token_flops(spec, context)     model FLOPs of one generated token
    prefill_flops(spec, prompt)           model FLOPs of one causal prefill
    paged_attention_model(spec, past_lens)
                                          FLOPs and bytes of one decode step's
                                          paged attention over every layer
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic
from chipbench.reference import _mm, _norm, _rope


def program_config(cfg_file: dict, spec: dict):
    """The program's ModelConfig for this configuration file, checked
    against the reference's sizes ``spec``."""
    from repro.configs.registry import get_config

    pcfg = dataclasses.replace(get_config(cfg_file["arch"]), **cfg_file["overrides"])
    have = {"layers": pcfg.n_layers, "d_model": pcfg.d_model, "heads": pcfg.n_heads,
            "kv_heads": pcfg.n_kv_heads, "head_dim": pcfg.hd, "d_ff": pcfg.d_ff,
            "vocab": pcfg.vocab_size, "norm": pcfg.norm, "compute_dtype": pcfg.dtype,
            "kv_dtype": pcfg.kv_cache_dtype, "rope_theta": pcfg.rope_theta,
            "rotary_dims": pcfg.hd // 2 if pcfg.rope == "rope2d" else pcfg.hd}
    bad = {k: (v, spec.get(k)) for k, v in have.items() if spec.get(k) != v}
    if bad or pcfg.activation != "swiglu" or pcfg.family != "dense":
        raise SystemExit(f"chipbench: program config departs from the file: {bad}")
    return pcfg


def pool_config(pcfg, mix: Dict):
    """A KV page for every attention layer, the pool sized by the mix
    (``traffic.pool_sizing``)."""
    from repro.core.kv_pool import KVPoolConfig

    num_blocks, per_seq = traffic.pool_sizing(mix)
    return KVPoolConfig(
        num_blocks=num_blocks, block_size=mix["block_size"], kv_heads=pcfg.n_kv_heads,
        head_dim=pcfg.hd, n_layers=pcfg.n_layers, max_seqs=mix["sessions"],
        max_blocks_per_seq=per_seq, blocks_per_arena=mix["blocks_per_arena"],
        dtype=pcfg.kv_cache_dtype)


# ---------------------------------------------------------------------------
# weights

def weight_shapes(spec: Dict, padded_vocab: int) -> Dict:
    """The benchmark's weight layout: per-layer leaves stacked on a leading
    layer axis, the embedding and head over the padded vocabulary."""
    L, d, H, KV, hd, f = (spec[k] for k in
                          ("layers", "d_model", "heads", "kv_heads", "head_dim", "d_ff"))
    norm = {"scale": (d,), "bias": (d,)} if spec["norm"] == "layernorm" else {"scale": (d,)}
    stacked = lambda g: {k: (L,) + s for k, s in g.items()}  # noqa: E731
    return {
        "embed": (padded_vocab, d),
        "head": (d, padded_vocab),
        "final_norm": norm,
        "layers": {
            "norm1": stacked(norm), "norm2": stacked(norm),
            "wq": (L, d, H, hd), "wk": (L, d, KV, hd), "wv": (L, d, KV, hd),
            "wo": (L, H, hd, d),
            "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
        },
    }


#: fan-in of each matrix (std 1/sqrt(fan_in)): the axes its input contracts
FAN_IN = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2), "w_gate": (1,),
          "w_up": (1,), "w_down": (1,), "head": (0,)}


def program_params(w: Dict, model) -> Dict:
    """The program's parameter tree over the same device arrays, checked
    against the tree ``model.init`` would build (structure, shapes, dtypes)."""
    lw = w["layers"]
    tree = {
        "embed": {"tok": w["embed"], "head": w["head"]},
        "final_ln": dict(w["final_norm"]),
        "layers": {
            "ln1": dict(lw["norm1"]), "ln2": dict(lw["norm2"]),
            "attn": {"wq": lw["wq"], "wk": lw["wk"], "wv": lw["wv"], "wo": lw["wo"]},
            "mlp": {"wg": lw["w_gate"], "wu": lw["w_up"], "wo": lw["w_down"]},
        },
    }
    want = jax.eval_shape(model.init, jax.random.key(0))
    sig = lambda t: [(a.shape, a.dtype) for a in jax.tree.leaves(t)]  # noqa: E731
    if jax.tree.structure(want) != jax.tree.structure(tree) or sig(want) != sig(tree):
        raise ValueError(f"weights do not fit the program's parameter tree:\n{sig(want)}\n"
                         f"vs\n{sig(tree)}")
    return tree


# ---------------------------------------------------------------------------
# reference

@functools.partial(jax.jit, static_argnames=("spec_items", "fp8"))
def _layer(x, layers, li, *, spec_items, fp8):
    spec = dict(spec_items)
    lw = jax.tree.map(lambda a: a[li], layers)
    T = x.shape[0]
    H, KV, hd = spec["heads"], spec["kv_heads"], spec["head_dim"]
    h = _norm(x, lw["norm1"], spec)
    q = _rope(_mm("td,dhk->thk", h, lw["wq"], fp8), spec)
    k = _rope(_mm("td,dhk->thk", h, lw["wk"], fp8), spec)
    v = _mm("td,dhk->thk", h, lw["wv"], fp8)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = _mm("qhd,khd->hqk", q, k, fp8) * hd ** -0.5
    causal = jnp.arange(T)[None, :, None] >= jnp.arange(T)[None, None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", p, v, fp8)
    x = x + _mm("thk,hkd->td", o, lw["wo"], fp8)
    h = _norm(x, lw["norm2"], spec)
    g = _mm("td,df->tf", h, lw["w_gate"], fp8)
    u = _mm("td,df->tf", h, lw["w_up"], fp8)
    return x + _mm("tf,fd->td", jax.nn.silu(g) * u, lw["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("spec_items", "span", "fp8"))
def _logits(x, final_norm, head, start, *, spec_items, span, fp8):
    """Logits over the published vocabulary at positions [start, start+span)."""
    spec = dict(spec_items)
    xs = jax.lax.dynamic_slice_in_dim(x, start, span, axis=0)
    return _mm("td,dv->tv", _norm(xs, final_norm, spec), head[:, :spec["vocab"]], fp8)


def forward_logits(w: Dict, spec: Dict, ids: Sequence[int], start: int, span: int,
                   bucket: int, fp8: bool = False) -> jax.Array:
    """Reference logits (span, vocab) of ``ids`` at positions start.., with
    the sequence padded to ``bucket`` tokens."""
    items = tuple(sorted(spec.items()))
    toks = np.zeros((bucket,), np.int32)
    toks[:len(ids)] = ids
    x = w["embed"][jnp.asarray(toks)]
    for li in range(spec["layers"]):
        x = _layer(x, w["layers"], li, spec_items=items, fp8=fp8)
    return _logits(x, w["final_norm"], w["head"], start, spec_items=items, span=span, fp8=fp8)


# ---------------------------------------------------------------------------
# counts: what the algorithm needs, from shapes alone; pads, grid steps and
# re-reads a kernel makes do not count

def paged_attention(spec: Dict, past_lens: Iterable[int], kv_bytes: int = 2,
                    q_bytes: int = 2) -> Dict[str, float]:
    """One call of decode attention over a batch (one layer): each sequence's
    one query row against its ``past_len`` cached tokens.  FLOPs: QK^T and
    PV, 2 * H * hd per token each.  Bytes: the live K and V pages at the
    pool dtype, plus q in and out at the compute dtype."""
    H, KV, hd = spec["heads"], spec["kv_heads"], spec["head_dim"]
    lens = [int(n) for n in past_lens]
    tokens = sum(lens)
    flops = 4 * H * hd * tokens
    nbytes = 2 * KV * hd * kv_bytes * tokens + 2 * len(lens) * H * hd * q_bytes
    return {"flops": float(flops), "bytes": float(nbytes)}


def paged_attention_model(spec: Dict, past_lens: Iterable[int]) -> Dict[str, float]:
    """One decode step's paged attention over the model: every layer is an
    attention layer."""
    c = paged_attention(spec, past_lens)
    return {k: spec["layers"] * v for k, v in c.items()}


def matmul_params_per_layer(spec: Dict) -> int:
    d, H, KV, hd, f = (spec[k] for k in ("d_model", "heads", "kv_heads", "head_dim", "d_ff"))
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f


def decode_token_flops(spec: Dict, context: int) -> float:
    """Model FLOPs of one generated token that attends to ``context``
    tokens (itself included): every layer's matmuls, attention, and the
    output head."""
    L, H, hd = spec["layers"], spec["heads"], spec["head_dim"]
    return float(2 * L * matmul_params_per_layer(spec) + 4 * L * H * hd * context
                 + 2 * spec["d_model"] * spec["vocab"])


def prefill_flops(spec: Dict, prompt: int) -> float:
    """Model FLOPs of a causal prefill of ``prompt`` tokens: matmuls for
    every token, causal attention (token i attends to i tokens), and the
    head for the last position only (the next token)."""
    L, H, hd = spec["layers"], spec["heads"], spec["head_dim"]
    return float(2 * L * matmul_params_per_layer(spec) * prompt
                 + 4 * L * H * hd * prompt * (prompt + 1) // 2
                 + 2 * spec["d_model"] * spec["vocab"])
