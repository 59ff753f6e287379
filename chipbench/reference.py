"""Plain reference forward pass, and the check that decides ``correct``.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernel, no cache, no batching.  It reads the configuration file's sizes
(``spec_of``) and the benchmark's own weights (``weights.py``), and
imports nothing of the program.  One prompt with its served tokens runs
through the layers one at a time, padded at the end to one bucket length
(causal attention leaves earlier positions untouched), so one compile
serves every request of a cell.

The number compared is the widest gap, over every served token, by which
the served token's reference logit lies below the reference's best logit
at that position.  Greedy serving at the configuration's precision puts it
near rounding; the control (``fp8=True``: every matmul operand fake-
quantized to float8 e4m3 with a per-tensor scale, the precision below
bfloat16) is read the same way, for the token it puts first.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                      # largest finite float8_e4m3fn


def spec_of(cfg_file: Dict) -> Dict:
    """The sizes the reference reads from a configuration file: each
    ``hf_keys`` entry from the published key it names, the rest from the
    file's ``reference`` group."""
    return {**cfg_file["reference"], **{k: cfg_file[hf] for k, hf in cfg_file["hf_keys"].items()}}


def _fp8(a):
    s = jnp.max(jnp.abs(a)) / F8_MAX
    s = jnp.where(s == 0.0, 1.0, s)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _norm(x, p, spec):
    if spec["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + spec["norm_eps"]) * p["scale"] + p["bias"]
    ms = (x * x).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + spec["norm_eps"]) * p["scale"]


def _rope(x, spec):
    """Rotate interleaved channel pairs (2i, 2i+1) of the first
    ``rotary_dims`` channels by position * theta^(-2i/rotary_dims)."""
    T = x.shape[0]
    rd = spec["rotary_dims"]
    inv = spec["rope_theta"] ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rd:2], x[..., 1:rd:2]
    rot = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(x.shape[:-1] + (rd,))
    return jnp.concatenate([rot, x[..., rd:]], axis=-1)


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


@jax.jit
def _gaps(ref, served):
    """Per position: best reference logit minus the reference logit of
    ``served`` (>= 0; 0 where the served token is the reference's best)."""
    return ref.max(-1) - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]


def served_gaps(w: Dict, spec: Dict, prompt: List[int], served: List[int], span: int,
                bucket: int, control: bool = False) -> Tuple[np.ndarray, np.ndarray | None]:
    """Gaps of every served token under the reference; with ``control``,
    also the gaps of the tokens the fp8 control puts first at the same
    positions.  Served ids outside the published vocabulary get +inf."""
    n = len(served)
    ids = list(prompt) + list(served[:-1])
    start = len(prompt) - 1
    ref = forward_logits(w, spec, ids, start, span, bucket)
    toks = np.zeros((span,), np.int32)
    toks[:n] = np.clip(served, 0, spec["vocab"] - 1)
    gaps = np.asarray(_gaps(ref, jnp.asarray(toks)))[:n]
    gaps = np.where(np.asarray(served) >= spec["vocab"], np.inf, gaps)
    ctrl = None
    if control:
        low = forward_logits(w, spec, ids, start, span, bucket, fp8=True)
        ctrl = np.asarray(_gaps(ref, jnp.argmax(low, axis=-1)))[:n]
    return gaps, ctrl
