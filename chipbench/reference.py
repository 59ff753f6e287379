"""Plain reference, shared by every family, and the check that decides
``correct``.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernel, no cache, no batching.  Here are the pieces every family's
reference uses (matmul, fp8 fake-quantization, norms, rotary) and the
comparison; each family's layers and layer loop are its
``forward_logits`` in ``families/<family>.py``.  It reads the
configuration file's sizes (``spec_of``) and the benchmark's own weights
(``weights.py``), and imports nothing of the program.  One prompt with its
served tokens runs through the layers one at a time, padded at the end to
one bucket length (causal attention leaves earlier positions untouched),
so one compile serves every request of a cell.

The number compared is the widest gap, over every served token, by which
the served token's reference logit lies below the reference's best logit
at that position.  Greedy serving at the configuration's precision puts it
near rounding; the control (``fp8=True``: every matmul operand fake-
quantized to float8 e4m3 with a per-tensor scale, the precision below
bfloat16) is read the same way, for the token it puts first.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

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


@jax.jit
def _gaps(ref, served):
    """Per position: best reference logit minus the reference logit of
    ``served`` (>= 0; 0 where the served token is the reference's best)."""
    return ref.max(-1) - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]


def served_gaps(forward_logits: Callable, w: Dict, spec: Dict, prompt: List[int],
                served: List[int], span: int, bucket: int,
                control: bool = False) -> Tuple[np.ndarray, np.ndarray | None]:
    """Gaps of every served token under the reference logits of the
    family's ``forward_logits``; with ``control``, also the gaps of the
    tokens the fp8 control puts first at the same positions.  Served ids
    outside the published vocabulary get +inf."""
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
