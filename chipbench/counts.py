"""Operations and bytes the algorithm needs, from shapes alone.

``spec`` is a configuration's sizes as the reference reads them
(``reference.spec_of``): layers, d_model, heads, kv_heads, head_dim, d_ff,
vocab.  Nothing here reads the program; pads, grid steps and re-reads a
kernel makes do not count.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {PEAKS_FILE}")
    return table[device_kind]


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


def roofline_seconds(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """Least time on the chip: the larger of compute and memory time."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
