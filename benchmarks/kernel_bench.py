"""Kernel micro-timings (CPU): jnp reference path wall time + kernel-vs-ref
agreement.  Interpret-mode Pallas timings are NOT hardware numbers — the TPU
kernel times come from the chip benchmark (``chipbench/``); this table tracks the
reference-path cost and correctness drift per shape."""
from __future__ import annotations

import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.pud_bulk import ops as pud_ops
from repro.kernels.flash_attention import ops as fl_ops


def _time(fn, *args, iters=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) * 1e6 / iters


def run(emit: Callable[[str, float, float], None]) -> Dict:
    rng = np.random.default_rng(0)
    out = {}

    for rows in [1024, 8192, 65536]:
        x = jnp.asarray(rng.integers(0, 1 << 30, (rows, 128)).astype(np.int32))
        y = jnp.asarray(rng.integers(0, 1 << 30, (rows, 128)).astype(np.int32))
        us = _time(lambda a, b: pud_ops.pud_and(a, b, use_kernel=False), x, y)
        k = pud_ops.pud_and(x, y, use_kernel=True)
        r = pud_ops.pud_and(x, y, use_kernel=False)
        match = float((np.asarray(k) == np.asarray(r)).all())
        emit(f"pud_and/ref_jnp/{rows}x128", us, match)
        out[f"pud_and_{rows}"] = us

    for (B, H, S, D) in [(1, 4, 256, 64), (2, 8, 512, 64)]:
        q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
        kv = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
        us = _time(
            lambda a, b, c: fl_ops.flash_attention(a, b, c, use_kernel=False),
            q, kv, kv,
        )
        ok = fl_ops.flash_attention(q, kv, kv, use_kernel=True)
        rf = fl_ops.flash_attention(q, kv, kv, use_kernel=False)
        err = float(jnp.max(jnp.abs(ok - rf)))
        emit(f"flash/ref_jnp/B{B}H{H}S{S}D{D}", us, err)
        out[f"flash_{B}_{H}_{S}_{D}"] = err
    return out
