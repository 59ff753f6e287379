"""Bring-up check on one TPU: serve stablelm-1.6b at its published widths.

    python chip_smoke.py [--seed N]

Drives the serving main path once through the entry points a user calls:
``ServeEngine`` over a bf16 PUMA paged KV pool, with seeded random
parameters, on the Pallas kernels.  Eight requests of one prompt length and
one ``max_new`` share one prefill shape and one decode batch shape.  Then:

* every request must finish with ``max_new`` tokens, none rejected or
  cancelled;
* the ``paged_attention`` kernel must match the jnp reference on the live
  pool and block tables, every layer, within the bf16 tolerance of the
  kernel tests;
* ``PagedKVPool.fork`` on the ``block_copy`` kernel must copy a live
  sequence's pages byte for byte.

Details (device, compile seconds, peak HBM, a decode tokens/s bring-up
figure) go to earlier lines; the last line is one JSON object naming the
device.  Without a TPU it exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.kernels.paged_attention import ops as paged_ops  # noqa: E402
from repro.launch.serve import pool_config, use_compile_cache  # noqa: E402
from repro.models.transformer import LM  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402

ARCH = "stablelm_1_6b"
N_REQUESTS = 8
PROMPT_LEN = 128
MAX_NEW = 16
ATTN_TOL = 2e-2          # bf16 tolerance of tests/test_kernels.py

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def require_tpu() -> jax.Device:
    """The first device, which must be a TPU; exits non-zero otherwise."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    return dev


def check_attention(eng: ServeEngine, seed: int) -> tuple[float, float]:
    """Paged attention on the kernel against the reference over every layer
    of the engine's live pool, at the lengths the decode step reads (past
    tokens).

    The reference runs at full f32 matmul precision, whatever the
    backend's default.  Returns two maxima over all layers: |kernel -
    reference| for f32 queries, whose outputs are not rounded, and for the
    serving dtype's bf16 queries the excess of |kernel - reference| over
    the bf16 rounding of the kernel's output, ``|reference| * 2**-8``
    (attention outputs here reach ~30, where one bf16 step is 0.125)."""
    cfg = eng.cfg
    slots = sorted(eng.live)
    tbl = eng.pool.block_table()[slots]
    past = eng.pool.seq_lens()[slots] - 1
    q = jax.random.normal(
        jax.random.key(seed), (len(slots), cfg.n_heads, cfg.hd), jnp.bfloat16
    ).astype(jnp.float32)
    err_f32 = excess_bf16 = 0.0
    for li in range(cfg.n_layers):
        k, v = eng.pool.k[li], eng.pool.v[li]
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(paged_ops.paged_attention(q, k, v, tbl, past, use_kernel=False))
        out = np.asarray(paged_ops.paged_attention(q, k, v, tbl, past, use_kernel=True))
        err_f32 = max(err_f32, float(np.max(np.abs(out - ref))))
        out = paged_ops.paged_attention(
            q.astype(jnp.bfloat16), k, v, tbl, past, use_kernel=True
        )
        diff = np.abs(np.asarray(out, np.float32) - ref) - np.abs(ref) * 2.0**-8
        excess_bf16 = max(excess_bf16, float(np.max(diff)))
    return err_f32, excess_bf16


def check_fork(eng: ServeEngine) -> int:
    """Fork one live sequence on the ``block_copy`` kernel and require its
    pages, every layer, to equal the parent's byte for byte.  The child is
    released again; returns the number of pages compared per layer."""
    pool = eng.pool
    parent = min(eng.live)
    child = pool.fork(parent, use_kernel=True)
    if child is None:
        raise SystemExit("chip_smoke: fork found no free slot or pages")
    src, dst = pool.tiles_of(parent), pool.tiles_of(child)
    for name, buf in (("K", pool.k), ("V", pool.v)):
        a = np.asarray(buf[:, np.asarray(src)]).view(np.uint16)
        b = np.asarray(buf[:, np.asarray(dst)]).view(np.uint16)
        if not a.any():
            raise SystemExit(f"chip_smoke: parent {name} pages are empty")
        if not np.array_equal(a, b):
            raise SystemExit(f"chip_smoke: forked {name} pages differ from the parent's")
    pool.release(child)
    return len(src)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = require_tpu()
    cache_dir = use_compile_cache()
    print(f"device_kind={dev.device_kind} count={len(jax.devices())} "
          f"compile_cache={cache_dir}")

    compile_s: dict = collections.defaultdict(float)
    cache_hits = []

    def on_duration(event, duration, fun_name="", **_):
        if event in _COMPILE_EVENTS:        # named "f", "jit(f)" by the phases
            compile_s[fun_name.removeprefix("jit(").removesuffix(")")] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    cfg = get_config(ARCH)
    model = LM(cfg, attn_impl="naive", remat=None)
    t = time.perf_counter()
    params = jax.block_until_ready(model.init(jax.random.key(args.seed)))
    print(f"model={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab_size} "
          f"params={cfg.n_params()} init_s={time.perf_counter() - t!r}")

    # one slot beyond the batch, so a live sequence can be forked mid-run
    pool_cfg = pool_config(cfg, max_seqs=N_REQUESTS + 1)
    eng = ServeEngine(model, params, pool_cfg)
    if not eng.use_kernel:
        raise SystemExit("chip_smoke: the engine did not select the Pallas kernels")
    print(f"pool pages={pool_cfg.num_blocks}x{pool_cfg.block_size} "
          f"dtype={pool_cfg.dtype} bytes_per_pool={eng.pool.k.nbytes}")

    rng = np.random.default_rng(args.seed)
    for i in range(N_REQUESTS):
        prompt = rng.integers(0, cfg.vocab_size, PROMPT_LEN).tolist()
        eng.submit(Request(rid=i, prompt=prompt, max_new=MAX_NEW))

    # step 1 admits and prefills every request, then decodes once: both
    # programs compile here
    t = time.perf_counter()
    eng.step()
    jax.block_until_ready((eng.pool.k, eng.pool.v))
    first_s = time.perf_counter() - t
    print(f"compile_s prefill={compile_s['decode_step']!r} "
          f"decode={compile_s['paged_decode_step']!r} (set-up time; "
          f"{len(cache_hits)} programs read from the persistent cache)")
    print(f"first_step_s={first_s!r} ({N_REQUESTS} prefills of {PROMPT_LEN} "
          "tokens + 1 decode step, compiles included)")

    err_f32, excess_bf16 = check_attention(eng, args.seed)
    print(f"paged_attention kernel vs reference over {cfg.n_layers} layers "
          f"(tol {ATTN_TOL}): f32 queries max_abs_diff={err_f32!r}; bf16 queries "
          f"max_abs_diff beyond bf16 output rounding={excess_bf16!r}")
    if not max(err_f32, excess_bf16) <= ATTN_TOL:
        raise SystemExit("chip_smoke: paged_attention kernel disagrees with the reference")
    pages = check_fork(eng)
    print(f"fork on block_copy: {pages} pages x {cfg.n_layers} layers of K and V "
          "equal the parent's byte for byte")

    tokens0, steps0 = eng.tokens_decoded, eng.steps
    t = time.perf_counter()
    done = eng.run()
    jax.block_until_ready((eng.pool.k, eng.pool.v))
    dt = time.perf_counter() - t
    bad = [r.rid for r in done if r.status != "done" or len(r.out) != MAX_NEW]
    print(f"served done={len(done)}/{N_REQUESTS} rejected={len(eng.rejected)} "
          f"cancelled={len(eng.cancelled)} tokens_each={sorted({len(r.out) for r in done})}")
    if len(done) != N_REQUESTS or bad or eng.rejected or eng.cancelled:
        raise SystemExit(f"chip_smoke: serving failed (incomplete requests {bad})")
    print(f"decode_tokens_per_s={(eng.tokens_decoded - tokens0) / dt!r} over "
          f"{eng.steps - steps0} steps (bring-up figure, not a benchmark)")
    print(f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
