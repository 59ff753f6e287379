"""Continuous-batching serving engine over the PUMA paged KV pool.

Lifecycle per step:

  1. **admit** — pull queued requests while pool blocks + seq slots allow;
     PUMA placement (worst-fit first allocation) assigns prompt blocks.
     Admission scans a bounded *lookahead window* of the queue, so one
     large head-of-line request cannot starve small requests behind it.
  2. **prefill** — teacher-forced pass with a dense scratch cache, then every
     layer's K/V pages are written into the pool blocks in one in-place call
     (a bulk RowClone-style block write); a hybrid also writes each Mamba2
     layer's final state into the sequence's state slot in one call.
  3. **decode** — one fused step for every live sequence via
     ``paged_decode_step`` (block tables + seq_lens), then the batch's
     new-token K/V, every layer, written to the PUMA-chosen blocks in one
     in-place call (a hybrid's new states to their slots in one more);
     greedy sampling.
  4. **bookkeeping** — tokens appended (``extend`` keeps arena locality),
     finished sequences release blocks.

Hardened (degraded-mode) path — no request is ever silently dropped:

  * ``submit`` rejects *never-admissible* requests (empty prompt, or
    prompt+max_new exceeding the per-sequence block ceiling) with a typed
    :class:`~repro.robustness.RequestRejected` — instead of queueing work
    that can never run.
  * A request may carry ``deadline_steps``; once ``clock`` passes it the
    request is cancelled with :class:`~repro.robustness.DeadlineExceeded`
    and its blocks are released (cooperative cancellation).
  * When a decode-time block ``extend`` fails (pool pressure or an injected
    fault), the engine preempts the *youngest* live sequence — the one
    whose blocks were allocated most recently, i.e. LRU over block
    allocation time and the cheapest prefill to redo — releasing its blocks
    and re-queueing it at the queue front.  On re-admission the preempted
    request *recomputes* its KV from ``prompt + out[:-1]`` (recompute-on-
    resume), so generation continues bit-exactly.
  * If the engine sits with an empty batch and a non-empty queue for more
    than ``stall_patience`` steps, the stuck requests are rejected with a
    stall report attached — loud failure instead of a silent busy-loop.

Metrics surface the paper's figure of merit: block-table contiguity (the
"% executable in PUD" analogue) plus throughput and degraded-mode counters
(rejected / cancelled / preemptions).  With ``KVPoolConfig.n_channels > 1``
the pool stripes each request's blocks round-robin across memory channels,
and ``metrics()``/``channel_occupancy()`` additionally report per-channel
block occupancy and its load balance.

Open-loop load support (:mod:`repro.serve.loadgen` is the consumer):
``cancel(rid)`` is client-side early cancellation, ``step_hooks`` receive a
:meth:`ServeEngine.step_sample` after every step, and ``run_for`` /
``drain`` slice engine time so a traffic driver can interleave arrivals
with bounded stepping instead of handing over the whole loop.

Profiler spans: every phase of :meth:`ServeEngine.step` is a
``jax.profiler.TraceAnnotation``, so a ``jax.profiler`` trace puts the
engine's host work on the device's clock.  While no trace is taken one
costs about half a microsecond on a TPU v5e host; the profiler's state is
their only switch:

  * ``serve.step`` — the whole ``step()``, hooks included; sequence
    bookkeeping (token append, release) is its self time;
  * ``serve.admit`` — the deadline sweep and the admission scan;
  * ``serve.prefill`` (``rid``, ``tokens``, ``states``) — one request's
    jitted prefill, its one ``write_prompt_kv`` call (and, ``states`` > 0,
    its one ``write_prompt_states`` of that many layers) and first-token
    argmax (inside ``admit``);
  * ``serve.decode_dispatch`` (``batch``) — block tables, ``seq_lens``,
    host-to-device inputs and the asynchronous call of the paged step;
  * ``serve.kv_writeback`` (``rid``) — one sequence's share of the write:
    the lookup of its token's page and offset.  The batch's one
    asynchronous ``write_token_kv`` call follows these spans, in the
    self time of ``serve.step``;
  * ``serve.sample`` — the host waiting for the step's argmax (the device
    runs the step, then the write, then the argmax);
  * ``serve.maintain`` — a compaction pass, only when one runs;
  * ``serve.cast_weights`` (``bytes``) — once, in the constructor: the one
    jitted cast of the weights to the compute dtype (below).

Weights in the compute dtype: the paged step and the prefill cast every
weight matrix to ``cfg.dtype`` before they use it, so f32 parameters would
be read at 4 bytes a weight, cast and read again at 2 in every call.  Once
the pool exists, the engine casts the matrices (``COMPUTE_DTYPE_LEAVES``)
to ``cfg.dtype`` in one call and serves from that copy; the step's own
casts are then no-ops and the matmuls see the same values.  Leaves read in
f32 (norms, biases, Mamba2's ``A_log``, ``D``, ``dt_bias`` and conv) stay
as they are, and the caller's arrays are neither changed nor freed.  The
copy is made only where it fits: its bytes plus ``CAST_RESERVE_BYTES`` for
the step's temporaries within the free bytes (``bytes_limit -
bytes_in_use``) of every device the weights are on; a device that reports
no memory stats (the CPU) has room.  Otherwise every matrix stays as given.
``metrics()["weights_in_compute_dtype"]`` is the share of the matrices'
elements held in ``cfg.dtype``: 1.0 after the cast, or where the weights
came in it.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import TYPE_CHECKING, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.kv_pool import KVPoolConfig, PagedKVPool, lane_dim
from repro.models import mamba2 as M2
from repro.robustness import (
    ClientCancelled,
    DeadlineExceeded,
    EngineStalled,
    RequestRejected,
)
from repro.serve.paged_runner import paged_decode_step_jit

if TYPE_CHECKING:
    from repro.robustness.faults import FaultInjector

#: parameter leaves that every use in the paged step and the prefill casts to
#: the compute dtype first: attention projections, MLP and MoE matrices, the
#: embedding and untied head, Mamba2's in/out projections, a shared block's
#: matrices and each use's adapter and linear
COMPUTE_DTYPE_LEAVES = frozenset({
    "wq", "wk", "wv", "wo", "wg", "wu", "router", "tok", "head", "in_proj", "out_proj",
    "w_gate_up", "w_down", "lora_a", "lora_b", "linear",
})

#: HBM left free beside the weights' copy for the step's and the prefill's
#: temporaries: the widest any benchmark cell's program needs, zamba2's hybrid
#: step at 2.57 GB (its compile for a v5e in ``tests/test_tpu_compile.py``)
CAST_RESERVE_BYTES = 2_570_000_000


def cast_fits(needed: int, stats: Optional[Dict[str, int]]) -> bool:
    """Whether a copy of ``needed`` bytes fits, with ``CAST_RESERVE_BYTES``
    to spare, a device whose ``memory_stats()`` are ``stats``; a device that
    reports none has room."""
    if not stats or "bytes_limit" not in stats:
        return True
    return needed + CAST_RESERVE_BYTES <= stats["bytes_limit"] - stats["bytes_in_use"]


def _memory_stats(device) -> Optional[Dict[str, int]]:
    """The device's memory stats (a test plants a full device here)."""
    return device.memory_stats()


def pool_fields(cfg: ModelConfig) -> Dict[str, int]:
    """The ``KVPoolConfig`` fields a model fixes: pages for its KV layers
    with its KV heads and head dimension, and a hybrid's state slots.  A
    hybrid's pages pad the head dimension to whole lane tiles
    (``lane_dim``), and each of its Mamba2 layers keeps a slot per sequence
    (``mamba2.slot_widths``)."""
    fields = {"kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd, "n_layers": cfg.kv_layers,
              "state_layers": 0, "conv_width": 0, "ssd_heads": 0, "ssd_width": 0}
    if cfg.family == "hybrid":
        fields.update(head_dim=lane_dim(cfg.hd), state_layers=cfg.n_layers,
                      **M2.slot_widths(cfg))
    return fields


@functools.partial(jax.jit, static_argnums=1)
def _cast(leaves, dtype):
    return [a.astype(dtype) for a in leaves]


@dataclasses.dataclass(frozen=True)
class MaintenanceConfig:
    """Watermarks for the background compaction hook in :meth:`ServeEngine.step`.

    A pass triggers when the pool's free-tile fraction falls below
    ``free_low`` *or* its fragmentation rises above ``frag_high`` *or* the
    live block tables' mean contiguous-run fraction falls below
    ``contig_low`` — but at most once every ``every`` engine clock ticks, so
    maintenance cannot monopolise the step loop.  ``max_moves`` bounds one
    pass; the pass cost (RowClone rows + host copies, see
    :func:`repro.core.pud.price_migration`) lands in the engine's
    ``maintenance_ns`` counter, competing with live traffic in the cost
    model.
    """

    free_low: float = 0.25
    frag_high: float = 0.5
    contig_low: float = 0.85
    max_moves: int = 32
    every: int = 4


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    # robustness / QoS fields
    deadline_steps: Optional[int] = None   # engine-clock budget from submit
    status: str = "queued"                 # queued|running|done|rejected|cancelled
    submit_clock: int = 0
    admit_clock: int = -1
    finish_clock: int = -1                 # clock at done/rejected/cancelled
    tenant: Optional[str] = None           # traffic class (loadgen bookkeeping)
    preemptions: int = 0
    error: Optional[Exception] = None

    def ctx_tokens(self) -> int:
        """Tokens whose KV must exist before the next decode step — the
        prompt plus all-but-the-last generated token (the last one is the
        next decode *input*).  This is what a resume-after-preemption
        prefill recomputes."""
        return len(self.prompt) + max(0, len(self.out) - 1)


class ServeEngine:
    def __init__(
        self,
        model,
        params,
        pool_cfg: KVPoolConfig,
        *,
        eos_id: Optional[int] = None,
        injector: Optional["FaultInjector"] = None,
        admission_lookahead: int = 8,
        stall_patience: int = 3,
        maintenance: Optional[MaintenanceConfig] = None,
        trace=None,
    ):
        cfg = model.cfg
        if cfg.family in ("ssm", "encdec") or cfg.is_encdec:
            raise ValueError(f"{cfg.name}: the paged engine serves attention and "
                             f"Mamba2-hybrid families, not {cfg.family}")
        bad = {k: (getattr(pool_cfg, k), v) for k, v in pool_fields(cfg).items()
               if getattr(pool_cfg, k) != v}
        if bad:
            raise ValueError(f"pool does not fit {cfg.name}, (pool, model): {bad}")
        self.model = model
        self.cfg = cfg
        self.pool = PagedKVPool(pool_cfg, injector=injector)
        self.params, self.weights_in_compute_dtype = self._in_compute_dtype(params)
        #: the Pallas kernels on the chip; elsewhere the jnp reference (the
        #: kernels would only run in the slow Pallas interpreter there)
        self.use_kernel = jax.default_backend() == "tpu"
        # the jitted prefill step is cached ON the model so every engine
        # over the same model shares one XLA cache (scenario reruns compile
        # nothing); the paged step's shared wrapper lives in paged_runner
        # for the same reason
        fn = getattr(model, "_jit_decode_step", None)
        if fn is None:
            fn = model._jit_decode_step = jax.jit(model.decode_step)
        self._decode_step = fn
        self._paged_step = paged_decode_step_jit
        self.eos_id = eos_id
        self.admission_lookahead = max(1, admission_lookahead)
        self.stall_patience = max(1, stall_patience)
        self.queue: Deque[Request] = deque()
        self.live: Dict[int, Request] = {}     # slot -> request
        self.done: List[Request] = []
        self.rejected: List[Request] = []
        self.cancelled: List[Request] = []
        self.steps = 0                          # decode steps (batch advanced)
        self.clock = 0                          # every step() call, incl. stalls
        self.tokens_decoded = 0
        self.tokens_prefilled = 0               # teacher-forced KV-fill tokens
        self.preemptions = 0
        self.submitted = 0
        self._stall_steps = 0
        #: step-level metric hooks: each callable gets ``(engine, sample)``
        #: after every :meth:`step`, where ``sample`` is :meth:`step_sample`.
        #: The load harness registers its occupancy/queue-depth sampler here.
        self.step_hooks: List = []
        # background maintenance (watermark-triggered compaction)
        self.maintenance = maintenance
        self.maintenance_ns = 0.0
        self.compaction_passes = 0
        self.blocks_migrated = 0
        self._last_maintenance = -(10 ** 9)
        #: tracegen recorder (:class:`repro.trace.record.TraceRecorder`) of
        #: simulated-DRAM accesses, not the profiler spans (those are always
        #: there, see the module docstring): shared with the pool so request
        #: lifecycle, prompt-KV fills, decode-token writes, and compaction
        #: all land in one trace.
        self.trace = trace
        self.pool.trace = trace
        self._step_writes: List = []   # (slot, block) token writes this step

    def _in_compute_dtype(self, params):
        """``params`` with the matrices in ``cfg.dtype`` where the copy fits
        (module docstring), and the share of their elements held in it."""
        dtype = jnp.dtype(self.cfg.dtype)
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = [a for _, a in flat]
        mats = [i for i, (path, _) in enumerate(flat)
                if getattr(path[-1], "key", None) in COMPUTE_DTYPE_LEAVES]
        todo = [i for i in mats if leaves[i].dtype != dtype]
        needed = sum(leaves[i].size for i in todo) * dtype.itemsize
        devices = {d for i in todo for d in leaves[i].devices()}
        # the pool's buffers count in ``bytes_in_use`` once they exist
        jax.block_until_ready((self.pool.k, self.pool.v, self.pool.conv, self.pool.ssd))
        if todo and all(cast_fits(needed, _memory_stats(d)) for d in devices):
            with TraceAnnotation("serve.cast_weights", bytes=needed):
                for i, a in zip(todo, _cast([leaves[i] for i in todo], dtype)):
                    leaves[i] = a
            params = jax.tree.unflatten(treedef, leaves)
        total = sum(leaves[i].size for i in mats)
        held = sum(leaves[i].size for i in mats if leaves[i].dtype == dtype)
        return params, held / total if total else 1.0

    # -- submission -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue a request; raises :class:`RequestRejected` immediately if it
        can *never* be admitted (so no work is silently parked forever)."""
        self.submitted += 1
        req.submit_clock = self.clock
        total_blocks = self.pool.blocks_for(len(req.prompt) + req.max_new)
        if not req.prompt:
            err = RequestRejected("empty prompt", rid=req.rid)
        elif total_blocks > self.pool.capacity_blocks:
            err = RequestRejected(
                "request can never be admitted: prompt+max_new exceeds the "
                "per-sequence block ceiling",
                rid=req.rid, blocks_needed=total_blocks,
                capacity_blocks=self.pool.capacity_blocks,
            )
        else:
            self.queue.append(req)
            return
        req.status = "rejected"
        req.error = err
        req.finish_clock = self.clock
        self.rejected.append(req)
        raise err

    def cancel(self, rid: int) -> bool:
        """Client-side early cancellation: drop ``rid`` from the queue or the
        live batch (releasing its KV blocks).  Returns False when the request
        is not in flight (already done / rejected / cancelled / unknown) —
        cancelling twice is a harmless no-op, like closing a dead socket."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                self._cancel(req, ClientCancelled(
                    "cancelled by client while queued", rid=rid,
                    waited=self.clock - req.submit_clock,
                ))
                return True
        for slot, req in list(self.live.items()):
            if req.rid == rid:
                del self.live[slot]
                self.pool.release(slot)
                req.slot = None
                self._cancel(req, ClientCancelled(
                    "cancelled by client mid-decode", rid=rid,
                    decoded=len(req.out),
                ))
                return True
        return False

    # -- degraded-mode bookkeeping --------------------------------------------
    def _reject(self, req: Request, err: RequestRejected) -> None:
        req.status = "rejected"
        req.error = err
        req.finish_clock = self.clock
        self.rejected.append(req)

    def _cancel(self, req: Request, err: Exception) -> None:
        req.status = "cancelled"
        req.error = err
        req.finish_clock = self.clock
        self.cancelled.append(req)

    def _sweep_deadlines(self) -> None:
        now = self.clock
        for i in range(len(self.queue) - 1, -1, -1):
            req = self.queue[i]
            if req.deadline_steps is not None and now - req.submit_clock > req.deadline_steps:
                del self.queue[i]
                self._cancel(req, DeadlineExceeded(
                    "deadline expired while queued",
                    rid=req.rid, deadline_steps=req.deadline_steps,
                    waited=now - req.submit_clock,
                ))
        expired = [
            s for s, r in self.live.items()
            if r.deadline_steps is not None and now - r.submit_clock > r.deadline_steps
        ]
        for slot in expired:
            req = self.live.pop(slot)
            self.pool.release(slot)
            req.slot = None
            self._cancel(req, DeadlineExceeded(
                "deadline expired mid-decode",
                rid=req.rid, deadline_steps=req.deadline_steps,
                decoded=len(req.out),
            ))

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Preemption victim: the youngest live sequence (blocks allocated
        most recently — LRU over allocation time, cheapest to recompute)."""
        candidates = [s for s in self.live if s != exclude]
        if not candidates:
            return None
        return max(candidates, key=lambda s: (self.live[s].admit_clock, s))

    def _preempt(self, slot: int) -> None:
        req = self.live.pop(slot)
        self.pool.release(slot)
        req.slot = None
        req.status = "queued"
        req.preemptions += 1
        self.preemptions += 1
        self.queue.appendleft(req)   # resume first: it already holds progress

    def _append_with_recovery(self, slot: int, *, allow_preempt: bool = True) -> bool:
        """`append_token` with transient-fault retries and preemption.

        Transient injected misses are retried (fresh fault draw each time);
        true exhaustion preempts the youngest *other* sequence and retries.
        Returns False only when the pool genuinely cannot host one more
        block for this sequence.

        ``allow_preempt=False`` is the admission-time mode: a sequence that
        is only being *prefilled* must never evict sequences holding decode
        progress — two near-full requests would otherwise evict each other
        forever inside one step (admit A, A's growth block preempts B, B
        lands back at the queue head, B is admitted and preempts A, ...).
        """
        for _ in range(3):
            if self.pool.append_token(slot):
                return True
            if self.pool.pool.free_tiles() > 0:
                continue                      # injected transient miss
            if not allow_preempt:
                return False
            victim = self._pick_victim(exclude=slot)
            if victim is None:
                return False
            self._preempt(victim)
        return self.pool.append_token(slot)

    # -- background maintenance ------------------------------------------------
    def _maybe_maintain(self) -> None:
        """Run one compaction pass when a watermark trips (rate-limited)."""
        mc = self.maintenance
        if mc is None or self.clock - self._last_maintenance < mc.every:
            return
        pool = self.pool.pool
        total = pool.total_tiles
        free_frac = pool.free_tiles() / total if total else 1.0
        frag = pool.fragmentation()
        contig = self.pool.contiguity_report()["mean_contiguous_fraction"]
        if free_frac > mc.free_low and frag < mc.frag_high and contig > mc.contig_low:
            return
        with TraceAnnotation("serve.maintain"):
            self._last_maintenance = self.clock
            report = self.pool.compact(
                max_moves=mc.max_moves, use_kernel=self.use_kernel
            )
            if report is not None and report.executed:
                self.compaction_passes += 1
                self.blocks_migrated += report.executed
                self.maintenance_ns += report.total_ns

    # -- prefill --------------------------------------------------------------
    def _prefill(self, req: Request) -> bool:
        """Teacher-forced KV fill over ``prompt + out[:-1]`` — identical for
        a fresh request (out empty) and a preempted one resuming
        (recompute-on-resume).  Returns False if the request had to be
        rejected (pathological: pool cannot host the sampled token)."""
        ctx = req.prompt + req.out[:-1]
        states = self.pool.cfg.state_layers
        with TraceAnnotation("serve.prefill", rid=req.rid, tokens=len(ctx), states=states):
            toks = jnp.asarray([ctx], jnp.int32)
            S = toks.shape[1]
            pos = jnp.arange(S, dtype=jnp.int32)[None]
            cache = self.model.init_cache(1, S, recent_size=S)
            batch = {"tokens": toks, "positions": pos}
            logits, cache = self._decode_step(self.params, batch, cache)
            self.tokens_prefilled += S
            # prompt KV lands in the recent ring (split cache, len_main == 0)
            layers = cache["layers"]
            if states:
                st = layers["mamba"]
                self.pool.write_prompt_states(req.slot, st.conv, st.ssd)
                layers = layers["attn"]
            k, v = layers["recent"]                     # (L, 1, S, KV, hd)
            self.pool.write_prompt_kv(req.slot, k, v)
            if self.trace is not None:
                self.trace.on_prefill(
                    req.slot, req.rid, S, self.pool.tiles_of(req.slot)
                )
            if not req.out:
                req.out.append(int(jnp.argmax(logits[0])))
        # account the pending token: it becomes the next decode input.
        # allow_preempt=False — admission must never evict decode progress
        # (see _append_with_recovery); the admission gate below makes this
        # failure genuinely pathological (faults / per-seq block ceiling).
        if not self._append_with_recovery(req.slot, allow_preempt=False):
            slot = req.slot
            self.pool.release(slot)
            del self.live[slot]
            req.slot = None
            self._reject(req, RequestRejected(
                "KV pool cannot host the sampled token", rid=req.rid,
            ))
            return False
        return True

    # -- one engine step ---------------------------------------------------------
    def step(self) -> bool:
        """Admit + decode one token for all live seqs. False when idle.

        After the step, every registered ``step_hooks`` callable receives
        ``(engine, step_sample())`` — the open-loop load harness samples
        occupancy / queue depth / degraded-mode counters this way without
        the engine knowing about any particular consumer.

        The sample is taken once, *after* the step (and any watermark
        compaction inside it) completes, and each hook gets its own
        snapshot copy: a consumer that mutates its sample — or registers /
        removes hooks from inside one — cannot leak an inconsistent view
        into the other consumers mid-iteration."""
        with TraceAnnotation("serve.step"):
            if self.trace is not None:
                self._step_writes = []
                d0 = self.tokens_decoded
            alive = self._step()
            if self.trace is not None:
                self.trace.on_step(
                    self.clock, self.tokens_decoded - d0, self._step_writes
                )
            if self.step_hooks:
                sample = self.step_sample()
                for hook in list(self.step_hooks):
                    hook(self, dict(sample))
            return alive

    def _step(self) -> bool:
        self.clock += 1
        with TraceAnnotation("serve.admit"):
            self._sweep_deadlines()

            # 1) admit — bounded lookahead so a large head request cannot starve
            #    admissible smaller requests behind it (HOL-blocking fix)
            idx = 0
            scanned = 0
            while idx < len(self.queue) and scanned < self.admission_lookahead:
                req = self.queue[idx]
                slot = self.pool.admit(req.ctx_tokens())
                if slot is None:
                    idx += 1
                    scanned += 1
                    continue
                # prefill appends the sampled token immediately: if that needs a
                # growth block the pool doesn't have, admitting now would either
                # reject the request or evict running work — leave it queued.
                if (self.pool.pool.free_tiles() == 0
                        and self.pool.blocks_for(req.ctx_tokens() + 1)
                        > self.pool.blocks_for(req.ctx_tokens())):
                    self.pool.release(slot)
                    idx += 1
                    scanned += 1
                    continue
                del self.queue[idx]
                req.slot = slot
                req.status = "running"
                req.admit_clock = self.clock
                self.live[slot] = req
                self._prefill(req)

        if not self.live:
            if not self.queue:
                return False
            # empty batch, non-empty queue: a stall.  Tolerate a few steps
            # (transient injected faults resolve), then fail loudly.
            self._stall_steps += 1
            if self._stall_steps > self.stall_patience:
                report = self.stall_report()
                while self.queue:
                    req = self.queue.popleft()
                    self._reject(req, RequestRejected(
                        "engine stalled: request not admissible with an idle pool",
                        rid=req.rid,
                        blocks_needed=self.pool.blocks_for(req.ctx_tokens()),
                        report=report,
                    ))
                self._stall_steps = 0
                return False
            # stalled admission is exactly when defrag helps most
            self._maybe_maintain()
            return True
        self._stall_steps = 0

        # 2) fused decode for all live sequences
        slots = sorted(self.live)
        cfg = self.cfg
        with TraceAnnotation("serve.decode_dispatch", batch=len(slots)):
            tbl_full = self.pool.block_table()
            lens_full = self.pool.seq_lens()
            tokens = np.array([[self.live[s].out[-1]] for s in slots], np.int32)
            positions = np.array([[lens_full[s] - 1] for s in slots], np.int32)
            tbl = jnp.asarray(tbl_full[slots])
            lens = jnp.asarray(lens_full[slots])
            # a hybrid's state slots are read by slot and written back below
            state = (() if self.pool.conv is None else
                     ((self.pool.conv, self.pool.ssd, jnp.asarray(slots, jnp.int32)),))

            logits, new_k, new_v, *new_states = self._paged_step(
                self.params, cfg,
                jnp.asarray(tokens), jnp.asarray(positions),
                self.pool.k, self.pool.v, tbl, lens, *state,
                use_kernel=self.use_kernel,
            )
        # 3) write every sequence's current-token KV into its PUMA-placed
        #    page in one in-place call, while the whole batch is still live:
        #    a sequence preempted below was written into pages it then frees,
        #    which seq_lens mask until they are rewritten
        dests = []
        for slot in slots:
            with TraceAnnotation("serve.kv_writeback", rid=self.live[slot].rid):
                dests.append(self.pool.token_dest(slot))
        self.pool.write_token_kv(dests, new_k, new_v)
        if new_states:
            self.pool.write_token_states(slots, *new_states)
        with TraceAnnotation("serve.sample"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))

        # 4) advance sequences
        for bi, slot in enumerate(slots):
            if slot not in self.live:
                continue                    # preempted earlier this loop
            req = self.live[slot]
            if self.trace is not None:
                # one block-granular write per decoded token (all layers'
                # planes of that block count as the one row touch)
                self._step_writes.append((slot, dests[bi][0]))
            tok = int(nxt[bi])
            self.tokens_decoded += 1
            finished = (
                len(req.out) + 1 >= req.max_new
                or (self.eos_id is not None and tok == self.eos_id)
            )
            req.out.append(tok)
            if finished:
                self.pool.release(slot)
                del self.live[slot]
                req.slot = None
                req.status = "done"
                req.finish_clock = self.clock
                self.done.append(req)
            elif not self._append_with_recovery(slot):
                self.pool.release(slot)
                del self.live[slot]
                req.slot = None
                self._reject(req, RequestRejected(
                    "KV pool cannot host the next token", rid=req.rid,
                    decoded=len(req.out),
                ))
        self.steps += 1
        self._maybe_maintain()
        return bool(self.live or self.queue)

    def drain(self, max_steps: int = 10_000) -> List[Request]:
        """Step until idle without raising — the open-loop load harness ends
        a scenario with this (rejections/cancellations stay recorded in the
        ledger rather than aborting the run)."""
        for _ in range(max_steps):
            if not self.step():
                break
        return self.done

    def run_for(self, n_steps: int) -> bool:
        """Time-sliced run: advance at most ``n_steps`` engine ticks.

        Returns the last ``step()`` result (False = engine went idle), so an
        open-loop driver can interleave arrival submission with bounded
        slices of engine time instead of handing over the whole loop."""
        alive = True
        for _ in range(max(0, n_steps)):
            alive = self.step()
            if not alive:
                break
        return alive

    def run(self, max_steps: int = 10_000, raise_on_error: bool = True) -> List[Request]:
        self.drain(max_steps)
        if raise_on_error:
            if self.queue or self.live:
                raise EngineStalled(
                    "serving loop ended with unfinished work",
                    report=self.stall_report(),
                )
            for r in self.rejected:
                if r.error is not None:
                    raise r.error
        return self.done

    # -- introspection --------------------------------------------------------
    def stall_report(self) -> Dict[str, object]:
        """Snapshot of why the engine is (or was) unable to make progress."""
        return {
            "clock": self.clock,
            "steps": self.steps,
            "queued": [
                {"rid": r.rid, "blocks_needed": self.pool.blocks_for(r.ctx_tokens()),
                 "preemptions": r.preemptions}
                for r in self.queue
            ],
            "live": len(self.live),
            "free_tiles": self.pool.pool.free_tiles(),
            "total_tiles": self.pool.pool.total_tiles,
            "free_slots": len(self.pool._free_slots),
            "done": len(self.done),
            "rejected": len(self.rejected),
            "cancelled": len(self.cancelled),
            "preemptions": self.preemptions,
        }

    def step_sample(self) -> Dict[str, float]:
        """One step-granular metric sample (what ``step_hooks`` receive):
        queue/batch depth, pool occupancy, live block-table contiguity (the
        paper's PUD-executable-fraction analogue — meaningful only while
        sequences are live, hence sampled here rather than post-drain), and
        the degraded-mode counters.  All floats."""
        occ = self.pool.occupancy()
        rep = self.pool.contiguity_report()
        return {
            "contiguity": rep["mean_contiguous_fraction"],
            "descriptors_per_tile": rep["descriptors_per_tile"],
            "channel_balance": rep["channel_balance"],
            "clock": float(self.clock),
            "steps": float(self.steps),
            "live": float(len(self.live)),
            "queued": float(len(self.queue)),
            "free_tiles": occ["free_tiles"],
            "used_fraction": occ["used_fraction"],
            "tokens_decoded": float(self.tokens_decoded),
            "tokens_prefilled": float(self.tokens_prefilled),
            "done": float(len(self.done)),
            "rejected": float(len(self.rejected)),
            "cancelled": float(len(self.cancelled)),
            "preemptions": float(self.preemptions),
        }

    def metrics(self) -> Dict[str, float]:
        rep = self.pool.contiguity_report()
        rep.update(
            clock=float(self.clock),
            steps=float(self.steps),
            tokens=float(self.tokens_decoded),
            tokens_prefilled=float(self.tokens_prefilled),
            submitted=float(self.submitted),
            done=float(len(self.done)),
            queue_depth=float(len(self.queue)),
            used_fraction=self.pool.occupancy()["used_fraction"],
            frag=self.pool.pool.fragmentation(),
            align_hits=float(self.pool.pool.stats.align_hits),
            align_misses=float(self.pool.pool.stats.align_misses),
            rejected=float(len(self.rejected)),
            cancelled=float(len(self.cancelled)),
            preemptions=float(self.preemptions),
            injected_misses=float(self.pool.pool.stats.injected_misses),
            maintenance_ns=float(self.maintenance_ns),
            compaction_passes=float(self.compaction_passes),
            blocks_migrated=float(self.blocks_migrated),
            weights_in_compute_dtype=float(self.weights_in_compute_dtype),
        )
        return rep

    def channel_occupancy(self) -> Dict[str, object]:
        """Per-channel block occupancy of the paged KV pool."""
        return self.pool.channel_occupancy()
