"""Paged KV-cache pool with PUMA placement — the serving-side integration.

One pool holds the KV blocks of *all* live requests for *all* layers:

  K pool: (n_layers, num_blocks, block_size, kv_heads, head_dim)
  V pool: same

A request's logical KV stream is a :class:`~repro.core.arena.TileHandle`
(one tile = one block).  Placement uses PUMA policy: the first request block
goes worst-fit, subsequent blocks of the same request go ``extend`` (same
arena, adjacent slot when possible), and the V handle is ``alloc_align``-ed
against the K handle so K/V block *k* live at mirrored offsets.

A hybrid model's recurrent layers keep a fixed-size state per sequence
instead of pages: with ``state_layers`` > 0 the pool also holds *state
slots*, ``conv`` (max_seqs, state_layers, conv_width) and ``ssd``
(max_seqs, state_layers, ssd_heads, ssd_width), float32: a slot's state
is one contiguous slab, with a minor dimension that fills whole 128-lane
tiles.  A sequence's state slot is its sequence slot, so admission, which
hands out a free sequence slot, is also the state's accounting; the
prefill overwrites the whole slot, so admit zeroes nothing.

The device side keeps everything as jnp arrays plus an int32 *block table*
(max_seqs, max_blocks) — the TPU-idiomatic replacement for the paper's
re-mmap.  `paged_attention` consumes the table and stages one page per grid
step; PUMA placement keeps consecutive table entries contiguous, so those
steps' DMAs read HBM in sequence, and it keeps a prompt's pages in few runs,
which ``write_prompt_kv`` writes one run at a time.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arena import TileHandle, TilePool

if TYPE_CHECKING:
    from repro.robustness.faults import FaultInjector
    from repro.robustness.journal import Journal

__all__ = ["KVPoolConfig", "PagedKVPool", "lane_dim"]


def lane_dim(n: int) -> int:
    """``n`` rounded up to whole 128-lane tiles.  A hybrid's pool pads its
    head dimension so: a v5e then keeps the pool row-major, where a head
    dimension off the tiles (224) has the page axis put in the lanes, and the
    paged kernel, which needs row-major pages, would copy the pool every
    step.  The padded lanes hold zeros."""
    return -(-n // 128) * 128


@dataclasses.dataclass(frozen=True)
class KVPoolConfig:
    num_blocks: int = 1024
    block_size: int = 16            # tokens per block
    kv_heads: int = 8
    head_dim: int = 128
    n_layers: int = 1               # layers sharing this pool object
    max_seqs: int = 64
    max_blocks_per_seq: int = 256
    blocks_per_arena: int = 64      # "subarray" capacity
    n_channels: int = 1             # memory channels the arenas stripe over
    policy: str = "puma"
    dtype: str = "bfloat16"
    # per-sequence state slots of a hybrid's recurrent layers (0: none)
    state_layers: int = 0
    conv_width: int = 0             # (K-1) * conv_dim: the conv inputs kept
    ssd_heads: int = 0
    ssd_width: int = 0              # head_dim * state: one head's SSD state

    @property
    def n_arenas(self) -> int:
        assert self.num_blocks % self.blocks_per_arena == 0
        return self.num_blocks // self.blocks_per_arena

    def __post_init__(self):
        n_arenas = self.num_blocks // self.blocks_per_arena
        if self.n_channels < 1 or n_arenas % self.n_channels:
            raise ValueError(
                f"n_channels={self.n_channels} must divide "
                f"n_arenas={n_arenas} (num_blocks/blocks_per_arena)"
            )


class PagedKVPool:
    """Host bookkeeping + device buffers for paged KV serving."""

    def __init__(
        self,
        cfg: KVPoolConfig,
        injector: Optional["FaultInjector"] = None,
        journal: Optional["Journal"] = None,
    ):
        self.cfg = cfg
        #: crash-consistency journal, shared with the inner tile pool so
        #: slot-level (kv_*) and tile-level events form one total order.
        self.journal = journal
        self.pool = TilePool(
            cfg.n_arenas, cfg.blocks_per_arena, cfg.policy,
            n_channels=cfg.n_channels, injector=injector, journal=journal,
        )
        dt = jnp.dtype(cfg.dtype)
        shape = (cfg.n_layers, cfg.num_blocks, cfg.block_size, cfg.kv_heads, cfg.head_dim)
        self.k = jnp.zeros(shape, dt)
        self.v = jnp.zeros(shape, dt)
        self.conv = self.ssd = None
        if cfg.state_layers:
            self.conv = jnp.zeros((cfg.max_seqs, cfg.state_layers, cfg.conv_width), jnp.float32)
            self.ssd = jnp.zeros(
                (cfg.max_seqs, cfg.state_layers, cfg.ssd_heads, cfg.ssd_width), jnp.float32)
        # seq slot -> (k_handle, token_count)
        self._seqs: Dict[int, Tuple[TileHandle, int]] = {}
        self._free_slots = list(range(cfg.max_seqs))
        #: trace recorder (:class:`repro.trace.record.TraceRecorder`);
        #: the serving engine wires it in — None = no tracing overhead.
        self.trace = None

    # -- capacity reasoning (admission control) -------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        """KV blocks needed to hold ``n_tokens`` tokens."""
        return -(-n_tokens // self.cfg.block_size)

    @property
    def capacity_blocks(self) -> int:
        """Hard per-sequence block ceiling: a request needing more than this
        can *never* be admitted, regardless of pool state."""
        return min(self.cfg.num_blocks, self.cfg.max_blocks_per_seq)

    # -- request lifecycle ----------------------------------------------------
    def admit(self, n_prompt_tokens: int) -> Optional[int]:
        """Admit a request; allocate blocks for its prompt. Returns seq slot."""
        if not self._free_slots:
            return None
        blocks = -(-n_prompt_tokens // self.cfg.block_size)
        h = self.pool.alloc(blocks)
        if h is None:
            return None
        slot = self._free_slots.pop(0)
        self._seqs[slot] = (h, n_prompt_tokens)
        if self.journal is not None:
            self.journal.append(
                "kv_admit", slot=slot, hid=h.hid, ntok=n_prompt_tokens
            )
        if self.trace is not None:
            self.trace.on_admit(slot, h.tiles, alloc=self.cfg.policy)
        return slot

    def fork(
        self, slot: int, copy_data: bool = True, use_kernel: bool = False
    ) -> Optional[int]:
        """Beam/prefix fork: new sequence whose blocks are PUMA-aligned to
        the parent's, with the KV pages cloned in-pool — the RowClone
        analogue (``pud_bulk.pool_block_copy``; PUMA placement keeps source
        and destination in the same arena, so on the PUD substrate the copy
        is a same-subarray row-to-row transfer)."""
        if slot not in self._seqs or not self._free_slots:
            return None
        parent, ntok = self._seqs[slot]
        h = self.pool.alloc_align(len(parent.tiles), parent)
        if h is None:
            return None
        if copy_data and parent.tiles:
            from repro.kernels.pud_bulk.ops import pool_block_copy

            src = jnp.asarray(parent.tiles, jnp.int32)
            dst = jnp.asarray(h.tiles, jnp.int32)
            L = self.cfg.n_layers
            nb = self.cfg.num_blocks
            # fold the layer dim into the block index so one kernel call
            # clones every layer's pages
            offs = (jnp.arange(L, dtype=jnp.int32) * nb)[:, None]
            src_all = (src[None, :] + offs).reshape(-1)
            dst_all = (dst[None, :] + offs).reshape(-1)
            kflat = self.k.reshape((L * nb,) + self.k.shape[2:])
            vflat = self.v.reshape((L * nb,) + self.v.shape[2:])
            self.k = pool_block_copy(kflat, src_all, dst_all, use_kernel=use_kernel).reshape(self.k.shape)
            self.v = pool_block_copy(vflat, src_all, dst_all, use_kernel=use_kernel).reshape(self.v.shape)
        new_slot = self._free_slots.pop(0)
        if copy_data and self.conv is not None:
            self.write_token_states([new_slot], self.conv[slot][:, None], self.ssd[slot][:, None])
        self._seqs[new_slot] = (h, ntok)
        if self.journal is not None:
            self.journal.append("kv_fork", slot=new_slot, hid=h.hid, ntok=ntok)
        return new_slot

    def append_token(self, slot: int) -> bool:
        """Decode step bookkeeping: extend by a block when the current one fills."""
        h, ntok = self._seqs[slot]
        ntok += 1
        if ntok > len(h.tiles) * self.cfg.block_size:
            if not self.pool.extend(h, 1):
                return False
            if self.trace is not None:
                contig = len(h.tiles) < 2 or h.tiles[-1] == h.tiles[-2] + 1
                self.trace.on_extend(slot, h.tiles[-1], contig)
        self._seqs[slot] = (h, ntok)
        if self.journal is not None:
            self.journal.append("kv_append", slot=slot)
        return True

    def release(self, slot: int) -> None:
        h, _ = self._seqs.pop(slot)
        self.pool.free(h)
        if self.journal is not None:
            self.journal.append("kv_release", slot=slot)
        if self.trace is not None:
            self.trace.on_release(slot)
        self._free_slots.append(slot)

    # -- maintenance ----------------------------------------------------------
    def compact(
        self,
        max_moves: int = 128,
        use_kernel: bool = False,
        model=None,
        controller=None,
    ):
        """One defragmentation pass over the block pool.

        Plans with :func:`~repro.robustness.compaction.plan_pool_compaction`
        (intra-arena run repair first — RowClone-cheap — then arena
        evacuation), applies every planned move to the device K/V buffers
        with one batched ``pool_block_copy`` per pool (the plan guarantees
        sources and destinations are disjoint), then commits the
        bookkeeping through :func:`~repro.robustness.compaction.compact_pool`
        — which journals the pass and prices it.  Live block tables pick up
        the new placement automatically because the moves mutate the
        handles' tile lists in place.

        Returns the :class:`~repro.robustness.compaction.CompactionReport`,
        or ``None`` when the planner found nothing worth moving.
        """
        from repro.robustness.compaction import compact_pool, plan_pool_compaction

        plan = plan_pool_compaction(self.pool, max_moves=max_moves)
        if not plan.moves:
            return None
        from repro.kernels.pud_bulk.ops import pool_block_copy

        src = jnp.asarray([m.src for m in plan.moves], jnp.int32)
        dst = jnp.asarray([m.dst for m in plan.moves], jnp.int32)
        L = self.cfg.n_layers
        nb = self.cfg.num_blocks
        # fold the layer dim into the block index so one kernel call moves
        # every layer's pages (same trick as fork)
        offs = (jnp.arange(L, dtype=jnp.int32) * nb)[:, None]
        src_all = (src[None, :] + offs).reshape(-1)
        dst_all = (dst[None, :] + offs).reshape(-1)
        kflat = self.k.reshape((L * nb,) + self.k.shape[2:])
        vflat = self.v.reshape((L * nb,) + self.v.shape[2:])
        self.k = pool_block_copy(
            kflat, src_all, dst_all, use_kernel=use_kernel
        ).reshape(self.k.shape)
        self.v = pool_block_copy(
            vflat, src_all, dst_all, use_kernel=use_kernel
        ).reshape(self.v.shape)
        cfg = self.cfg
        tile_bytes = (
            2 * cfg.n_layers * cfg.block_size * cfg.kv_heads * cfg.head_dim
            * jnp.dtype(cfg.dtype).itemsize
        )
        report = compact_pool(
            self.pool, plan,
            tile_bytes=tile_bytes, model=model, controller=controller,
        )
        if self.trace is not None and report is not None:
            self.trace.on_compact(
                [(m.src, m.dst) for m in plan.moves], report
            )
        return report

    # -- trace helpers -----------------------------------------------------------
    def tiles_of(self, slot: int) -> List[int]:
        """Current tile list of a live sequence (trace emission)."""
        return list(self._seqs[slot][0].tiles)

    # -- device views -----------------------------------------------------------
    def block_table(self) -> np.ndarray:
        """(max_seqs, max_blocks) int32, -1 padded."""
        cfg = self.cfg
        tbl = np.full((cfg.max_seqs, cfg.max_blocks_per_seq), -1, np.int32)
        for slot, (h, _) in self._seqs.items():
            n = min(len(h.tiles), cfg.max_blocks_per_seq)
            tbl[slot, :n] = h.tiles[:n]
        return tbl

    def seq_lens(self) -> np.ndarray:
        out = np.zeros((self.cfg.max_seqs,), np.int32)
        for slot, (_, ntok) in self._seqs.items():
            out[slot] = ntok
        return out

    def write_prompt_kv(self, slot: int, k: jax.Array, v: jax.Array) -> None:
        """Write a prompt's K/V of every layer, ``(n_layers, n_tokens,
        kv_heads, head_dim)`` each, into the sequence's pages in one in-place
        call; the last page's tail past ``n_tokens`` is zero.  A batch axis
        of one after the layers, as a prefill cache holds it, is folded
        inside the call, so the caller makes no copy to drop it."""
        h, _ = self._seqs[slot]
        found = h.runs()
        runs = np.zeros((len(h.tiles), 3), np.int32)
        src = 0
        for r, (first, length) in enumerate(found):
            runs[r] = first, src, length
            src += length
        self.k, self.v = _write_pages(
            self.k, self.v, runs, np.int32(len(found)), k, v
        )

    def token_dest(self, slot: int) -> Tuple[int, int]:
        """(page, offset) of the sequence's latest token: where a decode
        step's ``write_token_kv`` lands it."""
        h, ntok = self._seqs[slot]
        pos = ntok - 1
        return h.tiles[pos // self.cfg.block_size], pos % self.cfg.block_size

    def write_token_kv(
        self, dests: Sequence[Tuple[int, int]], k: jax.Array, v: jax.Array
    ) -> None:
        """Write one decoded token's K/V per sequence, every layer,
        ``(n_layers, B, kv_heads, head_dim)`` each, to the B ``(page,
        offset)`` destinations (``token_dest``) in one in-place call."""
        blocks = np.asarray([b for b, _ in dests], np.int32)
        offs = np.asarray([o for _, o in dests], np.int32)
        self.k, self.v = _write_tokens(self.k, self.v, blocks, offs, k, v)

    def write_prompt_states(self, slot: int, conv: jax.Array, ssd: jax.Array) -> None:
        """Write a prompt's final state of every recurrent layer into the
        sequence's slot in one in-place call: ``conv`` (layers, 1, K-1,
        conv_dim) and ``ssd`` (layers, 1, H, P, N), as a prefill cache
        holds them."""
        self.conv, self.ssd = _write_states(
            self.conv, self.ssd, np.asarray([slot], np.int32), conv, ssd)

    def write_token_states(self, slots: Sequence[int], conv: jax.Array, ssd: jax.Array) -> None:
        """Write a decode step's new states, ``conv`` (layers, B,
        conv_width) and ``ssd`` (layers, B, ssd_heads, ssd_width), into
        the B sequences' slots in one in-place call."""
        self.conv, self.ssd = _write_states(
            self.conv, self.ssd, np.asarray(slots, np.int32), conv, ssd)

    def occupancy(self) -> Dict[str, float]:
        """Point-in-time pool occupancy sample (all floats, JSON-friendly):
        tile counts, used fraction, and sequence-slot pressure.  The serving
        load harness samples this every engine step via ``step_hooks``."""
        total = self.pool.total_tiles
        free = self.pool.free_tiles()
        return {
            "total_tiles": float(total),
            "free_tiles": float(free),
            "used_tiles": float(total - free),
            "used_fraction": (total - free) / total if total else 0.0,
            "live_seqs": float(len(self._seqs)),
            "free_slots": float(len(self._free_slots)),
        }

    # -- PUMA metric --------------------------------------------------------------
    def contiguity_report(self) -> Dict[str, float]:
        """Pool-wide contiguous-run statistics (the paper's '% in PUD'
        analogue) plus the channel figure of merit: ``channel_balance`` is
        mean/max used blocks per channel (1.0 = block tables perfectly
        striped across the channel-parallel substrate)."""
        fracs, runs, tiles = [], 0, 0
        for h, _ in self._seqs.values():
            fracs.append(h.contiguous_run_fraction())
            runs += len(h.runs())
            tiles += len(h.tiles)
        occ = self.pool.channel_occupancy()
        return {
            "mean_contiguous_fraction": float(np.mean(fracs)) if fracs else 1.0,
            "descriptors_per_tile": runs / tiles if tiles else 0.0,
            "live_seqs": float(len(self._seqs)),
            "channels": float(occ["channels"]),
            "channel_balance": float(occ["balance"]),
        }

    def channel_occupancy(self) -> Dict[str, object]:
        """Per-channel used/free block counts (detail behind the balance)."""
        return self.pool.channel_occupancy()


# The pool's writes: each takes the K and V pools donated and returns them
# updated in place, so no copy of a pool outlives a write.  Compiled once per
# shape: per batch size, per prompt length.
#
# Both are made of dynamic slices and updates, because on a TPU the layout
# of a pool is the compiler's: a head dimension under 128 lanes (stablelm's
# 64) puts the page axis in the lanes, and a scatter there turns both pools
# row-major and back.  In that layout an update rewrites every tile of the
# lane groups it touches, so a prompt is written one contiguous run of pages
# at a time (a window of as many pages as the prompt has), not page by page:
# PUMA's placement, which keeps a sequence's pages in few runs, keeps this
# write short.


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_tokens(k_pool, v_pool, blocks, offs, k, v):
    for i in range(k.shape[1]):
        at = (0, blocks[i], offs[i], 0, 0)
        k_pool = jax.lax.dynamic_update_slice(
            k_pool, k[:, i, None, None].astype(k_pool.dtype), at)
        v_pool = jax.lax.dynamic_update_slice(
            v_pool, v[:, i, None, None].astype(v_pool.dtype), at)
    return k_pool, v_pool


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_states(conv_pool, ssd_pool, slots, conv, ssd):
    """Sequence i's state, every layer, into slot ``slots[i]``: one
    dynamic update of a contiguous slab per sequence (the slots are
    distinct).  ``conv``/``ssd`` lead with (layers, B) and hold a slot's
    elements in any shape."""
    conv, ssd = jnp.swapaxes(conv, 0, 1), jnp.swapaxes(ssd, 0, 1)
    B = conv.shape[0]
    conv = conv.reshape((B, 1) + conv_pool.shape[1:]).astype(conv_pool.dtype)
    ssd = ssd.reshape((B, 1) + ssd_pool.shape[1:]).astype(ssd_pool.dtype)
    for i in range(B):
        conv_pool = jax.lax.dynamic_update_slice_in_dim(conv_pool, conv[i], slots[i], axis=0)
        ssd_pool = jax.lax.dynamic_update_slice_in_dim(ssd_pool, ssd[i], slots[i], axis=0)
    return conv_pool, ssd_pool


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_pages(k_pool, v_pool, runs, n_runs, k, v):
    """``runs``: one row (first pool page, first prompt page, length) per
    contiguous run of the prompt's pages, the first ``n_runs`` rows used."""
    n_layers, nb, bs = k_pool.shape[:3]
    n = runs.shape[0]
    q = jnp.arange(n)[None, :, None, None, None]

    def pages(x, pool):
        # (L, n pages, bs, KV, hd), with n zero pages on either side and
        # zero lanes up to a pool head dimension padded to whole lane tiles
        x = x.reshape((n_layers, -1) + x.shape[-2:]).astype(pool.dtype)
        x = jnp.pad(x, ((0, 0), (n * bs, 2 * n * bs - x.shape[1]), (0, 0),
                        (0, pool.shape[-1] - x.shape[-1])))
        return x.reshape((n_layers, 3 * n) + pool.shape[2:])

    xk, xv = pages(k, k_pool), pages(v, v_pool)

    def one_run(r, pools):
        first, src, length = runs[r, 0], runs[r, 1], runs[r, 2]
        at = jnp.minimum(first, nb - n)           # an n-page window holding the run
        shift = first - at
        inside = (q >= shift) & (q < shift + length)

        def put(pool, x):
            old = jax.lax.dynamic_slice_in_dim(pool, at, n, axis=1)
            new = jax.lax.dynamic_slice_in_dim(x, n + src - shift, n, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                pool, jnp.where(inside, new, old), at, axis=1)

        return put(pools[0], xk), put(pools[1], xv)

    return jax.lax.fori_loop(0, n_runs, one_run, (k_pool, v_pool))
