"""Public jit'd wrappers for pud_bulk: shape-normalizing entry points used by
the KV pool, the serving engine, and the PUD microbenchmarks."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.pud_bulk import kernel as _k
from repro.kernels.pud_bulk import ref as _ref

LANES = _k.LANES


def _to_tiles(x: jax.Array) -> tuple:
    """Flatten any array to (rows, 128) int32-compatible tiles + restore info."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % (8 * LANES)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES), x.shape, n


def _from_tiles(t: jax.Array, shape, n) -> jax.Array:
    return t.reshape(-1)[:n].reshape(shape)


def _dispatch(op: str, *xs: jax.Array, use_kernel: bool = True) -> jax.Array:
    tiles = [_to_tiles(x) for x in xs]
    ts = [t for t, _, _ in tiles]
    if use_kernel:
        out = _k.bulk_op(*ts, op=op)
    else:
        out = _ref.bulk_op_ref(*ts, op=op)
    return _from_tiles(out, tiles[0][1], tiles[0][2])


def pud_zero(x: jax.Array, use_kernel: bool = True) -> jax.Array:
    """RowClone zero-init (shape/dtype donor ``x``)."""
    return _dispatch("zero", x, use_kernel=use_kernel)


def pud_copy(x: jax.Array, use_kernel: bool = True) -> jax.Array:
    return _dispatch("copy", x, use_kernel=use_kernel)


def pud_not(x: jax.Array, use_kernel: bool = True) -> jax.Array:
    return _dispatch("not", x, use_kernel=use_kernel)


def pud_and(x: jax.Array, y: jax.Array, use_kernel: bool = True) -> jax.Array:
    return _dispatch("and", x, y, use_kernel=use_kernel)


def pud_or(x: jax.Array, y: jax.Array, use_kernel: bool = True) -> jax.Array:
    return _dispatch("or", x, y, use_kernel=use_kernel)


def pud_xor(x: jax.Array, y: jax.Array, use_kernel: bool = True) -> jax.Array:
    return _dispatch("xor", x, y, use_kernel=use_kernel)


def pud_maj(x: jax.Array, y: jax.Array, z: jax.Array, use_kernel: bool = True) -> jax.Array:
    return _dispatch("maj", x, y, z, use_kernel=use_kernel)


def pool_block_copy(
    pool: jax.Array, src: jax.Array, dst: jax.Array, use_kernel: bool = True
) -> jax.Array:
    """RowClone over a block pool: pool[dst] <- pool[src], in place.

    ``pool``: (num_blocks, ...) — whole blocks are copied, any trailing
    shape.
    """
    src_dst = jnp.stack([src.astype(jnp.int32), dst.astype(jnp.int32)], axis=1)
    if not use_kernel:
        return _ref.block_copy_ref(pool, src_dst)
    # the kernel's page block needs two full minor dims: a flat pool gets a
    # unit one (a pool of 3+ dims keeps its layout, no relayout copy)
    paged = pool if pool.ndim >= 3 else pool.reshape(pool.shape[0], 1, -1)
    return _k.block_copy(paged, src_dst).reshape(pool.shape)
