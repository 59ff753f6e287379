"""Pallas TPU kernels for PUD-style bulk row operations.

TPU-native adaptation of the paper's substrate ops:

* RowClone zero / copy       -> whole-tile VMEM stores / streams,
* Ambit AND / OR / NOT       -> VPU bitwise ops on (8,128)-aligned int32
                                tiles (packed bitplanes),
* RowClone in-place block copy over a pool ("rows" = pool blocks) driven by
  a scalar-prefetched (src, dst) index list — the beam-fork / prefix-share
  path of the PUMA KV pool; one page per grid step.

The elementwise kernels operate on buffers shaped (rows, 128): `rows` is a
multiple of 8 (sublane) and blocks of ``BLOCK_ROWS`` rows are staged through
VMEM.  MXU is not involved — these are bandwidth ops; the roofline target is HBM bw, so
the only tiling decision is a VMEM-resident block large enough to amortize
grid overhead (256 rows x 128 lanes x 4 B = 128 KB per operand).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256
LANES = 128


def _grid(rows: int, block_rows: int) -> int:
    assert rows % 8 == 0, f"rows={rows} must be 8-aligned (sublane)"
    return -(-rows // block_rows)


# -- elementwise family -------------------------------------------------------

def _zero_kernel(o_ref):
    o_ref[...] = jnp.zeros_like(o_ref)


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _not_kernel(x_ref, o_ref):
    o_ref[...] = ~x_ref[...]


def _and_kernel(x_ref, y_ref, o_ref):
    o_ref[...] = x_ref[...] & y_ref[...]


def _or_kernel(x_ref, y_ref, o_ref):
    o_ref[...] = x_ref[...] | y_ref[...]


def _xor_kernel(x_ref, y_ref, o_ref):
    # beyond-Ambit: XOR composes from AND/OR/NOT in 3 triple-activations;
    # on TPU it is a single VPU op, so expose it directly.
    o_ref[...] = x_ref[...] ^ y_ref[...]


def _maj_kernel(x_ref, y_ref, z_ref, o_ref):
    # Ambit's native primitive is MAJ(A,B,C) (triple-row activation).
    x, y, z = x_ref[...], y_ref[...], z_ref[...]
    o_ref[...] = (x & y) | (y & z) | (x & z)


_ELEMENTWISE = {
    "zero": (_zero_kernel, 0),
    "copy": (_copy_kernel, 1),
    "not": (_not_kernel, 1),
    "and": (_and_kernel, 2),
    "or": (_or_kernel, 2),
    "xor": (_xor_kernel, 2),
    "maj": (_maj_kernel, 3),
}


@functools.partial(jax.jit, static_argnames=("op", "block_rows", "interpret"))
def bulk_op(
    *operands: jax.Array,
    op: str,
    block_rows: int = BLOCK_ROWS,
    interpret: bool | None = None,
) -> jax.Array:
    """Apply a PUD bulk op over (rows, 128) int32 operands."""
    kernel, n_in = _ELEMENTWISE[op]
    if op == "zero":
        # zero takes a shape donor operand (like RowClone's reserved zero row)
        donor = operands[0]
        operands = ()
        rows = donor.shape[0]
        dtype = donor.dtype
    else:
        assert len(operands) == n_in, (op, len(operands))
        rows = operands[0].shape[0]
        dtype = operands[0].dtype
        for x in operands:
            assert x.shape == (rows, LANES), x.shape
    block_rows = min(block_rows, rows)
    grid = (_grid(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * len(operands),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), dtype),
        interpret=jax.default_backend() != "tpu" if interpret is None else interpret,
    )(*operands)


# -- pool block copy (RowClone over the PUMA pool) ----------------------------

def _block_copy_kernel(src_dst_ref, pool_ref, o_ref):
    del src_dst_ref  # consumed by the index maps
    o_ref[...] = pool_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_copy(
    pool: jax.Array,          # (num_blocks, *page_shape), ndim >= 3 — any dtype
    src_dst: jax.Array,       # (n_pairs, 2) int32
    interpret: bool | None = None,
) -> jax.Array:
    """In-place RowClone: pool[dst_i] <- pool[src_i] for each pair.

    The (src, dst) list is scalar-prefetched so the BlockSpec index maps can
    steer both the read and the aliased write; untouched blocks pass through
    via input/output aliasing — the whole pool never round-trips through the
    compute units, matching RowClone's in-DRAM semantics.  A grid step moves
    one whole page: the block spans the page's full trailing dims, which is
    what makes its tiling legal for any page shape.
    """
    page = pool.shape[1:]
    n_pairs = src_dst.shape[0]
    zeros = (0,) * len(page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pairs,),
        in_specs=[pl.BlockSpec((1, *page), lambda i, sd: (sd[i, 0], *zeros))],
        out_specs=pl.BlockSpec((1, *page), lambda i, sd: (sd[i, 1], *zeros)),
    )
    return pl.pallas_call(
        _block_copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={1: 0},  # pool aliases the output
        interpret=jax.default_backend() != "tpu" if interpret is None else interpret,
    )(src_dst, pool)
