"""Banked row-gather kernel — the paper's shared-memory banking as a TPU
gather (embedding rows / paged-KV pages).

The table is stored *bank-major* in HBM: logical row r lives at physical row
``bank(r) · rows_per_bank + slot(r)`` (bank = LSB/offset/xor map of r, slot =
remaining bits).  The request stream is scalar-prefetched (SMEM), and each
grid step DMAs one requested row-tile HBM→VMEM via the BlockSpec index_map —
the Pallas idiom where the *index map does the gather* (same structure as
paged-attention page lookup).  The bank swizzle lives entirely in the index
computation, mirroring the paper's "mapping is free in the FPGA, conflicts
cost cycles" observation: on TPU the map costs nothing and what it buys is
HBM-page/stride diversity for sequential request streams.

A table row is one block: a 2-D ``(V, D)`` table's row is a vector, a
3-D ``(V, R, D)`` table's row is an ``(R, D)`` slab (a paged-KV page:
``R`` = page_len tokens of ``D`` = kv_heads·head_dim words).  Mosaic tiles
the last two dimensions of a block by (8, 128), so a one-row block of a
2-D table is refused; the kernel views a 2-D table as ``(V, 1, D)``, and
for both shapes the row axis is squeezed out of the block (``None``) and
the last two block dimensions ``(R, D_TILE)`` equal / divide the
array's.  A 3-D table in its default TPU layout reaches the kernel without
a relayout copy (the serving pools are 3-D for this reason).

Grid: (n_requests, D / D_TILE); block = (None, R, D_TILE).
D_TILE = 512 lanes (a multiple of 128 for the VPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.arch import physical_row_of
from repro.kernels import registry

D_TILE = 512


def _copy_kernel(idx_ref, src_ref, out_ref):
    # The BlockSpec index_map already selected the (physical row, d-tile)
    # block; the body is a pure VMEM copy.
    del idx_ref
    out_ref[...] = src_ref[...]


def _row_tile(d: int) -> int:
    """Row-tile width: the standard 512-lane tile when the row divides
    evenly, otherwise one tile spanning the whole row (narrow rows — e.g.
    paged-KV page lines — are a single DMA)."""
    return D_TILE if d % D_TILE == 0 else d


def as_rows(table: jax.Array) -> jax.Array:
    """The kernels' ``(V, R, D)`` view of a 2-D or 3-D table."""
    return table[:, None, :] if table.ndim == 2 else table


def row_block(rows: jax.Array) -> tuple:
    """One row-tile block of a ``(V, R, D)`` view; the caller adds the
    index map."""
    _, r, d = rows.shape
    return (None, r, _row_tile(d))


def banked_gather_kernel(table_banked: jax.Array, idx: jax.Array,
                         n_banks: int, mapping: str = "lsb",
                         shift: int = 1) -> jax.Array:
    """table_banked: (V, D) or (V, R, D) already in bank-major physical
    layout; idx: (N,) int32 logical rows.  Returns the (N, D) or (N, R, D)
    gathered rows."""
    rows = as_rows(table_banked)
    v, r, d = rows.shape
    n = idx.shape[0]
    assert v % n_banks == 0, (v, n_banks)
    d_tile = _row_tile(d)
    rows_per_bank = v // n_banks

    def table_map(i, j, idx_ref):
        phys = physical_row_of(idx_ref[i], n_banks, rows_per_bank, mapping,
                               shift)
        return (phys, 0, j)

    def out_map(i, j, idx_ref):
        return (i, 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, d // d_tile),
        in_specs=[pl.BlockSpec(row_block(rows), table_map)],
        out_specs=pl.BlockSpec(row_block(rows), out_map),
    )
    fn = pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, r, d), table_banked.dtype),
        interpret=registry.interpret_mode(),
        name="banked_gather",
    )
    out = fn(idx.astype(jnp.int32), rows)
    return out.reshape((n,) + table_banked.shape[1:])
