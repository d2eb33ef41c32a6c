"""Banked row-scatter kernel — the WRITE side of the paper's banked memory
(Table II's 6 %-efficient transposed stores are the problem this layout
solves on TPU).

Rows are written into the bank-major table through the same scalar-prefetched
index map as banked_gather: grid step i DMAs row-tile i of the update into
physical row ``bank(idx[i])·rows_per_bank + slot(idx[i])``.  Because the
output BlockSpec's index_map performs the scatter, each HBM write is a dense
row-tile — the "column write" of the FPGA benchmark never appears as a
strided store.  Duplicate indices resolve last-writer-wins in grid order
(the arbiter's grant order, matching ``jnp.ndarray.at[].set`` semantics of
the reference for unique indices; duplicate handling is asserted explicitly
in the tests).

Tables are 2-D ``(V, D)`` or 3-D ``(V, R, D)`` and are viewed as
``(V, R, D)`` for Mosaic's block tiling, exactly as in banked_gather (see
its module docstring).

Grid: (n_updates, D / D_TILE); block = (None, R, D_TILE).

Caveat (documented): Pallas requires every output block to be written each
grid step; rows NOT touched by any index keep their prior contents because
the kernel is applied with input_output_aliasing (the table is donated).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.arch import physical_row_of
from repro.kernels import registry
from repro.kernels.banked_gather.kernel import _row_tile, as_rows, row_block


def _scatter_kernel(idx_ref, updates_ref, table_ref, out_ref):
    del idx_ref, table_ref
    out_ref[...] = updates_ref[...]


def banked_scatter_kernel(table_banked: jax.Array, idx: jax.Array,
                          updates: jax.Array, n_banks: int,
                          mapping: str = "lsb", shift: int = 1) -> jax.Array:
    """Write updates[i] to logical row idx[i] of a bank-major table
    (updates: (N,) + the table's row shape)."""
    rows = as_rows(table_banked)
    v, r, d = rows.shape
    n = idx.shape[0]
    assert updates.shape == (n,) + table_banked.shape[1:]
    assert v % n_banks == 0, (v, n_banks)
    d_tile = _row_tile(d)
    rows_per_bank = v // n_banks

    def upd_map(i, j, idx_ref):
        return (i, 0, j)

    def out_map(i, j, idx_ref):
        phys = physical_row_of(idx_ref[i], n_banks, rows_per_bank, mapping,
                               shift)
        return (phys, 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, d // d_tile),
        in_specs=[pl.BlockSpec(row_block(rows), upd_map),
                  pl.BlockSpec(row_block(rows), out_map)],
        out_specs=pl.BlockSpec(row_block(rows), out_map),
    )
    fn = pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((v, r, d), table_banked.dtype),
        input_output_aliases={2: 0},   # donate the table (arg 1 after idx)
        interpret=registry.interpret_mode(),
    )
    out = fn(idx.astype(jnp.int32), as_rows(updates), rows)
    return out.reshape(table_banked.shape)
