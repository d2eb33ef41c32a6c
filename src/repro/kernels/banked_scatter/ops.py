from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.banked_scatter.kernel import banked_scatter_kernel


def banked_scatter_trace(arch, table, idx, updates=None, mask=None, **_):
    """The scatter's exact AddressTrace: the row-index stream as one store
    instruction (the paper's 6 %-efficiency write side — all lanes of a
    column-major stream hit one bank).  ``mask`` predicates lanes off."""
    from repro.kernels.registry import row_stream_trace
    return row_stream_trace(idx, kind="store", mask=mask)


def banked_scatter_symbolic(arch, table, idx, updates=None, mask=None, **_):
    """The scatter's traffic for the symbolic conflict prover (one store
    family; closed-form when the index stream is an arithmetic
    progression, exact enumeration otherwise)."""
    from repro.analysis.symbolic import SymbolicTrace, affine_from_indices
    fam = affine_from_indices(idx, "store", "scatter rows", mask=mask)
    return SymbolicTrace(families=(fam,), meta={"kernel": "banked_scatter"})


def banked_scatter_trace_blocks(arch, table, idx, updates=None, mask=None,
                                block_ops=None, **_):
    """Streaming counterpart of ``banked_scatter_trace``: the same ONE store
    instruction as at-most-``block_ops``-op blocks (bit-equal costing)."""
    from repro.kernels.registry import row_stream_blocks
    yield from row_stream_blocks(idx, kind="store", mask=mask,
                                 block_ops=block_ops)


@functools.partial(jax.jit,
                   static_argnames=("n_banks", "mapping", "shift"))
def banked_scatter(table_banked: jnp.ndarray, idx: jnp.ndarray,
                   updates: jnp.ndarray, n_banks: int = 16,
                   mapping: str = "lsb", shift: int = 1) -> jnp.ndarray:
    """Scatter update rows into logical rows `idx` of a bank-major table
    (see kernel.py; pairs with banked_gather for the paged-KV write path)."""
    return banked_scatter_kernel(table_banked, idx, updates, n_banks,
                                 mapping, shift=shift)
