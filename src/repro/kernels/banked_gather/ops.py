"""Public banked-gather op: logical-view wrapper over the bank-major kernel.

The logical↔physical row math lives in ``repro.core.arch.BankedLayout``
(single source of truth since the API redesign); the functions here are
thin legacy-compatible wrappers over it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.arch import BankedLayout
from repro.kernels.banked_gather.kernel import banked_gather_kernel


def physical_rows(v: int, n_banks: int, mapping: str) -> jnp.ndarray:
    """logical row -> physical (bank-major) row, vectorized.
    (offset map uses shift=1, matching the paper's layout calibration)"""
    return BankedLayout(n_banks, mapping).physical_rows(v)


def to_banked_layout(table: jnp.ndarray, n_banks: int,
                     mapping: str = "lsb") -> jnp.ndarray:
    """Host-side relayout: scatter logical rows into bank-major order."""
    return BankedLayout(n_banks, mapping).to_banked(table)


def from_banked_layout(table_banked: jnp.ndarray, n_banks: int,
                       mapping: str = "lsb") -> jnp.ndarray:
    return BankedLayout(n_banks, mapping).from_banked(table_banked)


def banked_gather_trace(arch, table, idx, mask=None, **_):
    """The gather's exact AddressTrace: lane j of op o requests logical row
    ``idx[16·o + j]``.  Rows are the banked unit (the bank map keys on the
    row index), so the row stream is the address stream — one gather call is
    one load instruction.  ``mask`` predicates lanes off (clamped-but-unused
    requests, e.g. unmapped paged-KV pages)."""
    from repro.kernels.registry import row_stream_trace
    return row_stream_trace(idx, kind="load", mask=mask)


def banked_gather_symbolic(arch, table, idx, mask=None, **_):
    """The gather's traffic for the symbolic conflict prover: an
    arithmetic-progression index stream proves in closed form (e.g. a
    unit-stride gather is conflict-free on any map), anything
    data-dependent is enumerated exactly (see
    ``repro.analysis.symbolic.affine_from_indices``)."""
    from repro.analysis.symbolic import SymbolicTrace, affine_from_indices
    fam = affine_from_indices(idx, "load", "gather rows", mask=mask)
    return SymbolicTrace(families=(fam,), meta={"kernel": "banked_gather"})


def banked_gather_trace_blocks(arch, table, idx, mask=None, block_ops=None,
                               **_):
    """Streaming counterpart of ``banked_gather_trace``: the same ONE load
    instruction, yielded as at-most-``block_ops``-op blocks (a million-index
    gather never shapes its full (ops × 16) matrix; costs bit-equal)."""
    from repro.kernels.registry import row_stream_blocks
    yield from row_stream_blocks(idx, kind="load", mask=mask,
                                 block_ops=block_ops)


@functools.partial(jax.jit,
                   static_argnames=("n_banks", "mapping", "shift"))
def banked_gather(table_banked: jnp.ndarray, idx: jnp.ndarray,
                  n_banks: int = 16, mapping: str = "lsb",
                  shift: int = 1) -> jnp.ndarray:
    """Gather logical rows `idx` from a bank-major table (see kernel.py)."""
    return banked_gather_kernel(table_banked, idx, n_banks, mapping,
                                shift=shift)
