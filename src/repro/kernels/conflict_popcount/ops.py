from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.conflict_popcount.kernel import conflict_popcount_kernel


def conflict_popcount_trace(arch, banks, n_banks=None, **_):
    """The (ops, 16) lane bank-id matrix as an AddressTrace: bank ids double
    as word addresses (id < n_banks, so the LSB map is the identity), making
    ``arch.cost`` reproduce the controller's own max-popcount cycles."""
    from repro.core.trace import AddressTrace
    return AddressTrace.from_ops(banks, kind="load")


def conflict_popcount_symbolic(arch, banks, n_banks=None, **_):
    """The controller's bank-id matrix for the symbolic conflict prover
    (bank ids double as word addresses, as in ``conflict_popcount_trace``):
    an exact ``DataFamily`` enumeration."""
    from repro.analysis.symbolic import DataFamily, SymbolicTrace
    from repro.core.trace import as_ops
    fam = DataFamily(name="lane bank ids", kind="load", addrs=as_ops(banks))
    return SymbolicTrace(families=(fam,),
                         meta={"kernel": "conflict_popcount"})


def conflict_popcount_trace_blocks(arch, banks, n_banks=None, block_ops=None,
                                   **_):
    """Streaming counterpart of ``conflict_popcount_trace``: the bank-id
    matrix chunked to at-most-``block_ops``-op blocks of the same one load
    instruction (bit-equal costing)."""
    from repro.core.trace import iter_op_chunks
    yield from iter_op_chunks(banks, kind="load", block_ops=block_ops)


@functools.partial(jax.jit, static_argnames=("n_banks",))
def conflict_popcount(banks: jnp.ndarray, n_banks: int = 16):
    """(ops, 16) lane bank ids -> ((ops, B) counts, (ops,) max cycles)."""
    return conflict_popcount_kernel(banks, n_banks)
