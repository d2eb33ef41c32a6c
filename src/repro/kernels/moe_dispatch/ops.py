from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_dispatch.kernel import moe_dispatch_kernel


def moe_dispatch_trace(arch, experts, n_experts, capacity, **_):
    """The dispatch's AddressTrace: the priority-ordered expert-id stream as
    one store instruction (experts play the role of banks — the arbiter's
    write-side occupancy at MoE scale)."""
    from repro.kernels.registry import row_stream_trace
    return row_stream_trace(experts, kind="store")


def moe_dispatch_symbolic(arch, experts, n_experts, capacity, **_):
    """The dispatch's traffic for the symbolic conflict prover: the
    expert-id store stream (data-dependent in any real routing — exact
    enumeration — but closed-form for synthetic striped assignments)."""
    from repro.analysis.symbolic import SymbolicTrace, affine_from_indices
    fam = affine_from_indices(experts, "store", "expert dispatch")
    return SymbolicTrace(families=(fam,), meta={"kernel": "moe_dispatch"})


def moe_dispatch_trace_blocks(arch, experts, n_experts, capacity,
                              block_ops=None, **_):
    """Streaming counterpart of ``moe_dispatch_trace``: the expert-id
    stream as at-most-``block_ops``-op blocks of the same one store
    instruction (bit-equal costing, O(block) construction)."""
    from repro.kernels.registry import row_stream_blocks
    yield from row_stream_blocks(experts, kind="store", block_ops=block_ops)


@functools.partial(jax.jit,
                   static_argnames=("n_experts", "capacity"))
def moe_dispatch_positions(experts: jnp.ndarray, n_experts: int,
                           capacity: int):
    """(R,) flat priority-ordered expert ids -> ((R,) position-in-expert,
    (R,) kept mask) under the capacity budget."""
    return moe_dispatch_kernel(experts, n_experts, capacity)
