from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.banked_transpose.kernel import banked_transpose_kernel


def _transpose_n(x) -> int:
    n, m = x.shape
    if n != m or n < 16 or n & (n - 1):
        raise NotImplementedError(
            f"transpose trace model needs square power-of-two N>=16, got "
            f"{(n, m)}")
    return n


def banked_transpose_trace(arch, x, **_):
    """Exact AddressTrace of the paper's N×N transpose benchmark (the Table
    II workload): the per-lane load/store address streams of the SIMT
    program, not a row-stream proxy.  Needs a square power-of-two N ≥ 16."""
    n = _transpose_n(x)
    from repro.core.trace import AddressTrace
    from repro.isa.programs.transpose import transpose_program
    return AddressTrace.from_program(transpose_program(n))


def banked_transpose_symbolic(arch, x, **_):
    """The Table II transpose traffic as closed-form lane families for the
    symbolic conflict prover (delegates to the SIMT program's own
    ``symbolic_trace`` — the proved ``TraceCost`` matches
    ``arch.cost(banked_transpose_trace(...))`` bit-exactly)."""
    from repro.isa.programs.transpose import symbolic_trace
    return symbolic_trace(_transpose_n(x))


def banked_transpose_trace_blocks(arch, x, block_ops=None, **_):
    """Streaming counterpart of ``banked_transpose_trace``: the Table II
    program stream emitted block-by-block from the lazy macro-op iterator —
    each program block's address vectors exist only while its blocks are
    drawn, so a million-op transpose trace is constructed in O(block)
    memory and costs bit-equal to the dense path."""
    n = _transpose_n(x)
    from repro.isa.programs.transpose import (iter_transpose_instrs,
                                              transpose_n_threads)
    from repro.isa.vm import instr_trace_blocks
    yield from instr_trace_blocks(iter_transpose_instrs(n),
                                  n_threads=transpose_n_threads(n),
                                  block_ops=block_ops)


@functools.partial(jax.jit, static_argnames=("tile",))
def banked_transpose(x: jnp.ndarray, tile: int = 128) -> jnp.ndarray:
    """(N, M) -> (M, N) via VMEM-tiled transpose."""
    return banked_transpose_kernel(x, tile=tile)
