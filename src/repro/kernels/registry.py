"""Kernel registry — layer 2 of the three-layer public API (see README.md).

A ``Kernel`` bundles the three entry points every kernel package already
ships, behind one common signature whose first argument is a
``MemoryArchitecture`` (or a name resolvable by ``repro.core.arch.get``):

  * ``pallas(arch, *args)`` — the TPU Pallas path, operating on *logical*
    inputs (bank-major relayout, where needed, is derived from
    ``arch.layout`` internally);
  * ``ref(arch, *args)``    — the pure-jnp oracle;
  * ``trace(arch, *args)``  — the kernel's exact ``AddressTrace``
    (repro.core.trace): the request stream this call puts on the shared
    memory.  ``arch.cost(trace)`` is the timing model; ``cost_cycles`` is
    the one-call convenience over both.  Optional — raises
    NotImplementedError when a kernel has no meaningful address stream.
  * ``blocks(arch, *args, block_ops=…)`` — the same request stream emitted
    block-by-block (``TraceStream`` source blocks), so the trace is
    *constructed* in O(block) memory, not just costed that way.  Optional —
    ``trace_blocks`` falls back to chunking the dense ``trace``.

Usage::

    from repro import kernels
    k = kernels.get("banked_gather")
    out = k.run(arch.get("16B-offset"), table, idx)
    t = k.address_trace("16B-offset", table, idx)     # first-class artifact
    cyc = arch.get("4B").cost(t).total_cycles         # cost anywhere
    s = k.trace_blocks("16B", table, idx, block_ops=4096)   # lazy Trace
    cyc = arch.get("4B").cost(s).total_cycles         # bit-equal, O(block)

New kernels are one decorator away::

    @register_kernel("my_kernel", ref=my_ref, trace=my_trace,
                     blocks=my_trace_blocks)
    def my_pallas(arch, x):
        ...
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable

import jax

from repro.core import arch as _arch


def interpret_mode() -> bool:
    """Whether the Pallas kernels run in the interpreter: never on a TPU,
    where the compiled kernel is what serving and the benchmarks run, and
    always elsewhere (the CPU, where the tests run).  Every ``pallas_call``
    in ``repro.kernels`` asks this one function; there is no option."""
    return jax.default_backend() != "tpu"


@dataclass(frozen=True)
class Kernel:
    """One registered kernel: uniform (arch, *args) entry points."""
    name: str
    pallas: Callable
    ref: Callable
    trace: Callable | None = None    # (arch, *args) -> AddressTrace
    #: (arch, *args, block_ops=…) -> iterator of TraceStream source blocks
    blocks: Callable | None = None
    cost: Callable | None = None     # legacy opaque override; prefer trace
    #: (arch, *args) -> repro.analysis.symbolic.SymbolicTrace — the kernel's
    #: address stream as closed-form lane families for the conflict prover
    symbolic: Callable | None = None
    description: str = ""

    def run(self, arch, *args, **kwargs):
        """Dispatch the Pallas path under an architecture (or its name)."""
        return self.pallas(_arch.resolve(arch), *args, **kwargs)

    def reference(self, arch, *args, **kwargs):
        """Run the pure-jnp oracle (same signature as ``run``)."""
        return self.ref(_arch.resolve(arch), *args, **kwargs)

    def address_trace(self, arch, *args, **kwargs):
        """The exact AddressTrace this call issues (see repro.core.trace)."""
        if self.trace is None:
            raise NotImplementedError(
                f"kernel {self.name!r} has no address-trace generator")
        return self.trace(_arch.resolve(arch), *args, **kwargs)

    def trace_blocks(self, arch, *args, block_ops: int | None = None,
                     **kwargs):
        """The same request stream as ``address_trace``, but as a lazy,
        re-iterable ``repro.core.trace.Trace`` of at-most-``block_ops``-op
        blocks — bit-equal to the dense trace under ``arch.cost`` /
        ``cost_many`` at any block size (the streaming-pipeline invariant,
        pinned in tests/test_cost_engine.py).

        Kernels registered with a native ``blocks`` generator construct the
        stream in O(block) memory; the rest fall back to a dense-chunking
        shim (build ``trace`` once, chunk it lazily)."""
        from repro.core.trace import TraceStream
        a = _arch.resolve(arch)
        meta = {"kernel": self.name, "block_ops": block_ops}
        if self.blocks is not None:
            return TraceStream(
                functools.partial(self.blocks, a, *args,
                                  block_ops=block_ops, **kwargs),
                meta={**meta, "streamed": True})
        t = self.address_trace(a, *args, **kwargs)   # dense-chunking shim
        return TraceStream(functools.partial(t.blocks, block_ops), meta=meta)

    def symbolic_trace(self, arch, *args, **kwargs):
        """The kernel's address stream as a ``SymbolicTrace`` (closed-form
        lane families; see repro.analysis.symbolic) — the input of the
        conflict prover.  ``analysis.symbolic.prove(arch, ...)`` derives
        per-instruction max-conflict bounds and a full ``TraceCost`` from
        it analytically, bit-exactly cross-checkable against
        ``arch.cost(self.address_trace(...))``."""
        if self.symbolic is None:
            raise NotImplementedError(
                f"kernel {self.name!r} has no symbolic trace description")
        return self.symbolic(_arch.resolve(arch), *args, **kwargs)

    def cost_cycles(self, arch, *args, **kwargs):
        """Cycles this operation costs under ``arch``'s timing model
        (= ``arch.cost(self.trace(arch, *args)).total_cycles``)."""
        a = _arch.resolve(arch)
        if self.trace is not None:
            return a.cost(self.trace(a, *args, **kwargs)).total_cycles
        if self.cost is not None:       # pre-redesign opaque cost callable
            return self.cost(a, *args, **kwargs)
        raise NotImplementedError(
            f"kernel {self.name!r} has no cost model")


_KERNELS: dict[str, Kernel] = {}

#: Kernel packages that self-register on import (the paper's seven).
_BUILTIN_PACKAGES = (
    "banked_gather", "banked_scatter", "banked_transpose", "carry_arbiter",
    "conflict_popcount", "fft_stage", "moe_dispatch",
)

#: Modules outside ``repro.kernels`` that also self-register kernels on
#: import (whole-model traffic lowerings: attn_decode / moe_a2a / ssm_scan).
#: Listed here so ``get``/``names`` — and the REPRO003 contract lint that
#: iterates ``names()`` — see them without a manual import.
_BUILTIN_MODULES = (
    "repro.models.trace",
)


def register(kernel: Kernel) -> Kernel:
    """Register a fully-built Kernel; returns it (usable as a decorator on
    module-level Kernel instances)."""
    _KERNELS[kernel.name] = kernel
    return kernel


def register_kernel(name: str, *, ref: Callable,
                    trace: Callable | None = None,
                    blocks: Callable | None = None,
                    cost: Callable | None = None,
                    symbolic: Callable | None = None,
                    description: str = "") -> Callable:
    """Decorator form: registers the decorated function as the Pallas entry
    point of a new Kernel and returns the Kernel."""
    def deco(pallas: Callable) -> Kernel:
        return register(Kernel(name=name, pallas=pallas, ref=ref, trace=trace,
                               blocks=blocks, cost=cost, symbolic=symbolic,
                               description=description))
    return deco


def _ensure_builtins() -> None:
    import importlib
    for pkg in _BUILTIN_PACKAGES:
        importlib.import_module(f"repro.kernels.{pkg}")
    for mod in _BUILTIN_MODULES:
        importlib.import_module(mod)


def get(name: str) -> Kernel:
    """Resolve a kernel by name (imports the builtin packages on demand)."""
    if name not in _KERNELS:
        _ensure_builtins()
    try:
        return _KERNELS[name]
    except KeyError:
        raise KeyError(f"unknown kernel {name!r}; registered: "
                       f"{sorted(_KERNELS)}") from None


def names() -> tuple[str, ...]:
    _ensure_builtins()
    return tuple(sorted(_KERNELS))


# --------------------------------------------------------------------------
# Shared trace helpers (kernels whose address trace is their index stream)
# --------------------------------------------------------------------------

def row_stream_trace(idx, kind: str = "load", mask=None):
    """A row-index request stream as a one-instruction AddressTrace: LANES
    indices per operation, interpreted as word addresses (rows are the
    banked unit, so the row stream IS the exact address stream).  ``mask``
    predicates lanes off (e.g. unmapped paged-KV pages issue no request)."""
    import numpy as np

    from repro.core.trace import AddressTrace
    return AddressTrace.from_stream(np.asarray(idx), kind=kind, mask=mask)


def row_stream_blocks(idx, kind: str = "load", mask=None,
                      block_ops: int | None = None):
    """Streaming counterpart of ``row_stream_trace``: the same ONE
    instruction yielded as at-most-``block_ops``-op blocks (continuation
    chunks ``instr_carry``-marked — the instruction overhead is charged
    once, and costing is bit-equal to the dense trace)."""
    from repro.core.trace import iter_op_chunks
    return iter_op_chunks(idx, kind, mask=mask, block_ops=block_ops)


def row_stream_cost(arch, idx, is_write: bool) -> int:
    """Legacy shim: cost a row-index request stream under ``arch``
    (= ``arch.cost(row_stream_trace(idx, ...)).total_cycles``)."""
    kind = "store" if is_write else "load"
    return arch.cost(row_stream_trace(idx, kind)).total_cycles
