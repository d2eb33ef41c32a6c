"""Stage plumbing: reshape passes to the kernel layout and compose a full
4096-point radix-4 FFT (digit-reversed output, like the SIMT program)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fft_stage.kernel import fft_stage_kernel


def _stage_twiddles(n: int, p: int) -> tuple[np.ndarray, np.ndarray]:
    m = n // 4 ** p
    sub = m // 4
    q = np.arange(sub)
    i = np.arange(4)[:, None]
    tw = np.exp(-2j * np.pi * (q[None, :] * i) / m).astype(np.complex64)
    return (tw.real[None], tw.imag[None])  # (1, 4, sub)


@functools.partial(jax.jit, static_argnames=("n", "p"))
def fft_stage_radix4(xr: jnp.ndarray, xi: jnp.ndarray, n: int, p: int):
    """Apply DIF pass p of a radix-4 size-n FFT to (batch, n) planes."""
    batch = xr.shape[0]
    m = n // 4 ** p
    sub = m // 4
    twr, twi = _stage_twiddles(n, p)
    view = lambda t: t.reshape(batch * (n // m), 4, sub)
    rows = batch * (n // m)
    yr, yi = fft_stage_kernel(view(xr), view(xi),
                              jnp.asarray(twr), jnp.asarray(twi))
    return yr.reshape(batch, n), yi.reshape(batch, n)


def fft_trace(arch, x, **_):
    """Exact AddressTrace of the paper's radix-4 FFT benchmark on ``x``'s
    last axis (Table III): the two-word I/Q load, twiddle-load, and store
    streams of every DIF pass, per lane."""
    from repro.core.trace import AddressTrace
    from repro.isa.programs.fft import fft_program
    try:
        prog = fft_program(x.shape[-1], 4)
    except ValueError as e:
        raise NotImplementedError(str(e)) from None
    return AddressTrace.from_program(prog)


def fft_symbolic(arch, x, **_):
    """The Table III FFT traffic as closed-form lane families for the
    symbolic conflict prover (delegates to the SIMT program's own
    ``symbolic_trace``; radix 4 like the Pallas path)."""
    from repro.isa.programs.fft import symbolic_trace
    try:
        return symbolic_trace(x.shape[-1], 4)
    except ValueError as e:
        raise NotImplementedError(str(e)) from None


def fft_trace_blocks(arch, x, block_ops=None, **_):
    """Streaming counterpart of ``fft_trace``: the Table III program stream
    emitted block-by-block from the lazy pass-by-pass macro-op iterator
    (each DIF pass's address vectors live only while its blocks are drawn);
    costs bit-equal to the dense trace at any block size."""
    from repro.isa.programs.fft import iter_fft_instrs
    from repro.isa.vm import instr_trace_blocks
    n = x.shape[-1]
    try:
        instrs = iter_fft_instrs(n, 4)
    except ValueError as e:
        raise NotImplementedError(str(e)) from None
    yield from instr_trace_blocks(instrs, n_threads=n // 4,
                                  block_ops=block_ops)


def fft4096_radix4(x: jnp.ndarray, n: int = 4096) -> jnp.ndarray:
    """(batch, n) complex64 -> FFT in digit-reversed order (batch, n)."""
    xr = jnp.real(x).astype(jnp.float32)
    xi = jnp.imag(x).astype(jnp.float32)
    passes = int(round(np.log(n) / np.log(4)))
    assert 4 ** passes == n
    for p in range(passes):
        xr, xi = fft_stage_radix4(xr, xi, n, p)
    return (xr + 1j * xi).astype(jnp.complex64)
