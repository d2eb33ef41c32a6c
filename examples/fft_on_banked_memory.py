"""End-to-end paper workload: 4096-point radix-4/8/16 FFTs executed on the
simulated SIMT processor under all nine memory architectures — regenerating
Table III — plus the TPU Pallas fft_stage kernel on the same input, verified
against numpy.

Run:  PYTHONPATH=src python examples/fft_on_banked_memory.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core.memsim import PAPER_MEMORIES
from repro.isa.programs.fft import (fft_program, make_fft_memory,
                                    oracle_spectrum)
from repro.isa.vm import run_program
from repro.kernels.fft_stage.ops import fft4096_radix4

n = 4096
rng = np.random.default_rng(0)
x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(np.complex64)

print(f"{'radix':>6} {'memory':>12} {'D load':>8} {'TW load':>8} "
      f"{'store':>8} {'total':>8} {'time us':>8}")
for radix in (4, 8, 16):
    prog = fft_program(n, radix)
    mem0, _ = make_fft_memory(n, x)
    res = run_program(prog, PAPER_MEMORIES[3], mem0)   # functional once
    got = res.memory[0:2 * n:2] + 1j * res.memory[1:2 * n:2]
    err = np.max(np.abs(got - oracle_spectrum(x, radix)))
    for spec in PAPER_MEMORIES:
        c = run_program(prog, spec, mem0, execute=False).cost
        print(f"{radix:>6} {spec.name:>12} {c.load_cycles:>8} "
              f"{c.tw_load_cycles:>8} {c.store_cycles:>8} "
              f"{c.total_cycles:>8} {c.time_us(spec.fmax_mhz):>8.2f}")
    print(f"   SIMT-VM functional max-abs error vs numpy: {err:.2e}")

print("\nTPU Pallas fft_stage kernel, same 4096-pt input:")
got = np.asarray(fft4096_radix4(jnp.asarray(x)[None]))[0]
want = oracle_spectrum(x, 4)
print(f"   kernel max-abs error vs numpy: {np.max(np.abs(got - want)):.2e}")
