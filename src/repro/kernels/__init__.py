"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel package ships three files:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (layout plumbing, shape checks)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

All kernels validate on CPU in interpret mode and run compiled on a TPU (the
mode comes from ``registry.interpret_mode``); BlockSpecs are chosen for the
TPU memory hierarchy (HBM→VMEM tiles, (8,128)/(128,128) MXU/VPU alignment —
see each kernel's docstring).

Kernels:
  banked_gather     — bank-major row gather (embedding / paged KV); the
                      paper's banking as a BlockSpec index-map swizzle
  banked_scatter    — the write side (the paper's 6 %-efficiency store
                      problem): index-map scatter into the bank-major table
  conflict_popcount — issue-controller conflict counting (one-hot popcount
                      + max) over operation batches
  carry_arbiter     — the carry-chain arbiter (v & -v / v & (v-1)) grant
                      schedule generator
  moe_dispatch      — sequential-grid running-count dispatch (position-in-
                      expert + capacity) — the arbiter math at MoE scale
  fft_stage         — radix-4 DIF butterfly stage (the paper's FFT workload)
  banked_transpose  — VMEM-tiled matrix transpose (the paper's other
                      workload)

All seven self-register with ``repro.kernels.registry`` on import;
``kernels.get("banked_gather").run(arch, table, idx)`` dispatches uniformly
(see registry.py for the Kernel protocol and the one-decorator registration
path for new kernels).
"""
from repro.kernels import registry
from repro.kernels.registry import Kernel, register, register_kernel

get = registry.get
names = registry.names

__all__ = ["registry", "Kernel", "register", "register_kernel", "get",
           "names"]
