"""Carry-chain arbiter kernel (paper §III.C, Fig 5): per bank, pick one
requesting lane per cycle via the subtract-one transition trick

    grant_c = v & -v ;  v <- v & (v - 1)

vectorized over (ops × banks) request words.  The FPGA evaluates one grant
per clock on a carry chain; the TPU evaluates all MAX_CYCLES grants of a
whole operation block per VPU pass — same math, bit-exact, which is what the
allclose sweep against the lax.scan reference asserts.

Grid: (n_ops / OP_BLOCK,); blocks:
  requests (OP_BLOCK, B)              uint32
  grants   (OP_BLOCK, MAX_CYCLES, B)  uint32  (one-hot lane word per cycle)
The cycle loop is a static Python unroll (16 iterations) — on TPU this keeps
everything in VREGs with zero VMEM round-trips between iterations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry

OP_BLOCK = 128
MAX_CYCLES = 16


def _arbiter_kernel(req_ref, grants_ref):
    v = req_ref[...]                                   # (BLK, B) uint32
    for c in range(MAX_CYCLES):
        w = v - jnp.uint32(1)
        grant = v & ~w                                 # lowest set bit
        v = v & w                                      # clear it
        grants_ref[:, c, :] = grant
    # all requests must drain within MAX_CYCLES (≤ lanes); v == 0 here.


def carry_arbiter_kernel(requests: jax.Array):
    n_ops, n_banks = requests.shape
    blk = min(OP_BLOCK, n_ops)
    assert n_ops % blk == 0
    return pl.pallas_call(
        _arbiter_kernel,
        grid=(n_ops // blk,),
        in_specs=[pl.BlockSpec((blk, n_banks), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((blk, MAX_CYCLES, n_banks),
                               lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_ops, MAX_CYCLES, n_banks),
                                       jnp.uint32),
        interpret=registry.interpret_mode(),
    )(requests.astype(jnp.uint32))
