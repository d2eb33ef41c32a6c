"""Conflict-popcount kernel — the issue controllers' math (paper §III.A):
one-hot the 16 lane bank-ids per operation, popcount the columns, take the
max (= cycles the operation needs).  Batched over operations.

Grid: (n_ops / OP_BLOCK,); blocks:
  banks (OP_BLOCK, LANES)  int32 in VMEM
  counts (OP_BLOCK, B)     int32
  cycles (OP_BLOCK, 1)     int32
OP_BLOCK = 256 rows (multiple of 8 sublanes; LANES=16 and B≤32 keep the
lane dimension inside one VREG tile).  The one-hot compare runs on the VPU
as a (OP_BLOCK, LANES, B) broadcasted equality — 16·B bytes/op of VMEM
traffic, trivially memory-bound, hence the large OP_BLOCK.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry

OP_BLOCK = 256
LANES = 16


def _popcount_kernel(n_banks: int, banks_ref, counts_ref, cycles_ref):
    banks = banks_ref[...]                                  # (BLK, LANES)
    iota = jax.lax.broadcasted_iota(jnp.int32,
                                    (1, 1, n_banks), 2)     # (1,1,B)
    onehot = (banks[:, :, None] == iota).astype(jnp.int32)  # (BLK,LANES,B)
    counts = onehot.sum(axis=1)                             # (BLK, B)
    counts_ref[...] = counts
    cycles_ref[...] = counts.max(axis=1, keepdims=True)     # (BLK, 1)


def conflict_popcount_kernel(banks: jax.Array, n_banks: int):
    n_ops, lanes = banks.shape
    assert lanes == LANES and n_ops % OP_BLOCK == 0 or n_ops < OP_BLOCK
    blk = min(OP_BLOCK, n_ops)
    assert n_ops % blk == 0
    kernel = functools.partial(_popcount_kernel, n_banks)
    counts, cycles = pl.pallas_call(
        kernel,
        grid=(n_ops // blk,),
        in_specs=[pl.BlockSpec((blk, LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((blk, n_banks), lambda i: (i, 0)),
                   pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_ops, n_banks), jnp.int32),
                   jax.ShapeDtypeStruct((n_ops, 1), jnp.int32)],
        interpret=registry.interpret_mode(),
    )(banks.astype(jnp.int32))
    return counts, cycles[:, 0]
