"""MoE dispatch-position kernel — the carry-chain arbiter's grant order at
router scale (DESIGN.md §2.2).

Input is the *flat priority-ordered* request stream (all first choices in
token order, then second choices — the FPGA's lane order).  For each request
the kernel emits its position-in-expert (arbiter grant slot) and whether it
fits the capacity budget.

The global exclusive cumsum is sequentialized over the grid: TPU grid steps
execute in order, so a VMEM scratch row carries the running per-expert
counts between blocks (``dimension_semantics=("arbitrary",)`` pins the order).
Within a block the exclusive cumsum is one MXU product of a strictly
lower-triangular (BLK, BLK) mask with the (BLK, E) one-hot (Mosaic has no
cumsum lowering; 0/1 operands are exact in bf16 and the f32 accumulator
holds counts up to BLK exactly); across blocks only the (1, E) running
counts persist — the kernel is O(E) state for arbitrarily long request
streams, exactly like the hardware arbiter.

Grid: (R / R_BLOCK,); blocks:
  experts  (R_BLOCK, 1) int32   positions (R_BLOCK, 1) int32
  kept     (R_BLOCK, 1) int32   scratch: (8, E) int32 (row 0 live; 8 rows
                                pad the sublane tile)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import registry

R_BLOCK = 512


def _dispatch_kernel(n_experts: int, capacity: int, experts_ref, pos_ref,
                     kept_ref, counts_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    e = experts_ref[...]                                        # (BLK, 1)
    blk = e.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_experts), 1)
    onehot = (e == iota).astype(jnp.int32)                      # (BLK, E)
    before = (jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
              > jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1))
    excl = jnp.dot(before.astype(jnp.bfloat16),
                   onehot.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32
                   ).astype(jnp.int32)                          # within block
    running = counts_ref[0:1, :]                                # (1, E)
    pos = excl + running                                        # (BLK, E)
    my_pos = (pos * onehot).sum(axis=1, keepdims=True)          # (BLK, 1)
    pos_ref[...] = my_pos
    kept_ref[...] = (my_pos < capacity).astype(jnp.int32)
    counts_ref[0:1, :] = running + onehot.sum(axis=0, keepdims=True)


def moe_dispatch_kernel(experts: jax.Array, n_experts: int, capacity: int):
    r = experts.shape[0]
    blk = min(R_BLOCK, r)
    assert r % blk == 0
    kernel = functools.partial(_dispatch_kernel, n_experts, capacity)
    pos, kept = pl.pallas_call(
        kernel,
        grid=(r // blk,),
        in_specs=[pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                   pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, 1), jnp.int32),
                   jax.ShapeDtypeStruct((r, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((8, n_experts), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=registry.interpret_mode(),
    )(experts.astype(jnp.int32)[:, None])
    return pos[:, 0], kept[:, 0].astype(bool)
