"""Tiled matrix transpose — the paper's memory-intensive workload on TPU.

The FPGA lesson (Table II): row reads are conflict-free, column writes
serialize 16:1.  The TPU analogue: HBM reads/writes want 512 B-contiguous
lanes, so both sides of a transpose must touch *tiles*, never strided
columns.  The kernel streams (T×T) VMEM tiles — grid step (i, j) reads tile
(i, j), transposes in-register, writes tile (j, i); both HBM transfers are
dense.  T = 128 aligns the lane dimension on both sides (the "offset map"
of this kernel: a full-tile swizzle instead of a bit swizzle).

Grid: (N/T, M/T); VMEM/step = 2·T²·4 B = 128 KB at T=128, f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry

TILE = 128


def _transpose_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].T


def banked_transpose_kernel(x: jax.Array, tile: int = TILE):
    n, m = x.shape
    t = min(tile, n, m)
    assert n % t == 0 and m % t == 0, (n, m, t)
    return pl.pallas_call(
        _transpose_kernel,
        grid=(n // t, m // t),
        in_specs=[pl.BlockSpec((t, t), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((t, t), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=registry.interpret_mode(),
    )(x)
