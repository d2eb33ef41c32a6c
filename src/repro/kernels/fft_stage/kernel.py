"""Radix-4 DIF FFT stage kernel — the paper's FFT workload, TPU-native.

One pass of the in-place Cooley-Tukey DIF recurrence over a batch of
transforms, on split re/im f32 planes (complex is not a VPU dtype).  The
caller reshapes the stage to (batch·blocks, 4, sub) so the butterfly is a
pure VPU elementwise pattern over the last axis; twiddles (4, sub) are
precomputed per pass and broadcast across rows from VMEM.

Grid: (rows / ROW_BLOCK,); blocks (per plane):
  x (ROW_BLOCK, 4, sub) f32 — ROW_BLOCK = 128 rows; sub is a power of 4 and
  the last axis is the 128-lane dimension (sub ≥ 128 keeps full lanes; the
  tail passes with sub < 128 trade lane occupancy for simplicity, noted in
  EXPERIMENTS §Perf).
VMEM per step = 2 planes × in+out × ROW_BLOCK·4·sub·4 B ≤ ~2 MB at sub=256.

The radix-4 DFT uses the ±1/±j pattern (adds + swaps only, no multiplies);
the three twiddle cmuls match the paper's per-butterfly FP-op template.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry

ROW_BLOCK = 128


def _stage_kernel(xr_ref, xi_ref, twr_ref, twi_ref, or_ref, oi_ref):
    xr, xi = xr_ref[...], xi_ref[...]            # (BLK, 4, sub)
    x0r, x1r, x2r, x3r = (xr[:, k] for k in range(4))
    x0i, x1i, x2i, x3i = (xi[:, k] for k in range(4))
    # radix-4 DFT: W4 = [[1,1,1,1],[1,-j,-1,j],[1,-1,1,-1],[1,j,-1,-j]]
    a_r, a_i = x0r + x2r, x0i + x2i              # x0 + x2
    b_r, b_i = x0r - x2r, x0i - x2i              # x0 - x2
    c_r, c_i = x1r + x3r, x1i + x3i              # x1 + x3
    d_r, d_i = x1r - x3r, x1i - x3i              # x1 - x3
    y0r, y0i = a_r + c_r, a_i + c_i
    y1r, y1i = b_r + d_i, b_i - d_r              # b - j·d
    y2r, y2i = a_r - c_r, a_i - c_i
    y3r, y3i = b_r - d_i, b_i + d_r              # b + j·d
    twr, twi = twr_ref[...], twi_ref[...]        # (1, 4, sub)
    ys_r = jnp.stack([y0r, y1r, y2r, y3r], axis=1)
    ys_i = jnp.stack([y0i, y1i, y2i, y3i], axis=1)
    or_ref[...] = ys_r * twr - ys_i * twi        # 3 twiddle cmuls (row 0 = 1)
    oi_ref[...] = ys_r * twi + ys_i * twr


def fft_stage_kernel(xr: jax.Array, xi: jax.Array, twr: jax.Array,
                     twi: jax.Array):
    rows, radix, sub = xr.shape
    assert radix == 4 and twr.shape == (1, 4, sub)
    blk = min(ROW_BLOCK, rows)
    assert rows % blk == 0
    return pl.pallas_call(
        _stage_kernel,
        grid=(rows // blk,),
        in_specs=[pl.BlockSpec((blk, 4, sub), lambda i: (i, 0, 0)),
                  pl.BlockSpec((blk, 4, sub), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, 4, sub), lambda i: (0, 0, 0)),
                  pl.BlockSpec((1, 4, sub), lambda i: (0, 0, 0))],
        out_specs=[pl.BlockSpec((blk, 4, sub), lambda i: (i, 0, 0)),
                   pl.BlockSpec((blk, 4, sub), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(xr.shape, jnp.float32),
                   jax.ShapeDtypeStruct(xi.shape, jnp.float32)],
        interpret=registry.interpret_mode(),
    )(xr, xi, twr, twi)
