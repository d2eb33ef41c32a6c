"""Pipeline parallelism: GPipe-style microbatch ring over one mesh axis
(production target: the ``pod`` axis — inter-pod links are the slowest, and
a pipeline crosses them once per microbatch instead of per-layer collective).

``pipelined(stage_fn, mesh, axis_name)`` returns a shard_map'd function
``f(stage_params, microbatches) -> outputs`` where

  * stage_params has a leading stage axis sharded over ``axis_name``
    (stage s's parameter slice lives on the devices of stage s),
  * microbatches is (n_micro, micro_batch, ...) and flows through the ring
    with ``lax.ppermute``; the schedule runs ``n_micro + n_stages − 1``
    ticks (the GPipe bubble: (S−1)/(M+S−1) idle fraction — pick M ≫ S).

The loop body is a ``lax.scan``, so the compiled HLO is one tick body plus a
collective-permute — exactly the "collective-permute ring" the §Roofline
collective-term hints refer to.  Correctness is asserted against the serial
stack in tests/test_pipeline.py on a 4-device host-platform mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs, check_rep):
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=check_rep)


def pipelined(stage_fn, mesh, axis_name: str = "stage"):
    """Build the pipelined apply function.

    stage_fn(stage_params, x) -> y — one stage's compute (same signature on
    every stage; heterogeneous stages go behind lax.switch inside stage_fn).
    """
    def inner(stage_params, xs):
        # stage_params arrives with the sharded stage axis as a leading dim
        # of local size 1 — squeeze it.
        params = jax.tree.map(lambda p: p[0], stage_params)
        n_stages = lax.psum(1, axis_name)
        stage = lax.axis_index(axis_name)
        n_micro = xs.shape[0]
        total = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, ys = carry
            feed = xs[jnp.minimum(t, n_micro - 1)]
            inp = jnp.where(stage == 0, feed, buf)
            out = stage_fn(params, inp)
            t_out = t - (n_stages - 1)
            emit = (stage == n_stages - 1) & (t_out >= 0)
            idx = jnp.maximum(t_out, 0)
            cur = lax.dynamic_index_in_dim(ys, idx, 0, keepdims=False)
            ys = lax.dynamic_update_index_in_dim(
                ys, jnp.where(emit, out, cur), idx, 0)
            buf = lax.ppermute(out, axis_name, perm)
            return (buf, ys), None

        buf0 = jnp.zeros_like(xs[0])
        ys0 = jnp.zeros_like(xs)
        (_, ys), _ = lax.scan(tick, (buf0, ys0), jnp.arange(total))
        # broadcast the last stage's outputs so the result is replicated
        ys = lax.psum(ys * (stage == n_stages - 1).astype(ys.dtype),
                      axis_name)
        return ys

    return shard_map(inner, mesh,
                     in_specs=(P(axis_name), P()),
                     out_specs=P(), check_rep=False)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe idle fraction = (S-1)/(M+S-1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


def stack_stage_params(per_stage_params: list):
    """[stage0_tree, stage1_tree, ...] -> stacked tree with leading stage
    axis (shard it over the pipeline mesh axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)
