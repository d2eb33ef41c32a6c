"""Elastic scaling: rebuild the mesh from whatever devices are healthy and
reshard the latest checkpoint onto it.

The data pipeline is stateless in (step, shard), parameters are restored by
``jax.device_put`` against the *new* mesh's NamedShardings, and the batch
axis re-splits across the new data-parallel width — so a job that loses (or
gains) a pod resumes from the last checkpoint at a different world size with
no reconfiguration beyond ``make_current_mesh()``.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

from repro.checkpoint.checkpoint import latest_step, restore_checkpoint


def _largest_pow2_factor(n: int) -> int:
    return n & -n


def make_current_mesh(prefer_model: int = 0):
    """Build the best (data, model) mesh from currently-visible devices.

    model axis = prefer_model if it divides the device count, else the
    largest power-of-two ≤ sqrt(n).  Survives arbitrary healthy-device
    counts (stragglers/failed hosts simply drop out of jax.devices()).
    """
    n = len(jax.devices())
    if prefer_model and n % prefer_model == 0:
        model = prefer_model
    else:
        model = 1
        while model * 2 <= math.isqrt(n) and n % (model * 2) == 0:
            model *= 2
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def elastic_restore(ckpt_dir: str, template_state):
    """Restore the latest checkpoint onto (a possibly different) mesh.

    template_state: a pytree of arrays already initialized/placed on the NEW
    mesh (shapes+dtypes+shardings are taken from it).  Returns
    (state, step) or (template_state, None) when no checkpoint exists.
    """
    step = latest_step(ckpt_dir)
    if step is None:
        return template_state, None
    shardings = jax.tree.map(
        lambda x: getattr(x, "sharding", None), template_state)
    state = restore_checkpoint(ckpt_dir, step, template_state, shardings)
    return state, step
