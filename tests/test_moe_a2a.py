"""shard_map all-to-all MoE == GShard einsum MoE on a real (2, 2) mesh
(4 host-platform devices, subprocess)."""
import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.launch.sharding import make_axes
from repro.models import init_tree
from repro.models.moe import moe_gshard, moe_specs
from repro.models.moe_a2a import a2a_applicable, moe_a2a

# n_experts=%(experts)d on a 2-way model axis: tests both the EP path
# (E >= tp) and the capacity-split virtual-expert path (E < tp)
cfg = dataclasses.replace(get_smoke_config("phi3.5-moe-42b-a6.6b"),
                          n_experts=%(experts)d, experts_per_token=%(k)d,
                          capacity_factor=16.0)  # no drops
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
ax = make_axes(mesh, None)
params = init_tree(moe_specs(cfg), jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                      jnp.float32)
assert a2a_applicable(cfg, ax, 16)

with mesh:
    y_ref, aux_ref = jax.jit(lambda p, x: moe_gshard(cfg, p, x, ax))(params, x)
    y_a2a, aux_a2a = jax.jit(lambda p, x: moe_a2a(cfg, p, x, ax))(params, x)

err = float(jnp.max(jnp.abs(y_ref - y_a2a)))
aux_err = abs(float(aux_ref) - float(aux_a2a))
print("RESULT " + json.dumps({"err": err, "aux_err": aux_err,
                              "norm": float(jnp.max(jnp.abs(y_ref)))}))
"""


def _run(experts: int, k: int = 2):
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"experts": experts, "k": k}],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    r = json.loads(line[len("RESULT "):])
    assert r["err"] < 1e-4 * max(r["norm"], 1.0), (experts, r)
    assert r["aux_err"] < 1e-4, (experts, r)


def test_a2a_equals_gshard_ep_path():
    _run(experts=4)          # E (4) >= tp (2): one-plus experts per device


def test_a2a_equals_gshard_virtual_expert_path():
    _run(experts=1, k=1)     # E (1) < tp (2): capacity-split co-ownership
