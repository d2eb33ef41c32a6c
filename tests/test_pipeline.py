"""Pipeline parallelism: ring schedule == serial stack (4-device subprocess,
host-platform mesh), bubble accounting."""
import json
import os
import subprocess
import sys

from repro.train.pipeline import bubble_fraction

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, json
from repro.train.pipeline import pipelined, stack_stage_params

from jax.sharding import AxisType
mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
D = 16
key = jax.random.PRNGKey(0)
stages = []
for s in range(4):
    k1, k2, key = jax.random.split(key, 3)
    stages.append({"w": jax.random.normal(k1, (D, D)) * 0.3,
                   "b": jax.random.normal(k2, (D,)) * 0.1})
params = stack_stage_params(stages)

def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])

xs = jax.random.normal(key, (8, 3, D))          # 8 microbatches

pipe = pipelined(stage_fn, mesh, "stage")
got = jax.jit(pipe)(params, xs)

want = xs
for s in range(4):
    want = jax.vmap(lambda x: stage_fn(stages[s], x))(want)

err = float(jnp.max(jnp.abs(got - want)))
print("RESULT " + json.dumps({"err": err}))
"""


def test_pipeline_matches_serial():
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    assert json.loads(line[len("RESULT "):])["err"] < 1e-5


def test_bubble_fraction():
    assert bubble_fraction(8, 4) == (4 - 1) / (8 + 4 - 1)
    assert bubble_fraction(1, 1) == 0.0
    assert bubble_fraction(32, 2) < 0.04
