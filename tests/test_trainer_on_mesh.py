"""Trainer on a real (2,2) device mesh (subprocess): the production pjit
path — sharded state, donated buffers, checkpoint + elastic restore onto a
DIFFERENT mesh shape (4,1)."""
import json
import os
import subprocess
import sys

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, numpy as np
from repro.configs import get_smoke_config
from repro.configs.base import RunConfig
from repro.data.pipeline import SyntheticLM
from repro.train import Trainer, TrainerConfig
from repro.runtime.elastic import elastic_restore
from repro.train.step import init_train_state

cfg = get_smoke_config("llama3.2-1b")
rc = RunConfig(remat="none", attn_impl="dense", learning_rate=3e-3,
               warmup_steps=2)
ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                 seed=1, branching=2)
ck = os.path.join("%(tmp)s", "ck")

from jax.sharding import AxisType
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
tc = TrainerConfig(total_steps=8, ckpt_dir=ck, ckpt_every=4, log_every=2)
out = Trainer(cfg, rc, tc, ds, mesh=mesh).run()
loss_mesh = out["final"]["loss"]

# elastic: restore the (2,2)-trained checkpoint onto a (4,1) mesh
mesh2 = jax.make_mesh((4, 1), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
template = init_train_state(cfg, rc, jax.random.PRNGKey(0))
state, step = elastic_restore(ck, template)
print("RESULT " + json.dumps({
    "loss": float(loss_mesh), "resumed_step": int(step),
    "hist_first": float(out["history"][0]["loss"]),
}))
"""


def test_trainer_mesh_and_elastic(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"tmp": str(tmp_path)}],
        capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    r = json.loads(line[len("RESULT "):])
    assert r["resumed_step"] == 8
    assert r["loss"] < r["hist_first"]     # trained on the mesh
