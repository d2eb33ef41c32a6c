"""End-to-end dry-run machinery test: run lower_cell in a subprocess with 8
host-platform placeholder devices on a (2, 4) mesh — the same code path the
512-chip production dry-run uses (lower → compile → memory/cost analysis →
collective parse → scan-adjusted accounting)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.configs import SHAPES, get_smoke_config
from repro.launch.dryrun import lower_cell

from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
import dataclasses
shape = dataclasses.replace(SHAPES["%(shape)s"], global_batch=8, seq_len=64)
res = lower_cell("%(arch)s", shape, multi_pod=False, verbose=False,
                 mesh=mesh, cfg=get_smoke_config("%(arch)s"))
print("RESULT " + json.dumps({
    "flops": res["full"]["flops"],
    "block_flops": res["block"]["flops"],
    "coll": res["full"]["collectives"]["total"],
    "args": res["full"]["memory"]["argument_bytes"],
    "n_sb": res["n_superblocks"],
}))
"""


def _run(arch: str, shape: str) -> dict:
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"arch": arch, "shape": shape}],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("arch,shape", [
    ("llama3.2-1b", "train_4k"),
    ("jamba-v0.1-52b", "decode_32k"),      # hybrid cache decode
    ("phi3.5-moe-42b-a6.6b", "prefill_32k"),
])
def test_lower_cell_on_8_devices(arch, shape):
    r = _run(arch, shape)
    assert r["flops"] > 0 and r["block_flops"] > 0
    assert r["args"] > 0
    assert r["n_sb"] >= 1
