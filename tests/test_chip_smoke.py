"""chip_smoke.py on the CPU: the script refuses to run, and its phases pass
at smoke size (so the one-chip proof keeps working as the code moves)."""
import importlib.util
import pathlib

import pytest

from repro.configs import get_smoke_config


def _load_chip_smoke():
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


cs = _load_chip_smoke()


def test_refuses_a_platform_other_than_tpu(capsys):
    with pytest.raises(SystemExit, match="JAX found platform 'cpu'"):
        cs.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_costing_phase_at_smoke_size():
    cs.costing_phase(model=get_smoke_config("mixtral-8x22b"), sizes=(32,))


def test_serving_phase_at_smoke_size():
    dense, paged = cs.build_engines(get_smoke_config("minicpm-2b"), seed=0,
                                    batch=4, max_seq=64)
    cs.decode_step_compiled(paged)
    cs.serving_phase(dense, paged, seed=0, prompt_len=12, new_tokens=6,
                     n_requests=6)
