"""Shared set-up of the benchmark's CPU tests: the import path, and a
checkout in a temporary directory that holds a copy of the benchmark plus
the tiny cells of ``tiny_cells``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(Path(__file__).resolve().parent), str(BENCH),
                str(BENCH.parent / "src")]

import pytest  # noqa: E402
from tiny_cells import make_checkout  # noqa: E402


@pytest.fixture(scope="session")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("checkout"))
