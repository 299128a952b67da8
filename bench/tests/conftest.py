"""Shared fixtures of the benchmark's own tests: a copy of the benchmark
at sizes a CPU run holds, and a run driven past the look for a chip.

    python -m pytest bench/tests
"""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# every traffic shrunk to CPU size; widths and the mix of operations
# stay, lengths shrink
TINY_TRAFFIC = {"trace_len": 600, "total_steps": 1600}


def make_tiny(dst: str) -> str:
    """A checkout at `dst` holding BENCHMARK.json and bench/, shrunk."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns(".jax_cache", "tests",
                                                  "__pycache__"))
    for name in os.listdir(os.path.join(dst, "bench", "traffic")):
        path = os.path.join(dst, "bench", "traffic", name)
        with open(path) as f:
            t = json.load(f)
        t.update({k: v for k, v in TINY_TRAFFIC.items() if k in t})
        with open(path, "w") as f:
            json.dump(t, f)
    return dst


@pytest.fixture(scope="session")
def tiny(tmp_path_factory) -> str:
    return make_tiny(str(tmp_path_factory.mktemp("tiny")))


def cpu_chips(n):
    import jax
    return jax.devices()[:n] if n == 1 else jax.devices()


def run_cell(root: str, workload: str, seed: int, seconds: float = 1.0,
             trace: int = 0, capsys=None) -> dict:
    """One run of a cell on the CPU, the look for a chip skipped;
    returns its result line."""
    from bench import run

    rc = run.run(["--workload", workload, "--seed", str(seed),
                  "--seconds", str(seconds), "--trace", str(trace)],
                 chips=cpu_chips, root=root)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
