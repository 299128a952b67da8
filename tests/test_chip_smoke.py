"""The chip entry points' CPU-side contracts.

`chip_smoke.py` must refuse to report success without a TPU, and refuse
when it is run away from the repo; the compile cache goes where
JAX_COMPILATION_CACHE_DIR says, else to the fixed `<repo>/.jax_cache`;
importing the simulator initialises no backend (so an entry point can
still choose one); the lowered tenant trace the smoke checks on the chip
carries the checksum recorded here; and the decode byte model refuses a
device it has no bandwidth for.  Child processes run with
JAX_PLATFORMS=cpu, like every subprocess test in this suite.
"""
import os
import shutil
import subprocess
import sys
import textwrap
import zlib

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd=ROOT, **env):
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src") + os.pathsep
           + ROOT, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    r = _run([SMOKE])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_chip_smoke_fails_alone(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    r = _run([str(alone)], cwd=tmp_path, PYTHONPATH="")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


_CACHE_PROBE = textwrap.dedent("""
    from benchmarks.run import use_compile_cache
    path = use_compile_cache()
    import jax, jax.numpy as jnp
    print(path)
    print(jax.config.jax_compilation_cache_dir)
    if {compile}:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
""")


def test_compile_cache_follows_the_environment(tmp_path):
    cache = tmp_path / "cache"
    r = _run(["-c", _CACHE_PROBE.format(compile=True)],
             JAX_COMPILATION_CACHE_DIR=str(cache),
             JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir()), "nothing was cached"


def test_compile_cache_defaults_to_the_repo():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(compile=False)],
        cwd=ROOT, env=dict(env, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    want = os.path.join(ROOT, ".jax_cache")
    assert r.stdout.split() == [want, want]


def test_importing_the_simulator_initialises_no_backend():
    r = _run(["-c", "from repro.core import simulator\n"
                    "from jax._src import xla_bridge\n"
                    "print(xla_bridge.backends_are_initialized())"])
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "False"


def test_lowered_trace_checksum_matches_chip_smoke():
    sys.path.insert(0, ROOT)
    import chip_smoke

    from repro import workloads
    name, length = chip_smoke.LOWERED_TRACE
    trace = np.ascontiguousarray(workloads.build_trace(name, length),
                                 np.int32)
    assert zlib.crc32(trace.tobytes()) == chip_smoke.LOWERED_TRACE_CRC


def test_decode_bandwidth_table_is_keyed_by_device_kind():
    from benchmarks import perf_slot_decode as psd
    assert psd.hbm_bandwidth("TPU v5 lite") == 819e9
    with pytest.raises(ValueError, match="cpu"):
        psd.hbm_bandwidth("cpu")


@pytest.mark.parametrize("mode", ["interpret", "jnp"])
def test_sweep_phase_on_a_tiny_grid(monkeypatch, mode):
    """The smoke's sweep phase on a tiny grid.  With the window kernel (in
    interpret mode) it equals jnp and the scan; when the sweep never
    reaches the kernel, the phase fails instead of comparing jnp with
    itself."""
    sys.path.insert(0, ROOT)
    import chip_smoke
    from benchmarks import fig7_multi
    from repro.core import scheduler
    from repro.kernels import window_distance

    pairs, fleets = scheduler.make_pairs()[:2], scheduler.make_fleets(4)[:1]
    monkeypatch.setattr(scheduler, "make_pairs", lambda: pairs)
    monkeypatch.setattr(scheduler, "make_fleets", lambda k: fleets)
    monkeypatch.setattr(fig7_multi, "TRACE_LEN", 1_000)
    monkeypatch.setattr(fig7_multi, "TOTAL_STEPS", 24_000)
    monkeypatch.setattr(fig7_multi, "FLEET_TOTAL_STEPS", 24_000)
    monkeypatch.setattr(window_distance, "DEFAULT_MODE", mode)
    clock = chip_smoke.Clock()
    if mode == "jnp":
        with pytest.raises(RuntimeError, match="never reached the window"):
            chip_smoke.phase_sweep(clock)
        return
    lines = chip_smoke.phase_sweep(clock)
    assert len(clock.steps) == 5
    assert "kernel == jnp, first 2 pairs == scan" in lines[0]
    assert "kernel == jnp" in lines[1]
