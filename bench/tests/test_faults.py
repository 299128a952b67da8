"""A run with the timed path broken underneath comes out not correct:
one test for each fault a cell can have.  Each drives the whole run
(set-up, window, check) on the CPU at a tiny size, the look for a chip
skipped."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from unittest import mock

import jax.numpy as jnp
import pytest

from conftest import ROOT, run_cell


def _altered(fn, edit):
    def wrapped(*args, **kw):
        return edit(fn(*args, **kw))
    return wrapped


def _bump_last_cycle(res):
    c = jnp.asarray(res.cycles)
    flat = c.reshape(-1)
    return res._replace(cycles=flat.at[-1].add(1).reshape(c.shape))


def _drop_half(axis):
    """Half of the fleets left out: their cells read zero.  `axis` is
    the fleet axis of the entry's fields that carry one."""
    def edit(res):
        def half(x):
            x = jnp.asarray(x)
            if x.ndim <= axis:
                return x
            keep = jnp.arange(x.shape[axis]) < (x.shape[axis] + 1) // 2
            shape = [1] * x.ndim
            shape[axis] = x.shape[axis]
            return jnp.where(keep.reshape(shape), x, 0)
        return type(res)(*(half(x) for x in res))
    return edit


@pytest.mark.parametrize("workload", ["fig7.preempted", "bitstream.solo"])
def test_sound_run_is_correct(tiny, capsys, workload):
    assert run_cell(tiny, workload, 7, capsys=capsys)["correct"]


@pytest.mark.parametrize("workload,entry,edit", [
    ("fig7.preempted", "sweep_fleet", _bump_last_cycle),
    ("fig7.preempted", "sweep_fleet", _drop_half(1)),
    ("bitstream.solo", "sweep_bitstream", _bump_last_cycle),
    ("bitstream.solo", "sweep_bitstream", _drop_half(0)),
])
def test_sweep_faults_are_caught(tiny, capsys, workload, entry, edit):
    from repro.core import simulator

    fn = getattr(simulator, entry)
    with mock.patch.object(simulator, entry, _altered(fn, edit)):
        res = run_cell(tiny, workload, 8, capsys=capsys)
    assert not res["correct"]
    assert res["failed"] > 0


MESH_SCRIPT = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
import jax, jax.numpy as jnp
from unittest import mock
from conftest import make_tiny
from bench import run
from repro.core import simulator
root = make_tiny({tmp!r})
orig = simulator._mesh_sweep_preempted

def no_exchange(*args, **kw):
    # every device's shard replaced by the first device's: the results
    # of the other chips never reach the host
    res = orig(*args, **kw)
    def first(x):
        b = x.shape[1] // 4
        return jnp.concatenate([x[:, :b]] * 4, axis=1)
    return type(res)(*(first(x) for x in res))

patch = {patch}
ctx = mock.patch.object(simulator, "_mesh_sweep_preempted", no_exchange) \
    if patch else mock.patch.object(simulator, "_mesh_sweep_preempted", orig)
with ctx:
    rc = run.run(["--workload", "fig7.preempted", "--seed", "11", "--seconds",
                  "1", "--trace", "0"], chips=lambda n: jax.devices(),
                 root=root)
sys.exit(rc)
"""


@pytest.mark.parametrize("patch", [False, True])
def test_mesh_exchange_left_out_is_caught(tmp_path, patch):
    """On a host of four devices a sweep grid's fleet axis shards over
    the mesh; a run whose shards never leave their devices is caught."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = MESH_SCRIPT.format(
        root=ROOT, src=os.path.join(ROOT, "src"),
        tests=os.path.dirname(os.path.abspath(__file__)),
        tmp=str(tmp_path), patch=patch)
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is (not patch)
