"""The readers of the dispatcher's own spans and the engines' cell
counters: on a recorded chip trace, and in a traced run of every cell."""
from __future__ import annotations

import json
import os
from types import SimpleNamespace

import pytest

from conftest import ROOT, run_cell
from bench import run
from bench.trace import Event, Trace

NEW = ("plan_host_ms.sweep", "stage_host_ms.sweep", "launch_host_ms.sweep",
       "assemble_host_ms.sweep", "dispatch_idle_pct.sweep",
       "pad_cells_pct.sweep")

# the numbers the recorded run printed
PRINTED = {
    "plan_host_ms.sweep": 2.7405899999999996,
    "stage_host_ms.sweep": 2.272358,
    "launch_host_ms.sweep": 2.7196546666666666,
    "assemble_host_ms.sweep": 7.285372666666667,
    "dispatch_idle_pct.sweep": 2.0132958470663476,
    "pad_cells_pct.sweep": 3.8461538461538463,
}


def _reader(name):
    return run.load_module(os.path.join(ROOT, "bench", "metrics",
                                        name + ".py"))


def _workloads(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    return next(m for m in b["per_layer"] if m["name"] == name)["workloads"]


def test_recorded_trace_gives_the_run_its_numbers(monkeypatch):
    """A traced `fig7.preempted` run on one TPU v5 lite (seed 3141592701,
    6 grids in a 2 s window), its device lines and host spans kept as
    `trace_fig7.preempted.spans.json`: reduced again, with the cell
    counters that run read (the warm grid and the window's, 300 cells
    each launched as 312), it gives the numbers that run printed."""
    from repro.core import simulator

    with open(os.path.join(os.path.dirname(__file__),
                           "trace_fig7.preempted.spans.json")) as f:
        t = Trace([Event(*e) for e in json.load(f)], "bench.window")
    units = 6
    ctx = SimpleNamespace(trace=t, window=SimpleNamespace(units=units))
    monkeypatch.setattr(simulator, "_cells_real", 300 * (units + 1))
    monkeypatch.setattr(simulator, "_cells_launched", 312 * (units + 1))
    read = {m: _reader(m).read(ctx) for m in NEW}
    assert read == pytest.approx(PRINTED, rel=1e-9)
    # the dispatcher's idle is part of the device's
    assert read["dispatch_idle_pct.sweep"] <= _reader(
        "device_idle_pct.sweep").read(ctx)
    # the kernel is still found by its pinned name
    assert _reader("window_kernel_ms.sweep").kernel_seconds(t) > 0


def test_readers_find_nothing_without_spans():
    """A program that opens no `sim.` span (one from before them) gives
    no number, and the metric is left out of the line."""
    with open(os.path.join(os.path.dirname(__file__),
                           "trace_fig7.preempted.json")) as f:
        t = Trace([Event(*e) for e in json.load(f)], "bench.window")
    ctx = SimpleNamespace(trace=t, window=SimpleNamespace(units=6))
    for m in NEW[:-1]:
        assert _reader(m).read(ctx) is None, m


@pytest.mark.parametrize("workload", ["fig7.preempted", "bitstream.solo"])
def test_traced_run_reports_every_new_metric(tiny, capsys, monkeypatch,
                                             workload):
    from repro.core import simulator

    # the counters hold the whole process's sweeps, as in a run of its own
    monkeypatch.setattr(simulator, "_cells_real", 0)
    monkeypatch.setattr(simulator, "_cells_launched", 0)
    res = run_cell(tiny, workload, 2900000013, trace=1, capsys=capsys)
    assert res["correct"]
    got = res["metrics"]
    want = {m for m in NEW if workload in _workloads(m)}
    assert want <= set(got), want - set(got)
    stages = [got[m]["value"] for m in NEW[:4] if m in got]
    assert 0 < sum(stages) <= got["dispatch_host_ms.sweep"]["value"]
    if workload == "fig7.preempted":
        # 50 fleets padded to the bucket of 4: 52
        assert got["pad_cells_pct.sweep"]["value"] == pytest.approx(
            100.0 * 2 / 52)
