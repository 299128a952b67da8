"""The reduction from a profiler trace to the per-layer numbers."""
from __future__ import annotations

import pytest

from bench.trace import MODULES_LINE, OPS_LINE, Event, Trace, union_ns

DEV = "/device:TPU:0"


def ev(line, name, start, dur, plane=DEV):
    return Event(plane, line, name, float(start), float(dur))


def synthetic():
    return [
        ev("python", "bench.window", 100, 1000, plane="/host:CPU"),
        ev("python", "bench.dispatch", 100, 300, plane="/host:CPU"),
        ev("python", "bench.wait", 400, 700, plane="/host:CPU"),
        ev(MODULES_LINE, "jit_window_grid", 150, 500),
        ev(OPS_LINE, "_kernel", 150, 400),
        ev(OPS_LINE, "fusion.1", 500, 100),       # overlaps the kernel
        ev(OPS_LINE, "copy", 800, 100),
        ev(OPS_LINE, "outside", 2000, 50),        # after the window
    ]


def test_union_counts_overlaps_once():
    assert union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert union_ns([]) == 0


def test_busy_idle_kernel_and_launches():
    t = Trace(synthetic(), "bench.window")
    assert t.devices == [DEV]
    assert t.window_s == pytest.approx(1000e-9)
    assert t.busy_s(DEV) == pytest.approx(550e-9)    # 150..600, 800..900
    assert t.op_seconds(lambda n: "_kernel" in n) == pytest.approx(400e-9)
    assert len(t.launches(DEV)) == 1
    assert t.top_ops(2)[0] == ["_kernel", pytest.approx(400e-9)]
    gaps = dict(t.idle_gaps(lambda n: n.startswith("bench.")))
    # 100..150 under dispatch; 600..800 and 900..1100 under wait
    assert gaps["bench.dispatch"] == pytest.approx(50e-9)
    assert gaps["bench.wait"] == pytest.approx(400e-9)


def test_recorded_trace_gives_the_run_its_numbers():
    """A traced `fig7.preempted` run on one TPU v5 lite (seed 2900000201,
    6 grids in a 2 s window), its device lines and the host spans the
    reduction reads kept as `trace_fig7.preempted.json`: reduced again,
    it gives the numbers that run printed."""
    import json
    import os
    from types import SimpleNamespace

    from conftest import ROOT
    from bench import run, work

    with open(os.path.join(os.path.dirname(__file__),
                           "trace_fig7.preempted.json")) as f:
        t = Trace([Event(*e) for e in json.load(f)], "bench.window")
    assert t.devices == [DEV]
    assert t.window_s == pytest.approx(2.034875904, rel=1e-12)
    assert t.mean_busy_s() == pytest.approx(1.976054451, rel=1e-12)
    assert len(t.launches(DEV)) == 83     # 84 modules, one before the window
    cell = SimpleNamespace(kernel_bytes=work.window_kernel_bytes(
        (2, 50, 3, 1), 2, 160000))
    ctx = SimpleNamespace(trace=t, cell=cell, device_kind="TPU v5 lite",
                          window=SimpleNamespace(units=6))
    read = {m: run.load_module(os.path.join(ROOT, "bench", "metrics",
                                            m + ".py")).read(ctx)
            for m in ("device_idle_pct.sweep", "window_kernel_ms.sweep",
                      "window_kernel_roofline_pct.sweep")}
    assert read == pytest.approx({
        "device_idle_pct.sweep": 2.890665366097933,
        "window_kernel_ms.sweep": 218.39069966666668,
        "window_kernel_roofline_pct.sweep": 0.2146967139137845}, rel=1e-9)
    # the kernel's share of the device's busy time
    kernel = run.load_module(os.path.join(
        ROOT, "bench", "metrics", "window_kernel_ms.sweep.py"))
    assert kernel.kernel_seconds(t) / t.mean_busy_s() > 0.6
