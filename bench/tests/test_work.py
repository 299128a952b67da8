"""The work counts of `bench/work.py` against the program's own counters,
on tiny grids on the CPU."""
from __future__ import annotations

import os

import numpy as np
import pytest

from conftest import ROOT


@pytest.mark.parametrize("k,quanta,slots,lats", [
    (2, [1000, 20000], [2, 4, 8], [50]),
    (4, [20000], [4], [10, 50, 250]),
])
def test_sweep_work_is_the_summed_instructions(k, quanta, slots, lats):
    from bench import work
    from bench.embench import Core
    from repro.core import isa, simulator

    core = Core(os.path.join(ROOT, "bench", "configs", "embench-core.json"))
    fleets = core.fleets(k)[:3]
    traces = np.stack([np.stack([core.trace(n, 500, 1) for n in f])
                       for f in fleets])
    steps = 1200
    res = simulator.sweep_fleet(
        traces, lats, isa.SlotScenario("t", 4, core.tags),
        simulator.SchedulerConfig(), slot_counts=slots, quanta=quanta,
        total_steps=steps)
    shape = (len(quanta), len(fleets), len(slots), len(lats))
    assert int(np.asarray(res.instructions).sum()) == \
        work.sweep_instructions(shape, steps)


def test_kernel_bytes_count_streams_and_counters():
    from bench import work

    # 2 cells x (2 words x 4 bytes x 10 accesses + 4 bytes x (4 x 3 + 1))
    assert work.window_kernel_bytes((1, 2, 1, 1), 3, 10) == \
        2 * (80 + 52)


def test_peaks_refuse_an_unknown_device():
    from bench import peaks

    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu", "hbm_bytes_per_s")
