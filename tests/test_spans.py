"""The sweep entries' host spans and cell counters.

`sweep_fleet` and `sweep_bitstream` each open one entry span per call
(`sim.sweep_fleet`, `sim.sweep_bitstream`) with their stages nested
inside it (`sim.plan`, `sim.stage`, `sim.launch`, `sim.assemble`), and
add the grid cells they ask for and launch to `simulator.cell_counts()`,
the launched cells also by the engine that ran them.
The spans go to the JAX profiler's host plane, where the benchmark's
per-layer readers find them; here they are read back from the profile
the profiler writes.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import isa, scheduler, simulator, traces

ENTRY = {"sim.sweep_fleet", "sim.sweep_bitstream"}
STAGES = ("sim.plan", "sim.stage", "sim.launch", "sim.assemble")
SCHED = simulator.SchedulerConfig(quantum_cycles=2_000, handler_cycles=150)
STEPS = 1_200


def _counts(**nonzero):
    """A `cell_counts()` advance: the given counters, every other 0."""
    return {**dict.fromkeys(simulator.cell_counts(), 0), **nonzero}


def _host_spans(log_dir):
    """[(name, start_ns, end_ns)] of the `sim.` events on the host plane
    of the profile written under `log_dir`, in start order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.name.startswith("sim."))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _traced(tmp_path, fn, calls=2):
    """Run `fn` `calls` times under the profiler, each result waited
    for; returns the sim spans and the counters' advance."""
    before = simulator.cell_counts()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(calls):
            jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    after = simulator.cell_counts()
    return (_host_spans(str(tmp_path)),
            {k: after[k] - before[k] for k in after})


def _per_call(spans, entry):
    """The stage names nested in each entry span, per call; and checks
    that every sim span lies inside one entry span."""
    entries = [s for s in spans if s[0] == entry]
    inner = [[] for _ in entries]
    for name, a, b in spans:
        if name in ENTRY:
            continue
        hits = [i for i, (_, s, e) in enumerate(entries) if s <= a and b <= e]
        assert len(hits) == 1, (name, a, b)
        inner[hits[0]].append(name)
    return inner


def _fleets(n):
    return scheduler.fleet_traces(scheduler.make_fleets(2)[:n], 500)


def test_sweep_fleet_spans_nest_per_call_and_count_padding(tmp_path):
    """Five fleets on the interleaved engine: one entry span per call,
    the four stages inside it on the compiling call and on the cached
    one alike, and the fleet axis launched padded to the bucket of 4."""
    tensor = _fleets(5)
    quanta, slots, lats = [300, 2_000], [2, 4], [50]

    def call():
        return simulator.sweep_fleet(
            tensor, lats, isa.SCENARIO_2, SCHED, slot_counts=slots,
            quanta=quanta, total_steps=STEPS, path="interleaved")

    spans, counted = _traced(tmp_path, call)
    inner = _per_call(spans, "sim.sweep_fleet")
    assert len(inner) == 2
    assert set(inner[0]) == set(STAGES)
    assert inner[0] == inner[1]
    cells = len(quanta) * len(slots) * len(lats)
    assert counted == _counts(cells_real=2 * cells * 5,
                              cells_launched=2 * cells * 8,
                              cells_interleaved=2 * cells * 8)


def test_sweep_bitstream_spans_nest_per_call(tmp_path):
    """The stacked cold pass: plan, stage and launch inside one entry
    span per call, nothing to assemble, every cell launched once."""
    tr = np.stack([traces.build_trace("minver", 500),
                   traces.build_trace("nettle-aes", 500)])
    kw = dict(slot_counts=[2, 4], miss_latencies=[50],
              bs_entries=[2, 8, 16], bs_miss_extras=[50, 250],
              total_steps=STEPS)

    spans, counted = _traced(
        tmp_path, lambda: simulator.sweep_bitstream(tr, isa.SCENARIO_2,
                                                    **kw))
    inner = _per_call(spans, "sim.sweep_bitstream")
    assert len(inner) == 2
    assert inner[0] == inner[1] == ["sim.plan", "sim.stage", "sim.launch"]
    cells = 2 * 2 * 1 * 3 * 2       # (B, K, L, E, X), per call
    assert counted == _counts(cells_real=2 * cells,
                              cells_launched=2 * cells,
                              cells_stackdist_cold=2 * cells)


@pytest.mark.parametrize("path,sched", [
    ("scan", SCHED),
    ("stackdist", simulator.SchedulerConfig.no_preempt()),
    ("stackdist_cold", simulator.SchedulerConfig.no_preempt()),
])
def test_every_engine_path_counts_its_cells(path, sched):
    """The scan and the stack-distance passes launch exactly the cells
    asked for, and count them under their engine; the quantum axis
    counts even where it is broadcast."""
    before = simulator.cell_counts()
    res = simulator.sweep_fleet(
        _fleets(3), [10, 50], isa.SCENARIO_2, sched, slot_counts=[2, 4],
        quanta=[sched.quantum_cycles], total_steps=STEPS, path=path,
        bs_cache_entries=4 if path == "stackdist_cold" else 64)
    jax.block_until_ready(res)
    after = simulator.cell_counts()
    assert {k: after[k] - before[k] for k in after} == _counts(
        cells_real=3 * 2 * 2, cells_launched=3 * 2 * 2,
        **{f"cells_{path}": 3 * 2 * 2})
