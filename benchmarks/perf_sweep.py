"""§Perf — sweep-engine wall-clock: PR 2's two claims plus the PR-5
interleaved fast path, measured head-to-head.

1. **Stack-distance fast path vs the `lax.scan` path** on the Fig. 6 grid
   ({3 scenarios x 3 miss latencies x 5 FM benchmarks}, the paper's §V-D
   axis): the scan pays one 120k-step LRU state machine per {slot count x
   latency} lane, the fast path one Mattson pass per benchmark with the
   grid reconstructed affinely (`repro.core.stackdist`).  Both are run to
   completion and asserted bit-for-bit equal before timing is reported.

2. **Optimized preempted scan vs the PR-1 step** on a P=4 round-robin
   fleet: the PR-1 implementation (dependent double gather per step, two
   separate `slots.lookup` calls, no unroll) is frozen below as
   `_legacy_simulate_fleet` so the gather-hoist + fused-lookup win stays
   measurable after the live code moves on; a `scan_unroll` sweep records
   where unrolling pays on this backend.

3. **Interleaved fast path vs the optimized scan** on preempted
   fig6-style grids ({slot counts x miss latencies}, preempting quantum,
   P=2..4): the regime the serving stack lives in (placement search,
   online re-placement pricing), where the unpreempted engine cannot go —
   switch points are cost-dependent, so every cell replays its own
   interleaving at scheduler-window granularity
   (`repro.core.stackdist_interleaved`).  Parity is asserted bit-for-bit
   before timing; an `interleave_window` sweep records where the window
   knob pays on this backend.

4. **Stacked cold-bitstream pass vs the per-cell scan loop** on the
   bitstream_study grid ({capacity x penalty} on the FM benches): one
   `sweep_bitstream` call (`repro.core.stackdist_cold`) against one scan
   per cell — the loop `benchmarks/bitstream_study.py` used to run.

5. **Resumable interleaved engine vs the scan on state-seeded segments**:
   a preempted P=3 run split at the midpoint, its second half resumed
   from the materialised `FleetState` on both engines — the shape of
   every online-serving epoch advance and migration probe.

6. **Fused window-distance kernel vs the jnp window pass** (PR 9): the
   `window_kernel` section, delegated to `benchmarks/window_kernel.py` —
   one-shot sweep + resumed segment through `use_kernel="kernel"`
   (compiled Pallas on TPU, interpret mode on CPU, recorded as
   `kernel_mode` so the regimes are never conflated).

Emits machine-readable `BENCH_sweep.json` at the repo root so the perf
trajectory is tracked PR-over-PR, and a CSV under experiments/bench via
benchmarks.run.  The JSON is keyed per backend (``{"cpu": {...sections,
meta}, "tpu": {...}}``): a run replaces its own backend's section and
preserves the others, and every section's meta carries {backend, device,
platform_version}.  Standalone flags::

    PYTHONPATH=src python -m benchmarks.perf_sweep [--backend tpu]
    PYTHONPATH=src python -m benchmarks.perf_sweep [--interpret]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.run import _backend_meta
from repro.core import isa, scheduler, simulator, slots, traces
from repro.kernels import window_distance

FIG6_TRACE_LEN = 120_000          # matches benchmarks/fig6_single.py
FIG6_LATENCIES = (10, 50, 250)
FIG6_SCENARIOS = (("s1", isa.SCENARIO_1), ("s2", isa.SCENARIO_2),
                  ("s3", isa.SCENARIO_3))

P4_FLEETS = 6
P4_TRACE_LEN = 30_000
P4_TOTAL_STEPS = 60_000
P4_QUANTUM = 20_000
# always include the live default so retuning SCAN_UNROLL keeps the sweep
# (and the optimized_s lookup below) well-defined
UNROLLS = tuple(sorted({1, 2, 4, 8, simulator.SCAN_UNROLL}))
REPS = 2

# BENCH_sweep.json lives at the repo root (not the cwd), next to
# BENCH_fleet.json, so the perf trajectory is diffable PR-over-PR
SWEEP_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_sweep.json")


def _best_of(fn, reps: int = REPS) -> float:
    """Compile/warm once, then best-of-`reps` wall-clock seconds."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------------------
# 1. fig6 grid: fast path vs scan path
# ---------------------------------------------------------------------------


def _fig6_grid(fleet, path: str):
    out = []
    for _, scen in FIG6_SCENARIOS:
        out.append(simulator.sweep_fleet(
            fleet, FIG6_LATENCIES, scen, simulator.SchedulerConfig.no_preempt(),
            slot_counts=(scen.num_slots,), total_steps=FIG6_TRACE_LEN,
            path=path))
    return out


def bench_fig6_grid() -> dict:
    fleet = np.stack([traces.build_trace(n, FIG6_TRACE_LEN)
                      for n in traces.FM_BENCHES])[:, None, :]
    # correctness first: the two engines must agree bit-for-bit
    for scan_r, fast_r in zip(_fig6_grid(fleet, "scan"),
                              _fig6_grid(fleet, "stackdist")):
        for a, b in zip(scan_r, fast_r):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    scan_s = _best_of(lambda: _fig6_grid(fleet, "scan"))
    fast_s = _best_of(lambda: _fig6_grid(fleet, "stackdist"))
    return {
        "grid": f"{len(FIG6_SCENARIOS)} scenarios x {len(FIG6_LATENCIES)} "
                f"latencies x {fleet.shape[0]} benches @ {FIG6_TRACE_LEN} steps",
        "scan_s": scan_s,
        "stackdist_s": fast_s,
        "speedup": scan_s / fast_s,
    }


# ---------------------------------------------------------------------------
# 2. preempted P=4 fleet: PR-1 step (frozen) vs optimized scan
# ---------------------------------------------------------------------------


def _legacy_simulate_fleet_impl(trs, tag_table, miss_latency, active_slots,
                                quantum, handler, num_slots: int,
                                bs_entries: int, bs_miss_extra,
                                total_steps: int):
    """The PR-1 fleet scan, frozen verbatim as the perf baseline: per-step
    dependent double gather (trace -> instr -> tag/hw) and two separate
    `slots.lookup` calls, unroll=1."""
    hw = jnp.asarray(isa.INSTR_HW_CYCLES, jnp.int32)
    tags = jnp.asarray(tag_table, jnp.int32)
    num_progs, trace_len = trs.shape

    def step(c, _):
        p = c["active"]
        ins = trs[p, jnp.remainder(c["cursors"][p], trace_len)]
        tag = tags[p, ins]
        res = slots.lookup(c["slot_st"], tag, active_slots)
        bs_res = slots.lookup(
            c["bs_st"], jnp.where(res.hit, jnp.int32(-1), tag))
        cost = hw[ins]
        cost = cost + jnp.where(res.hit, 0, miss_latency).astype(jnp.int32)
        cost = cost + jnp.where(res.hit | bs_res.hit, 0,
                                bs_miss_extra).astype(jnp.int32)
        q = c["q_cycles"] + cost
        do_switch = q >= quantum
        cost_p = cost + jnp.where(do_switch, handler, 0).astype(jnp.int32)
        return {
            "slot_st": res.state,
            "bs_st": bs_res.state,
            "cursors": c["cursors"].at[p].add(1),
            "active": jnp.where(do_switch, (p + 1) % num_progs, p),
            "q_cycles": jnp.where(do_switch, 0, q),
            "cycles": c["cycles"].at[p].add(cost_p),
            "instrs": c["instrs"].at[p].add(1),
            "misses": c["misses"].at[p].add((~res.hit).astype(jnp.int32)),
            "bs_misses": c["bs_misses"].at[p].add(
                (~(res.hit | bs_res.hit)).astype(jnp.int32)),
            "switches": c["switches"] + do_switch.astype(jnp.int32),
        }, None

    init = {
        "slot_st": slots.init(num_slots),
        "bs_st": slots.init(bs_entries),
        "cursors": jnp.zeros((num_progs,), jnp.int32),
        "active": jnp.int32(0),
        "q_cycles": jnp.int32(0),
        "cycles": jnp.zeros((num_progs,), jnp.int32),
        "instrs": jnp.zeros((num_progs,), jnp.int32),
        "misses": jnp.zeros((num_progs,), jnp.int32),
        "bs_misses": jnp.zeros((num_progs,), jnp.int32),
        "switches": jnp.int32(0),
    }
    final, _ = jax.lax.scan(step, init, None, length=total_steps)
    return simulator.FleetResult(
        final["cycles"], final["instrs"], final["misses"],
        final["bs_misses"], final["switches"])


@functools.partial(
    jax.jit, static_argnames=("num_slots", "bs_entries", "total_steps"))
def _legacy_sweep(fleets, tag_table, miss_latencies, slot_counts, quantum,
                  handler, num_slots: int, bs_entries: int, bs_miss_extra,
                  total_steps: int):
    def one(t, s, lat):
        return _legacy_simulate_fleet_impl(
            t, tag_table, lat, s, quantum, handler, num_slots, bs_entries,
            bs_miss_extra, total_steps)

    f = jax.vmap(one, in_axes=(None, None, 0))
    f = jax.vmap(f, in_axes=(None, 0, None))
    f = jax.vmap(f, in_axes=(0, None, None))
    return f(fleets, slot_counts, miss_latencies)


def bench_p4_preempted() -> dict:
    tensor = jnp.asarray(scheduler.fleet_traces(
        scheduler.make_fleets(4)[:P4_FLEETS], P4_TRACE_LEN), jnp.int32)
    table = simulator.fleet_tag_table(isa.SCENARIO_2, 4)
    sched = simulator.SchedulerConfig(quantum_cycles=P4_QUANTUM)

    def legacy():
        return _legacy_sweep(
            tensor, table, jnp.asarray([50], jnp.int32),
            jnp.asarray([4], jnp.int32), jnp.int32(P4_QUANTUM),
            jnp.int32(sched.handler_cycles), 4, 64, jnp.int32(100),
            P4_TOTAL_STEPS)

    def optimized(unroll):
        return simulator.sweep_fleet(
            tensor, [50], isa.SCENARIO_2, sched, slot_counts=[4],
            total_steps=P4_TOTAL_STEPS, path="scan", scan_unroll=unroll)

    # the optimized step must reproduce the PR-1 numbers exactly
    np.testing.assert_array_equal(
        np.asarray(legacy().cycles),
        np.asarray(optimized(simulator.SCAN_UNROLL).cycles))

    legacy_s = _best_of(legacy)
    unroll_sweep = {str(u): _best_of(lambda u=u: optimized(u))
                    for u in UNROLLS}
    optimized_s = unroll_sweep[str(simulator.SCAN_UNROLL)]
    return {
        "grid": f"{P4_FLEETS} fleets x P=4 x {P4_TOTAL_STEPS} steps, "
                f"quantum {P4_QUANTUM}, 50c misses",
        "legacy_s": legacy_s,
        "optimized_s": optimized_s,
        "speedup": legacy_s / optimized_s,
        "default_unroll": simulator.SCAN_UNROLL,
        "unroll_sweep_s": unroll_sweep,
    }


# ---------------------------------------------------------------------------
# 3. preempted fig6-style grid: interleaved fast path vs optimized scan
# ---------------------------------------------------------------------------

PG_FLEETS = 3
PG_TRACE_LEN = 30_000
PG_TOTAL_STEPS = 60_000
PG_QUANTUM = 20_000           # preempting: the paper's Fig. 7 quantum
PG_SLOT_COUNTS = (2, 4, 8)
PG_LATENCIES = (10, 50, 250)
PG_PROGRAMS = (2, 3, 4)
# 256/512/1024 stay fixed so the recorded sweep is comparable across
# backends whose defaults differ (cpu retuned to 256, the TPU keeps 512);
# the live default `simulator.interleave_window()` joins them at run time
PG_WINDOWS = (256, 512, 1024)


def bench_preempted_grid() -> dict:
    """Interleaved fast path vs optimized scan, P=2..4, preempting quanta.

    This is the grid the unpreempted engine can never serve (every {slot
    count x latency} cell has its own cost-dependent switch points); the
    acceptance bar for the interleaved engine is >= 5x over the optimized
    scan here, recorded per fleet size in BENCH_sweep.json.
    """
    sched = simulator.SchedulerConfig(quantum_cycles=PG_QUANTUM)
    default_w = simulator.interleave_window()
    windows = sorted({*PG_WINDOWS, default_w})
    out = {}
    for p in PG_PROGRAMS:
        tensor = scheduler.fleet_traces(
            scheduler.make_fleets(p)[:PG_FLEETS], PG_TRACE_LEN)

        def sweep(path, window=None, p=p, tensor=tensor):
            return simulator.sweep_fleet(
                tensor, PG_LATENCIES, isa.SCENARIO_2, sched,
                slot_counts=PG_SLOT_COUNTS, total_steps=PG_TOTAL_STEPS,
                path=path, interleave_window=window)

        # correctness first: the two engines must agree bit-for-bit
        scan_r, fast_r = sweep("scan"), sweep("interleaved")
        for a, b in zip(scan_r, fast_r):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        scan_s = _best_of(lambda: sweep("scan"))
        window_sweep = {str(w): _best_of(lambda w=w: sweep("interleaved", w))
                        for w in windows}
        fast_s = window_sweep[str(default_w)]
        out[f"p{p}"] = {
            "grid": f"{PG_FLEETS} fleets x P={p} x {PG_TOTAL_STEPS} steps, "
                    f"quantum {PG_QUANTUM}, {len(PG_SLOT_COUNTS)} slots x "
                    f"{len(PG_LATENCIES)} latencies",
            "scan_s": scan_s,
            "interleaved_s": fast_s,
            "speedup": scan_s / fast_s,
            "default_window": default_w,
            "window_sweep_s": window_sweep,
        }
    return out


# ---------------------------------------------------------------------------
# 4. cold-bitstream grid: stacked Mattson pass vs per-cell scan loop
# ---------------------------------------------------------------------------

BS_TRACE_LEN = 20_000
BS_CAPACITIES = (2, 4, 8, 16)
BS_PENALTIES = (50, 250)


def bench_cold_bitstream() -> dict:
    """`benchmarks/bitstream_study.py`'s {capacity x penalty} grid: one
    stacked-pass `sweep_bitstream` call vs the per-cell scan loop it
    replaced.  The acceptance bar is >= 5x on this grid; parity is
    asserted bit-for-bit before timing."""
    trs = np.stack([traces.build_trace(n, BS_TRACE_LEN)
                    for n in traces.FM_BENCHES])
    kw = dict(slot_counts=[4], miss_latencies=[50],
              bs_entries=BS_CAPACITIES, bs_miss_extras=BS_PENALTIES,
              total_steps=BS_TRACE_LEN)

    def grid(path):
        return simulator.sweep_bitstream(trs, isa.SCENARIO_2, path=path,
                                         **kw)

    for a, b in zip(grid("scan"), grid("stackdist_cold")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    scan_s = _best_of(lambda: grid("scan"))
    fast_s = _best_of(lambda: grid("stackdist_cold"))
    return {
        "grid": f"{trs.shape[0]} benches x {len(BS_CAPACITIES)} capacities "
                f"x {len(BS_PENALTIES)} penalties @ {BS_TRACE_LEN} steps",
        "scan_s": scan_s,
        "stackdist_cold_s": fast_s,
        "speedup": scan_s / fast_s,
    }


# ---------------------------------------------------------------------------
# 5. resumed segments: resumable interleaved engine vs scan
# ---------------------------------------------------------------------------

RS_TRACE_LEN = 30_000
RS_TOTAL_STEPS = 60_000


def bench_resumed_segment() -> dict:
    """State-seeded resume (the online layer's epoch-advance shape): a
    preempted P=3 run split at the midpoint, the second half resumed from
    the materialised FleetState on both engines."""
    tensor = scheduler.fleet_traces(
        scheduler.make_fleets(3)[:1], RS_TRACE_LEN)[0]
    sched = simulator.SchedulerConfig(quantum_cycles=PG_QUANTUM)
    cfg = simulator.ReconfigConfig(num_slots=4, miss_latency=50)
    half = RS_TOTAL_STEPS // 2
    _, seed = simulator.simulate_many(tensor, cfg, isa.SCENARIO_2, sched,
                                      half, return_state=True)

    def segment(path):
        return simulator.simulate_many(tensor, cfg, isa.SCENARIO_2, sched,
                                       half, state=seed, return_state=True,
                                       path=path)

    # correctness first: results AND final states must agree bit-for-bit
    (scan_r, scan_st), (fast_r, fast_st) = segment("scan"), segment(
        "interleaved")
    for a, b in zip(scan_r, fast_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(scan_st),
                    jax.tree_util.tree_leaves(fast_st)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    scan_s = _best_of(lambda: segment("scan"))
    fast_s = _best_of(lambda: segment("interleaved"))
    return {
        "grid": f"P=3 x {half} resumed steps, quantum {PG_QUANTUM}, "
                f"50c misses, mid-run FleetState seed",
        "scan_s": scan_s,
        "interleaved_resume_s": fast_s,
        "speedup": scan_s / fast_s,
    }


# ---------------------------------------------------------------------------


def _merge_per_backend(report: dict) -> dict:
    """BENCH_sweep.json is keyed per backend: this run replaces its own
    backend's section and preserves the others (a legacy flat layout —
    sections at the top level — is migrated under its meta backend)."""
    existing: dict = {}
    if os.path.exists(SWEEP_JSON):
        try:
            with open(SWEEP_JSON) as f:
                existing = json.load(f)
        except (json.JSONDecodeError, OSError):
            existing = {}
    if "meta" in existing:            # legacy single-backend flat layout
        existing = {existing["meta"].get("backend", "cpu"): existing}
    existing[report["meta"]["backend"]] = report
    return existing


def run() -> tuple[list[str], dict]:
    from benchmarks import window_kernel
    report = {
        "fig6_grid": bench_fig6_grid(),
        "p4_preempted": bench_p4_preempted(),
        "preempted_grid": bench_preempted_grid(),
        "cold_bitstream": bench_cold_bitstream(),
        "resumed_segment": bench_resumed_segment(),
        "window_kernel": window_kernel.bench_kernel_vs_jnp(),
        "meta": {
            **_backend_meta(),
            "machine": platform.machine(),
            "reps": REPS,
        },
    }
    with open(SWEEP_JSON, "w") as f:
        json.dump(_merge_per_backend(report), f, indent=2)
    g, p = report["fig6_grid"], report["p4_preempted"]
    pg = report["preempted_grid"]
    rows = [
        "section,variant,seconds,speedup",
        f"fig6_grid,scan,{g['scan_s']:.3f},1.00x",
        f"fig6_grid,stackdist,{g['stackdist_s']:.3f},{g['speedup']:.1f}x",
        f"p4_preempted,legacy_pr1,{p['legacy_s']:.3f},1.00x",
        f"p4_preempted,optimized,{p['optimized_s']:.3f},{p['speedup']:.2f}x",
    ]
    rows += [f"p4_preempted,unroll={u},{s:.3f},-"
             for u, s in p["unroll_sweep_s"].items()]
    for key in sorted(pg):
        e = pg[key]
        rows += [
            f"preempted_grid_{key},scan,{e['scan_s']:.3f},1.00x",
            f"preempted_grid_{key},interleaved,{e['interleaved_s']:.3f},"
            f"{e['speedup']:.1f}x",
        ]
        rows += [f"preempted_grid_{key},window={w},{s:.3f},-"
                 for w, s in e["window_sweep_s"].items()]
    cb, rs = report["cold_bitstream"], report["resumed_segment"]
    wk = report["window_kernel"]
    rows += [
        f"cold_bitstream,scan,{cb['scan_s']:.3f},1.00x",
        f"cold_bitstream,stackdist_cold,{cb['stackdist_cold_s']:.3f},"
        f"{cb['speedup']:.1f}x",
        f"resumed_segment,scan,{rs['scan_s']:.3f},1.00x",
        f"resumed_segment,interleaved,{rs['interleaved_resume_s']:.3f},"
        f"{rs['speedup']:.1f}x",
        f"window_kernel,jnp,{wk['jnp_s']:.3f},1.00x",
        f"window_kernel,kernel[{wk['kernel_mode']}],{wk['kernel_s']:.3f},"
        f"{wk['speedup']:.2f}x",
    ]
    worst = min(e["speedup"] for e in pg.values())
    rows.append(f"# fast path {g['speedup']:.1f}x on the fig6 grid; "
                f"optimized scan {p['speedup']:.2f}x on the preempted P=4 "
                f"fleet; interleaved >= {worst:.1f}x on the preempted "
                f"fig6-style grids; stacked cold-bitstream "
                f"{cb['speedup']:.1f}x on the bitstream_study grid; "
                f"resumed segments {rs['speedup']:.1f}x; window kernel "
                f"[{wk['kernel_mode']}] {wk['speedup']:.2f}x vs jnp; "
                "BENCH_sweep.json written "
                f"[{report['meta']['backend']}]")
    return rows, report


def main(print_fn=print, argv=None):
    ap = argparse.ArgumentParser(description="sweep-engine wall-clock")
    ap.add_argument("--backend", default=None,
                    choices=("cpu", "tpu"),
                    help="select the jax backend before any computation "
                         "runs (the recorded section is keyed by it)")
    ap.add_argument("--interpret", action="store_true",
                    help="force the window-distance kernel parity path "
                         "(use_kernel session default -> 'interpret')")
    args = ap.parse_args(argv if argv is not None else [])
    if args.backend:
        # jax is imported but no backend is initialised until the first
        # computation, so the platform choice still lands
        os.environ["JAX_PLATFORMS"] = args.backend
        jax.config.update("jax_platforms", args.backend)
    if args.interpret:
        os.environ["REPRO_WINDOW_KERNEL"] = "interpret"
        window_distance.set_default_mode("interpret")
    t0 = time.time()
    rows, _ = run()
    for r in rows:
        print_fn(r)
    print_fn(f"# perf_sweep done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    import sys
    main(argv=sys.argv[1:])

