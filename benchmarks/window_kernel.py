"""§Perf — fused window-distance kernel vs the jnp window pass.

The interleaved engine's `use_kernel` knob (PR 9) swaps the jnp window
pass in `repro.core.stackdist_interleaved._simulate_cell` for the fused
Pallas kernel in `repro.kernels.window_distance`.  This module times the
two implementations head-to-head on a small preempted grid — the
one-shot counter sweep AND a state-seeded resume segment (the serving
stack's epoch-advance shape) — with bit-for-bit parity asserted before
any timing, mirroring every other engine benchmark in this directory.

The kernel mode is whatever `resolve("kernel")` picks for the local
backend: the compiled Pallas kernel on TPU, interpret mode on CPU.
Interpret mode is a correctness vehicle, not a fast path, so CPU records
honestly show the kernel losing to XLA's fused jnp loop — the recorded
`kernel_mode` field keeps the two regimes from ever being compared as if
they were one (see benchmarks/perf_gate.py's same-backend rule).

Feeds the `window_kernel` section of BENCH_sweep.json via
benchmarks/perf_sweep.py and runs standalone through benchmarks/run.py.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import isa, scheduler, simulator
from repro.kernels import window_distance

WK_FLEETS = 2
WK_PROGRAMS = 2
WK_TRACE_LEN = 4_000
WK_TOTAL_STEPS = 8_000
WK_QUANTUM = 2_000
WK_SLOT_COUNTS = (2, 4)
WK_LATENCIES = (10, 50)
REPS = 2


def _best_of(fn, reps: int = REPS) -> float:
    """Compile/warm once, then best-of-`reps` wall-clock seconds (the
    perf_sweep protocol)."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def bench_kernel_vs_jnp() -> dict:
    """Kernel vs jnp window pass, one-shot sweep + resumed segment."""
    _, interpret = window_distance.resolve("kernel")
    mode = "interpret" if interpret else "compiled"
    tensor = scheduler.fleet_traces(
        scheduler.make_fleets(WK_PROGRAMS)[:WK_FLEETS], WK_TRACE_LEN)
    sched = simulator.SchedulerConfig(quantum_cycles=WK_QUANTUM)
    kw = dict(slot_counts=WK_SLOT_COUNTS, total_steps=WK_TOTAL_STEPS,
              path="interleaved")

    def sweep(use_kernel):
        return simulator.sweep_fleet(tensor, WK_LATENCIES, isa.SCENARIO_2,
                                     sched, use_kernel=use_kernel, **kw)

    # correctness first: the kernel must agree with the jnp pass
    # bit-for-bit (the randomized grid lives in tests/test_window_kernel)
    for a, b in zip(sweep("jnp"), sweep("kernel")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    jnp_s = _best_of(lambda: sweep("jnp"))
    kernel_s = _best_of(lambda: sweep("kernel"))

    # state-seeded resume: the materialise/seeded kernel form behind
    # resume_preempted (what every online epoch advance rides)
    cfg = simulator.ReconfigConfig(num_slots=4, miss_latency=50)
    tr = np.asarray(tensor)[0]
    half = WK_TOTAL_STEPS // 2
    _, seed = simulator.simulate_many(tr, cfg, isa.SCENARIO_2, sched, half,
                                      return_state=True)

    def segment(use_kernel):
        return simulator.simulate_many(tr, cfg, isa.SCENARIO_2, sched,
                                       half, state=seed,
                                       path="interleaved",
                                       use_kernel=use_kernel)

    for a, b in zip(segment("jnp"), segment("kernel")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    resume_jnp_s = _best_of(lambda: segment("jnp"))
    resume_kernel_s = _best_of(lambda: segment("kernel"))
    return {
        "grid": f"{WK_FLEETS} fleets x P={WK_PROGRAMS} x "
                f"{WK_TOTAL_STEPS} steps, quantum {WK_QUANTUM}, "
                f"{len(WK_SLOT_COUNTS)} slots x {len(WK_LATENCIES)} "
                f"latencies",
        "kernel_mode": mode,
        "window": simulator.interleave_window(),
        "jnp_s": jnp_s,
        "kernel_s": kernel_s,
        "speedup": jnp_s / kernel_s,
        "resume_jnp_s": resume_jnp_s,
        "resume_kernel_s": resume_kernel_s,
        "resume_speedup": resume_jnp_s / resume_kernel_s,
    }


def run() -> tuple[list[str], dict]:
    r = bench_kernel_vs_jnp()
    mode = r["kernel_mode"]
    rows = [
        "section,variant,seconds,speedup",
        f"window_kernel,jnp,{r['jnp_s']:.3f},1.00x",
        f"window_kernel,kernel[{mode}],{r['kernel_s']:.3f},"
        f"{r['speedup']:.2f}x",
        f"window_kernel_resume,jnp,{r['resume_jnp_s']:.3f},1.00x",
        f"window_kernel_resume,kernel[{mode}],{r['resume_kernel_s']:.3f},"
        f"{r['resume_speedup']:.2f}x",
        f"# finding fused window kernel ({mode}, window {r['window']}) "
        f"{r['speedup']:.2f}x vs jnp on the one-shot sweep, "
        f"{r['resume_speedup']:.2f}x on resumed segments; parity asserted "
        f"bit-for-bit before timing",
    ]
    return rows, r


def main(print_fn=print):
    t0 = time.time()
    rows, _ = run()
    for r in rows:
        print_fn(r)
    print_fn(f"# window_kernel done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
