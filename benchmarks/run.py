"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one row per benchmark module),
writes each module's full output under experiments/bench/, and records the
same {us_per_call, derived} per module in ``BENCH_fleet.json`` at the repo
root — the machine-readable perf trajectory CI uploads per PR.  Partial
runs (``--only``) merge into the existing JSON instead of clobbering it.

    PYTHONPATH=src python -m benchmarks.run [--only fig6]
    PYTHONPATH=src python -m benchmarks.run [--only fig6,placement_search]
    PYTHONPATH=src python -m benchmarks.run --list   # names --only matches
    PYTHONPATH=src python -m benchmarks.run --backend tpu   # JAX_PLATFORMS
    PYTHONPATH=src python -m benchmarks.run --interpret     # kernel parity

Every recorded entry carries {backend, device, platform_version}
provenance so numbers from different backends are never conflated (the
perf gate only compares same-backend entries).
"""
from __future__ import annotations

import argparse
import json
import os
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# anchored to the repo root (not the cwd) so partial runs always merge into
# the same file CI uploads
FLEET_JSON = os.path.join(REPO_ROOT, "BENCH_fleet.json")


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory:
    wherever JAX_COMPILATION_CACHE_DIR says (JAX reads the variable
    itself), else the fixed `<repo>/.jax_cache`, so a second run finds the
    first run's programs.  Entry points call this; importing the library
    never does."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _backend_meta() -> dict:
    """{backend, device, platform_version} provenance stamped into every
    recorded entry.  Imports jax lazily so `--backend` can set
    JAX_PLATFORMS before the backend is chosen; CPU devices carry no
    platform_version attribute, so the jax version stands in."""
    import jax
    dev = jax.devices()[0]
    version = getattr(dev, "platform_version", "") or f"jax-{jax.__version__}"
    return {"backend": jax.default_backend(), "device": str(dev),
            "platform_version": " ".join(str(version).split())}


def _capture(mod_main):
    lines: list[str] = []
    mod_main(print_fn=lines.append)
    return lines


def bench_fig4():
    from benchmarks import fig4_extensions
    lines = _capture(fig4_extensions.main)
    minver = [l for l in lines if l.startswith("minver,")][0].split(",")
    return lines, f"minver_speedup_F={minver[6]} (paper 27.5)"


def bench_fig5():
    from benchmarks import fig5_classification
    lines = _capture(fig5_classification.main)
    return lines, [l for l in lines if l.startswith("# classes")][0][2:]


def bench_fig6():
    from benchmarks import fig6_single
    lines = _capture(fig6_single.main)
    s2_50 = [l for l in lines if l.startswith("AVERAGE,s2,50")][0]
    return lines, f"avg_s2@50c={s2_50.split(',')[-1]} (paper ~0.71)"


def bench_fig7():
    from benchmarks import fig7_multi
    lines, _ = fig7_multi.run()   # full rows (main() prints only the tail)
    head = [l for l in lines if l.startswith("# 4slot@20K")][0]
    return lines, head[2:]


def bench_fleet_sweep():
    """Beyond-paper P=4 fleet sweep (one jitted sweep_fleet call)."""
    from benchmarks import fig7_multi
    lines, agg = fig7_multi.run_fleets()
    import numpy as np
    derived = "; ".join(f"P4_avg@{lat}c={np.mean(v):.3f}"
                        for lat, v in sorted(agg.items()))
    return lines, derived


def bench_expert_slots():
    from benchmarks import bench_expert_slots as mod
    lines = _capture(mod.main)
    return lines, lines[1] if len(lines) > 1 else ""


def bench_bitstream_study():
    from benchmarks import bitstream_study
    lines = _capture(bitstream_study.main)
    return lines, [l for l in lines if l.startswith("# finding")][0][2:]


def bench_perf_slot_decode():
    from benchmarks import perf_slot_decode
    lines = _capture(perf_slot_decode.main)
    best = [l for l in lines if l.startswith("slots,2,4.0")]
    return lines, (best[0] if best else "")


def bench_roofline():
    from benchmarks import roofline_table
    lines = _capture(roofline_table.main)
    return lines, f"{len(lines) - 1} dry-run cells tabulated"


def bench_perf_sweep():
    """Sweep-engine wall-clock: stack-distance vs scan (+ BENCH_sweep.json)."""
    from benchmarks import perf_sweep
    lines, _ = perf_sweep.run()
    head = [l for l in lines if l.startswith("# fast path")][0]
    return lines, head[2:]


def bench_placement_study():
    """Contention-aware placement vs random/FIFO co-residency (repro.sched)."""
    from benchmarks import placement_study
    lines, _ = placement_study.run()
    head = [l for l in lines if l.startswith("# finding")][0]
    return lines, head[2:]


def bench_placement_search():
    """Placement-search timing anchor (rides the interleaved fast path)."""
    from benchmarks import placement_search
    lines, _ = placement_search.run()
    head = [l for l in lines if l.startswith("# finding")][0]
    return lines, head[2:]


def bench_online_churn():
    """Warm-state-aware online re-placement vs never/always baselines."""
    from benchmarks import online_churn
    lines, _ = online_churn.run()
    head = [l for l in lines if l.startswith("# finding")][0]
    return lines, head[2:]


def bench_chaos_serve():
    """Online serving under a fault storm: recovery-policy comparison."""
    from benchmarks import chaos_serve
    lines, _ = chaos_serve.run()
    head = [l for l in lines if l.startswith("# finding")][0]
    return lines, head[2:]


def bench_model_serve_study():
    """Model-zoo fleets (prefill/decode workloads) through place_tenants."""
    from benchmarks import model_serve_study
    lines, _ = model_serve_study.run()
    head = [l for l in lines if l.startswith("# finding")][0]
    return lines, head[2:]


def bench_fleet_scale_study():
    """Incremental vs full per-epoch re-solve at datacenter fleet sizes."""
    from benchmarks import fleet_scale_study
    lines, _ = fleet_scale_study.run()
    head = [l for l in lines if l.startswith("# finding")][0]
    return lines, head[2:]


def bench_window_kernel():
    """Fused window-distance kernel vs the jnp window pass (parity first)."""
    from benchmarks import window_kernel
    lines, _ = window_kernel.run()
    head = [l for l in lines if l.startswith("# finding")][0]
    return lines, head[2:]


BENCHES = {
    "fig4_extensions": bench_fig4,
    "fig5_classification": bench_fig5,
    "fig6_single": bench_fig6,
    "fig7_multi": bench_fig7,
    "fleet_sweep": bench_fleet_sweep,
    "expert_slots": bench_expert_slots,
    "bitstream_study": bench_bitstream_study,
    "perf_slot_decode": bench_perf_slot_decode,
    "roofline_table": bench_roofline,
    "perf_sweep": bench_perf_sweep,
    "placement_study": bench_placement_study,
    "placement_search": bench_placement_search,
    "online_churn": bench_online_churn,
    "chaos_serve": bench_chaos_serve,
    "model_serve_study": bench_model_serve_study,
    "fleet_scale_study": bench_fleet_scale_study,
    "window_kernel": bench_window_kernel,
}

# registration audit: every benchmark module in this directory must either
# back a BENCHES entry or be listed here with the reason it is excluded.
# `audit_registration()` enforces the invariant (tests call it), so a new
# module that forgets both shows up as a test failure, not a silent orphan.
MODULE_OF = {
    "fig4_extensions": "fig4_extensions",
    "fig5_classification": "fig5_classification",
    "fig6_single": "fig6_single",
    "fig7_multi": "fig7_multi",
    "fleet_sweep": "fig7_multi",            # second entry point (run_fleets)
    "expert_slots": "bench_expert_slots",
    "bitstream_study": "bitstream_study",
    "perf_slot_decode": "perf_slot_decode",
    "roofline_table": "roofline_table",
    "perf_sweep": "perf_sweep",
    "placement_study": "placement_study",
    "placement_search": "placement_search",
    "online_churn": "online_churn",
    "chaos_serve": "chaos_serve",
    "model_serve_study": "model_serve_study",
    "fleet_scale_study": "fleet_scale_study",
    "window_kernel": "window_kernel",
}
EXCLUDED = {
    "run": "the harness itself",
    "perf_gate": "CI gate comparing BENCH_fleet.json across refs, "
                 "not a benchmark",
}


def audit_registration() -> None:
    """Raise if any benchmarks/*.py module is neither registered (MODULE_OF)
    nor explicitly excluded (EXCLUDED), or if either map is stale."""
    bench_dir = os.path.dirname(os.path.abspath(__file__))
    modules = {os.path.splitext(f)[0] for f in os.listdir(bench_dir)
               if f.endswith(".py") and not f.startswith("_")}
    missing_map = set(BENCHES) - set(MODULE_OF)
    registered = set(MODULE_OF.values())
    orphans = modules - registered - set(EXCLUDED)
    stale = (registered | set(EXCLUDED)) - modules
    if missing_map or orphans or stale:
        raise AssertionError(
            f"benchmark registration audit failed: "
            f"BENCHES entries missing from MODULE_OF={sorted(missing_map)}, "
            f"orphan modules={sorted(orphans)}, "
            f"stale references={sorted(stale)}")


PROVENANCE_KEYS = ("backend", "device", "platform_version")


def _record_fleet_json(results: dict, path: str = FLEET_JSON) -> None:
    """Merge this run's {bench: {us_per_call, derived}} into BENCH_fleet.json
    at the repo root, preserving entries for modules not run this time.

    Preserved entries must carry {backend, device, platform_version}
    provenance.  A legacy entry written before the per-backend keying
    migration has none — merging it forward would hand the perf gate a
    number it cannot attribute to a backend and would happily compare
    same-backend, so legacy entries are dropped (the next full run
    re-records them with provenance), and the merged result is asserted
    clean before it is written."""
    existing: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                existing = json.load(f)
        except (json.JSONDecodeError, OSError):
            existing = {}
    dropped = [name for name, entry in existing.items()
               if name not in results
               and any(k not in entry for k in PROVENANCE_KEYS)]
    for name in dropped:
        print(f"# dropping provenance-free legacy entry {name!r} from "
              f"{os.path.basename(path)} (re-run it to re-record)")
        del existing[name]
    existing.update(results)
    bad = sorted(name for name, entry in existing.items()
                 if any(k not in entry for k in PROVENANCE_KEYS))
    assert not bad, (
        f"entries {bad} lack {PROVENANCE_KEYS} provenance after merge")
    with open(path, "w") as f:
        json.dump(existing, f, indent=2)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substrings; a module runs when "
                         "any of them matches its name")
    ap.add_argument("--list", action="store_true",
                    help="print the registered module names (the values "
                         "--only matches against) and exit")
    ap.add_argument("--out", default="experiments/bench")
    ap.add_argument("--backend", default=None,
                    choices=("cpu", "tpu"),
                    help="set JAX_PLATFORMS before any benchmark imports "
                         "jax (entries are stamped with the backend that "
                         "actually ran)")
    ap.add_argument("--interpret", action="store_true",
                    help="force the window-distance kernel parity path "
                         "(REPRO_WINDOW_KERNEL=interpret) — a correctness "
                         "vehicle, not a fast path")
    args = ap.parse_args(argv)
    if args.list:
        for name in BENCHES:
            print(name)
        return
    # env, not jax.config: benchmark modules import jax lazily inside the
    # bench functions, so nothing has initialised a backend yet
    if args.backend:
        os.environ["JAX_PLATFORMS"] = args.backend
    if args.interpret:
        os.environ["REPRO_WINDOW_KERNEL"] = "interpret"
    only = [s for s in (args.only or "").split(",") if s]
    # a substring matching nothing is a typo, not an empty run: silently
    # running zero modules and exiting 0 once masked a dead perf gate
    dead = [s for s in only if not any(s in name for name in BENCHES)]
    if dead:
        ap.error(
            f"--only substring(s) {dead} match no registered module; "
            f"valid names: {', '.join(BENCHES)}")
    use_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    results: dict = {}
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if only and not any(s in name for s in only):
            continue
        t0 = time.time()
        lines, derived = fn()
        us = (time.time() - t0) * 1e6
        with open(os.path.join(args.out, f"{name}.csv"), "w") as f:
            f.write("\n".join(lines) + "\n")
        derived = str(derived).replace(",", ";")
        results[name] = {"us_per_call": round(us), "derived": derived,
                         **_backend_meta()}
        print(f"{name},{us:.0f},{derived}", flush=True)
    if results:
        _record_fleet_json(results)


if __name__ == "__main__":
    main()
