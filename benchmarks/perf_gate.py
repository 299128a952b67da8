"""CI perf-regression gate over BENCH_fleet.json anchors.

Compares a freshly benchmarked `BENCH_fleet.json` against a baseline
artifact and fails when any gated module's `us_per_call` regressed by more
than `--max-slowdown` — so sweep-engine changes can't silently slow the
grid down.  Absolute wall-clock only compares meaningfully on the SAME
machine, so the baseline must be produced on the machine running the gate:
CI re-runs the smoke from the PR's base ref in a worktree (see
.github/workflows/ci.yml); locally, snapshot before re-benchmarking:

    cp BENCH_fleet.json /tmp/bench_baseline.json
    PYTHONPATH=src python -m benchmarks.run --only fig6
    python -m benchmarks.perf_gate --baseline /tmp/bench_baseline.json \\
        --modules fig6_single

`--modules` restricts the gate to entries actually re-benchmarked on both
sides (BENCH_fleet.json merges partial runs, so other entries are stale
carry-overs).  Modules below `--min-us` are skipped (timer noise), as are
modules present on only one side (new or retired benchmarks) and modules
whose two sides were recorded on different backends (entries carry
{backend, device, platform_version} provenance since PR 9 — a CPU
baseline must never gate a TPU run).

Exit code 0 = within budget, 1 = regression (CI fails the step).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_CURRENT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_fleet.json")


def compare(baseline: dict, current: dict, *, max_slowdown: float,
            min_us: float, modules=None) -> tuple[list[str], list[str]]:
    """Returns (report_rows, failures).  `modules` restricts the gate to the
    listed names (the ones actually re-benchmarked on both sides — stale
    carried-over entries must not be compared)."""
    rows, failures = [], []
    shared = sorted(set(baseline) & set(current))
    if modules is not None:
        shared = [n for n in shared if n in set(modules)]
        if not shared:
            # fail CLOSED: an allowlist that matches nothing means the gate
            # isn't gating anything (renamed module, missing rerun) — that
            # must surface as a failure, not a silent green
            failures.append(
                f"none of the allowlisted modules {sorted(set(modules))} "
                f"exist on both sides — gate is vacuous")
    for name in shared:
        base_be = baseline[name].get("backend")
        cur_be = current[name].get("backend")
        if base_be and cur_be and base_be != cur_be:
            # cross-backend wall-clock is not comparable; entries without
            # provenance (pre-PR-9 baselines) keep the old behaviour
            rows.append(f"{name}: skipped (baseline backend {base_be} != "
                        f"current {cur_be})")
            continue
        base_us = float(baseline[name].get("us_per_call", 0))
        cur_us = float(current[name].get("us_per_call", 0))
        if base_us < min_us or cur_us <= 0:
            rows.append(f"{name}: skipped (baseline {base_us:.0f}us below "
                        f"{min_us:.0f}us floor)")
            continue
        ratio = cur_us / base_us
        verdict = "OK" if ratio <= max_slowdown else "REGRESSION"
        rows.append(f"{name}: {base_us:.0f}us -> {cur_us:.0f}us "
                    f"({ratio:.2f}x) {verdict}")
        if ratio > max_slowdown:
            failures.append(
                f"{name} slowed {ratio:.2f}x (> {max_slowdown:.2f}x budget)")
    for name in sorted(set(current) - set(baseline)):
        rows.append(f"{name}: new module (no baseline), skipped")
    if not shared:
        rows.append("no shared modules between baseline and current — "
                    "nothing gated")
    return rows, failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", required=True,
                    help="previous-PR BENCH_fleet.json snapshot")
    ap.add_argument("--current", default=DEFAULT_CURRENT)
    ap.add_argument("--max-slowdown", type=float, default=1.25,
                    help="fail when us_per_call exceeds baseline by this "
                         "factor (default 1.25 = >25%% slower)")
    ap.add_argument("--min-us", type=float, default=100_000,
                    help="ignore modules whose baseline is below this "
                         "(timer noise)")
    ap.add_argument("--modules", default=None,
                    help="comma-separated module allowlist — gate only "
                         "entries re-benchmarked on both sides")
    args = ap.parse_args(argv)
    with open(args.baseline) as f:
        baseline = json.load(f)
    with open(args.current) as f:
        current = json.load(f)
    rows, failures = compare(
        baseline, current, max_slowdown=args.max_slowdown,
        min_us=args.min_us,
        modules=args.modules.split(",") if args.modules else None)
    for r in rows:
        print(r)
    if failures:
        print("PERF GATE FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    print(f"perf gate passed (budget {args.max_slowdown:.2f}x)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
