"""The control of a cell's comparison: the plain reference with the
configuration's LRU guarantee broken (FIFO replacement) put in the
program's place, at the cell's own size.  Its numbers are the upper
readings the limits are set below; the benchmark's runs never run it.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] [--units 3]

For each seed: set-up as a run does, `--units` grids of the program,
then the check twice: on the program's grids and on the FIFO
reference's grids in their place.  Prints one JSON line per seed.
Like a run, it needs the cell's chips.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from bench import run  # noqa: E402


class Grid(NamedTuple):
    """A sweep's answers as the check reads them."""

    cycles: object
    instructions: object = None
    slot_misses: object = None
    bs_misses: object = None
    switches: object = None


def control_outputs(cell, outputs) -> list:
    """The answers of the control in place of the program's `outputs`."""
    grids = []
    for i in range(len(outputs)):
        g = cell.reference(int(cell._offsets[i]), policy="fifo")
        if "instructions" not in g:   # a bitstream grid: per (B, K), (B, K, E)
            g = {"cycles": g["cycles"],
                 "slot_misses": g["slot_misses"][:, :, 0, 0, 0],
                 "bs_misses": g["bs_misses"][:, :, 0, :, 0]}
        grids.append(Grid(**g))
    return grids


def control(workload: str, seeds, units: int, *, chips=run.require_chips,
            root: str = run.ROOT) -> list[dict]:
    spec = run.Spec.load(workload, root)
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    run.configure_jax(root)
    import jax

    chips(spec.workload["chips"])
    kind = run.load_module(os.path.join(root, "bench", "kinds",
                                        spec.traffic["kind"] + ".py"))
    rows = []
    for seed in seeds:
        cell = kind.Cell(spec, seed)
        cell.warm()
        outputs = [jax.block_until_ready(cell.call(i)) for i in range(units)]
        sound, _ = cell.check(outputs)
        ctrl, failed = cell.check(control_outputs(cell, outputs))
        rows.append({"workload": workload, "seed": seed,
                     "program": {n: v for n, v, _ in sound},
                     "control": {n: v for n, v, _ in ctrl},
                     "control_failed_units": failed,
                     "limits": {n: lim for n, _, lim in sound}})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--units", type=int, default=3)
    args = ap.parse_args(argv)
    try:
        rows = control(args.workload, args.seeds, args.units)
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for r in rows:
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
