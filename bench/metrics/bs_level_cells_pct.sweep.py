"""Share of the grid cells the sweep engines were launched over whose
interleaved window pass ran the bitstream level (a bitstream cache
smaller than the fleet's merged tag set): 100 x cells_bs_level /
cells_launched, from the program's counters (`simulator.cell_counts`),
summed over the run.  A program that does not count them gives no
number."""


def read(ctx):
    from repro.core import simulator

    counts = getattr(simulator, "cell_counts", None)
    if counts is None:
        return None
    c = counts()
    if "cells_bs_level" not in c or not c["cells_launched"]:
        return None
    return 100.0 * c["cells_bs_level"] / c["cells_launched"]
