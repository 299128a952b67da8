"""Share of the grid cells the sweep engines were launched over that
are padding: 100 x (launched - asked for) / launched, from the
program's counters (`simulator.cell_counts`), summed over the run.
Every call of a cell has the same shape, so the share of the totals is
the share of each grid."""


def read(ctx):
    from repro.core import simulator

    counts = getattr(simulator, "cell_counts", None)
    if counts is None:
        return None
    c = counts()
    if not c["cells_launched"]:
        return None
    return 100.0 * (c["cells_launched"] - c["cells_real"]) \
        / c["cells_launched"]
