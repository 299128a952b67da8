"""Simulated instructions committed in the window, in millions, over the
window's wall time.  A grid's work is its real cells times
`total_steps` (`bench/work.py`), which the check holds equal to the sum
of its `instructions` counters."""


def read(ctx):
    w = ctx.window
    return ctx.cell.work * w.units / w.seconds / 1e6
