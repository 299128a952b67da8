"""Host time per grid from the call into the sweep entry to its return,
before `block_until_ready`: the dispatcher's own work, mean over the
window's grids."""


def read(ctx):
    d = ctx.window.dispatch_seconds()
    return 1e3 * sum(d) / len(d)
