"""Share of the traced window in which the busiest chip is idle while
one of the dispatcher's `sim.*` spans is open on the host: each idle
gap goes to the innermost span open at its midpoint, as
`device_idle_pct.sweep`'s breakdown does, and the gaps under no such
span ("untraced host": the harness's loop and wait) are left out."""
from bench import spans


def read(ctx):
    t = ctx.trace
    if not spans.opened(t):
        return None
    gaps = t.idle_gaps(lambda n: n.startswith(spans.PREFIX), n=1 << 30)
    idle = sum(s for name, s in gaps if name != "untraced host")
    return 100.0 * idle / t.window_s
