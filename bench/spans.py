"""The program's own host spans in a traced window: the `sim.*` spans
that `repro.core.simulator` opens around the stages of its sweep
entries, read by the dispatcher's per-layer metrics.  A program that
opens none (one from before the spans) gives None, and the metric is
left out of the result line."""
from __future__ import annotations

PREFIX = "sim."


def _spans(trace, match):
    return [e for e in trace.events if e.plane.startswith("/host:")
            and match(e.name) and e.end_ns > trace.t0_ns
            and e.start_ns < trace.t1_ns]


def opened(trace) -> bool:
    """Whether the window holds any of the program's spans."""
    return bool(_spans(trace, lambda n: n.startswith(PREFIX)))


def host_seconds(trace, name: str) -> float | None:
    """Summed duration of the host spans called `name`, clipped to the
    window; None where the window holds none."""
    found = _spans(trace, lambda n: n == name)
    if not found:
        return None
    return sum(min(e.end_ns, trace.t1_ns) - max(e.start_ns, trace.t0_ns)
               for e in found) / 1e9


def host_ms_per_unit(ctx, name: str) -> float | None:
    """Milliseconds per unit of the window spent in spans called `name`."""
    s = host_seconds(ctx.trace, name)
    return None if s is None else 1e3 * s / ctx.window.units
