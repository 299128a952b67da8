"""From a profiler trace to the numbers the per-layer metrics read.

`capture` records the JAX profiler over a block and returns the trace
as plain `Event`s; `Trace` reduces them.  A device op is an event on the
"XLA Ops" line of a device plane (`/device:TPU:<n>`), a launch an event
on its "XLA Modules" line; host spans are the events of the host
plane's lines.  Busy time is the union of a device's op intervals, so
overlapping ops count once.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
from typing import NamedTuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_xplane(path: str) -> list[Event]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend(Event(plane.name, line.name, e.name,
                             float(e.start_ns), float(e.duration_ns))
                       for e in line.events)
    return out


class Events(list):
    """The events of a capture, and the bytes its raw trace took."""

    raw_bytes = 0


@contextlib.contextmanager
def capture():
    """Trace the block; yields a list that holds the events once the
    block has ended.  The raw trace is written under TMPDIR and deleted."""
    import jax

    events = Events()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            yield events
        finally:
            jax.profiler.stop_trace()
        for path in glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                              recursive=True):
            events.raw_bytes += os.path.getsize(path)
            events.extend(read_xplane(path))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def union_ns(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


class Trace:
    """The reduction of one traced window.

    `t0_ns`, `t1_ns` bound the window on the trace's clock: the first
    start and the last end of the host spans named `window_span`.
    """

    def __init__(self, events: list[Event], window_span: str):
        self.events = events
        marks = [e for e in events if e.name == window_span]
        if not marks:
            raise ValueError(f"trace holds no {window_span!r} span")
        self.t0_ns = min(e.start_ns for e in marks)
        self.t1_ns = max(e.end_ns for e in marks)
        self.devices = sorted({e.plane for e in events
                               if _is_device(e.plane)})

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def _clip(self, e: Event):
        return max(e.start_ns, self.t0_ns), min(e.end_ns, self.t1_ns)

    def ops(self, device: str | None = None) -> list[Event]:
        return [e for e in self.events if e.line == OPS_LINE
                and _is_device(e.plane)
                and (device is None or e.plane == device)
                and e.end_ns > self.t0_ns and e.start_ns < self.t1_ns]

    def launches(self, device: str | None = None) -> list[Event]:
        return [e for e in self.events if e.line == MODULES_LINE
                and _is_device(e.plane)
                and (device is None or e.plane == device)
                and self.t0_ns <= e.start_ns < self.t1_ns]

    def busy_s(self, device: str) -> float:
        return union_ns(self._clip(e) for e in self.ops(device)) / 1e9

    def mean_busy_s(self) -> float:
        """Busy seconds averaged over the devices in the trace."""
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def op_seconds(self, match, device: str | None = None) -> float:
        """Summed duration of the ops whose name `match` accepts."""
        return sum(b - a for a, b in (self._clip(e) for e in self.ops(device)
                                      if match(e.name))) / 1e9

    def top_ops(self, n: int = 10) -> list[list]:
        """The `n` ops with the most device seconds (all devices), each
        named by its HLO instruction (the op's text up to " = ")."""
        acc: dict[str, float] = {}
        for e in self.ops():
            a, b = self._clip(e)
            name = e.name.split(" = ")[0]
            acc[name] = acc.get(name, 0.0) + (b - a) / 1e9
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, host_spans, n: int = 10) -> list[list]:
        """Idle time of the busiest device, summed by the host span that
        covers each gap's midpoint: the innermost event of the host
        plane whose name `host_spans` accepts, else "untraced host"."""
        if not self.devices:
            return []
        dev = max(self.devices, key=self.busy_s)
        ops = sorted(self._clip(e) for e in self.ops(dev))
        gaps, end = [], self.t0_ns
        for s, e in ops:
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        if self.t1_ns > end:
            gaps.append((end, self.t1_ns))
        spans = sorted((e for e in self.events if e.plane.startswith(
            "/host:") and host_spans(e.name)),
            key=lambda e: (e.start_ns, -e.dur_ns))
        # host spans of one thread nest, so a stack of the open spans
        # holds the innermost one on top
        acc: dict[str, float] = {}
        stack: list[Event] = []
        j = 0
        for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
            mid = (a + b) / 2
            while j < len(spans) and spans[j].start_ns <= mid:
                while stack and stack[-1].end_ns <= spans[j].start_ns:
                    stack.pop()
                stack.append(spans[j])
                j += 1
            while stack and stack[-1].end_ns <= mid:
                stack.pop()
            name = stack[-1].name if stack else "untraced host"
            acc[name] = acc.get(name, 0.0) + (b - a) / 1e9
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]
