"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic come from `BENCHMARK.json`
at the root of the checkout.  The traffic file (`bench/traffic/<name>.json`)
names its kind, and `bench/kinds/<kind>.py` builds the cell from the
configuration, the traffic and the seed: its inputs, one unit of window
work (a sweep grid) and the comparison with the plain
reference.  Every metric is read by `bench/metrics/<metric>.py`.  Adding
a configuration, a traffic mix, a kind or a metric adds a file; nothing
here names one.

A run: set-up (imports, inputs from the seed, one warm call that compiles
or loads every program from the persistent cache in `bench/.jax_cache`),
then a closed loop of units for `--seconds`, then the check against the
reference.  With `--trace 0` the result carries the cell's end-to-end
metrics; with `--trace 1` the window runs under the profiler and the
result carries its per-layer metrics.  Without a TPU, or with another
number of chips than the cell asks for, the run exits 2 and prints no
result.  The last line of standard output is the result; the last lines
of standard error are each compared number beside its limit.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import statistics
import sys
import time
from dataclasses import dataclass, field

START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


class NoChip(RuntimeError):
    pass


def load_module(path: str):
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = "bench_" + os.path.basename(os.path.dirname(path)) + "_" + \
        os.path.basename(path)[:-3].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Spec:
    """One cell as `BENCHMARK.json` states it, with its files resolved."""

    bench: dict
    workload: dict
    config_path: str
    traffic: dict
    end_to_end: list
    per_layer: list
    root: str

    @classmethod
    def load(cls, name: str, root: str = ROOT) -> "Spec":
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; cells: "
                             f"{sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        config_path = os.path.join(root, configs[w["config"]]["file"])
        with open(os.path.join(root, "bench", "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)

        def mine(ms):
            return [m for m in ms if name in m.get("workloads", [name])]

        return cls(bench, w, config_path, traffic,
                   mine(bench["end_to_end"]), mine(bench["per_layer"]), root)


@dataclass
class Window:
    """What the measured window did, for the metric readers."""

    seconds: float = 0.0               # wall time of the whole window
    starts: list = field(default_factory=list)    # per unit, host clock
    returns: list = field(default_factory=list)   # entry returned
    dones: list = field(default_factory=list)     # results ready
    compiles: int = 0

    @property
    def units(self) -> int:
        return len(self.dones)

    def unit_seconds(self) -> list[float]:
        return [d - s for s, d in zip(self.starts, self.dones)]

    def dispatch_seconds(self) -> list[float]:
        return [r - s for s, r in zip(self.starts, self.returns)]


@dataclass
class Context:
    """Everything a metric reader may read."""

    spec: Spec
    cell: object
    device_kind: str
    setup_s: float
    window: Window
    trace: object = None    # bench.trace.Trace under --trace 1


def require_chips(n: int):
    """The devices of this run: `n` TPU chips, or NoChip."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) != n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devs)}")
    return devs


# The TPU runtime maps a premapped host buffer for transfers at start-up.
# At its default size, on a TPU v5e host without transparent hugepages,
# that took 5-15 s of every run's set-up and most of its spread; at this
# size, 1-5 s.  The cells' transfers are a few MB.
PREMAPPED_BUFFER_BYTES = 256 << 20


def configure_jax(root: str) -> str:
    """The TPU runtime's premapped buffer, and JAX's persistent
    compilation cache at a fixed path inside the checkout, for every
    program however quickly it compiles.  Call before JAX starts its
    backend."""
    os.environ["TPU_PREMAPPED_BUFFER_SIZE"] = str(PREMAPPED_BUFFER_BYTES)
    import jax

    path = os.path.join(root, "bench", ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def measure(cell, seconds: float, traced: bool):
    """The closed loop: units back to back until `seconds` have passed.
    Returns the Window, the units' outputs and the trace's events."""
    import jax
    import jax.monitoring

    from bench import trace as tr

    win = Window()
    outputs = []
    counting = [False]

    def on_event(name, *args, **kw):
        if counting[0] and name in COMPILE_EVENTS:
            win.compiles += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    # what set-up left behind moves to the permanent generation, so no
    # collection inside the window walks it
    gc.collect()
    gc.freeze()
    with (tr.capture() if traced else contextlib.nullcontext([])) \
            as captured:
        counting[0] = True
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            i = 0
            while True:
                s = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    out = cell.call(i)
                r = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(out)
                d = time.perf_counter()
                win.starts.append(s)
                win.returns.append(r)
                win.dones.append(d)
                outputs.append(out)
                i += 1
                if d - t0 >= seconds:
                    break
            win.seconds = time.perf_counter() - t0
        counting[0] = False
    return win, outputs, captured


def host_spans(root: str):
    """What names the host's work in an idle gap: the benchmark's own
    spans, and the functions of the program's files that the profiler's
    Python tracer records as "$<file>:<line> <function>"."""
    files = {f for _, _, fs in os.walk(os.path.join(root, "src"))
             for f in fs if f.endswith(".py")}

    def accept(name: str) -> bool:
        return name.startswith("bench.") or (
            name.startswith("$") and name[1:].split(":")[0] in files)
    return accept


def read_metrics(metrics, ctx: Context) -> dict:
    out = {}
    for m in metrics:
        mod = load_module(os.path.join(ctx.spec.root, "bench", "metrics",
                                       m["name"] + ".py"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(argv=None, *, chips=require_chips, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = Spec.load(args.workload, root)
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    configure_jax(root)
    phases = {"imports": time.perf_counter() - START}
    try:
        devs = chips(spec.workload["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    phases["runtime"] = time.perf_counter() - START

    kind = load_module(os.path.join(root, "bench", "kinds",
                                    spec.traffic["kind"] + ".py"))
    cell = kind.Cell(spec, args.seed)
    phases["inputs"] = time.perf_counter() - START
    cell.warm()
    setup_s = time.perf_counter() - START
    phases["warm"] = setup_s

    win, outputs, events = measure(cell, args.seconds, bool(args.trace))
    peak = memory_peak(devs)
    trace = None
    if args.trace:
        from bench.trace import Trace
        trace = Trace(events, "bench.window")
        print(f"bench: trace of {len(events)} events, "
              f"{events.raw_bytes} bytes raw", file=sys.stderr)
    ctx = Context(spec, cell, devs[0].device_kind, setup_s, win, trace)
    metrics = read_metrics(spec.per_layer if args.trace
                           else spec.end_to_end, ctx)

    cell.release()
    t_check = time.perf_counter()
    checks, failed = cell.check(outputs)
    t_check = time.perf_counter() - t_check
    correct = all(v <= lim for _, v, lim in checks)
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": win.units, "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.mean_busy_s()
        device["window_s"] = trace.window_s
        result["breakdown"] = {
            "device_ops": trace.top_ops(10),
            "idle_gaps": trace.idle_gaps(host_spans(root), 10)}
    result["compiles_in_window"] = win.compiles
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in checks}
    print(f"bench: {args.workload} seed {args.seed}: {win.units} "
          f"{cell.unit}s in {win.seconds:.3f} s, setup {setup_s:.3f} s, "
          f"{win.compiles} compiles in the window, median unit "
          f"{statistics.median(win.unit_seconds()):.6f} s, check "
          f"{t_check:.3f} s", file=sys.stderr)
    marks = [0.0] + list(phases.values())
    print("bench: set-up phases (s): " + ", ".join(
        f"{k} {b - a:.3f}" for k, a, b in zip(phases, marks, marks[1:])),
        file=sys.stderr)
    slow = sorted(range(win.units), key=lambda i: -win.unit_seconds()[i])
    print("bench: slowest units (index, seconds, of which dispatch): " +
          ", ".join(f"({i}, {win.unit_seconds()[i]:.6f}, "
                    f"{win.dispatch_seconds()[i]:.6f})" for i in slow[:5]),
          file=sys.stderr)
    for n, v, lim in checks:
        print(f"check {n}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(run())
