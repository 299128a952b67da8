"""Run the simulator's main paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: sweep, serve, decode, lowering
    python chip_smoke.py --chips 4   # four chips: the fig7 grid sharded over
                                     # the fleet mesh vs the same grid on one

Every phase goes through the entry points a user calls, at the sizes the
repo's benchmarks use, in this one process:

* sweep — `sweep_fleet(path="auto")` over the Fig. 7 grid (2 quanta x 50
  pairs x 3 slot counts, P=2, 60K-access traces, 160K steps) and the P=4
  fleet sweep.  Both must run the compiled window kernel and equal the
  jnp window pass bit for bit; the first two pairs also equal the
  cycle-by-cycle scan.  Prints the Fig. 7 anchor (4slot@20K vs IMF).
* serve — the chaos storm under warm recovery, with its epoch-6
  checkpoint restored into a fresh replacer, and the 256-tenant / 32-core
  fleet-scale serve with incremental re-solve.  Each report must equal
  the same serve run with the jnp window pass, field by field.
* decode — `SlotServeEngine` over arctic-480b's 128-expert top-2 router
  (width-reduced), 16 expert shards of 2 slots, 12 decode steps; logits
  must be finite.
* lowering — one model-zoo tenant trace, lowered from HLO compiled for
  the CPU device, must carry the checksum the CPU backend gives.

Each phase prints the device kind, its host wall time (compilation
included; not a device metric) and checksums of its results.  Without a
TPU, on a comparison that differs, or when a phase raises, the script
exits non-zero and prints no result line; otherwise its last line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import zlib
from unittest import mock

import numpy as np

# one lowered tenant trace and its crc32 as the CPU backend lowers it
LOWERED_TRACE = ("qwen1.5-4b:decode", 6_000)
LOWERED_TRACE_CRC = 0x83B5BFE0


def crc(*arrays) -> str:
    h = 0
    for a in arrays:
        h = zlib.crc32(np.ascontiguousarray(np.asarray(a)).tobytes(), h)
    return f"{h:08x}"


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def same_grid(what: str, got, want) -> str:
    """Bit-for-bit equality of two FleetResults; returns their crc32."""
    for name, a, b in zip(got._fields, got, want):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"{what}: field {name} differs")
    return crc(*got)


def same_report(what: str, got, want) -> str:
    """Field-by-field equality of two OnlineReports; returns a crc32."""
    a, b = dataclasses.asdict(got), dataclasses.asdict(want)
    diff = [k for k in a if a[k] != b[k]]
    check(not diff, f"{what}: report fields {diff} differ")
    return f"{zlib.crc32(repr(a).encode()):08x}"


@contextlib.contextmanager
def kernel_traces():
    """Count the window kernel's entry points traced inside the block.
    jit traces once per new shape, so a first run that takes the kernel
    counts at least one; a run that silently took another engine counts
    none."""
    from repro.kernels import window_distance as wd
    with mock.patch.object(wd, "window_grid", wraps=wd.window_grid) as g, \
            mock.patch.object(wd, "window_cell", wraps=wd.window_cell) as c:
        yield lambda: g.call_count + c.call_count


def on_kernel(what: str, clock, name: str, fn, *args, **kw):
    """`clock(name, fn, ...)`, failing unless it traced the kernel."""
    with kernel_traces() as traced:
        out = clock(name, fn, *args, **kw)
        check(traced() > 0, f"{what} never reached the window kernel")
    return out


class Clock:
    """Host wall time of each named step, compilation included."""

    def __init__(self):
        self.steps: list[str] = []

    def __call__(self, name: str, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        self.steps.append(f"{name} {time.perf_counter() - t0:.2f}s")
        return out


def phase_sweep(clock: Clock) -> list[str]:
    from benchmarks import fig7_multi
    from repro.core import scheduler

    pairs = scheduler.make_pairs()
    res = on_kernel("fig7 sweep", clock, "fig7[kernel]", fig7_multi.grid,
                    pairs)
    ref = clock("fig7[jnp]", fig7_multi.grid, pairs, use_kernel="jnp")
    fig7_crc = same_grid("fig7 kernel vs jnp", res, ref)
    scan = clock("fig7[scan,2 pairs]", fig7_multi.grid, pairs[:2],
                 path="scan")
    same_grid("fig7 kernel vs scan (first 2 pairs)",
              type(res)(*(x[:, :2] for x in res)), scan)
    _, agg = fig7_multi.run(pairs, res=res)
    anchor = float(np.mean(agg[("4slot", 20_000)]))

    fleets = scheduler.make_fleets(fig7_multi.FLEET_K)[:24]
    p4 = on_kernel("P=4 fleet sweep", clock, "p4[kernel]",
                   fig7_multi.fleet_grid, fleets, 20_000)
    p4_ref = clock("p4[jnp]", fig7_multi.fleet_grid, fleets, 20_000,
                   use_kernel="jnp")
    p4_crc = same_grid("P=4 fleet sweep kernel vs jnp", p4, p4_ref)
    return [f"fig7 grid {np.asarray(res.cycles).shape} crc {fig7_crc}: "
            f"kernel == jnp, first 2 pairs == scan",
            f"P=4 fleet grid {np.asarray(p4.cycles).shape} crc {p4_crc}: "
            f"kernel == jnp",
            f"fig7 anchor 4slot@20K vs IMF {anchor:.4f} (paper 0.82)"]


def phase_serve(clock: Clock) -> list[str]:
    from benchmarks import chaos_serve
    from benchmarks import fleet_scale_study as fss
    from repro.kernels import window_distance
    from repro.sched import ContentionModel

    def chaos():
        snaps: dict = {}
        rep = chaos_serve._serve(ContentionModel(chaos_serve.PCFG), "warm",
                                 snap_box=snaps)
        epoch, resumed = chaos_serve.resume_from(snaps)
        same_report(f"chaos restore from epoch {epoch}", resumed, rep)
        return rep

    label, tenants, topo = fss.FULL_SIZES[0]

    def fleet_scale():
        rep, _, _ = fss._serve(ContentionModel(fss.PCFG), topo,
                               fss._events(tenants), "incremental")
        return rep

    lines = []
    for name, serve in (("chaos", chaos), (f"fleet_scale {label}",
                                            fleet_scale)):
        rep = on_kernel(f"{name} serve", clock, f"{name}[kernel]", serve)
        mode = window_distance.DEFAULT_MODE
        window_distance.set_default_mode("jnp")
        try:
            ref = clock(f"{name}[jnp]", serve)
        finally:
            window_distance.set_default_mode(mode)
        lines.append(f"{name} report crc {same_report(name, rep, ref)}: "
                     f"kernel == jnp, {rep.migrations} migrations, worst "
                     f"lifetime slowdown {rep.worst_lifetime_slowdown:.4f}")
    return lines


def phase_decode(clock: Clock) -> list[str]:
    import jax

    from benchmarks import perf_slot_decode as psd
    from repro.models import transformer

    steps = 12
    _, cfg = psd.reduced_config()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = psd.make_engine(cfg, params, slots=2, hit_bias=0.0, steps=steps)
    rep = clock("decode", eng.run, steps)
    check(rep["steps"] == steps, f"decode ran {rep['steps']} steps")
    check(rep["nonfinite_steps"] == 0,
          f"{rep['nonfinite_steps']} decode steps gave non-finite logits")
    return [f"{steps} decode steps, {cfg.num_experts} experts top-"
            f"{cfg.top_k}, {psd.SHARDS} shards x 2 slots: finite logits, "
            f"fills {rep['fills']} accesses {rep['accesses']} hit rate "
            f"{rep['hit_rate']:.4f}"]


def phase_lowering(clock: Clock) -> list[str]:
    from repro import workloads

    name, length = LOWERED_TRACE
    got = crc(clock("lower", workloads.build_trace, name, length))
    check(int(got, 16) == LOWERED_TRACE_CRC,
          f"{name} trace crc {got} != CPU {LOWERED_TRACE_CRC:08x}")
    return [f"{name} x {length} lowered trace crc {got} == CPU value"]


def phase_fleet_mesh(clock: Clock) -> list[str]:
    from benchmarks import fig7_multi
    from repro.core import scheduler, simulator

    ndev = simulator.fleet_mesh_size()
    check(ndev == 4, f"fleet mesh spans {ndev} devices, expected 4")
    pairs = scheduler.make_pairs()
    sharded = on_kernel("fig7 mesh sweep", clock, "fig7[4-device mesh]",
                        fig7_multi.grid, pairs)
    # the same sweep with no mesh runs whole on the default device
    with mock.patch.object(simulator, "_fleet_mesh", lambda: None):
        one = clock("fig7[1 device]", fig7_multi.grid, pairs)
    got = same_grid("fig7 4-device mesh vs 1 device", sharded, one)
    return [f"fig7 grid crc {got}: sharded over {ndev} devices == one "
            f"device"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the fig7 grid on the four-chip mesh "
                         "against the same grid on one chip")
    args = ap.parse_args(argv)

    # the lowering phase compiles for the CPU device, so keep its backend
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "src"))
    from benchmarks.run import use_compile_cache
    cache = use_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    check(len(devs) == args.chips,
          f"{len(devs)} devices visible, --chips {args.chips}")
    from repro.kernels import window_distance
    check(window_distance.resolve(None) == (True, False),
          f"window pass would not run the compiled kernel (mode "
          f"{window_distance.DEFAULT_MODE!r})")

    print(f"# {len(devs)} x {dev.device_kind}, jax {jax.__version__}, "
          f"compile cache {cache}", flush=True)
    phases = ([phase_fleet_mesh] if args.chips == 4 else
              [phase_sweep, phase_serve, phase_decode, phase_lowering])
    for phase in phases:
        clock = Clock()
        t0 = time.perf_counter()
        lines = phase(clock)
        print(f"{phase.__name__[6:]}: {dev.device_kind}, host wall time "
              f"incl. compile {time.perf_counter() - t0:.1f}s "
              f"({', '.join(clock.steps)})", flush=True)
        for line in lines:
            print(f"  {line}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
