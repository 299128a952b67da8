"""Compile the main path's device programs for a described TPU v5e.

Interpret mode (tests/test_window_kernel.py) proves what the window
kernel computes; only the TPU compiler says whether it lowers at all —
block shapes, dynamic indexing, scalar placement, VMEM.  These tests
compile, without a chip, at the shapes the benchmarks and
`chip_smoke.py` run:

* `window_grid` at the Fig. 7 grid and the P=4 fleet sweep, and at the
  Fig. 7 grid with the bitstream level of a 4-entry bitstream cache,
  which a warm cache's kernel leaves out;
* `window_cell` at the chaos-serve resume shape (N=4 000) and the
  fleet-scale shape (N=768, ~8 tenants per core);
* the jnp `_sweep_impl` at the Fig. 7 grid, which must fit one chip;
* the fleet-axis mesh sweep (`simulator._mesh_sweep_preempted`) over
  four chips, running the kernel on each, at the Fig. 7 grid and at the
  P=4 fleet sweep's 88 padded fleets;
* the Fig. 7 kernel sweep and the stacked cold pass at the bitstream
  grid, which must hold no element gather or scatter over a stream:
  their lookups go through `repro.core.lookup`.

The kernel entry points are compiled directly with `interpret=False`:
`sweep_fleet` asks the running backend, which is the CPU here.  The
topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core import isa, simulator
from repro.core import stackdist_cold as sdc
from repro.core import stackdist_interleaved as sdi
from repro.kernels import window_distance as wd

HBM_BYTES = 16 * 2**30   # one v5e chip
WINDOW = 512             # simulator.interleave_window() on a TPU
NUM_TAGS = int(np.max(simulator.fleet_tag_table(isa.SCENARIO_2, 2))) + 1

# (fleets padded to the batch bucket, programs, trace length, steps,
#  quantum cells, slot counts, latencies) of each sweep grid
FIG7 = (52, 2, 60_000, 160_000, 2, 3, 1)
P4_FLEETS = (24, 4, 60_000, 240_000, 1, 1, 3)
# (traces, trace length = steps, slot counts, latencies, capacities,
#  penalties) of the bitstream study's grid
BITSTREAM = (5, 100_000, 1, 1, 4, 2)
# the most elements a gather or scatter may move: tables and histograms,
# never a stream
SMALL_GATHER = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, mem
    return used


def _kernel_names(compiled) -> list[str]:
    """The HLO instruction names of the compiled program's Pallas
    kernels (its `tpu_custom_call`s)."""
    return [line.split(" = ")[0].split()[-1]
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _grid_args(shape, sharding):
    b, p, n, _, q, k, l = shape
    return (_i32((b, p, n), sharding), _i32((b, p, n), sharding),
            _i32((k,), sharding), _i32((l,), sharding),
            _i32((q, p), sharding), _i32((p,), sharding),
            _i32((), sharding), _i32((), sharding))


@pytest.mark.parametrize("shape", [FIG7, P4_FLEETS], ids=["fig7", "p4"])
def test_window_grid_compiles(one_chip, shape):
    compiled = jax.jit(
        lambda *a: wd.window_grid(*a, num_tags=NUM_TAGS,
                                  total_steps=shape[3], window=WINDOW,
                                  interpret=False)
    ).lower(*_grid_args(shape, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel's own `name`, which the device trace's readers look for
    names = _kernel_names(compiled)
    assert names and all(n.startswith("%window_grid") for n in names), names
    _fits_one_chip(compiled)


def test_window_grid_with_bitstream_level_compiles(one_chip):
    """The Fig. 7 grid on a core with a 4-entry bitstream cache: the
    kernel with its bitstream level lowers under its VMEM limit, keeps
    its pinned name, and fits one chip."""
    compiled = jax.jit(
        lambda *a: wd.window_grid(*a, num_tags=NUM_TAGS,
                                  total_steps=FIG7[3], window=WINDOW,
                                  interpret=False, bs_entries=4)
    ).lower(*_grid_args(FIG7, one_chip)).compile()
    names = _kernel_names(compiled)
    assert names and all(n.startswith("%window_grid") for n in names), names
    _fits_one_chip(compiled)


def _eqns(jaxpr) -> int:
    """Equations of a jaxpr, those of its nested jaxprs included."""
    n = 0
    for e in jaxpr.eqns:
        n += 1
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _eqns(inner)
    return n


def _kernel_body_ops(bs_entries) -> int:
    """The operations of the Fig. 7 window kernel's body, as traced."""
    b, p, n, steps, q, k, l = FIG7
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    closed = jax.make_jaxpr(
        lambda *a: wd.window_grid(*a, num_tags=NUM_TAGS, total_steps=steps,
                                  window=WINDOW, interpret=False,
                                  bs_entries=bs_entries)
    )(s(b, p, n), s(b, p, n), s(k), s(l), s(q, p), s(p), s(), s())

    def find(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                return e.params["jaxpr"]
            for v in e.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    found = find(inner)
                    if found is not None:
                        return found
        return None

    return _eqns(find(closed.jaxpr))


def test_warm_kernel_leaves_the_bitstream_level_out():
    """A 64-entry bitstream cache holds Fig. 7's ten tags: the engine
    passes the kernel no bitstream level, and the kernel it then runs
    has fewer operations than the 4-entry core's, whose level it
    leaves out."""
    warm = sdi.bs_level(64, NUM_TAGS)
    assert warm is None
    assert _kernel_body_ops(warm) < _kernel_body_ops(4)


# (programs, trace length, steps): a chaos-serve epoch advance and probe
# (PlacementConfig trace_len 4 000, epoch 8 000 / probe 2 000 steps), and
# a fleet-scale core of 8 tenants (trace_len 768, epoch 1 024 steps)
@pytest.mark.parametrize("p,n,steps", [(4, 4_000, 8_000), (2, 4_000, 2_000),
                                       (8, 768, 1_024)],
                         ids=["chaos-epoch", "chaos-probe", "fleet-scale"])
def test_window_cell_compiles(one_chip, p, n, steps):
    s = lambda *shape: _i32(shape, one_chip)  # noqa: E731
    seed = (s(NUM_TAGS), s(p), s(), s(), s(p), s(p), s(p), s(p), s())
    compiled = jax.jit(
        lambda *a: wd.window_cell(*a, num_tags=NUM_TAGS, total_steps=steps,
                                  window=WINDOW, materialise=True,
                                  interpret=False)
    ).lower(s(p, n), s(p, n), s(), s(), s(p), s(p), s(), s(),
            seed).compile()
    assert "tpu_custom_call" in compiled.as_text()
    names = _kernel_names(compiled)
    assert names and all(n.startswith("%window_cell") for n in names), names
    _fits_one_chip(compiled)


def test_jnp_sweep_fits_one_chip(one_chip):
    """The jnp window pass (`use_kernel="jnp"`, and the vmap^4 the kernel
    replaces) at the Fig. 7 grid compiles and fits one chip's HBM."""
    b, p, n, steps, q, k, l = FIG7
    s = lambda *shape: _i32(shape, one_chip)  # noqa: E731
    compiled = jax.jit(
        lambda *a: sdi._sweep_impl(*a, num_tags=NUM_TAGS,
                                   total_steps=steps, window=WINDOW,
                                   kernel=False, interpret=False)
    ).lower(s(b, p, n), s(p, isa.NUM_INSTRUCTIONS), s(isa.NUM_INSTRUCTIONS),
            s(k), s(l), s(q, p), s(p), s(), s()).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    _fits_one_chip(compiled)


def test_fleet_mesh_sweep_compiles_for_four_chips(topo, monkeypatch):
    """The fleet axis sharded over a 2x2 mesh, each chip running the
    compiled kernel over its 13 of the Fig. 7 grid's 52 fleets."""
    # steer the dispatcher to the compiled kernel: the backend here is CPU
    monkeypatch.setattr(sdi.window_distance, "resolve",
                        lambda use_kernel=None: (True, False))
    mesh = Mesh(np.array(topo.devices), ("fleet",))
    b, p, n, steps, _, _, _ = FIG7
    table = simulator.fleet_tag_table(isa.SCENARIO_2, p)
    part = jax.ShapeDtypeStruct((b, p, n), jnp.int32,
                                sharding=NamedSharding(
                                    mesh, PartitionSpec("fleet")))
    compiled = jax.jit(
        lambda pt: simulator._mesh_sweep_preempted(
            mesh, pt, table, jnp.asarray([2, 4, 8], jnp.int32),
            jnp.asarray([50], jnp.int32),
            np.asarray([[1_000] * p, [20_000] * p]), np.arange(p), 150, 100,
            NUM_TAGS, steps, WINDOW, None)
    ).lower(part).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits_one_chip(compiled) > 0


def test_p4_mesh_sweep_compiles_for_four_chips(topo, monkeypatch):
    """The P=4 fleet sweep's 85 fleets, padded to 88, over a 2x2 mesh:
    each chip runs the kernel, under its pinned name, on 22 fleets x 3
    latencies."""
    monkeypatch.setattr(sdi.window_distance, "resolve",
                        lambda use_kernel=None: (True, False))
    mesh = Mesh(np.array(topo.devices), ("fleet",))
    _, p, n, steps, _, _, _ = P4_FLEETS
    table = simulator.fleet_tag_table(isa.SCENARIO_2, p)
    part = jax.ShapeDtypeStruct((88, p, n), jnp.int32,
                                sharding=NamedSharding(
                                    mesh, PartitionSpec("fleet")))
    compiled = jax.jit(
        lambda pt: simulator._mesh_sweep_preempted(
            mesh, pt, table, jnp.asarray([4], jnp.int32),
            jnp.asarray([10, 50, 250], jnp.int32),
            np.asarray([[20_000] * p]), np.arange(p), 150, 100, NUM_TAGS,
            steps, WINDOW, None, 64)
    ).lower(part).compile()
    names = _kernel_names(compiled)
    assert names and all(n.startswith("%window_grid") for n in names), names
    _fits_one_chip(compiled)


_DEF = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = \w+\[([\d,]*)\]")


def _big_gathers_and_scatters(text: str) -> list[str]:
    """The gathers and scatters of an HLO module, fused ones included,
    that move more than `SMALL_GATHER` elements: a gather's output, a
    scatter's output or updates (its third operand)."""
    size = {}
    for line in text.splitlines():
        m = _DEF.match(line)
        if m:
            size[m.group(1)] = int(np.prod(
                [int(d) for d in m.group(2).split(",") if d]))
    big = []
    for line in text.splitlines():
        op = re.search(r" (gather|scatter)\((.*?)\)", line)
        m = _DEF.match(line)
        if not (op and m):
            continue
        moved = size[m.group(1)]
        if op.group(1) == "scatter":
            operands = re.findall(r"%[\w.\-]+", op.group(2))
            moved = max(moved, size.get(operands[2], 0))
        if moved > SMALL_GATHER:
            big.append(line.strip()[:200])
    return big


def test_big_gather_detector_sees_stream_gathers(one_chip):
    """The check below can fail: an opcode-table gather over a stream and
    a histogram scatter are both caught."""
    s = lambda *shape: _i32(shape, one_chip)  # noqa: E731
    # (XLA itself turns a gather from a small table by a 1-D index into
    # selects, so the index here is 2-D, as the engines' streams are)
    compiled = jax.jit(
        lambda t, x: (t[x], jnp.bincount(x.reshape(-1), length=11))
    ).lower(s(isa.NUM_INSTRUCTIONS), s(5, 100_000)).compile()
    big = _big_gathers_and_scatters(compiled.as_text())
    assert any(" gather(" in b for b in big), big
    assert any(" scatter(" in b for b in big), big


def test_fig7_kernel_sweep_has_no_stream_gathers(one_chip):
    """The opcode->tag and opcode->cost lookups of `_sweep_impl` compile
    to elementwise selects, beside the window kernel."""
    b, p, n, steps, q, k, l = FIG7
    s = lambda *shape: _i32(shape, one_chip)  # noqa: E731
    compiled = jax.jit(
        lambda *a: sdi._sweep_impl(*a, num_tags=NUM_TAGS,
                                   total_steps=steps, window=WINDOW,
                                   kernel=True, interpret=False)
    ).lower(s(b, p, n), s(p, isa.NUM_INSTRUCTIONS), s(isa.NUM_INSTRUCTIONS),
            s(k), s(l), s(q, p), s(p), s(), s()).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _big_gathers_and_scatters(text) == []


def test_cold_pass_has_no_stream_gathers(one_chip):
    """`sweep_cold` at the bitstream grid: the stream wrap, the opcode
    lookups, the tag-axis picks and the distance histogram hold no
    gather or scatter over the (traces, steps) streams."""
    b, n, k, l, e, x = BITSTREAM
    num_tags = isa.SCENARIO_2.num_tags
    s = lambda *shape: _i32(shape, one_chip)  # noqa: E731
    compiled = jax.jit(
        lambda *a: sdc.sweep_cold(*a, num_tags=num_tags, total_steps=n)
    ).lower(s(b, n), s(isa.NUM_INSTRUCTIONS), s(isa.NUM_INSTRUCTIONS),
            s(k), s(l), s(e), s(x)).compile()
    assert _big_gathers_and_scatters(compiled.as_text()) == []
    _fits_one_chip(compiled)
