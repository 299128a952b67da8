"""HLO-text analysis for the roofline: FLOPs, bytes and collective traffic
with correct `while`-loop (lax.scan) accounting.

XLA's `compiled.cost_analysis()` counts a while-loop body ONCE regardless
of trip count — useless for layer-scanned models (80x undercount).  This
module re-derives the three roofline numerators by walking the optimized
HLO computation graph:

  * per computation: dot FLOPs (2 * out_elems * contraction), elementwise
    FLOPs (1/output element of compute instructions), collective wire
    bytes (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute operand bytes), and an HBM-traffic proxy
    (operand + result bytes of every non-plumbing instruction — i.e.
    post-fusion boundaries, the standard fusion-level traffic model);
  * call graph roll-up: `fusion`/`call`/`conditional` add callee cost,
    `while` adds trip_count * body + trip_count * condition, with the trip
    count read from the loop-condition's comparison constant (scans lower
    to 0..N counters; unknown conditions conservatively count once).

Shapes in post-SPMD HLO are per-device, so all results are per-chip.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z]\d*[a-z]*\d*)\[([0-9,]*)\]")
_COMP_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->")
_INSTR = re.compile(
    r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*(\(?[^=]*?)\s*"
    r"([a-z][\w\-]*)\((.*)$")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_PLUMBING = {"parameter", "get-tuple-element", "tuple", "bitcast",
             "constant", "iota", "after-all", "custom-call"}


def _shape_elems_bytes(shape_str: str) -> tuple[int, int]:
    """Total (elements, bytes) over every array shape in the string."""
    elems = 0
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        nbytes += n * _DTYPE_BYTES[dt]
    return elems, nbytes


@dataclass
class Instr:
    name: str
    result: str          # result type string
    op: str
    rest: str            # operands + attrs (raw)
    operands: list = field(default_factory=list)
    is_root: bool = False


@dataclass
class Computation:
    name: str
    instrs: list = field(default_factory=list)
    shapes: dict = field(default_factory=dict)  # instr name -> result str


def parse_module(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in text.splitlines():
        line = re.sub(r"/\*.*?\*/", "", line)  # strip /*index=N*/ comments
        if not line.strip():
            continue
        if not line.startswith(" "):
            m = _COMP_HEADER.match(line.strip())
            if m and line.rstrip().endswith("{"):
                cur = Computation(m.group(1))
                comps[cur.name] = cur
                # parameters: name: shape pairs in the header
                for pm in re.finditer(r"([\w.\-]+):\s*(\(?[^,()]*(?:\([^)]*"
                                      r"\))?[^,()]*)", m.group(2)):
                    cur.shapes[pm.group(1)] = pm.group(2)
                continue
            if line.startswith("}"):
                cur = None
            continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        root, name, result, op, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", rest.split("),")[0])
        inst = Instr(name=name, result=result, op=op, rest=rest,
                     operands=operands, is_root=bool(root))
        cur.instrs.append(inst)
        cur.shapes[name] = result
    return comps


def _trip_count(cond: Computation) -> int:
    """Scan conditions compare the counter against a constant."""
    consts = {}
    for inst in cond.instrs:
        if inst.op == "constant":
            cm = re.search(r"constant\((-?\d+)\)", "constant(" + inst.rest)
            if cm:
                consts[inst.name] = int(cm.group(1))
    best = None
    for inst in cond.instrs:
        if inst.op in ("compare", "fusion") or "compare" in inst.rest:
            for opnd in inst.operands:
                if opnd in consts:
                    best = max(best or 0, consts[opnd])
    if best is None and consts:
        best = max(consts.values())
    return best if best and best > 0 else 1


def _dot_flops(inst: Instr, comp: Computation) -> float:
    out_elems, _ = _shape_elems_bytes(inst.result)
    contraction = 1
    cm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", inst.rest)
    if cm and inst.operands:
        lhs_shape = comp.shapes.get(inst.operands[0], "")
        sm = _SHAPE_RE.search(lhs_shape)
        if sm:
            dims = [int(d) for d in sm.group(2).split(",") if d]
            for idx in cm.group(1).split(","):
                if idx and int(idx) < len(dims):
                    contraction *= dims[int(idx)]
    return 2.0 * out_elems * contraction


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_counts: dict = field(default_factory=dict)

    def __iadd__(self, other):
        self.flops += other.flops
        self.bytes += other.bytes
        self.coll_bytes += other.coll_bytes
        for k, v in other.coll_counts.items():
            d = self.coll_counts.setdefault(k, {"count": 0, "bytes": 0})
            d["count"] += v["count"]
            d["bytes"] += v["bytes"]
        return self

    def scaled(self, k: float) -> "Cost":
        return Cost(self.flops * k, self.bytes * k, self.coll_bytes * k,
                    {n: {"count": v["count"] * k, "bytes": v["bytes"] * k}
                     for n, v in self.coll_counts.items()})


_SLICING = ("dynamic-slice", "gather", "slice")


def _fusion_bytes(comp: Computation) -> float:
    """HBM traffic of one fused computation: each parameter is read once at
    its largest interior use (window-sized when every use is a slice), and
    the root result is written once.  Interior intermediates stay in
    registers/VMEM.  This is what makes scan-stacked params/caches cost one
    layer's bytes per trip instead of the whole (L, ...) stack."""
    params = {i.name for i in comp.instrs if i.op == "parameter"}
    read: dict[str, float] = {}
    root_bytes = 0.0
    users: dict[str, list] = {}
    for inst in comp.instrs:
        for o in inst.operands:
            users.setdefault(o, []).append(inst)
    # convert/bitcast/copy are transparent: XLA:CPU's bf16 normalization
    # wraps whole buffers in converts that a TPU compile (native bf16,
    # aliased in-place updates) never materialises
    TRANSPARENT = ("convert", "bitcast", "copy")

    def consumers(name):
        out = []
        frontier = [name]
        seen = set()
        while frontier:
            n = frontier.pop()
            for inst in users.get(n, []):
                if inst.name in seen:
                    continue
                seen.add(inst.name)
                if inst.op in TRANSPARENT:
                    frontier.append(inst.name)
                else:
                    out.append((n, inst))
        return out

    for p in params:
        best = 0.0
        for via, inst in consumers(p):
            _, out_b = _shape_elems_bytes(inst.result)
            if inst.op in _SLICING:
                size = float(out_b)           # window-sized read
            elif inst.op == "dynamic-update-slice" and \
                    via == inst.operands[0]:
                # aliased buffer: window write only (size of the update)
                upd = inst.operands[1] if len(inst.operands) > 1 else None
                size = float(_shape_elems_bytes(
                    comp.shapes.get(upd, ""))[1]) if upd else float(out_b)
            else:
                size = float(_shape_elems_bytes(comp.shapes.get(p, ""))[1])
            best = max(best, size)
        if users.get(p) and not consumers(p):
            # param feeds only transparent ops ending at the root
            best = float(_shape_elems_bytes(comp.shapes.get(p, ""))[1])
        read[p] = best
    # root result writes; aliased in-place roots (dynamic-update-slice /
    # scatter) write only their window; multi-output fusions root at a
    # tuple whose elements are handled individually
    by_name = {i.name: i for i in comp.instrs}

    def write_bytes(inst, depth=0) -> float:
        if depth > 8:
            return float(_shape_elems_bytes(inst.result)[1])
        if inst.op == "tuple":
            return sum(write_bytes(by_name[o], depth + 1)
                       for o in inst.operands if o in by_name)
        if inst.op in TRANSPARENT and inst.operands and \
                inst.operands[0] in by_name:
            return write_bytes(by_name[inst.operands[0]], depth + 1)
        if inst.op in ("dynamic-update-slice", "scatter") and \
                len(inst.operands) > 1:
            upd = inst.operands[1]
            return float(_shape_elems_bytes(comp.shapes.get(upd, ""))[1])
        return float(_shape_elems_bytes(inst.result)[1])

    root = next((i for i in comp.instrs if i.is_root), None)
    if root is None:
        for inst in reversed(comp.instrs):
            if inst.op != "parameter":
                root = inst
                break
    root_bytes = write_bytes(root) if root is not None else 0.0
    return sum(read.values()) + root_bytes


def _comp_cost(comp: Computation, comps, memo) -> Cost:
    if comp.name in memo:
        return memo[comp.name]
    memo[comp.name] = Cost()  # cycle guard
    total = Cost()
    for inst in comp.instrs:
        op = inst.op
        base = op.replace("-start", "").replace("-done", "")
        if op.endswith("-done"):
            continue
        # sub-computation roll-up
        called = []
        for attr, mult_kind in (("calls", "call"), ("body", "body"),
                                ("condition", "cond"),
                                ("branch_computations", "call"),
                                ("to_apply", "call")):
            am = re.search(attr + r"=\{?%?([\w.\-]+(?:, *%[\w.\-]+)*)\}?",
                           inst.rest)
            if am:
                for cname in re.findall(r"[\w.\-]+", am.group(1)):
                    if cname in comps:
                        called.append((mult_kind, cname))
        if op == "while":
            body = next((c for k, c in called if k == "body"), None)
            cond = next((c for k, c in called if k == "cond"), None)
            trips = _trip_count(comps[cond]) if cond else 1
            if body:
                total += _comp_cost(comps[body], comps, memo).scaled(trips)
            if cond:
                total += _comp_cost(comps[cond], comps, memo).scaled(trips)
            continue
        for _, cname in called:
            sub = _comp_cost(comps[cname], comps, memo)
            if op == "fusion":
                # fused interiors never materialise: keep FLOPs and
                # collectives; replace byte traffic with the fusion model
                # (per-parameter max read size — window-sized when consumed
                # via slicing — plus the root result write)
                sub = Cost(sub.flops, _fusion_bytes(comps[cname]),
                           sub.coll_bytes, sub.coll_counts)
            total += sub

        if base in _COLLECTIVES:
            _, nbytes = _shape_elems_bytes(inst.result)
            if base == "all-reduce" and op.endswith("-start"):
                nbytes //= 2  # (in, out) tuple on async start
            total += Cost(0.0, nbytes, nbytes,
                          {base: {"count": 1, "bytes": nbytes}})
            continue
        if base == "dot" or base == "convolution":
            total += Cost(_dot_flops(inst, comp), 0.0)
        elif base not in _PLUMBING and not called:
            out_elems, _ = _shape_elems_bytes(inst.result)
            total += Cost(float(out_elems), 0.0)
        # HBM-traffic proxy: results + operands of non-plumbing instrs.
        # Slicing ops only touch their window, not the whole operand —
        # critical for scan-stacked params/caches (a dynamic-slice of the
        # (L, ...) stack reads one layer, not L layers).
        if base == "fusion":
            continue  # traffic handled via _fusion_bytes above
        if base not in _PLUMBING or base == "custom-call":
            _, out_b = _shape_elems_bytes(inst.result)
            if base in ("dynamic-slice", "gather", "slice", "reshape",
                        "transpose", "broadcast", "copy", "convert",
                        "reduce"):
                opnd_b = out_b  # window/stream-sized read
                if base in ("reshape", "transpose", "copy", "convert"):
                    opnd_b = out_b
                if base == "reduce":
                    opnd_b = 0
                    for o in inst.operands:
                        if o in comp.shapes:
                            opnd_b += _shape_elems_bytes(comp.shapes[o])[1]
            elif base in ("dynamic-update-slice", "scatter"):
                # read update + write window; the big buffer aliases
                upd_b = 0
                if len(inst.operands) >= 2:
                    o = inst.operands[1]
                    if o in comp.shapes:
                        upd_b = _shape_elems_bytes(comp.shapes[o])[1]
                total += Cost(0.0, 2.0 * upd_b)
                continue
            else:
                opnd_b = 0
                for o in inst.operands:
                    if o in comp.shapes:
                        opnd_b += _shape_elems_bytes(comp.shapes[o])[1]
            total += Cost(0.0, out_b + opnd_b)
    memo[comp.name] = total
    return total


def _entry_name(text: str, comps: dict[str, Computation]) -> str:
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HEADER.match(line[len("ENTRY"):].strip())
            if m:
                entry = m.group(1)
            break
    if entry is None or entry not in comps:
        # fall back: largest computation
        entry = max(comps, key=lambda c: len(comps[c].instrs))
    return entry


def analyze_module(text: str) -> dict:
    """Per-device {flops, bytes, collective_bytes, collectives} with scan
    trip counts applied."""
    comps = parse_module(text)
    entry = _entry_name(text, comps)
    cost = _comp_cost(comps[entry], comps, {})
    return {
        "flops": cost.flops,
        "bytes": cost.bytes,
        "collective_bytes": cost.coll_bytes,
        "collectives": cost.coll_counts,
    }


# ---------------------------------------------------------------------------
# executed-op histogram (the workloads layer's per-op accounting source)
# ---------------------------------------------------------------------------

_FLOAT_DTYPES = {"f16", "bf16", "f32", "f64", "c64", "c128"}


def _dtype_class(shape_str: str) -> str:
    """'f' for float/complex results, 'i' for integer/pred ones."""
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return "i"
    return "f" if m.group(1) in _FLOAT_DTYPES else "i"


def _comp_hist(comp: Computation, comps, memo) -> dict[str, float]:
    """Executed-op histogram of one computation: ``"op:dtypeclass"`` ->
    output-element count (``"dot:f"`` / ``"convolution:f"`` -> FLOPs),
    rolled up through the call graph with `while` trip multipliers —
    the same traversal as `_comp_cost`, but keeping per-opcode identity
    instead of collapsing everything into three roofline numerators."""
    if comp.name in memo:
        return memo[comp.name]
    memo[comp.name] = {}  # cycle guard
    total: dict[str, float] = {}

    def acc(d: dict, k: float = 1.0) -> None:
        for key, v in d.items():
            total[key] = total.get(key, 0.0) + v * k

    for inst in comp.instrs:
        op = inst.op
        base = op.replace("-start", "").replace("-done", "")
        if op.endswith("-done"):
            continue
        called = []
        for attr, mult_kind in (("calls", "call"), ("body", "body"),
                                ("condition", "cond"),
                                ("branch_computations", "call"),
                                ("to_apply", "call")):
            am = re.search(attr + r"=\{?%?([\w.\-]+(?:, *%[\w.\-]+)*)\}?",
                           inst.rest)
            if am:
                for cname in re.findall(r"[\w.\-]+", am.group(1)):
                    if cname in comps:
                        called.append((mult_kind, cname))
        if op == "while":
            body = next((c for k, c in called if k == "body"), None)
            cond = next((c for k, c in called if k == "cond"), None)
            trips = _trip_count(comps[cond]) if cond else 1
            if body:
                acc(_comp_hist(comps[body], comps, memo), trips)
            if cond:
                acc(_comp_hist(comps[cond], comps, memo), trips)
            continue
        for _, cname in called:
            # fused/called interiors execute element-for-element
            acc(_comp_hist(comps[cname], comps, memo))
        if called or base in _COLLECTIVES or base in _PLUMBING:
            continue
        if base in ("dot", "convolution"):
            total["dot:f"] = total.get("dot:f", 0.0) + _dot_flops(inst, comp)
            continue
        if base in ("compare", "select", "reduce", "reduce-window"):
            # result dtype lies (compare -> pred, reduce collapses); judge
            # by the first operand, and charge reductions per input element
            opnd = comp.shapes.get(inst.operands[0], "") if inst.operands \
                else inst.result
            cls = _dtype_class(opnd)
            if base in ("reduce", "reduce-window"):
                n = float(_shape_elems_bytes(opnd)[0])
            else:
                n = float(_shape_elems_bytes(inst.result)[0])
        else:
            cls = _dtype_class(inst.result)
            n = float(_shape_elems_bytes(inst.result)[0])
        key = f"{base}:{cls}"
        total[key] = total.get(key, 0.0) + n
    memo[comp.name] = total
    return total


def op_histogram(text: str) -> dict[str, float]:
    """Executed-op histogram of a compiled module.

    Keys are ``"{hlo_op}:{f|i}"`` (float vs integer/pred class); values are
    executed output elements — except ``"dot:f"``, which carries FLOPs so
    callers can convert contractions into fused multiply-add counts.  While
    bodies are multiplied by their trip count, exactly like
    `analyze_module`, so layer-scanned models report per-layer ops L times.
    """
    comps = parse_module(text)
    return dict(_comp_hist(comps[_entry_name(text, comps)], comps, {}))


# ---------------------------------------------------------------------------
# legacy helpers (kept for tests / quick greps)
# ---------------------------------------------------------------------------

def xla_cost_analysis(compiled) -> dict:
    """Normalised view of ``Compiled.cost_analysis()`` across jax versions.

    Older jax returns a per-device *list* of property dicts; the pinned
    jax returns a single flat dict.  Callers always
    want one flat mapping — for a per-device list we take device 0 (SPMD
    programs are identical across devices).

    Backends are allowed to ship without cost analysis (PJRT plugins often
    stub it out, returning nothing or raising).  The workloads layer
    (`repro.workloads`) builds instruction mixes on top of this call, so a
    missing/empty analysis raises a `ValueError` naming the backend instead
    of surfacing as a bare `KeyError`/`AttributeError`/`None` deep inside
    the mix pipeline.
    """
    backend = getattr(compiled, "platform", None)
    if backend is None:
        try:
            import jax
            backend = jax.default_backend()
        except Exception:  # noqa: BLE001 — name *something* in the error
            backend = "<unknown>"
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # noqa: BLE001
        raise ValueError(
            f"Compiled.cost_analysis() is unavailable on backend "
            f"{backend!r} ({type(e).__name__}: {e}) — this backend cannot "
            f"drive HLO cost accounting (repro.analysis.hlo / "
            f"repro.workloads)") from e
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not ca:
        raise ValueError(
            f"Compiled.cost_analysis() returned no properties on backend "
            f"{backend!r} — this backend cannot drive HLO cost accounting "
            f"(repro.analysis.hlo / repro.workloads)")
    return dict(ca)


def collective_stats(hlo_text: str) -> dict:
    res = analyze_module(hlo_text)
    out = dict(res["collectives"])
    out["total_bytes"] = res["collective_bytes"]
    return out


def count_op(hlo_text: str, opname: str) -> int:
    return len(re.findall(rf"\b{re.escape(opname)}\(", hlo_text))


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   *, peak_flops: float = 197e12,
                   hbm_bw: float = 819e9, link_bw: float = 50e9) -> dict:
    """Terms in seconds, all PER-DEVICE (post-SPMD shapes are per-chip)."""
    compute = flops / peak_flops
    memory = hbm_bytes / hbm_bw
    collective = coll_bytes / link_bw
    dominant = max(("compute", compute), ("memory", memory),
                   ("collective", collective), key=lambda kv: kv[1])[0]
    return {
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "dominant": dominant,
    }
