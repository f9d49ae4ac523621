"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), all in seconds, per the assignment:

    compute    = HLO_FLOPs / (chips * PEAK_FLOPS)
    memory     = HLO_bytes / (chips * HBM_BW)
    collective = sum over collective ops of wire-byte cost / LINK_BW

``cost_analysis()`` on an SPMD-compiled module reports *per-partition* flops
and bytes, so chips-normalization is already done — we use them directly as
per-chip quantities.  Collective bytes are parsed from the optimized HLO
(``compiled.as_text()``), whose shapes are also per-partition; per-op wire
coefficients follow the standard ring/bidirectional-exchange costs:

    all-gather        result_bytes           (each chip receives the gathered copy)
    reduce-scatter    operand_bytes
    all-reduce        2 x result_bytes       (reduce-scatter + all-gather)
    all-to-all        operand_bytes
    collective-permute result_bytes

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<type>\([^()]*\)|\S+)\s+"
    r"(?P<op>[\w\-]+)\((?P<args>.*)$"
)

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def _type_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Scan optimized HLO; returns per-op-kind wire bytes + counts (per chip)."""
    shape_of: dict[str, int] = {}
    per_kind_bytes: dict[str, float] = defaultdict(float)
    per_kind_count: dict[str, int] = defaultdict(int)

    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, op = m.group("name"), m.group("type"), m.group("op")
        rb = _type_bytes(type_str)
        shape_of[name] = rb
        kind = None
        for k in COLLECTIVE_OPS:
            if op == k or op.startswith(k + "-start") or op == k + "-start":
                kind = k
                break
        if kind is None:
            continue
        # operand bytes: resolve %name references in the argument list
        operand_bytes = 0
        for ref in re.findall(r"%([\w.\-]+)", m.group("args")):
            operand_bytes += shape_of.get(ref, 0)
        if operand_bytes == 0:
            operand_bytes = rb  # fallback: assume same-size operand
        if kind == "all-gather":
            wire = rb
        elif kind == "all-reduce":
            wire = 2 * rb
        elif kind == "reduce-scatter":
            wire = operand_bytes
        elif kind == "all-to-all":
            wire = operand_bytes
        else:  # collective-permute
            wire = rb
        per_kind_bytes[kind] += wire
        per_kind_count[kind] += 1

    return {
        "bytes_by_kind": dict(per_kind_bytes),
        "count_by_kind": dict(per_kind_count),
        "total_wire_bytes": float(sum(per_kind_bytes.values())),
    }


# ---------------------------------------------------------------------------
# Scan-aware HLO analysis
# ---------------------------------------------------------------------------
# XLA's built-in cost analysis counts a while-loop body ONCE, so every scanned
# model (period scan, flash-attention chunk loops, SSM sequence scans) is
# undercounted by its trip count.  This analyzer walks the call graph
# (ENTRY -> fusion/call/while/conditional), multiplies while bodies by their
# trip count (recovered from the loop condition's s32 constant), and
# accumulates dot FLOPs, HBM-traffic bytes (operands+results at fusion
# boundaries) and collective wire bytes per chip.

# computation headers sit at column 0: ``%name (params...) -> type {`` or
# ``ENTRY %name (...) -> type {`` — params may nest parens (tuple types)
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_CALL_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_WHILE_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_WHILE_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_CONST_RE = re.compile(r"s32\[\]\{?[^}]*\}?\s+constant\((\d+)\)")
_DOT_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


@dataclasses.dataclass
class _Instr:
    name: str
    type_str: str
    op: str
    args: str
    rest: str


def _split_computations(hlo_text: str) -> dict[str, list[_Instr]]:
    comps: dict[str, list[_Instr]] = {}
    cur: list[_Instr] | None = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            h = _COMP_HDR_RE.match(line)
            if h:
                cur = comps.setdefault(h.group(1), [])
                continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        args_rest = m.group("args")
        depth, idx = 1, 0
        for idx, ch in enumerate(args_rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        args, rest = args_rest[:idx], args_rest[idx + 1 :]
        cur.append(_Instr(m.group("name"), m.group("type"), m.group("op"), args, rest))
    return comps


def _shape_dims(type_str: str) -> list[tuple[str, list[int]]]:
    out = []
    for dtype, dims in _SHAPE_RE.findall(type_str):
        out.append((dtype, [int(d) for d in dims.split(",")] if dims else []))
    return out


@dataclasses.dataclass
class HLOAnalysis:
    flops: float = 0.0
    bytes_hbm: float = 0.0
    wire_bytes: float = 0.0
    wire_by_kind: dict = dataclasses.field(default_factory=dict)
    coll_count_by_kind: dict = dataclasses.field(default_factory=dict)
    n_while: int = 0
    max_trip: int = 1


def analyze_hlo(hlo_text: str) -> HLOAnalysis:
    comps = _split_computations(hlo_text)
    # global shape table (instruction names are module-unique in practice;
    # collisions across computations resolve to same-shape params anyway)
    shape_of: dict[str, str] = {}
    for instrs in comps.values():
        for ins in instrs:
            shape_of[ins.name] = ins.type_str

    fused_names = set()
    for instrs in comps.values():
        for ins in instrs:
            if ins.op == "fusion":
                m = _CALL_RE.search(ins.rest)
                if m:
                    fused_names.add(m.group(1))

    res = HLOAnalysis()
    memo: dict[tuple[str, bool], tuple] = {}

    def instr_flops(ins: _Instr) -> float:
        tb = _shape_dims(ins.type_str)
        n_out = sum(float(_prod(d)) for _, d in tb)
        if ins.op == "dot":
            md = _DOT_DIMS_RE.search(ins.rest)
            refs = re.findall(r"%([\w.\-]+)", ins.args)
            contract = 1.0
            if md and refs:
                lhs_shape = _shape_dims(shape_of.get(refs[0], ""))
                if lhs_shape:
                    dims = lhs_shape[0][1]
                    for di in (int(x) for x in md.group(1).split(",") if x):
                        if di < len(dims):
                            contract *= dims[di]
            return 2.0 * n_out * contract
        if ins.op in ("reduce", "reduce-window"):
            refs = re.findall(r"%([\w.\-]+)", ins.args)
            n_in = sum(
                float(_prod(d)) for r in refs for _, d in _shape_dims(shape_of.get(r, ""))
            )
            return max(n_in, n_out)
        if ins.op in ("parameter", "constant", "get-tuple-element", "tuple", "bitcast",
                      "copy", "reshape", "transpose", "broadcast", "iota", "while",
                      "fusion", "call", "conditional", "custom-call"):
            return 0.0
        return n_out  # elementwise and everything else: 1 flop per output elem

    def instr_bytes(ins: _Instr) -> float:
        if ins.op in ("parameter", "constant", "get-tuple-element", "tuple", "bitcast",
                      "while", "call", "conditional"):
            return 0.0
        total = _type_bytes(ins.type_str)
        for r in re.findall(r"%([\w.\-]+)", ins.args):
            total += _type_bytes(shape_of.get(r, ""))
        return float(total)

    def wire_cost(ins: _Instr) -> tuple[str, float] | None:
        kind = None
        for k in COLLECTIVE_OPS:
            if ins.op.startswith(k):
                kind = k
                break
        if kind is None:
            return None
        rb = _type_bytes(ins.type_str)
        ob = sum(_type_bytes(shape_of.get(r, "")) for r in
                 re.findall(r"%([\w.\-]+)", ins.args)) or rb
        if kind == "all-gather":
            wire = rb
        elif kind == "all-reduce":
            wire = 2 * rb
        elif kind in ("reduce-scatter", "all-to-all"):
            wire = ob
        else:
            wire = rb
        return kind, float(wire)

    def trip_count(cond_name: str) -> int:
        ints = []
        for ins in comps.get(cond_name, []):
            ints += [int(x) for x in _CONST_RE.findall(
                f"{ins.type_str} {ins.op}({ins.args}){ins.rest}"
            )]
            if ins.op == "constant" and ins.type_str.startswith("s32[]"):
                m2 = re.search(r"constant\((\d+)\)", f"{ins.op}({ins.args})")
                if m2:
                    ints.append(int(m2.group(1)))
        return max(ints) if ints else 1

    def walk(comp_name: str, in_fusion: bool) -> tuple:
        key = (comp_name, in_fusion)
        if key in memo:
            return memo[key]
        fl = by = wi = 0.0
        wk: dict[str, float] = {}
        ck: dict[str, int] = {}
        for ins in comps.get(comp_name, []):
            fl += instr_flops(ins)
            if not in_fusion:
                by += instr_bytes(ins)
            w = wire_cost(ins)
            if w:
                wk[w[0]] = wk.get(w[0], 0.0) + w[1]
                ck[w[0]] = ck.get(w[0], 0) + 1
                wi += w[1]
            if ins.op == "while":
                mb = _WHILE_BODY_RE.search(ins.rest)
                mc = _WHILE_COND_RE.search(ins.rest)
                if mb and mc:
                    body, cond = mb.group(1), mc.group(1)
                    t = trip_count(cond)
                    res.n_while += 1
                    res.max_trip = max(res.max_trip, t)
                    bfl, bby, bwi, bwk, bck = walk(body, in_fusion)
                    fl += t * bfl
                    by += t * bby
                    wi += t * bwi
                    for kk, vv in bwk.items():
                        wk[kk] = wk.get(kk, 0.0) + t * vv
                    for kk, vv in bck.items():
                        ck[kk] = ck.get(kk, 0) + t * vv
            elif ins.op in ("fusion", "call", "conditional", "custom-call"):
                m = _CALL_RE.search(ins.rest)
                if m:
                    sub_fused = in_fusion or ins.op == "fusion"
                    bfl, bby, bwi, bwk, bck = walk(m.group(1), sub_fused)
                    fl += bfl
                    by += bby
                    wi += bwi
                    for kk, vv in bwk.items():
                        wk[kk] = wk.get(kk, 0.0) + vv
                    for kk, vv in bck.items():
                        ck[kk] = ck.get(kk, 0) + vv
        memo[key] = (fl, by, wi, wk, ck)
        return memo[key]

    entry = None
    for name in comps:
        if name.startswith("main") or ".main" in name or name.endswith("main"):
            entry = name
    if entry is None:  # fall back: the largest computation
        entry = max(comps, key=lambda n: len(comps[n]))
    # avoid double-walking computations reachable only via fusion at top level
    fl, by, wi, wk, ck = walk(entry, False)
    res.flops = fl
    res.bytes_hbm = by
    res.wire_bytes = wi
    res.wire_by_kind = wk
    res.coll_count_by_kind = ck
    return res


def _prod(dims) -> float:
    p = 1.0
    for d in dims:
        p *= d
    return p


@dataclasses.dataclass
class RooflineTerms:
    flops_per_chip: float
    bytes_per_chip: float
    wire_bytes_per_chip: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_per_chip: float = 0.0
    useful_ratio: float = 0.0

    def as_dict(self):
        return dataclasses.asdict(self)


def derive_terms(
    cost: dict, collectives: dict, model_flops_total: float = 0.0, chips: int = 1
) -> RooflineTerms:
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    wire = float(collectives.get("total_wire_bytes", 0.0))
    compute_s = flops / PEAK_FLOPS
    memory_s = byts / HBM_BW
    collective_s = wire / LINK_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf_chip = model_flops_total / max(chips, 1)
    return RooflineTerms(
        flops_per_chip=flops,
        bytes_per_chip=byts,
        wire_bytes_per_chip=wire,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops_per_chip=mf_chip,
        useful_ratio=(mf_chip / flops) if flops > 0 else 0.0,
    )


# ---------------------------------------------------------------------------
# %-of-peak for engine programs (the benchmark-row wiring)
# ---------------------------------------------------------------------------
# The dry-run path above targets the TPU v5e constants; the engine benches
# analyze compiled programs for whatever device runs them, so the roofline
# keys its peaks by ``jax.devices()[0].device_kind``.  A device missing here
# is an error, never a default.
#
#   "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e" —
#       197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI (4 links x 50 GB/s).
#   "cpu": NOT a device peak.  A CPU-only relative figure (one commodity
#       core: a few GFLOP/s of f32, ~10 GB/s stream bandwidth), kept only so
#       the CPU scaling bench (benchmarks/scaling_bench.py) can track its
#       own "% of peak" across runs on one runner class.

DEVICE_PEAKS = {
    "TPU v5 lite": {"peak_flops": PEAK_FLOPS, "mem_bw": HBM_BW, "link_bw": LINK_BW},
    "cpu": {"peak_flops": 8e9, "mem_bw": 10e9, "link_bw": 10e9},
}


def device_peaks(device_kind: str | None = None) -> dict:
    """{peak_flops, mem_bw, link_bw} for ``device_kind`` (default: the kind
    of the first live jax device).  Raises for a kind with no recorded
    peaks."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device kind {device_kind!r}: add its "
            "published figures, with their source, to DEVICE_PEAKS"
        ) from None


def analyze_compiled(hlo_text: str, device_kind: str | None = None) -> dict:
    """Scan-aware cost of one compiled module + its roofline-predicted
    runtime on ``device_kind`` (see ``device_peaks``): ``{flops, bytes_hbm, wire_bytes, n_while,
    max_trip, predicted_s, compute_s, memory_s, collective_s, dominant}``.

    ``predicted_s`` is the max of the three terms — the time a perfectly
    overlapped execution at peak rates would need.
    """
    an = analyze_hlo(hlo_text)
    peaks = device_peaks(device_kind)
    compute_s = an.flops / peaks["peak_flops"]
    memory_s = an.bytes_hbm / peaks["mem_bw"]
    collective_s = an.wire_bytes / peaks["link_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    return {
        "flops": an.flops,
        "bytes_hbm": an.bytes_hbm,
        "wire_bytes": an.wire_bytes,
        "n_while": an.n_while,
        "max_trip": an.max_trip,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "predicted_s": max(terms.values()),
        "dominant": dominant,
    }


def percent_of_peak(
    analysis: dict, measured_s: float, calls: float = 1.0
) -> float:
    """Roofline utilization of a measured wall clock: 100 x predicted / actual
    for ``calls`` executions of the analyzed module.

    100 means the run hit the device's roofline (never in practice; the
    peaks are marketing numbers and the analysis undercounts overheads);
    the value is a *relative* efficiency tracked across PRs — a warm sweep
    whose %-of-peak halves got slower in a way wall clock alone can't
    attribute.  Clamped below at 0; not clamped above (a >100 reading means
    the peaks in ``DEVICE_PEAKS`` are stale for this machine —
    visible is better than silently capped).
    """
    if measured_s <= 0:
        raise ValueError(f"measured_s must be > 0, got {measured_s}")
    return max(0.0, 100.0 * analysis["predicted_s"] * calls / measured_s)


# ---------------------------------------------------------------------------
# Analytic MODEL_FLOPS (the 6ND / 2ND yardstick)
# ---------------------------------------------------------------------------
def active_params(cfg) -> int:
    """Total params counted with only top_k of n_experts active per MoE layer."""
    import jax

    from repro import models
    from repro.models.module import tree_size

    shapes = jax.eval_shape(lambda k: models.init(k, cfg)[0], jax.random.PRNGKey(0))
    total = tree_size(jax.tree.leaves(shapes))
    if cfg.moe is None:
        return total
    # subtract the inactive expert fraction of expert weights
    expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        keys = [str(getattr(p, "key", "")) for p in path]
        if any(k in ("w_gate", "w_up", "w_down") for k in keys) and leaf.ndim >= 3:
            expert += int(leaf.size)
    inactive_frac = 1.0 - cfg.moe.top_k / cfg.moe.n_experts
    return int(total - expert * inactive_frac)


def model_flops(cfg, shape, n_active: int | None = None, d_redundancy: int = 1) -> float:
    """6*N*D for a train step (x d for LAD redundancy), 2*N*D per served token."""
    n_act = n_active if n_active is not None else active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens * d_redundancy
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens
    # decode: one token per sequence
    return 2.0 * n_act * shape.global_batch
