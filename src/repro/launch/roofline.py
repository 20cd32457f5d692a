"""Roofline analysis from compiled HLO artifacts.

XLA's ``HloCostAnalysis`` visits while bodies once (verified empirically), so
deriving per-step cost for scan-over-layers models requires scaling loop bodies by
their trip counts. This module parses the post-optimization HLO text into
computations, extracts while trip counts from loop-condition constants, and
accumulates three terms per device:

  * flops      — 2*M*N*K per dot (plus 1 flop/element for fusions/reductions);
  * hbm bytes  — operands + results of top-level instructions (post-fusion, so
                 each fusion is one HBM round-trip — the standard model);
  * collective bytes — ring-model per-device bytes for all-gather / all-reduce /
                 reduce-scatter / all-to-all / collective-permute.

Hardware constants come from ``core.devices`` (the v5e row: 197 TFLOP/s bf16,
819 GB/s HBM, 50 GB/s per ICI link).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from ..core.devices import peaks

_TARGET = peaks()
PEAK_FLOPS = _TARGET.flops_bf16
HBM_BW = _TARGET.hbm_bw
LINK_BW = _TARGET.link_bw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SKIP_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "add-dependency", "opt-barrier", "partition-id", "replica-id",
    "custom-call",  # sharding annotations etc.
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\([^)]*\)|[^(]*?)\s*([\w\-]+)\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_elems(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n
    return total


def _first_shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Computation:
    name: str
    lines: List[str]
    params: Dict[str, str]          # param name -> type string
    instr_types: Dict[str, str]     # instr name -> result type string


def parse_computations(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    header_re = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\((.*?)\)\s*->")
    for raw in hlo.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" ") and "{" in line and "->" in line:
            m = header_re.match(line.strip())
            if m:
                params = {}
                for pm in re.finditer(r"%?([\w\.\-]+):\s*([^,)]+)", m.group(2)):
                    params[pm.group(1)] = pm.group(2)
                cur = Computation(m.group(1), [], params, {})
                comps[cur.name] = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        cur.lines.append(line.strip())
        im = _INSTR_RE.match(line.strip())
        if im:
            cur.instr_types[im.group(1)] = im.group(2)
    return comps


def _entry_name(hlo: str) -> Optional[str]:
    m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", hlo, re.M)
    return m.group(1) if m else None


def _trip_count(cond: Computation) -> int:
    """Loop bound: the max integer constant in the condition computation."""
    best = 1
    for line in cond.lines:
        for m in re.finditer(r"constant\((\d+)\)", line):
            best = max(best, int(m.group(1)))
    return best


def _operand_names(line: str) -> List[str]:
    m = re.search(r"\w\(([^)]*)\)", line)
    if not m:
        return []
    return re.findall(r"%([\w\.\-]+)", m.group(1))


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


@dataclasses.dataclass
class RooflineCosts:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_bytes: float = 0.0                       # per-device bytes on the fabric
    coll_by_kind: Dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float))
    coll_count: int = 0
    dot_flops: float = 0.0

    def merged(self, other: "RooflineCosts", mult: float) -> "RooflineCosts":
        out = RooflineCosts(
            self.flops + other.flops * mult,
            self.hbm_bytes + other.hbm_bytes * mult,
            self.coll_bytes + other.coll_bytes * mult,
            defaultdict(float, self.coll_by_kind),
            self.coll_count + int(other.coll_count * mult),
            self.dot_flops + other.dot_flops * mult)
        for k, v in other.coll_by_kind.items():
            out.coll_by_kind[k] += v * mult
        return out


_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _fusion_operand_bytes(body: "Computation", op_names: List[str],
                          types: Dict[str, str], rbytes: int) -> int:
    """HBM read bytes of a fusion's operands, slice-aware.

    A kLoop fusion whose body only *slices* a big operand (cache lookup,
    scan weight slice) reads the slice, not the operand — charging the full
    operand was measured to overcount the whisper decode cell ~40x. For each
    fusion parameter: if every body use is a slice/dynamic-slice/gather, charge
    the sliced result sizes; otherwise charge the full operand.
    """
    # map body param index -> slice-result bytes (None = used non-sliced)
    param_names = list(body.params)
    sliced: Dict[str, int] = {}
    nonsliced: set = set()
    for bl in body.lines:
        im = _INSTR_RE.match(bl)
        if not im:
            continue
        _, brtype, bop = im.groups()
        for o in _operand_names(bl):
            if o not in body.params:
                continue
            if bop in ("dynamic-slice", "slice", "gather"):
                sliced[o] = sliced.get(o, 0) + _shape_bytes(brtype)
            elif bop not in ("bitcast", "copy", "parameter"):
                nonsliced.add(o)
    total = 0
    for i, o in enumerate(op_names):
        full = _shape_bytes(types.get(o, ""))
        pname = param_names[i] if i < len(param_names) else None
        if pname is not None and pname in sliced and pname not in nonsliced:
            total += min(sliced[pname], full)
        else:
            total += full
    return total


def analyze_computation(comp: Computation, comps: Dict[str, Computation],
                        memo: Dict[str, RooflineCosts]) -> RooflineCosts:
    if comp.name in memo:
        return memo[comp.name]
    costs = RooflineCosts()
    types = dict(comp.params)
    types.update(comp.instr_types)

    for line in comp.lines:
        im = _INSTR_RE.match(line)
        if not im:
            continue
        name, rtype, op = im.groups()
        if op in ("while",):
            bm = re.search(r"body=%?([\w\.\-]+)", line)
            cm = re.search(r"condition=%?([\w\.\-]+)", line)
            if bm and bm.group(1) in comps:
                body_costs = analyze_computation(comps[bm.group(1)], comps, memo)
                trips = _trip_count(comps[cm.group(1)]) if cm and \
                    cm.group(1) in comps else 1
                costs = costs.merged(body_costs, trips)
            continue
        if op in ("conditional", "call"):
            for sub in re.findall(
                    r"(?:branch_computations=\{|to_apply=|"
                    r"called_computations=\{)%?([\w\.\-]+)", line):
                if sub in comps:
                    costs = costs.merged(
                        analyze_computation(comps[sub], comps, memo), 1)
            continue

        base = op[:-6] if op.endswith("-start") else op
        if base in _COLLECTIVES:
            k = _group_size(line)
            nbytes = _shape_bytes(rtype)
            if base == "all-reduce":
                moved = 2 * nbytes * (k - 1) / max(k, 1)
            elif base == "all-gather":
                moved = nbytes * (k - 1) / max(k, 1)
            elif base == "reduce-scatter":
                moved = nbytes * (k - 1)
            elif base == "all-to-all":
                moved = nbytes * (k - 1) / max(k, 1)
            else:  # collective-permute
                moved = nbytes
            costs.coll_bytes += moved
            costs.coll_by_kind[base] += moved
            costs.coll_count += 1
            # collectives also read/write HBM
            costs.hbm_bytes += 2 * nbytes
            continue
        if op.endswith("-done") or op in _SKIP_OPS:
            continue

        rbytes = _shape_bytes(rtype)
        if op == "dynamic-slice":
            # reads the slice, writes the result (not the whole operand)
            costs.hbm_bytes += 2 * rbytes
            continue
        if op == "dynamic-update-slice":
            # in-place: reads the update and writes it into the buffer
            ops_ = _operand_names(line)
            upd = _shape_bytes(types.get(ops_[1], "")) if len(ops_) > 1 else rbytes
            costs.hbm_bytes += 2 * upd
            continue
        op_names = _operand_names(line)
        if op == "fusion":
            fm = re.search(r"calls=%?([\w\.\-]+)", line)
            body = comps.get(fm.group(1)) if fm else None
            if body is not None:
                costs.hbm_bytes += rbytes + _fusion_operand_bytes(
                    body, op_names, types, rbytes)
            else:
                costs.hbm_bytes += rbytes + sum(
                    _shape_bytes(types[o]) for o in op_names if o in types)
        else:
            obytes = 0
            for o in op_names:
                if o in types:
                    obytes += _shape_bytes(types[o])
            costs.hbm_bytes += rbytes + obytes

        if op == "dot":
            ops_ = _operand_names(line)
            lhs_t = types.get(ops_[0], "") if ops_ else ""
            lhs_dims = _first_shape_dims(lhs_t)
            cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
            K = 1
            if cm and lhs_dims:
                for d in cm.group(1).split(","):
                    if d and int(d) < len(lhs_dims):
                        K *= lhs_dims[int(d)]
            # result elems already include batch dims
            f = 2.0 * _shape_elems(rtype) * K
            costs.flops += f
            costs.dot_flops += f
        elif op == "convolution":
            # rare here (conv frontends are stubbed); approximate via result*K
            costs.flops += 2.0 * _shape_elems(rtype) * 16
        elif op in ("fusion", "reduce", "reduce-window", "scatter", "select-and-scatter"):
            costs.flops += float(_shape_elems(rtype))
            # look inside fusions for dots (XLA:CPU keeps most dots unfused,
            # but output-fused dots exist)
            fm = re.search(r"calls=%?([\w\.\-]+)", line)
            if fm and fm.group(1) in comps:
                inner = analyze_computation(comps[fm.group(1)], comps, memo)
                if inner.dot_flops:
                    costs.flops += inner.dot_flops
                    costs.dot_flops += inner.dot_flops
        else:
            costs.flops += float(_shape_elems(rtype))

    memo[comp.name] = costs
    return costs


def analyze_hlo(hlo: str) -> RooflineCosts:
    comps = parse_computations(hlo)
    entry = _entry_name(hlo)
    if entry is None or entry not in comps:
        # fall back: largest computation
        entry = max(comps, key=lambda c: len(comps[c].lines))
    memo: Dict[str, RooflineCosts] = {}
    return analyze_computation(comps[entry], comps, memo)


# ------------------------------------------------------------------ reporting


def roofline_terms(costs: RooflineCosts, chips: int) -> Dict[str, float]:
    """Per-step times in seconds. Costs are per-device (SPMD module)."""
    return {
        "compute_s": costs.flops / PEAK_FLOPS,
        "memory_s": costs.hbm_bytes / HBM_BW,
        "collective_s": costs.coll_bytes / LINK_BW,
    }


def dominant_term(terms: Dict[str, float]) -> str:
    return max(("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS per step: 6*N*D train / 2*N*D serve (active params)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n_active * tokens
