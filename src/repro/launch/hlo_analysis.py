"""Compiled-HLO structural analysis: trip-count-aware FLOPs, HBM-traffic
bytes, collective-operand bytes, and roofline terms.

Why not just ``compiled.cost_analysis()``: XLA's flat cost analysis counts
each ``while`` body **once**, so scan-over-layers programs (everything here)
under-report FLOPs/bytes/collectives by ~n_layers, and its "bytes accessed"
charges a gather with the full table size.  This module re-derives the
costs *structurally from the compiled artifact* (assignment §Roofline —
"derive the three roofline terms from the dry-run's compiled artifact"):

  * the module text is parsed into computations/instructions;
  * ``while`` ops carry ``known_trip_count`` in backend_config (fallback:
    the loop-bound constant in the condition) — body costs multiply by it,
    nested loops compose by recursion;
  * FLOPs = MXU work: 2 * prod(result dims) * prod(contracting dims) per
    ``dot``, wherever it appears (VPU transcendentals are excluded — they
    ride the memory term);
  * bytes = post-fusion HBM traffic: per *control-flow-level* instruction,
    result + operand bytes (fusion internals live in registers/VMEM and are
    not charged; gathers charge gathered rows + indices, not the table);
  * collective bytes = operand sizes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute, trip-count scaled.

Shapes in the partitioned module are per-device, so every roofline term is
per-device against per-chip peak rates — equivalent to the global/chips
formulation.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict

__all__ = ["collective_bytes", "analyze_hlo", "roofline_terms", "HW",
           "HW_BY_KIND", "DEFAULT_HW_KIND", "hw_for_device",
           "parse_module", "inst_operands"]

#: per-chip constants keyed by the ``tune.table.device_kind()`` spelling of
#: what the device reports (a TPU v5e reports ``device_kind`` "TPU v5
#: lite").  v5e peaks: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
#: bf16, 819 GB/s HBM, 16 GB HBM, 1,600 Gbit/s ICI (4 links x 50 GB/s).
#: ``vmem_bytes`` is the scoped VMEM limit the TPU compiler enforces on a
#: kernel's pipelined blocks and temporaries ("Scoped allocation ... limit
#: 16.00M"), not the 128 MiB of physical VMEM.  The cpu entry exists so the
#: checker can run on CPU; its ``vmem_bytes`` models the v5e limit
#: (interpret mode runs the v5e kernels) and its rates are nominal.
HW_BY_KIND = {
    "tpu:tpu_v5_lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "hbm_bw": 819e9,             # B/s
        "hbm_bytes": 16e9,           # B
        "ici_bw": 50e9,              # B/s per link
        "vmem_bytes": 16 * 2**20,    # scoped VMEM limit per kernel
    },
    "cpu:cpu": {
        "peak_flops_bf16": 2e12,
        "hbm_bw": 100e9,
        "hbm_bytes": 16e9,
        "ici_bw": 50e9,
        "vmem_bytes": 16 * 2**20,
    },
}

#: the chip this repository targets; roofline modelling of a compile-only
#: dry-run (no device attached) uses it explicitly
DEFAULT_HW_KIND = "tpu:tpu_v5_lite"

HW = HW_BY_KIND[DEFAULT_HW_KIND]


def hw_for_device(kind: str) -> dict:
    """The constants for ``kind``; a kind with no entry is an error (no
    peak is assumed for a device nobody measured)."""
    if kind not in HW_BY_KIND:
        raise KeyError(
            f"device kind {kind!r} has no entry in HW_BY_KIND (known: "
            f"{', '.join(sorted(HW_BY_KIND))}); add its published peaks "
            f"to launch/hlo_analysis.py")
    return HW_BY_KIND[kind]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
    "token": 0, "f8e8m0fnu": 1,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)

# `%name = f32[1,2,3]{...} op-name(...)` or tuple results
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\([^)]*\)|[\w\[\],\s]+?)\s+"
    r"([\w\-]+)(?:\.\d+)?\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")


def _shape_bytes(shape_text: str) -> int:
    """Total bytes of all array shapes in a type string (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum of operand bytes per collective kind (per-device, post-SPMD)."""
    shapes: dict[str, str] = {}
    # pass 1: record result type of every named instruction
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            shapes[m.group(1)] = m.group(2)

    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        op = m.group(3)
        kind = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-start") or op == c + "-start":
                kind = c
                break
        if kind is None:
            continue
        # operands: everything inside the first (...) argument list
        args = line.split("(", 1)[1]
        args = args.split("), ")[0] if "), " in args else args.rsplit(")", 1)[0]
        nbytes = 0
        for name in _OPERAND_RE.findall(args):
            if name in shapes:
                nbytes += _shape_bytes(shapes[name])
        if nbytes == 0:
            # fall back to result size (covers unnamed-constant operands)
            nbytes = _shape_bytes(m.group(2))
        out[kind] += nbytes
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


# ---------------------------------------------------------------------------
# structural (trip-count-aware) analyzer
# ---------------------------------------------------------------------------

# computation headers may contain '/*index=N*/' comments in the param list,
# so only anchor on the name + opening paren and the trailing '{'
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\{\s*$")
_INST_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*"
    r"(\([^)]*\)|[\w\[\],]+(?:\{[\d,]*\})?)\s+"
    r"([\w\-]+)\("
)
_TRIP_RE = re.compile(r'"known_trip_count":\s*\{"n":\s*"(\d+)"')
_CALL_RE = re.compile(r"(?:calls|to_apply|condition|body)=%([\w\.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_NOBYTE_OPS = frozenset({
    "tuple", "get-tuple-element", "parameter", "constant", "bitcast",
    "partition-id", "replica-id", "after-all", "while", "conditional",
    "custom-call", "call",
})


@dataclasses.dataclass
class _Inst:
    name: str
    type_text: str
    op: str
    args: str
    line: str


def _balanced(text: str, start: int) -> int:
    """Index just past the paren group opening at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


_OP_AT = re.compile(r"([\w\-]+)\(")


def _parse_inst(line: str):
    """Robust instruction parse handling nested tuple types
    ('((f32[2], s32[]), f32[4]) while(...)')."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    name, sep, rhs = s.partition(" = ")
    if not sep or not name.strip():
        return None
    name = name.strip().lstrip("%")
    rhs = rhs.strip()
    if rhs.startswith("("):
        end = _balanced(rhs, 0)
        type_text, rest = rhs[:end], rhs[end:].lstrip()
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        type_text, rest = rhs[:sp], rhs[sp + 1:].lstrip()
    m = _OP_AT.match(rest)
    if m is None:
        return None
    op = m.group(1)
    arg_end = _balanced(rest, m.end() - 1)
    args = rest[m.end(): arg_end - 1]
    return _Inst(name, type_text, op, args, line)


def _parse_module(hlo_text: str):
    """-> (computations: {name: [inst]}, shapes: {inst_name: type_text},
    entry_name, fused_comps: set of computations called from fusions)"""
    comps: dict[str, list[_Inst]] = {}
    shapes: dict[str, str] = {}
    entry = None
    fused: set[str] = set()
    cur: list[_Inst] | None = None
    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if " = " not in line:
            m = _COMP_RE.match(line.strip())
            if m:
                name = m.group(1)
                comps[name] = []
                cur = comps[name]
                if line.strip().startswith("ENTRY"):
                    entry = name
            continue
        inst = _parse_inst(line)
        if inst is None or cur is None:
            continue
        cur.append(inst)
        shapes[inst.name] = inst.type_text
        if inst.op == "fusion":
            cm = re.search(r"calls=%([\w\.\-]+)", line)
            if cm:
                fused.add(cm.group(1))
    return comps, shapes, entry, fused


def _operands(inst: _Inst):
    return _OPERAND_RE.findall(inst.args)


def _trip_count(inst: _Inst, comps, shapes) -> int:
    m = _TRIP_RE.search(inst.line)
    if m:
        return int(m.group(1))
    # fallback: the constant compared against in the condition computation
    cm = re.search(r"condition=%([\w\.\-]+)", inst.line)
    if cm and cm.group(1) in comps:
        for ci in comps[cm.group(1)]:
            k = re.search(r"constant\((\d+)\)", ci.line)
            if k and ci.op == "constant":
                return int(k.group(1))
    return 1


def _fusion_bytes(inst: _Inst, ops_list, comps, shapes) -> int:
    """HBM traffic of a fusion: operands + output, with two refinements —
    a parameter consumed only by gathers is charged the gathered bytes (not
    the table), and a parameter updated in place by dynamic-update-slice is
    charged (and emitted as) the update size (XLA aliases the buffer)."""
    called = None
    cm = re.search(r"calls=%([\w\.\-]+)", inst.line)
    if cm:
        called = comps.get(cm.group(1))
    out_b = _shape_bytes(inst.type_text)
    if called is None:
        return out_b + sum(_shape_bytes(shapes[o]) for o in ops_list
                           if o in shapes)
    # param index -> local name, and local uses
    param_names = {}
    for ci in called:
        if ci.op == "parameter":
            pm = re.search(r"parameter\((\d+)\)", ci.line)
            if pm:
                param_names[int(pm.group(1))] = ci.name
    local_shapes = {ci.name: ci.type_text for ci in called}
    total = 0
    dus_update_b = None
    for idx, oname in enumerate(ops_list):
        if oname not in shapes:
            continue
        full_b = _shape_bytes(shapes[oname])
        lname = param_names.get(idx)
        if lname is None:
            total += full_b
            continue
        uses = [ci for ci in called if lname in _operands(ci)]
        if uses and all(ci.op in _SLICE_OPS and _operands(ci)[0] == lname
                        for ci in uses):
            total += sum(_shape_bytes(ci.type_text) for ci in uses)
        elif uses and all(ci.op == "dynamic-update-slice"
                          and _operands(ci)[0] == lname for ci in uses):
            upd = 0
            for ci in uses:
                o2 = _operands(ci)
                if len(o2) > 1 and o2[1] in local_shapes:
                    upd += _shape_bytes(local_shapes[o2[1]])
            total += upd
            if _shape_bytes(shapes[oname]) == out_b:
                dus_update_b = upd  # in-place aliased output
        else:
            total += full_b
    return total + (dus_update_b if dus_update_b is not None else out_b)


#: ops whose operand-0 is a large buffer of which only a slice moves
_SLICE_OPS = frozenset({"gather", "dynamic-slice", "slice"})
#: tensors at or below this size are assumed VMEM-resident across loop
#: iterations (TPU v5e class VMEM); their traffic charges once per loop
VMEM_RESIDENT_BYTES = 32 * 1024 * 1024


def analyze_hlo(hlo_text: str, vmem_resident: int = VMEM_RESIDENT_BYTES
                ) -> Dict:
    """Trip-count-aware per-device totals:
    {'flops', 'bytes', 'collectives': {kind: bytes, 'total', 'count'},
     'num_whiles', 'max_trip'}

    Bytes model: per control-flow-level instruction, output + operand sizes
    (a produced-then-consumed edge costs write+read — the post-fusion HBM
    round trip), except (a) slice/gather ops charge moved bytes, not their
    source buffer, (b) dynamic-update-slice charges the update (XLA aliases
    the buffer), and (c) inside loop bodies, charges on tensors <=
    ``vmem_resident`` accumulate once per loop entry instead of per
    iteration (VMEM residency of carries/accumulators); explicitly sliced
    data always streams per iteration."""
    comps, shapes, entry, fused = _parse_module(hlo_text)
    memo: dict[tuple, tuple] = {}
    info = {"num_whiles": 0, "max_trip": 1}

    def comp_cost(name: str, in_fusion: bool):
        key = (name, in_fusion)
        if key in memo:
            return memo[key]
        flops = 0.0
        stream_b = 0.0   # charged per loop iteration
        once_b = 0.0     # VMEM-resident: charged once per loop entry
        coll = {k: 0.0 for k in _COLLECTIVES}
        ccount = 0
        for inst in comps.get(name, ()):  # pragma: no branch
            op = inst.op
            if op == "dot":
                ops = _operands(inst)
                lhs_shape = shapes.get(ops[0], "") if ops else ""
                cm = _CONTRACT_RE.search(inst.line)
                csize = 1
                if cm and lhs_shape:
                    dims_m = _SHAPE_RE.search(lhs_shape)
                    if dims_m:
                        lhs_dims = [int(d) for d in
                                    dims_m.group(2).split(",") if d.strip()]
                        for ci in cm.group(1).split(","):
                            if ci.strip():
                                csize *= lhs_dims[int(ci)]
                out_elems = 1
                om = _SHAPE_RE.search(inst.type_text)
                if om:
                    for d in om.group(2).split(","):
                        if d.strip():
                            out_elems *= int(d)
                flops += 2.0 * out_elems * csize
            kind = None
            for c in _COLLECTIVES:
                if op == c or op.startswith(c + "-start"):
                    kind = c
                    break
            if kind:
                nb = 0
                for o in _operands(inst):
                    if o in shapes:
                        nb += _shape_bytes(shapes[o])
                if nb == 0:
                    nb = _shape_bytes(inst.type_text)
                coll[kind] += nb
                ccount += 1
            # bytes: control-flow level only, skip plumbing ops
            if not in_fusion and op not in _NOBYTE_OPS and kind is None:
                ops_list = _operands(inst)
                force_stream = op in _SLICE_OPS or op == "dynamic-update-slice"
                if op in _SLICE_OPS and ops_list:
                    ops_list = ops_list[1:]  # moved bytes, not the source
                if op == "dynamic-update-slice" and ops_list:
                    # aliased in-place write: charge the update (read+write)
                    upd = sum(_shape_bytes(shapes[o]) for o in ops_list[1:]
                              if o in shapes)
                    stream_b += 2 * upd
                    continue
                if op == "fusion":
                    fb = _fusion_bytes(inst, ops_list, comps, shapes)
                    if fb <= vmem_resident:
                        once_b += fb
                    else:
                        stream_b += fb
                else:
                    charge = _shape_bytes(inst.type_text) + sum(
                        _shape_bytes(shapes[o]) for o in ops_list
                        if o in shapes
                    )
                    if not force_stream and charge <= vmem_resident:
                        once_b += charge
                    else:
                        stream_b += charge
            # recurse into called computations
            mult = 1
            sub_in_fusion = in_fusion or op == "fusion"
            if op == "while":
                mult = _trip_count(inst, comps, shapes)
                info["num_whiles"] += 1
                info["max_trip"] = max(info["max_trip"], mult)
            for sub in _CALL_RE.findall(inst.line):
                if sub not in comps:
                    continue
                sf, s_stream, s_once, sc, scnt = comp_cost(
                    sub, sub_in_fusion or sub in fused
                )
                flops += mult * sf
                if op == "while":
                    # body streams per iteration; VMEM-resident charges once
                    stream_b += mult * s_stream + s_once
                else:
                    stream_b += mult * s_stream
                    once_b += s_once
                for k in sc:
                    coll[k] += mult * sc[k]
                ccount += mult * scnt
        memo[key] = (flops, stream_b, once_b, coll, ccount)
        return memo[key]

    flops, stream_b, once_b, coll, ccount = comp_cost(entry, False)
    collectives = {k: int(v) for k, v in coll.items()}
    collectives["total"] = int(sum(coll.values()))
    collectives["count"] = int(ccount)
    return {
        "flops": flops,
        "bytes": stream_b + once_b,
        "collectives": collectives,
        "num_whiles": info["num_whiles"],
        "max_trip": info["max_trip"],
    }


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        vals = {"compute": self.compute_s, "memory": self.memory_s,
                "collective": self.collective_s}
        return max(vals, key=vals.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float,
                   device_kind: str | None = None) -> RooflineTerms:
    hw = HW if device_kind is None else hw_for_device(device_kind)
    return RooflineTerms(
        compute_s=flops_per_dev / hw["peak_flops_bf16"],
        memory_s=bytes_per_dev / hw["hbm_bw"],
        collective_s=coll_bytes_per_dev / hw["ici_bw"],
    )


# public parser surface for repro.check's HLO pass
parse_module = _parse_module
inst_operands = _operands
