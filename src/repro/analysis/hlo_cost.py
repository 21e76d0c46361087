"""Loop-aware HLO cost analysis — the dry-run "profiler".

XLA's ``compiled.cost_analysis()`` counts a ``while`` body ONCE, which
under-reports scanned-layer models by ~L× (verified: an 8-step scan of a
matmul reports 1/8 of the unrolled FLOPs). Since every model here scans
its layer stack (and attention/CE scan internally), we walk the optimized
HLO ourselves:

  * per-computation FLOP/byte/collective tallies,
  * ``while`` bodies multiplied by ``backend_config.known_trip_count``
    (fallback ×1 + a warning flag so nothing fails silently),
  * fusions costed from their fused computations, with HBM bytes counted
    at fusion boundaries only (post-fusion HLO ≈ real traffic),
  * collective bytes per op type (all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute), also loop-scaled.

The compiled module is the per-device SPMD program, so every number is
per-device per-step — exactly what the roofline terms need.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional

__all__ = ["analyze_hlo", "HloCost", "entry_boundary_bytes", "op_names"]

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_ARRAY_RE = re.compile(
    r"(pred|f8e4m3fn|f8e5m2|[sub]\d+|bf16|f16|f32|f64|c64|c128)\[([\d,]*)\]")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# ops that define values but move/alias no data worth counting
_FREE_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
             "constant", "after-all", "opt-barrier", "partition-id",
             "replica-id", "rng-bit-generator", "iota", "domain",
             "reshape"}

_TRANSCENDENTAL = {"tanh", "exponential", "log", "rsqrt", "sqrt", "power",
                   "logistic", "sine", "cosine", "exponential-minus-one",
                   "log-plus-one", "atan2", "erf", "cbrt", "divide"}


def _type_elems_bytes(type_str: str) -> tuple[int, int]:
    elems = 0
    nbytes = 0
    for dt, dims in _ARRAY_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        nbytes += n * _DTYPE_BYTES.get(dt, 4)
    return elems, nbytes


def _array_dims(type_str: str) -> list[int]:
    m = _ARRAY_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    op: str
    operands: list[str]
    attrs: str
    raw_operands: str = ""


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    transcendentals: float = 0.0
    collective_bytes: Optional[dict] = None
    warnings: Optional[list] = None

    def scaled(self, k: float) -> "HloCost":
        return HloCost(self.flops * k, self.bytes * k,
                       self.transcendentals * k,
                       {kk: v * k for kk, v in self.collective_bytes.items()},
                       list(self.warnings))

    def add(self, other: "HloCost"):
        self.flops += other.flops
        self.bytes += other.bytes
        self.transcendentals += other.transcendentals
        for k, v in other.collective_bytes.items():
            self.collective_bytes[k] = self.collective_bytes.get(k, 0) + v
        self.warnings.extend(other.warnings)

    @staticmethod
    def zero() -> "HloCost":
        return HloCost(0, 0, 0, {c: 0.0 for c in _COLLECTIVES}, [])


def _split_computations(text: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur_name, cur_lines = None, []
    for line in text.splitlines():
        stripped = line.strip()
        if cur_name is None:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{$", stripped)
            if m and " = " not in stripped:
                cur_name = m.group(1)
                cur_lines = []
                if stripped.startswith("ENTRY"):
                    comps["__entry__"] = cur_lines
        else:
            if stripped == "}":
                comps[cur_name] = cur_lines
                cur_name = None
            else:
                cur_lines.append(stripped)
    return comps


def _match_paren(s: str, start: int) -> int:
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(s) - 1


_INSTR_RE = re.compile(
    r"^(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s([a-z][\w\-]*)\(")


def _parse_instr(line: str) -> Optional[Instr]:
    m = _INSTR_RE.match(line)
    if not m:
        return None
    name, type_str, op = m.groups()
    open_idx = m.end() - 1
    close_idx = _match_paren(line, open_idx)
    operand_str = line[open_idx + 1:close_idx]
    attrs = line[close_idx + 1:]
    operands = re.findall(r"%([\w.\-]+)", operand_str)
    return Instr(name, type_str, op, operands, attrs, operand_str)


_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def _op_name(attrs: str) -> str:
    m = _OP_NAME_RE.search(attrs)
    return m.group(1) if m else ""


def op_names(text: str) -> dict[str, str]:
    """Each instruction's ``op_name`` metadata ("" where it has none), by
    instruction name, over every computation of an HLO module's text —
    what names the instruction of a profiler's device event."""
    return {ins.name: _op_name(ins.attrs)
            for lines in _split_computations(text).values()
            for ins in map(_parse_instr, lines) if ins is not None}


_PASSTHROUGH = {"bitcast", "reshape", "copy", "transpose", "convert",
                "broadcast"}


def _fusion_in_bytes(callee_instrs: list, operand_names: list,
                     outer_shapes: dict) -> float:
    """Boundary read bytes of a fusion: parameters consumed only through
    slicing ops (possibly via bitcast/reshape/convert chains) are charged
    at the slice size, not the full buffer — XLA fuses the layer-stack
    dynamic-slice into consumers, and charging the whole stack per loop
    iteration overcounts by L×."""
    consumers: dict[str, list] = {}
    for ins in callee_instrs:
        for o in ins.operands:
            consumers.setdefault(o, []).append(ins)
    param_list = [i for i in callee_instrs if i.op == "parameter"]
    total = 0.0
    for pins in param_list:
        full = _type_elems_bytes(pins.type_str)[1]
        # BFS through pass-through ops to the real consumers
        frontier = [pins.name]
        sliced_bytes = 0.0
        only_slices = True
        seen = set()
        hops = 0
        while frontier and only_slices and hops < 16:
            hops += 1
            nxt = []
            for nm in frontier:
                for cc in consumers.get(nm, []):
                    if cc.name in seen:
                        continue
                    seen.add(cc.name)
                    if cc.op in ("dynamic-slice", "slice", "gather"):
                        sliced_bytes += _type_elems_bytes(cc.type_str)[1]
                    elif cc.op in _PASSTHROUGH:
                        nxt.append(cc.name)
                    else:
                        only_slices = False
                        break
            frontier = nxt
        if only_slices and sliced_bytes > 0:
            total += min(sliced_bytes, full)
        else:
            total += full
    return total


def entry_boundary_bytes(text: str) -> dict:
    """Bytes crossing the ENTRY computation boundary: parameter reads +
    ROOT output writes.

    This is the "touch every operand once, write the result once" floor
    of a compiled module — the same semantics as an analytic HBM model
    of a perfectly fused kernel. ``analyze_hlo``'s instruction-level
    total is the wrong comparator for that model under Pallas
    *interpret* mode: emulation materializes every VMEM-resident
    intermediate as an instruction, inflating byte counts ~17× over real
    kernel traffic. The boundary count is emulation-invariant, so the
    kernel benchmark's model-vs-compiler cross-check
    (``benchmarks.kernel_bench.hbm_model_crosscheck``) gates against it.
    """
    comps = _split_computations(text)
    lines = comps.get("__entry__", [])
    param_bytes = 0
    root_bytes = 0
    for line in lines:
        ins = _parse_instr(line)
        if ins is None:
            continue
        if ins.op == "parameter":
            param_bytes += _type_elems_bytes(ins.type_str)[1]
        if line.startswith("ROOT"):
            root_bytes += _type_elems_bytes(ins.type_str)[1]
    return {"parameter_bytes": param_bytes, "root_bytes": root_bytes,
            "total": param_bytes + root_bytes}


def analyze_hlo(text: str) -> HloCost:
    comps = _split_computations(text)
    parsed: dict[str, list[Instr]] = {}
    for cname, lines in comps.items():
        parsed[cname] = [i for i in (_parse_instr(l) for l in lines) if i]

    memo: dict[str, HloCost] = {}

    def comp_cost(cname: str, stack=()) -> HloCost:
        if cname in memo:
            return memo[cname]
        if cname in stack or cname not in parsed:
            return HloCost.zero()
        total = HloCost.zero()
        shapes = {}
        for ins in parsed[cname]:
            shapes[ins.name] = ins.type_str
            total.add(_instr_cost(ins, shapes, stack + (cname,)))
        memo[cname] = total
        return total

    def _called(attrs: str, key: str) -> Optional[str]:
        m = re.search(key + r"=%([\w.\-]+)", attrs)
        return m.group(1) if m else None

    def _instr_cost(ins: Instr, shapes: dict, stack) -> HloCost:
        c = HloCost.zero()
        op = ins.op
        out_elems, out_bytes = _type_elems_bytes(ins.type_str)
        in_bytes = sum(_type_elems_bytes(shapes.get(o, ""))[1]
                       for o in ins.operands)

        if op in _FREE_OPS:
            return c

        if op == "while":
            body = _called(ins.attrs, "body")
            cond = _called(ins.attrs, "condition")
            m = re.search(r'known_trip_count[^0-9]*"n":"(\d+)"', ins.attrs)
            trips = int(m.group(1)) if m else 1
            if not m:
                c.warnings.append(f"while {ins.name}: unknown trip count")
            inner = HloCost.zero()
            if body:
                inner.add(comp_cost(body, stack))
            if cond:
                inner.add(comp_cost(cond, stack))
            c.add(inner.scaled(trips))
            return c

        if op in ("fusion", "call"):
            callee = _called(ins.attrs, "calls") or _called(ins.attrs,
                                                            "to_apply")
            if callee:
                inner = comp_cost(callee, stack)
                # flops from inside; bytes at the fusion boundary
                c.flops += inner.flops
                c.transcendentals += inner.transcendentals
                for k, v in inner.collective_bytes.items():
                    c.collective_bytes[k] += v
                c.warnings.extend(inner.warnings)
                c.bytes += _fusion_in_bytes(
                    parsed.get(callee, []), ins.operands, shapes) + out_bytes
            else:
                c.bytes += in_bytes + out_bytes
            return c

        if op == "conditional":
            branches = re.findall(r"%([\w.\-]+)", ins.attrs)
            if branches:
                worst = max((comp_cost(b, stack) for b in branches),
                            key=lambda x: x.flops, default=HloCost.zero())
                c.add(worst)
            c.bytes += in_bytes + out_bytes
            return c

        if op == "dot":
            m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.attrs)
            lhs_shape = _array_dims(shapes.get(ins.operands[0], ""))
            contract = 1
            if m and lhs_shape:
                for d in m.group(1).split(","):
                    if d:
                        contract *= lhs_shape[int(d)]
            c.flops += 2.0 * out_elems * contract
            c.bytes += in_bytes + out_bytes
            return c

        if op == "convolution":
            rhs_dims = _array_dims(shapes.get(ins.operands[1], ""))
            m = re.search(r"dim_labels=\S*_(\S*?)->", ins.attrs)
            k = 1
            if m and rhs_dims:
                labels = m.group(1)
                for i, ch in enumerate(labels):
                    if ch != "o" and i < len(rhs_dims):
                        k *= rhs_dims[i]
            c.flops += 2.0 * out_elems * k
            c.bytes += in_bytes + out_bytes
            return c

        for coll in _COLLECTIVES:
            if op == coll or op == coll + "-start":
                c.collective_bytes[coll] += out_bytes
                c.bytes += in_bytes + out_bytes
                return c
        if op.endswith("-done"):
            return c

        if op in ("reduce", "reduce-window", "select-and-scatter"):
            in_elems = sum(_type_elems_bytes(shapes.get(o, ""))[0]
                           for o in ins.operands[:1])
            c.flops += float(in_elems)
            c.bytes += in_bytes + out_bytes
            return c

        if op == "custom-call":
            c.warnings.append(f"custom-call {ins.name}: flops not counted")
            c.bytes += in_bytes + out_bytes
            return c

        # Slicing ops touch only the sliced region, not the whole buffer
        # (counting the full stacked-parameter operand would overcharge
        # every loop iteration by L×).
        if op in ("dynamic-slice", "slice"):
            c.bytes += 2.0 * out_bytes
            return c
        if op == "gather":
            idx_bytes = sum(_type_elems_bytes(shapes.get(o, ""))[1]
                            for o in ins.operands[1:])
            c.bytes += 2.0 * out_bytes + idx_bytes
            return c
        if op == "dynamic-update-slice":
            upd_bytes = _type_elems_bytes(
                shapes.get(ins.operands[1], ""))[1] if len(ins.operands) > 1 \
                else out_bytes
            c.bytes += 2.0 * upd_bytes
            return c
        if op == "scatter":
            upd_bytes = sum(_type_elems_bytes(shapes.get(o, ""))[1]
                            for o in ins.operands[2:])
            c.bytes += 3.0 * upd_bytes
            c.flops += float(out_elems)
            return c
        if op == "broadcast":
            c.bytes += out_bytes
            return c

        # default: elementwise-ish (add/multiply/select/compare/copy/
        # transpose/pad/...)
        if op in _TRANSCENDENTAL:
            c.transcendentals += float(out_elems)
        c.flops += float(out_elems)
        c.bytes += in_bytes + out_bytes
        return c

    entry = comp_cost("__entry__")
    # computations reachable only via entry are already included; report
    return entry


def attribute_hlo(text: str, top: int = 25,
                  key: str = "bytes") -> list[dict]:
    """Per-instruction attribution with loop-trip multipliers.

    Returns the top-N contributors by `key` ∈ {bytes, flops, coll} with
    their op, result type, source metadata (op_name) and multiplier —
    the dry-run substitute for a profiler's per-op view.
    """
    comps = _split_computations(text)
    parsed = {c: [i for i in (_parse_instr(l) for l in lines) if i]
              for c, lines in comps.items()}
    records: list[dict] = []

    def walk(cname: str, mult: float, stack=()):
        if cname in stack or cname not in parsed:
            return
        shapes = {}
        for ins in parsed[cname]:
            shapes[ins.name] = ins.type_str
            op = ins.op
            if op == "while":
                m = re.search(r'known_trip_count[^0-9]*"n":"(\d+)"',
                              ins.attrs)
                trips = int(m.group(1)) if m else 1
                for key_ in ("body", "condition"):
                    mm = re.search(key_ + r"=%([\w.\-]+)", ins.attrs)
                    if mm:
                        walk(mm.group(1), mult * trips, stack + (cname,))
                continue
            if op in ("fusion", "call"):
                mm = re.search(r"(?:calls|to_apply)=%([\w.\-]+)", ins.attrs)
                # flops live inside; bytes at the boundary
                inner_flops = 0.0
                if mm:
                    inner = _comp_cost_cache.get(mm.group(1))
                    if inner is not None:
                        inner_flops = inner.flops
                out_b = _type_elems_bytes(ins.type_str)[1]
                in_b = _fusion_in_bytes(parsed.get(mm.group(1), []) if mm
                                        else [], ins.operands, shapes)
                records.append({
                    "comp": cname, "op": op, "name": ins.name,
                    "type": ins.type_str[:48], "mult": mult,
                    "flops": inner_flops * mult,
                    "bytes": (in_b + out_b) * mult, "coll": 0.0,
                    "meta": _op_name(ins.attrs)[-80:],
                })
                continue
            is_coll = any(op == c or op == c + "-start"
                          for c in _COLLECTIVES)
            out_elems, out_b = _type_elems_bytes(ins.type_str)
            in_b = sum(_type_elems_bytes(shapes.get(o, ""))[1]
                       for o in ins.operands)
            if op in _FREE_OPS and not is_coll:
                continue
            flops = 0.0
            if op == "dot":
                m2 = re.search(r"lhs_contracting_dims=\{([\d,]*)\}",
                               ins.attrs)
                lhs = _array_dims(shapes.get(ins.operands[0], ""))
                contract = 1
                if m2 and lhs:
                    for d in m2.group(1).split(","):
                        if d:
                            contract *= lhs[int(d)]
                flops = 2.0 * out_elems * contract
            records.append({
                "comp": cname, "op": op, "name": ins.name,
                "type": ins.type_str[:48], "mult": mult,
                "flops": flops * mult,
                "bytes": (in_b + out_b) * mult,
                "coll": out_b * mult if is_coll else 0.0,
                "meta": _op_name(ins.attrs)[-80:],
            })

    # prime the per-computation flops cache via analyze_hlo's machinery
    global _comp_cost_cache
    _comp_cost_cache = {}
    full = analyze_hlo(text)
    # re-derive per-computation costs cheaply: reuse analyze on each comp
    for cname in parsed:
        sub = HloCost.zero()
        shapes = {}
        # approximate: fusion computations are small; count dot/elementwise
        for ins in parsed[cname]:
            shapes[ins.name] = ins.type_str
            if ins.op == "dot":
                m2 = re.search(r"lhs_contracting_dims=\{([\d,]*)\}",
                               ins.attrs)
                lhs = _array_dims(shapes.get(ins.operands[0], ""))
                contract = 1
                if m2 and lhs:
                    for d in m2.group(1).split(","):
                        if d:
                            contract *= lhs[int(d)]
                sub.flops += 2.0 * _type_elems_bytes(ins.type_str)[0] * \
                    contract
            elif ins.op not in _FREE_OPS:
                sub.flops += float(_type_elems_bytes(ins.type_str)[0])
        _comp_cost_cache[cname] = sub

    walk("__entry__", 1.0)
    records.sort(key=lambda r: r[key], reverse=True)
    return records[:top]


_comp_cost_cache: dict = {}


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("hlo_file")
    ap.add_argument("--top", type=int, default=0,
                    help="also print top-N per-op attribution")
    ap.add_argument("--key", default="bytes",
                    choices=["bytes", "flops", "coll"])
    args = ap.parse_args()
    text = open(args.hlo_file).read()
    cost = analyze_hlo(text)
    print(json.dumps(dataclasses.asdict(cost), indent=2))
    if args.top:
        for r in attribute_hlo(text, args.top, args.key):
            print(f"{r[args.key]:.3e}  {r['op']:18s} ×{r['mult']:<6.0f} "
                  f"{r['type']:40s} {r['meta']}")


if __name__ == "__main__":
    main()
