"""HLO static cost model: trip-count-aware FLOPs / bytes / collective traffic.

``compiled.cost_analysis()`` counts every while-loop body ONCE — useless for
scan-over-layers programs (an 88-layer model reports 1/88th of its FLOPs). We
therefore walk the optimized, partitioned HLO text ourselves:

  * computations are parsed into instruction lists with a result-size symbol
    table;
  * ``while`` instructions get their trip count recovered from the loop
    condition's compare-against-constant, and their body/cond costs are
    multiplied through (nested loops compose);
  * FLOPs come from ``dot`` ops (2 x prod(result) x prod(contracting dims)),
    wherever they sit (fusion bodies included);
  * HBM bytes are counted at fusion granularity (operands + results of
    top-level instructions; fusion internals stay in registers/VMEM);
  * collective traffic sums operand bytes per collective type, multiplied by
    the enclosing loops' trip counts.

Roofline terms then follow from the hardware constants. All numbers are
PER-DEVICE (the HLO is the partitioned per-device module).
"""
from __future__ import annotations

import dataclasses
import re

# TPU v5e-class hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link (~per-chip usable collective bw)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Bytes of one HLO type string (possibly a tuple type)."""
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


@dataclasses.dataclass
class Instruction:
    name: str
    op: str
    result_bytes: int
    operand_names: list
    attrs: str
    type_str: str


@dataclasses.dataclass
class Computation:
    name: str
    instructions: list
    is_entry: bool = False


_COMP_HEAD_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_INST_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^()]*\)|[\w\[\],{}\s]+?)\s+"
                      r"([\w\-]+)\((.*)$")
# a shape's layout, e.g. the TPU's ``f32[8]{0:T(128)S(1)}``: its tiling and
# memory-space parens would end the type match early, and no analysis reads it
_LAYOUT_RE = re.compile(r"\]\{[^{}]*\}")


def parse_hlo(hlo_text: str) -> dict:
    """Parse HLO text into {computation_name: Computation}."""
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in hlo_text.splitlines():
        # computation headers sit at column 0 (instructions are indented)
        if line and not line[0].isspace() and "->" in line and line.rstrip().endswith("{"):
            hm = _COMP_HEAD_RE.match(line.strip())
            if hm:
                cur = Computation(hm.group(2), [], is_entry=bool(hm.group(1)))
                comps[cur.name] = cur
                continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INST_RE.match(_LAYOUT_RE.sub("]", line))
        if not m:
            continue
        name, type_str, op, rest = m.groups()
        # split rest into "(operands), attrs"
        depth, idx = 1, 0
        for idx, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        operand_str, attrs = rest[:idx], rest[idx + 1:]
        opnames = re.findall(r"%([\w.\-]+)", operand_str)
        if not opnames:  # operands may be bare names (no % in new dumps)
            opnames = [t.strip().split(" ")[-1] for t in operand_str.split(",")
                       if t.strip() and not t.strip()[0].isdigit()]
            opnames = [re.sub(r"[^\w.\-]", "", t) for t in opnames if t]
        comps[cur.name].instructions.append(
            Instruction(name, op, _shape_bytes(type_str), opnames, attrs, type_str))
    return comps


_CALLED_RE = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)="
                        r"[{]?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)[}]?")
_DOT_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


@dataclasses.dataclass
class ProgramCost:
    flops: float
    hbm_bytes: float
    bytes_by_type: dict
    count_by_type: dict

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.bytes_by_type.values())


def analyze(hlo_text: str, bf16_equiv: bool = False) -> ProgramCost:
    """bf16_equiv: the CPU backend's float-normalization pass upcasts bf16
    dots (and the collectives scheduled on their outputs) to f32 — a TPU
    lowering of the same program keeps bf16. When the program's compute dtype
    is bf16, this flag counts f32 dot/collective payloads at 2 bytes/elem so
    the roofline reflects the TPU target, not the CPU host. Fusion bytes are
    left raw (documented upper bound)."""
    comps = parse_hlo(hlo_text)
    # symbol table per computation: name -> (result_bytes, type_str)
    tables = {cn: {i.name: i for i in c.instructions} for cn, c in comps.items()}
    memo: dict[str, ProgramCost] = {}

    def dot_flops(inst: Instruction, table: dict) -> float:
        res_elems = 0
        for dt, dims in _SHAPE_RE.findall(inst.type_str):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            res_elems += n
        k = 1
        m = _DOT_CDIMS_RE.search(inst.attrs)
        if m and inst.operand_names:
            lhs = table.get(inst.operand_names[0])
            if lhs is not None:
                lhs_dims = _SHAPE_RE.search(lhs.type_str)
                if lhs_dims:
                    dims = [int(d) for d in lhs_dims.group(2).split(",") if d]
                    for ci in m.group(1).split(","):
                        if ci != "" and int(ci) < len(dims):
                            k *= dims[int(ci)]
        return 2.0 * res_elems * k

    def trip_of(while_inst: Instruction) -> int:
        # XLA annotates scans: backend_config={"known_trip_count":{"n":"10"}}
        m = re.search(r'known_trip_count[^0-9]*(\d+)', while_inst.attrs)
        if m:
            return int(m.group(1))
        m = re.search(r"condition=%?([\w.\-]+)", while_inst.attrs)
        if not m or m.group(1) not in comps:
            return 1
        cond = comps[m.group(1)]
        best = 1
        for i in cond.instructions:
            for mm in re.finditer(r"constant\((\d+)\)", i.type_str + " " + i.attrs):
                best = max(best, int(mm.group(1)))
        return best

    def cost_of(comp_name: str, top_level: bool) -> ProgramCost:
        key = comp_name
        if key in memo:
            return memo[key]
        comp = comps[comp_name]
        table = tables[comp_name]
        flops = 0.0
        hbm = 0.0
        bby = {c: 0.0 for c in COLLECTIVES}
        cby = {c: 0 for c in COLLECTIVES}

        for inst in comp.instructions:
            op = inst.op
            if op == "dot":
                flops += dot_flops(inst, table)
                db = inst.result_bytes + sum(
                    table[o].result_bytes for o in inst.operand_names if o in table)
                if bf16_equiv and inst.type_str.startswith("f32"):
                    db *= 0.5
                hbm += db
            elif op == "while":
                mb = re.search(r"body=%?([\w.\-]+)", inst.attrs)
                trip = trip_of(inst)
                if mb and mb.group(1) in comps:
                    sub = cost_of(mb.group(1), True)
                    flops += trip * sub.flops
                    hbm += trip * sub.hbm_bytes
                    for c in COLLECTIVES:
                        bby[c] += trip * sub.bytes_by_type[c]
                        cby[c] += trip * sub.count_by_type[c]
            elif op == "fusion":
                mc = re.search(r"calls=%?([\w.\-]+)", inst.attrs)
                called = mc.group(1) if mc and mc.group(1) in comps else None
                if called:
                    sub = cost_of(called, False)
                    flops += sub.flops  # dots inside fusions still count
                    for c in COLLECTIVES:
                        bby[c] += sub.bytes_by_type[c]
                        cby[c] += sub.count_by_type[c]
                # HBM at fusion granularity. kLoop fusions touch each operand
                # at output cardinality (a dynamic-slice of a stacked scan
                # operand reads one slice, not the stack) -> cap per-operand
                # bytes at the result size; kInput (reduction) fusions read
                # operands fully. Fusions ROOTED at a dynamic-update-slice
                # write in place: traffic is the update slice (2x), not the
                # full aliased buffer (a scan writing per-layer KV caches
                # into a stacked ys buffer would otherwise be charged the
                # whole stack every iteration — 28x overcount observed).
                root_op = None
                if called and comps[called].instructions:
                    root_op = comps[called].instructions[-1].op
                if root_op == "dynamic-update-slice":
                    opbs = sorted(table[o].result_bytes
                                  for o in inst.operand_names if o in table)
                    hbm += 2 * sum(opbs[:-1])  # everything but the aliased buffer
                else:
                    kloop = "kind=kLoop" in inst.attrs or "kind=kOutput" in inst.attrs
                    for o in inst.operand_names:
                        if o in table:
                            ob = table[o].result_bytes
                            hbm += min(ob, inst.result_bytes) if kloop else ob
                    hbm += inst.result_bytes
            elif op in ("call", "conditional", "async-start"):
                for mc in re.finditer(
                        r"(?:to_apply|branch_computations=\{|called_computations=\{|calls=)"
                        r"%?([\w.\-]+)", inst.attrs):
                    if mc.group(1) in comps:
                        sub = cost_of(mc.group(1), True)
                        flops += sub.flops
                        hbm += sub.hbm_bytes
                        for c in COLLECTIVES:
                            bby[c] += sub.bytes_by_type[c]
                            cby[c] += sub.count_by_type[c]
            else:
                base = op[:-6] if op.endswith("-start") else op
                if base in COLLECTIVES:
                    opb = sum(table[o].result_bytes for o in inst.operand_names
                              if o in table)
                    if opb == 0:
                        opb = inst.result_bytes
                    if bf16_equiv and "f32" in inst.type_str:
                        opb *= 0.5
                    bby[base] += opb
                    cby[base] += 1
                    hbm += opb + inst.result_bytes
                elif op in ("dynamic-slice", "gather"):
                    # reads only the sliced/gathered extent
                    hbm += 2 * inst.result_bytes
                elif op == "dynamic-update-slice":
                    # in-place: traffic ~ 2x update bytes (operand 1)
                    upd = (table[inst.operand_names[1]].result_bytes
                           if len(inst.operand_names) > 1
                           and inst.operand_names[1] in table else inst.result_bytes)
                    hbm += 2 * upd
                elif top_level and op not in ("parameter", "constant", "tuple",
                                              "get-tuple-element", "bitcast",
                                              "after-all", "partition-id"):
                    hbm += inst.result_bytes + sum(
                        table[o].result_bytes for o in inst.operand_names if o in table)
        res = ProgramCost(flops, hbm, bby, cby)
        memo[key] = res
        return res

    entry = next((cn for cn, c in comps.items() if c.is_entry), None)
    if entry is None:
        return ProgramCost(0.0, 0.0, {c: 0.0 for c in COLLECTIVES},
                           {c: 0 for c in COLLECTIVES})
    return cost_of(entry, True)


_OFFSET_DIMS_RE = re.compile(r"offset_dims=\{([\d,]*)\}")


def _dims(type_str: str) -> tuple[int, ...] | None:
    m = _SHAPE_RE.search(type_str)
    return tuple(int(d) for d in m.group(2).split(",") if d) if m else None


def _splits_batch_dims(dims, offset_dims, result) -> bool:
    """True iff ``result`` is the gather shape ``dims`` with only its batch
    dims split or merged: size-1 dims aside, every offset (slice) dim keeps
    its size and place, and each run of batch dims becomes a run of result
    dims with the same element count. A transposing bitcast (layouts are
    stripped, so it prints like a reshape) moves a dim and fails."""
    g = [(d, i not in offset_dims) for i, d in enumerate(dims) if d != 1]
    r = [d for d in result if d != 1]
    i = j = 0
    while i < len(g):
        if not g[i][1]:
            if j >= len(r) or r[j] != g[i][0]:
                return False
            i, j = i + 1, j + 1
            continue
        run = 1
        while i < len(g) and g[i][1]:
            run *= g[i][0]
            i += 1
        got = 1
        while got < run and j < len(r):
            got *= r[j]
            j += 1
        if got != run:
            return False
    return j == len(r)


def instruction_shapes(hlo_text: str, op: str = "gather") -> list[tuple[int, ...]]:
    """Result shapes (dim tuples) of every ``op`` instruction in the module,
    fusion bodies included. The tensor-parallel dry-run reads SPMD
    invariants straight off the partitioned per-device HLO with this (see
    ``tp_gathers_shard_local``): the shapes are the proof of where the
    partitioner actually split the work. ``op`` matches the base opcode
    (async ``-start`` variants included).

    XLA may flatten a gather's batch dims into one and restore the requested
    shape with a ``bitcast``/``reshape`` of its result (``f32[192,8,1]
    gather`` with ``offset_dims={1,2}``, then ``f32[1,32,6,8] bitcast``).
    Where such a consumer only splits or merges the gather's batch dims
    (``_splits_batch_dims``), the restored shape is the one reported."""
    comps = parse_hlo(hlo_text)
    out: list[tuple[int, ...]] = []
    for c in comps.values():
        reshapes: dict[str, list] = {}
        for i in c.instructions:
            if i.op in ("bitcast", "reshape") and i.operand_names:
                reshapes.setdefault(i.operand_names[0], []).append(i)
        for i in c.instructions:
            if i.op != op and i.op != op + "-start":
                continue
            dims = _dims(i.type_str)
            if dims is None:
                continue
            m = _OFFSET_DIMS_RE.search(i.attrs) if op == "gather" else None
            if m:
                offsets = {int(d) for d in m.group(1).split(",") if d}
                for u in reshapes.get(i.name, []):
                    r = _dims(u.type_str)
                    if r is not None and _splits_batch_dims(dims, offsets, r):
                        dims = r
                        break
            out.append(dims)
    return out


def matmuls_spanning(hlo_text: str, size: int) -> list[Instruction]:
    """Every ``dot`` and ``convolution`` in the module, fusion bodies
    included, whose result or one of whose operands has a dimension of
    ``size``. Each one inside a loop body is one pass over the loop's
    trips, so with ``size`` the vocabulary this counts a program's passes
    over its LM head."""
    comps = parse_hlo(hlo_text)
    types = {i.name: i.type_str for c in comps.values() for i in c.instructions}
    return [i for c in comps.values() for i in c.instructions
            if i.op in ("dot", "convolution")
            and any(size in (_dims(t) or ())
                    for t in [i.type_str] + [types.get(o, "") for o in i.operand_names])]


def tp_gathers_shard_local(shapes, nloc: int, k: int, d_out: int) -> bool:
    """The serving SPMD invariant on a tensor-parallel program's gather
    shapes (``instruction_shapes``): the condensed gather runs shard-local
    and never replicated.

    The TP apply vmaps the gather over the ``d_out / nloc`` neuron blocks,
    so its restored shape reads ``(blocks, nloc, k, rows)``: ``blocks`` is 1
    on each device when the partitioner split the block axis, and all of
    them when it did not. True iff some gather holds ``(1, nloc, k)``, none
    holds ``(blocks, nloc, k)``, and none the unblocked ``(d_out, k)``."""
    def has(seq):
        n = len(seq)
        return any(tuple(g[i:i + n]) == seq
                   for g in shapes for i in range(len(g) - n + 1))
    if nloc == d_out:
        return has((nloc, k))
    return (has((1, nloc, k)) and not has((d_out // nloc, nloc, k))
            and not has((d_out, k)))


# backwards-compatible wrapper used by dryrun.py
@dataclasses.dataclass
class CollectiveStats:
    bytes_by_type: dict
    count_by_type: dict

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_type.values())


def collective_stats(hlo_text: str) -> CollectiveStats:
    cost = analyze(hlo_text)
    return CollectiveStats(cost.bytes_by_type, cost.count_by_type)


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   n_chips: int) -> dict:
    """The three §Roofline terms, in seconds. Inputs are PER-DEVICE numbers
    (cost_analysis of the partitioned module is per-device)."""
    return {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": hbm_bytes / HBM_BW,
        "collective_s": coll_bytes / ICI_BW,
    }


def dominant_term(terms: dict) -> str:
    return max(("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
