"""Post-compile HLO analysis: loop-aware FLOPs, bytes, collective traffic.

``compiled.cost_analysis()`` counts every while-loop body ONCE — with
lax.scan over layers and microbatches that under-counts by the product of
trip counts (measured 32x on llama3.2-3b train_4k).  This module walks the
partitioned HLO text instead:

 * computations are parsed into blocks; ``while`` instructions are mapped
   to their condition/body computations, and the loop trip count is
   recovered from the largest integer constant in the condition,
 * per computation we count: dot FLOPs (2 * prod(out dims) * prod(lhs
   contracting dims)), output bytes of top-level instructions (an HBM
   write-traffic proxy), and collective result bytes per op kind,
 * totals are accumulated through the call graph (while/call/fusion
   edges), multiplying by trip counts.

Shapes in the partitioned module are per-device, so all totals are
per-chip — exactly what the roofline terms need.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_WHILE_RE = re.compile(
    r"condition=%?([\w\.\-]+)\s*,\s*body=%?([\w\.\-]+)")
_CALL_RE = re.compile(r"to_apply=%?([\w\.\-]+)")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
# conditional( branch computations: 2-way true/false form and the N-way
# branch_computations={...} form
_COND_TF_RE = re.compile(
    r"true_computation=%?([\w\.\-]+)\s*,\s*false_computation=%?([\w\.\-]+)")
_COND_BR_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_DOT_OUT_RE = re.compile(r"=\s*((?:\([^=]*?\))|(?:[\w\[\],{}]+))\s+dot\(")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERAND_RE = re.compile(r"dot\(\s*%?([\w\.\-]+)\s*,")
_DOT_ARGS_RE = re.compile(r"dot\(([^)]*)\)")
_TRIP_BC_RE = re.compile(r'known_trip_count[^}]*?"n"\s*:\s*"(\d+)"')


def _shape_elems_bytes(text: str) -> Tuple[int, int]:
    """(total elements, total bytes) over all dtype[...] shapes in text."""
    elems = 0
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        total += n * _DTYPE_BYTES[dt]
    return elems, total


def _collective_bytes(rhs: str) -> int:
    """Bytes a collective instruction moves: its result shape.  An async
    ``*-start`` on TPU returns an (operand, result, context...) tuple;
    its traffic is the result element."""
    if not rhs.startswith("("):
        return _shape_elems_bytes(rhs.split("(")[0])[1]
    depth = 0
    for end, ch in enumerate(rhs):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            break
    shapes = _SHAPE_RE.findall(rhs[:end + 1])
    if len(shapes) >= 2:
        shapes = shapes[1:2]
    return sum(_shape_elems_bytes(f"{dt}[{dims}]")[1] for dt, dims in shapes)


def split_computations(hlo: str) -> Tuple[Dict[str, List[str]], str]:
    """computation name -> instruction lines; plus the ENTRY name.

    HLO text puts computation headers at column 0 and instructions
    indented, so we key on indentation rather than parsing signatures
    (whose tuple types contain nested parens).
    """
    comps: Dict[str, List[str]] = {}
    entry = None
    current = None
    for line in hlo.splitlines():
        if not line:
            continue
        if not line[0].isspace():
            token = line.split("(")[0].strip()
            if token.startswith("ENTRY"):
                token = token[len("ENTRY"):].strip()
                name = token.lstrip("%").strip()
                entry = name
                current = name
                comps[current] = []
            elif "{" in line and "(" in line and "->" in line:
                name = token.lstrip("%").strip()
                current = name
                comps[current] = []
            else:
                current = None
            continue
        if current is not None:
            if line.strip() == "}":
                current = None
            else:
                comps[current].append(line)
    return comps, entry


def _trip_count(cond_lines: List[str]) -> int:
    best = 1
    for line in cond_lines:
        for c in _CONST_RE.findall(line):
            best = max(best, int(c))
    return best


def _dot_flops(line: str, out_shapes: Dict[str, str]) -> float:
    m_out = _DOT_OUT_RE.search(line)
    if not m_out:
        return 0.0
    out_elems, _ = _shape_elems_bytes(m_out.group(1))
    contract = 1
    lhs_dims = None
    # modern HLO prints operands with inline shapes:
    #   dot(f32[32,64]{1,0} %lhs, f32[64,64]{1,0} %rhs), ...
    # so the first shape inside the call IS the lhs shape.
    m_args = _DOT_ARGS_RE.search(line)
    if m_args:
        sm = _SHAPE_RE.search(m_args.group(1))
        if sm:
            lhs_dims = [int(d) for d in sm.group(2).split(",") if d]
    if lhs_dims is None:
        # older shape-less operand format: dot(%lhs, %rhs) — resolve the
        # operand's shape through the per-module result map.
        m_lhs = _OPERAND_RE.search(line)
        if m_lhs:
            dims_txt = _SHAPE_RE.search(out_shapes.get(m_lhs.group(1), ""))
            if dims_txt:
                lhs_dims = [int(d) for d in dims_txt.group(2).split(",")
                            if d]
    m_dims = _LHS_CONTRACT_RE.search(line)
    if lhs_dims and m_dims:
        for idx in m_dims.group(1).split(","):
            if idx and int(idx) < len(lhs_dims):
                contract *= lhs_dims[int(idx)]
    return 2.0 * out_elems * contract


_RESULT_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+)$")

# ops that do not write HBM (aliases, metadata, control flow — their bodies
# are walked separately)
_FREE_OPS = {
    "get-tuple-element", "tuple", "parameter", "bitcast", "constant",
    "while", "conditional", "call", "after-all", "opt-barrier",
    "reshape", "partition-id", "replica-id", "add-dependency",
}
_OP_NAME_RE = re.compile(r"\)?\s*([a-z][a-z0-9\-]*)\(")


def _build_shape_map(comps: Dict[str, List[str]]) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for lines in comps.values():
        for line in lines:
            m = _RESULT_RE.match(line)
            if m:
                rhs = m.group(2)
                # shape text is everything before the op name's '('
                out[m.group(1)] = rhs.split("(")[0]
    return out


def _call_edges(comps: Dict[str, List[str]]):
    """Call-graph edges and conditional-branch groups of an HLO module.

    Returns ``(edges, cond_groups)``: ``edges[name]`` is a list of
    ``(child, multiplier, counts_bytes)`` — loop bodies/conditions carry
    their trip count, fusion/call bodies multiplier 1 (their interior ops
    do not write HBM, hence ``counts_bytes=False``); ``cond_groups[name]``
    lists the branch-computation groups of each ``conditional`` (exactly
    one branch runs per execution).
    """
    edges: Dict[str, List[Tuple[str, int, bool]]] = defaultdict(list)
    cond_groups: Dict[str, List[List[str]]] = defaultdict(list)
    for name, lines in comps.items():
        for line in lines:
            m_while = _WHILE_RE.search(line)
            if m_while:
                cond, body = m_while.groups()
                # XLA annotates resolved loops with known_trip_count in
                # the while's backend_config; fall back to the largest
                # integer constant in the condition computation.
                m_bc = _TRIP_BC_RE.search(line)
                trips = int(m_bc.group(1)) if m_bc else \
                    _trip_count(comps.get(cond, []))
                edges[name].append((body, trips, True))
                edges[name].append((cond, trips, True))
            m_tf = _COND_TF_RE.search(line)
            if m_tf:
                cond_groups[name].append([m_tf.group(1), m_tf.group(2)])
            else:
                m_br = _COND_BR_RE.search(line)
                if m_br:
                    cond_groups[name].append(
                        [b.strip().lstrip("%")
                         for b in m_br.group(1).split(",") if b.strip()])
        text = "\n".join(lines)
        for child in _CALL_RE.findall(text):
            edges[name].append((child, 1, False))
        for child in _CALLS_RE.findall(text):
            if child not in [c for c, _, _ in edges[name]]:
                edges[name].append((child, 1, False))
    return edges, cond_groups


_CP_PAIRS_RE = re.compile(
    r"source_target_pairs=\{(\{\d+,\d+\}(?:,\{\d+,\d+\})*)\}")
_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")


def collective_permute_pairs(hlo: str) -> Dict[Tuple[int, int], int]:
    """Loop-aware collective-permute bytes per directed DEVICE pair.

    ``analyze()['collective_by_op']`` charges the whole module with every
    collective-permute instruction's full result bytes — the right number
    for "what does the SPMD program execute per chip", but an overcount of
    what any single link actually carries: a device appearing in none of
    an instruction's ``source_target_pairs`` transmits nothing for it.
    This walk attributes each instruction's result bytes (x loop trips) to
    each of its (src, dst) pairs individually, so callers can aggregate
    true per-link traffic (``repro.launch.split_hub.hlo_link_bytes`` maps
    device ids back to pod stages via the mesh).
    """
    comps, entry = split_computations(hlo)
    per_comp: Dict[str, List[Tuple[List[Tuple[int, int]], int]]] = {}
    for name, lines in comps.items():
        items = []
        for line in lines:
            m = _RESULT_RE.match(line.strip())
            if not m:
                continue
            rhs = m.group(2)
            if not re.search(r"\bcollective-permute(?:-start)?\(", rhs):
                continue
            out_b = _collective_bytes(rhs)
            pm = _CP_PAIRS_RE.search(rhs)
            if not pm:
                continue
            pairs = [(int(a), int(b)) for a, b in
                     _PAIR_RE.findall(pm.group(1))]
            items.append((pairs, out_b))
        if items:
            per_comp[name] = items

    edges, cond_groups = _call_edges(comps)
    out: Dict[Tuple[int, int], int] = defaultdict(int)
    visiting = set()

    def walk(name: str, mult: int) -> None:
        if name not in comps or name in visiting or mult <= 0:
            return
        visiting.add(name)
        for pairs, b in per_comp.get(name, []):
            for p in pairs:
                out[p] += b * mult
        for child, m, _cb in edges.get(name, []):
            walk(child, mult * m)
        # a conditional runs one branch per execution; a ship op lives in
        # at most one branch in our programs, so charging each branch at
        # the parent multiplier attributes it correctly
        for branches in cond_groups.get(name, []):
            for br in branches:
                walk(br, mult)
        visiting.discard(name)

    if entry:
        walk(entry, 1)
    return dict(out)


def analyze(hlo: str) -> Dict:
    """Loop-aware per-device totals: dot FLOPs, output bytes, collectives."""
    comps, entry = split_computations(hlo)
    shape_map = _build_shape_map(comps)

    per_comp = {}
    for name, lines in comps.items():
        flops = 0.0
        bytes_out = 0
        coll: Dict[str, int] = defaultdict(int)
        coll_counts: Dict[str, int] = defaultdict(int)
        for line in lines:
            stripped = line.strip()
            m = _RESULT_RE.match(stripped)
            if not m:
                continue
            rhs = m.group(2)
            head = rhs.split("(")[0]
            opm = _OP_NAME_RE.search(rhs)
            op_name = opm.group(1) if opm else ""
            _, out_b = _shape_elems_bytes(head)
            if op_name not in _FREE_OPS:
                bytes_out += out_b
            if " dot(" in rhs or rhs.startswith("dot("):
                flops += _dot_flops(stripped, shape_map)
            for op in COLLECTIVE_OPS:
                if re.search(rf"\b{op}(?:-start)?\(", rhs):
                    coll[op] += _collective_bytes(rhs)
                    coll_counts[op] += 1
                    break
        per_comp[name] = (flops, bytes_out, dict(coll), dict(coll_counts))

    # call-graph edges: (child, multiplier, counts_bytes) — see
    # _call_edges.  conditional( branches are NOT plain edges: exactly one
    # branch runs per execution, so each conditional contributes the
    # elementwise MAX over its branch subtrees, once — not the sum
    # ("always-taken").
    edges, cond_groups = _call_edges(comps)

    def _zero():
        return dict(flops=0.0, bytes=0, coll=defaultdict(int),
                    coll_n=defaultdict(int))

    memo: Dict[Tuple[str, bool], Dict] = {}
    visiting = set()
    truncations = [0]  # bumped whenever a back-edge is skipped

    def subtree(name: str, count_bytes: bool) -> Dict:
        """Per-execution totals of ``name`` including everything it calls.

        The call graph of valid HLO is a DAG, so memoization makes the
        walk linear; ``visiting`` breaks cycles a malformed module could
        contain, and any subtree that hit a back-edge is NOT memoized
        (nor are its ancestors), so truncated totals never poison the
        cache.
        """
        if name not in per_comp:
            return _zero()
        if name in visiting:
            truncations[0] += 1
            return _zero()
        key = (name, count_bytes)
        if key in memo:
            return memo[key]
        trunc_before = truncations[0]
        visiting.add(name)
        flops, bytes_out, coll, coll_counts = per_comp[name]
        tot = _zero()
        tot["flops"] = flops
        tot["bytes"] = bytes_out if count_bytes else 0
        tot["coll"].update(coll)
        tot["coll_n"].update(coll_counts)
        for child, mult, cb in edges.get(name, []):
            sub = subtree(child, count_bytes and cb)
            tot["flops"] += sub["flops"] * mult
            tot["bytes"] += sub["bytes"] * mult
            for k, v in sub["coll"].items():
                tot["coll"][k] += v * mult
            for k, v in sub["coll_n"].items():
                tot["coll_n"][k] += v * mult
        for branches in cond_groups.get(name, []):
            subs = [subtree(b, count_bytes) for b in branches]
            if not subs:
                continue
            tot["flops"] += max(s["flops"] for s in subs)
            tot["bytes"] += max(s["bytes"] for s in subs)
            for field in ("coll", "coll_n"):
                for k in set().union(*[s[field].keys() for s in subs]):
                    tot[field][k] += max(s[field].get(k, 0) for s in subs)
        visiting.discard(name)
        if truncations[0] == trunc_before:
            memo[key] = tot
        return tot

    tot = subtree(entry, True) if entry else _zero()
    return dict(
        dot_flops=tot["flops"],
        bytes_out=float(tot["bytes"]),
        collective_bytes=int(sum(tot["coll"].values())),
        collective_by_op={k: int(v) for k, v in tot["coll"].items()},
        collective_counts={k: int(v) for k, v in tot["coll_n"].items()},
        n_computations=len(comps),
    )


def entry_parameter_bytes(hlo: str) -> int:
    """Total bytes of the ENTRY computation's parameter instructions.

    For a jitted function this is what the executable streams in per call
    — for a weights-consuming forward, the weight HBM read floor.  The
    wq benchmark compares this between the dense and the packed stacks to
    assert the int4 weight-byte cut survives compilation (codes stay u8,
    scales f16 — nothing silently widened by XLA).
    """
    comps, entry = split_computations(hlo)
    total = 0
    for line in comps.get(entry, []):
        m = _RESULT_RE.match(line.strip())
        if not m:
            continue
        rhs = m.group(2)
        opm = _OP_NAME_RE.search(rhs)
        if not opm or opm.group(1) != "parameter":
            continue
        _, b = _shape_elems_bytes(rhs.split("(")[0])
        total += b
    return total


def collective_bytes(hlo: str) -> Tuple[int, Dict[str, int]]:
    res = analyze(hlo)
    return res["collective_bytes"], res["collective_by_op"]


def count_op(hlo: str, opname: str) -> int:
    return len(re.findall(rf"\s{opname}(?:-start)?\(", hlo))
