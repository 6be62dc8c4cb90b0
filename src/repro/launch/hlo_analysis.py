"""While-aware HLO analysis: exact dot-FLOPs and collective bytes.

XLA's HloCostAnalysis (and a naive text scan) counts a `while` body ONCE,
but our models lax.scan over layers (and over attention KV chunks), so
both FLOPs and collective bytes would be undercounted by the trip count.

This module parses `compiled.as_text()` (post-SPMD, scheduled HLO):

  1. split the module into computations,
  2. build a symbol table (op name -> shape) per computation,
  3. walk the call graph from the entry computation, carrying a
     MULTIPLIER: while-loop bodies multiply by the loop trip count
     (parsed from the `compare(..., constant(N))` in the loop condition);
     fusions / calls / to_apply multiply by 1; conditionals take both
     branches (upper bound),
  4. accumulate per-device
       * dot FLOPs: 2 * prod(result dims) * prod(contracting dims)
         (MAC-dominant accounting — elementwise/transcendental excluded,
         standard MFU practice),
       * collective result bytes per op kind.

Validated against hand-counted toys (scan of matmuls: exactly trips x
one-body) in tests/test_hlo_analysis.py.
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_TRIP_CFG = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_OP_LINE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.+)$")
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_TUPLE_SHAPE = re.compile(r"^\(")


def _shape_of(typestr: str):
    """First (dtype, dims) in a type string like 'f32[8,128]{1,0}'."""
    m = _SHAPE.match(typestr.strip().lstrip("("))
    if not m:
        return None
    dt = m.group(1)
    dims = [int(d) for d in m.group(2).split(",") if d]
    return dt, dims


def _all_shapes(typestr: str):
    out = []
    for m in _SHAPE.finditer(typestr):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        out.append((dt, dims))
    return out


def _nbytes(dt, dims):
    n = _DTYPE_BYTES.get(dt, 0)
    for d in dims:
        n *= d
    return n


def parse_module(text: str) -> dict[str, list[str]]:
    """Split HLO text into {computation_name: [op lines]}."""
    comps: dict[str, list[str]] = {}
    cur = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" ") and ("->" in line) and ("{" in line):
            m = _COMP_HDR.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
        if line.strip() == "}":
            continue
        if cur is not None:
            comps[cur].append(line.strip())
    return comps


def _entry_name(text: str, comps) -> str:
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR.match(line[len("ENTRY"):].strip())
            if m:
                return m.group(1)
    # fallback: the computation named like the module's main
    return next(iter(comps))


class _Comp:
    def __init__(self, lines: list[str]):
        self.lines = lines
        self.shapes: dict[str, str] = {}
        for ln in lines:
            m = _OP_LINE.match(ln)
            if m:
                self.shapes[m.group(1)] = m.group(2)

    def type_of(self, ref: str) -> str | None:
        return self.shapes.get(ref.lstrip("%"))


def _trip_count(cond: _Comp, comps: dict[str, "_Comp"]) -> int:
    """Max integer constant in the condition computation (and any
    computation it calls) — scan conditions compare the induction var
    against the trip count."""
    best = 1
    seen = set()

    def walk(c: _Comp):
        for ln in c.lines:
            for m in re.finditer(r"constant\((\d+)\)", ln):
                best_local = int(m.group(1))
                nonlocal best
                best = max(best, best_local)
            for m in re.finditer(r"(?:calls|to_apply|condition|body)="
                                 r"%?([\w\.\-]+)", ln):
                name = m.group(1)
                if name in comps and name not in seen:
                    seen.add(name)
                    walk(comps[name])
    walk(cond)
    return best


def _dot_flops(line: str, comp: _Comp, rhs: str) -> float:
    """2 * prod(result) * prod(contracting dims of lhs)."""
    res = _shape_of(rhs)
    if res is None:
        return 0.0
    _, rdims = res
    args = re.findall(r"\(([^)]*)\)", rhs)
    refs = re.findall(r"%([\w\.\-]+)", args[0]) if args else []
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rhs)
    cdims = [int(d) for d in m.group(1).split(",") if d] if m else []
    k = 1
    if refs:
        lhs_t = comp.type_of(refs[0])
        if lhs_t:
            sh = _shape_of(lhs_t)
            if sh:
                _, ldims = sh
                for cd in cdims:
                    if cd < len(ldims):
                        k *= ldims[cd]
    nres = 1
    for d in rdims:
        nres *= d
    return 2.0 * nres * k


def op_scopes(text: str, prefixes=("kv.", "cascade.")) -> dict[str, str]:
    """Instruction name -> its innermost named scope (`jax.named_scope`)
    whose name starts with one of `prefixes`, read from the `op_name`
    metadata of compiled HLO text (`compiled.as_text()`). Instructions in
    no such scope are left out. A fusion carries the metadata of its
    root, so it takes the scope of its root even where it fused ops of
    another scope in."""
    out: dict[str, str] = {}
    for line in text.splitlines():
        m = _OP_LINE.match(line)
        md = m and _OP_NAME.search(m.group(2))
        if not md:
            continue
        for part in reversed(md.group(1).split("/")):
            if part.startswith(tuple(prefixes)):
                out[m.group(1)] = part
                break
    return out


def analyze(text: str, top_ops: int = 0) -> dict:
    """Returns {'dot_flops', 'collectives': {kind: bytes, 'total': ...},
    'collective_counts': {kind: n (static ops x multiplier)}} and, with
    top_ops > 0, the largest individual collective contributors
    (bytes x loop multiplier, with the op_name metadata for attribution)."""
    raw = parse_module(text)
    comps = {k: _Comp(v) for k, v in raw.items()}
    entry = _entry_name(text, comps)

    flops = 0.0
    coll = {c: 0.0 for c in COLLECTIVES}
    ccount = defaultdict(float)
    contributors: list[tuple[float, str, str, str]] = []
    dot_contribs: list[tuple[float, str, str]] = []
    visiting: list[str] = []

    def walk(name: str, mult: float):
        nonlocal flops
        comp = comps.get(name)
        if comp is None or name in visiting:
            return
        visiting.append(name)
        for ln in comp.lines:
            m = _OP_LINE.match(ln)
            if not m:
                continue
            rhs = m.group(2)
            opm = re.match(r"(?:\(?[\w\[\],{}/ ]*\)?\s*)?([a-z][a-z0-9\-]*)"
                           r"(?:\.\d+)?\(", rhs.split(" ", 1)[1]
                           if _SHAPE.match(rhs) or rhs.startswith("(")
                           else rhs)
            # op name: the token right before the first '(' after the type
            op = None
            mm = re.search(r"\}?\s*([a-z][a-z0-9\-]*)\(", rhs)
            if mm:
                op = mm.group(1)
            if op == "dot":
                f = mult * _dot_flops(ln, comp, rhs)
                flops += f
                if top_ops:
                    meta = re.search(r'op_name="([^"]*)"', ln)
                    dot_contribs.append(
                        (f, rhs.split("dot")[0].strip()[:50],
                         meta.group(1)[-100:] if meta else ""))
            elif op in COLLECTIVES or (op or "").rstrip("-start").rstrip(
                    "-done") in COLLECTIVES:
                base = (op[:-6] if op.endswith("-start") else
                        op[:-5] if op.endswith("-done") else op)
                if base in COLLECTIVES and not op.endswith("-done"):
                    bytes_ = sum(_nbytes(dt, dims)
                                 for dt, dims in _all_shapes(
                                     rhs.split(base)[0]))
                    coll[base] += mult * bytes_
                    ccount[base] += mult
                    if top_ops:
                        meta = re.search(r'op_name="([^"]*)"', ln)
                        shape = rhs.split(base)[0].strip()[:60]
                        contributors.append(
                            (mult * bytes_, base, shape,
                             meta.group(1)[-110:] if meta else ""))
            if op == "while":
                bm = re.search(r"body=%?([\w\.\-]+)", rhs)
                cm = re.search(r"condition=%?([\w\.\-]+)", rhs)
                tc = _TRIP_CFG.search(rhs)          # XLA-annotated trip count
                if tc:
                    trips = int(tc.group(1))
                elif cm and cm.group(1) in comps:
                    trips = _trip_count(comps[cm.group(1)], comps)
                else:
                    trips = 1
                if bm:
                    walk(bm.group(1), mult * trips)
            else:
                for sub in re.finditer(r"(?:calls|to_apply)=%?([\w\.\-]+)",
                                       rhs):
                    walk(sub.group(1), mult)
                if op == "conditional":
                    for sub in re.finditer(
                            r"(?:true_computation|false_computation|"
                            r"branch_computations)=\{?%?([\w\.\-,% ]+)", rhs):
                        for nm in re.split(r"[,%\s]+", sub.group(1)):
                            if nm in comps:
                                walk(nm, mult)
        visiting.pop()

    walk(entry, 1.0)
    coll_out = {k: int(v) for k, v in coll.items()}
    coll_out["total"] = int(sum(coll.values()))
    out = {"dot_flops": flops, "collectives": coll_out,
           "collective_counts": {k: int(v) for k, v in ccount.items()}}
    if top_ops:
        contributors.sort(reverse=True)
        out["top_collectives"] = contributors[:top_ops]
        dot_contribs.sort(reverse=True)
        out["top_dots"] = dot_contribs[:top_ops]
    return out
