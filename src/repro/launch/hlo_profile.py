"""Collective/dot attribution: rank individual HLO instructions by
trip-count-corrected cost.  The hillclimb's 'profiler' (no real hardware —
we read the compiled module instead of a trace).

  python -m repro.launch.hlo_profile <hlo.txt> [--top 20]
"""
from __future__ import annotations

import argparse
import re
from collections import defaultdict

from .hlo_analysis import (_COLLECTIVES, _COMMENT_RE, _CONTRACT, _INSTR_RE,
                           _TRIP_COUNT_RE, _WHILE_ATTRS, _CALLSITE,
                           _first_shape, _group_size, _operand_names,
                           _shape_bytes, split_computations)


def attribute(hlo: str, default_group: int = 1):
    comps = split_computations(hlo)
    # per-computation: (items, children)
    info = {}
    for name, lines in comps.items():
        symtab, items, children = {}, [], []
        for line in lines:
            line = _COMMENT_RE.sub("", line)
            m = _INSTR_RE.match(line)
            if not m:
                continue
            iname, shape, opcode = m.groups()
            symtab[iname] = shape
            if any(opcode == k or opcode == k + "-start"
                   for k in _COLLECTIVES):
                kind = opcode.removesuffix("-start")
                op_names = _operand_names(line, opcode)
                op_bytes = sum(_shape_bytes(symtab.get(o, ""))
                               for o in op_names)
                n_full = max(op_bytes, _shape_bytes(shape))
                n = _group_size(line, default_group)
                ring = (n - 1) / n if n > 1 else 0.0
                factor = {"all-gather": ring, "reduce-scatter": ring,
                          "all-reduce": 2 * ring, "all-to-all": ring,
                          "collective-permute": 1.0}[kind]
                meta = re.search(r'op_name="([^"]*)"', line)
                items.append((kind, shape[:60], n_full * factor,
                              (meta.group(1) if meta else "")))
            elif opcode == "dot":
                mc = _CONTRACT.search(line)
                ops = _operand_names(line, "dot")
                out = _first_shape(shape)
                if out and mc and ops:
                    lhs = _first_shape(symtab.get(ops[0], ""))
                    if lhs:
                        csize = 1
                        for dd in (int(v) for v in
                                   mc.group(1).split(",") if v):
                            if dd < len(lhs[1]):
                                csize *= lhs[1][dd]
                        out_n = 1
                        for dd in out[1]:
                            out_n *= dd
                        meta = re.search(r'op_name="([^"]*)"', line)
                        items.append(("dot", shape[:60],
                                      2.0 * out_n * csize,
                                      (meta.group(1) if meta else "")))
            if opcode == "while":
                m2 = _WHILE_ATTRS.search(line)
                if m2:
                    mt = _TRIP_COUNT_RE.search(line)
                    children.append((m2.group(2),
                                     int(mt.group(1)) if mt else 1))
                    continue
            for callee in _CALLSITE.findall(line):
                children.append((callee, 1))
        info[name] = (items, children)

    referenced = {c for _, ch in info.values() for c, _ in ch}
    entry = next((n for n in info if "main" in n),
                 next((n for n in info if n not in referenced), None))

    totals = defaultdict(float)   # (kind, shape, opname) -> folded cost
    seen = {}

    def fold(name, mult, stack=()):
        if name in stack or name not in info:
            return
        items, children = info[name]
        for kind, shape, cost, opname in items:
            totals[(kind, shape, opname)] += cost * mult
        for child, trips in children:
            fold(child, mult * trips, stack + (name,))

    fold(entry, 1.0)
    return totals


def scoped_dot_flops(hlo: str, scope: str, default_group: int = 1) -> float:
    """Trip-folded dot FLOPs attributed to one ``jax.named_scope``.

    Sums every dot whose ``op_name`` metadata contains ``scope`` — e.g.
    ``scope="ffn_pattern"`` isolates the pattern-compacted FFN matmuls
    (``models/layers.py`` wraps ``ffn_block`` in that scope), which is how
    the tests check the 1/dp FLOP claim on each compiled bucket.
    """
    totals = attribute(hlo, default_group=default_group)
    return sum(v for (kind, _, opname), v in totals.items()
               if kind == "dot" and scope in opname)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.hlo_profile",
        description="Rank HLO instructions by trip-count-corrected cost.")
    ap.add_argument("hlo", help="path to an HLO text dump (compiled module)")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--kind", default="coll", choices=["coll", "dot"])
    ap.add_argument("--group", type=int, default=256,
                    help="default collective group size when the HLO "
                         "omits replica_groups")
    ap.add_argument("--scope", default=None,
                    help="only show instructions whose op_name contains "
                         "this named_scope substring")
    args = ap.parse_args(argv)
    try:
        with open(args.hlo) as f:
            hlo = f.read()
    except OSError as e:
        ap.exit(2, f"error: cannot read {args.hlo!r}: {e}\n")
    totals = attribute(hlo, default_group=args.group)
    if not totals:
        print("no attributable instructions found "
              "(is this an optimized HLO text dump?)")
        return 1
    rows = [(v, k) for k, v in totals.items()
            if (k[0] == "dot") == (args.kind == "dot")
            and (args.scope is None or args.scope in k[2])]
    rows.sort(reverse=True)
    unit = "FLOP" if args.kind == "dot" else "wire-B"
    for v, (kind, shape, opname) in rows[:args.top]:
        print(f"{v:.3e} {unit:7s} {kind:18s} {shape:40s} {opname[-90:]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
