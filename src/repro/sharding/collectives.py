"""What a compiled SPMD program moves between devices, read from its HLO.

``collective_bytes(compiled.as_text())`` gives, by kind, the bytes one
device's collectives work on per run of the program: the buffer an
all-reduce, all-gather, all-to-all or collective-permute produces, and the
buffer a reduce-scatter consumes (its result times the group's size).  An
asynchronous collective is counted once, at its ``-done``.  A collective
inside a loop counts once per iteration (the trip count XLA prints, else
the bound on the loop's counter), so a scanned layer's all-reduces count
once per layer.
"""
from __future__ import annotations

import re

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")
#: opcode -> kind, for the ops at which a collective is counted
_COUNTED = {**{k: k for k in KINDS},
            **{f"{k}-done": k for k in ("all-reduce", "all-gather",
                                        "collective-permute")}}

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
          "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
          "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}
_ARRAY = re.compile(r"\b([a-z]\w*)\[([\d,]*)\]")
_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\b(?:body|calls|to_apply|branch_computations)="
                    r"(\{[^}]*\}|%?[\w.\-]+)")
_COND = re.compile(r"\bcondition=%?([\w.\-]+)")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([\d,]*)\}")


def shape_bytes(shape: str) -> int:
    """Bytes of every array in an HLO shape (a tuple's summed)."""
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        n = _BYTES.get(dtype)
        if n is None:
            continue
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n
    return total


def _group_size(attrs: str) -> int:
    m = _GROUPS_IOTA.search(attrs)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST.search(attrs)
    return len(m.group(1).split(",")) if m else 1


class _Comp:
    def __init__(self):
        self.instrs: dict[str, tuple] = {}   # name -> (op, args, shape)
        self.root = None

    def constant(self, name: str):
        """The integer an s32 scalar holds, through copies; else None."""
        for _ in range(8):
            op, rest, _ = self.instrs.get(name, (None, "", ""))
            if op == "constant":
                m = re.match(r"(-?\d+)\)", rest)
                return int(m.group(1)) if m else None
            if op != "copy":
                return None
            name = _OPERAND.match(rest).group(1)
        return None


def _parse(text: str) -> tuple[dict, str | None]:
    comps, entry, comp = {}, None, None
    for line in text.splitlines():
        head = _HEADER.match(line)
        if head:
            comp = comps[head.group(1)] = _Comp()
            if line.startswith("ENTRY"):
                entry = head.group(1)
            continue
        ins = _INSTR.match(line) if comp is not None else None
        if ins:
            root, name, shape, op, rest = ins.groups()
            comp.instrs[name] = (op, rest, shape)
            if root:
                comp.root = name
    return comps, entry


def _trips(comps: dict, comp: _Comp, rest: str) -> int:
    """Iterations of a while loop: XLA's known trip count where it prints
    one, else ``limit - start`` of a loop whose condition is ``i < limit``
    on the first tuple element, started from a constant (as a scan's is);
    1 where neither can be read."""
    m = _TRIPS.search(rest)
    if m:
        return int(m.group(1))
    m = _COND.search(rest)
    cond = comps.get(m.group(1)) if m else None
    if cond is None or cond.root is None:
        return 1
    op, args, _ = cond.instrs[cond.root]
    if op != "compare" or "direction=LT" not in args:
        return 1
    limit = cond.constant(_OPERAND.findall(args.split(")")[0])[-1])
    init = _OPERAND.match(rest)
    op, args, _ = comp.instrs.get(init.group(1) if init else "", ("", "", ""))
    first = _OPERAND.match(args) if op == "tuple" else None
    start = comp.constant(first.group(1)) if first else None
    if limit is None or start is None:
        return 1
    return max(limit - start, 0)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per device and per run of the program, the bytes of each kind of
    collective (``KINDS``; kinds with none are 0)."""
    comps, entry = _parse(hlo_text)
    out = dict.fromkeys(KINDS, 0)

    def walk(name: str, times: int, depth: int = 0) -> None:
        comp = comps.get(name)
        if comp is None or depth > 64:
            return
        for op, rest, shape in comp.instrs.values():
            kind = _COUNTED.get(op)
            if kind is not None:
                n = shape_bytes(shape)
                out[kind] += times * (n * _group_size(rest)
                                      if kind == "reduce-scatter" else n)
            if op == "async-done":       # its start names the same callee
                continue
            k = _trips(comps, comp, rest) if op == "while" else 1
            for ref in _CALLS.findall(rest):
                for callee in ref.strip("{}").split(","):
                    walk(callee.strip().lstrip("%"), times * k, depth + 1)

    if entry is not None:
        walk(entry, 1)
    return out
