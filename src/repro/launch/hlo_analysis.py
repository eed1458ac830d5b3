"""HLO-level roofline accounting from compiled (SPMD-partitioned) modules.

- Collective bytes: parse ``compiled.as_text()``; every collective op's
  result/operand shape is local (post-partitioning).  Ops inside while-loop
  bodies are multiplied by the loop's exact ``known_trip_count`` from
  backend_config (scan-over-layers correction).  Ring discounts from
  replica_groups: all-gather / reduce-scatter move (g-1)/g of the full buffer
  per device; all-reduce 2(g-1)/g; all-to-all (g-1)/g; collective-permute 1.
- cost_analysis() counts while bodies ONCE; launch/dryrun.py corrects FLOPs /
  HBM bytes by L-differencing (compile at L=1 and L=2; see DESIGN.md §7).
"""
from __future__ import annotations


import re
from typing import Any

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_COMP_HDR_RE = re.compile(r"^(%[\w\.\-]+|ENTRY\s+%?[\w\.\-]+)\s*\(")
_WHILE_RE = re.compile(r"while\(.*?body=(%[\w\.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_OLD_RE = re.compile(r"replica_groups=\{\{([\d,]+)")


def shape_bytes(text: str) -> int:
    """Sum byte sizes of every typed shape in an HLO result-type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        m = _COMP_HDR_RE.match(line)
        if m and line.rstrip().endswith("{"):
            name = m.group(1)
            if name.startswith("ENTRY"):
                name = "ENTRY"
            cur = name
            comps[cur] = []
            continue
        if stripped == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(stripped)
    return comps


def _group_size(line: str, n_devices: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))           # [ngroups, group_size]<=[...]
    m = _GROUPS_OLD_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return n_devices


_RING_FACTOR = {
    "all-gather": lambda g: (g - 1) / g,
    "reduce-scatter": lambda g: (g - 1) / g,
    "all-reduce": lambda g: 2 * (g - 1) / g,
    "all-to-all": lambda g: (g - 1) / g,
    "collective-permute": lambda g: 1.0,
}


def collective_stats(hlo: str, n_devices: int) -> dict[str, Any]:
    """Per-device collective traffic in bytes (ring-model, trip-count exact)."""
    comps = _split_computations(hlo)

    # computation -> multiplier from enclosing while loops
    mult: dict[str, float] = {name: 1.0 for name in comps}
    for name, lines in comps.items():
        for line in lines:
            wm = _WHILE_RE.search(line)
            if wm:
                body = wm.group(1)
                tm = _TRIP_RE.search(line)
                trip = int(tm.group(1)) if tm else 1
                if body in mult:
                    mult[body] *= trip
    # propagate one nesting level (scan inside scan)
    for name, lines in comps.items():
        for line in lines:
            wm = _WHILE_RE.search(line)
            if wm and wm.group(1) in mult:
                tm = _TRIP_RE.search(line)
                trip = int(tm.group(1)) if tm else 1
                mult[wm.group(1)] = max(mult[wm.group(1)],
                                        trip * mult.get(name, 1.0))

    per_kind: dict[str, float] = {}
    total = 0.0
    ops = 0
    for name, lines in comps.items():
        m = mult.get(name, 1.0)
        for line in lines:
            cm = _COLL_RE.search(line)
            if not cm:
                continue
            kind = cm.group(1)
            # local result shape(s) = bytes each device holds for this op
            lhs = line.split(" = ", 1)
            if len(lhs) != 2:
                continue
            nbytes = shape_bytes(lhs[1].split(cm.group(1))[0])
            g = _group_size(line, n_devices)
            traffic = nbytes * _RING_FACTOR[kind](g) * m
            per_kind[kind] = per_kind.get(kind, 0.0) + traffic
            total += traffic
            ops += int(m)
    return {"collective_bytes": total, "per_kind": per_kind, "n_ops": ops}


def cost_summary(compiled) -> dict[str, float]:
    ca = compiled.cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def memory_summary(compiled) -> dict[str, float]:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": float(ma.argument_size_in_bytes),
        "output_bytes": float(ma.output_size_in_bytes),
        "temp_bytes": float(ma.temp_size_in_bytes),
        "alias_bytes": float(ma.alias_size_in_bytes),
        "peak_bytes": float(ma.argument_size_in_bytes
                            + ma.output_size_in_bytes
                            + ma.temp_size_in_bytes
                            - ma.alias_size_in_bytes),
    }


# ---------------------------------------------------------------------------
# Roofline terms (TPU v5e constants from the assignment)
# ---------------------------------------------------------------------------

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link


def roofline_terms(flops_dev: float, bytes_dev: float, coll_dev: float,
                   model_flops_total: float, n_chips: int) -> dict[str, Any]:
    t_c = flops_dev / PEAK_FLOPS
    t_m = bytes_dev / HBM_BW
    t_x = coll_dev / ICI_BW
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])
    t_bound = max(t_c, t_m, t_x, 1e-12)
    useful = model_flops_total / max(flops_dev * n_chips, 1.0)
    return {
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom[0],
        "roofline_fraction": t_c / t_bound,   # fraction of bound spent computing
        "model_flops": model_flops_total,
        "useful_flops_ratio": useful,
    }
