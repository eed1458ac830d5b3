"""Readings that the limits of ``correct`` are set from (never run by the
benchmark's own runs).

    python bench/calibrate.py --workload <cell> --seed <first> --seeds 12 \
        [--controls 3] [--faults 3]

For each of ``--seeds`` seeds from ``--seed`` on, the numbers that a run
compares, as the program gives them (the lower reading); for the first
``--controls`` seeds the same numbers with the fp8 reference in the
program's place (the control, the upper reading); and for the first
``--faults`` seeds with each fault the cell can have planted in the timed
path.  A QFT cell needs no window for its readings; a serving cell runs a
short window at the cell's own load.  One JSON line per reading on
stdout; all in one process, so set-up compiles once.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.run import Run  # noqa: E402
from bench.trace import Tracer  # noqa: E402

#: the faults each kind of cell can have (a state left unchanged reads 1 by
#: construction and needs no run)
FAULTS = {"qft": ("half_batch",), "serve": ("altered",)}


def readings(kind, run: Run, variant) -> dict:
    return {c.name: c.value for c in kind.run(run, variant=variant)["checks"]}


def main(argv=None, *, root: pathlib.Path = ROOT,
         require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(root, args.workload)
    import importlib
    import jax
    devices = jax.devices()
    if require_chip:
        harness.require_devices(devices, cell.chips)
    harness.enable_compile_cache(root)
    kind_name = cell.traffic["kind"]
    kind = importlib.import_module(f"bench.kinds.{kind_name}")
    plan = [(None, i) for i in range(args.seeds)]
    plan += [("control", i) for i in range(args.controls)]
    plan += [(f, i) for f in FAULTS[kind_name] for i in range(args.faults)]
    for variant, i in plan:
        seed = args.seed + i
        run = Run(cell=cell, seed=seed, seconds=args.seconds, trace=False,
                  devices=devices, spans=harness.Spans(False),
                  tracer=Tracer(False), t_start=time.perf_counter())
        line = {"variant": variant or "program", "seed": seed,
                **readings(kind, run, variant)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
