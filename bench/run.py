"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic and per-layer metrics are found by name
(``bench/harness.py``).  Set-up makes everything from the seed and warms
every shape the window uses; the window runs ``--seconds``; then the
timed path's output is compared with the plain reference
(``bench/reference.py``).  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled window.  The
last line of stdout is the result; without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints none.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import flops, harness  # noqa: E402
from bench.harness import BenchError, log  # noqa: E402
from bench.trace import Tracer, idle_gaps, mean_busy_s, top_ops  # noqa: E402


@dataclasses.dataclass
class Run:
    cell: harness.Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    spans: harness.Spans
    tracer: Tracer
    t_start: float

    def memory_peak(self) -> int:
        return harness.memory_peak(self.devices[:self.cell.chips])


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell, out: dict, trace, run: Run, pk: dict) -> dict:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    ctx = {"trace": trace, "spans": run.spans.records, "counts": out["counts"],
           "peaks": pk, "chips": cell.chips, "flops": flops}
    metrics = {}
    for m in cell.per_layer:
        value = harness.load_metric_reader(cell.root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None, *, root: pathlib.Path = ROOT, require_chip: bool = True,
         variant: str | None = None) -> int:
    """``root``: where BENCHMARK.json and the cell's files are.
    ``require_chip`` False and ``variant`` serve the CPU tests only."""
    args = parse(argv)
    cell = harness.load_cell(root, args.workload)
    import jax
    devices = jax.devices()
    if require_chip:
        harness.require_devices(devices, cell.chips)
    log(f"devices: {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); jax {jax.__version__}; backend up at "
        f"{time.perf_counter() - T_START!r} s")
    log(f"compile cache: {harness.enable_compile_cache(root)}")
    pk = harness.peaks(root, devices[0].device_kind)
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), devices=devices,
              spans=harness.Spans(annotate=bool(args.trace)),
              tracer=Tracer(bool(args.trace)), t_start=T_START)
    kind = importlib.import_module(f"bench.kinds.{cell.traffic['kind']}")
    out = kind.run(run, variant=variant)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell.chips,
              "memory_peak_bytes": out["memory_peak_bytes"]}
    breakdown = None
    if run.trace:
        trace = run.tracer.load()
        log(f"trace: {len(trace.devices)} devices, window "
            f"{trace.window_s!r} s, read in {trace.read_s!r} s")
        device["window_s"] = trace.window_s
        device["busy_s"] = mean_busy_s(trace) if trace.devices else 0.0
        dev0 = min(trace.devices, default=None)
        breakdown = {"device_ops": [] if dev0 is None else top_ops(trace, dev0),
                     "idle_gaps": [] if dev0 is None else idle_gaps(trace,
                                                                    dev0)}
        metrics = per_layer(cell, out, trace, run, pk)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in out["end_to_end"].items() if k in units}
    for name, rec in run.spans.records.items():
        log(f"span {name}: {len(rec)} calls, {sum(rec)!r} s")
    harness.emit_result(checks=out["checks"], attempted=out["attempted"],
                        failed=out["failed"], metrics=metrics, device=device,
                        breakdown=breakdown)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"bench: {e.code}", file=sys.stderr)
        sys.exit(2)
