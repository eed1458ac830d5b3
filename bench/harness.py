"""What every cell shares: finding a cell's files by name, the device check,
the peaks table, spans, percentiles and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its files are
found by name under the benchmark's root:

- ``bench/configs/<config>.json`` (the entry's ``file``): the model as run,
  its published source and cuts, and how the program builds it;
- ``bench/traffic/<traffic>.json``: the traffic's parameters; its ``kind``
  names the driver in ``bench/kinds/`` that runs it;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric; a metric
  ``<name>.<traffic>`` with no file of its own is read by ``<name>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


class BenchError(SystemExit):
    """A refusal: exits non-zero and prints no result line."""


@dataclasses.dataclass
class Cell:
    name: str
    root: pathlib.Path
    config: dict          # the configuration file
    traffic: dict         # the traffic file
    chips: int
    end_to_end: list      # BENCHMARK.json metric entries of this cell
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    traffic = root / "bench" / "traffic" / f"{w['traffic']}.json"
    return Cell(name=name, root=root,
                config=json.loads((root / cfg["file"]).read_text()),
                traffic=json.loads(traffic.read_text()),
                chips=int(w["chips"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def metric_reader_path(root: pathlib.Path, name: str) -> pathlib.Path:
    """``bench/metrics/<name>.py``; where there is none, the reader shared
    by every traffic, ``<name without its last .suffix>.py``."""
    metrics = root / "bench" / "metrics"
    path = metrics / f"{name}.py"
    if not path.exists() and "." in name:
        path = metrics / f"{name.rsplit('.', 1)[0]}.py"
    return path


def load_metric_reader(root: pathlib.Path, name: str):
    """The per-layer metric ``name``'s ``read(ctx)``."""
    path = metric_reader_path(root, name)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_devices(devices, chips: int) -> None:
    """Refuse unless JAX sees TPUs, at least ``chips`` of them."""
    d = devices[0]
    if d.platform != "tpu":
        raise BenchError(f"the benchmark needs a TPU; JAX's first device is "
                         f"{d.platform} ({d.device_kind})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX sees "
                         f"{len(devices)}")


def peaks(root: pathlib.Path, device_kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; a kind not in the
    table is an error, never a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][device_kind]


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` points), for every program."""
    import os
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def percentile(values, p: float) -> float:
    """Nearest-rank percentile; ``inf`` entries stand for missing answers."""
    xs = sorted(values)
    if not xs:
        return math.inf
    return xs[max(math.ceil(p / 100 * len(xs)) - 1, 0)]


class Spans:
    """Host spans of the harness's own calls into the program, kept in
    memory.  In a traced run each is also a profiler annotation named
    ``bench:<name>``, so the trace can label idle gaps by them."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.records: dict[str, list[float]] = {}

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, spans: Spans, name: str):
        self.spans, self.name, self.ann = spans, name, None

    def __enter__(self):
        if self.spans.annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation("bench:" + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.spans.records.setdefault(self.name, []).append(
            time.perf_counter() - self.t0)
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def emit_result(*, checks: list[Check], attempted: int, failed: int,
                metrics: dict, device: dict, breakdown: dict | None) -> bool:
    """Print the checks as the last lines of stderr, then the result line
    as the last line of stdout.  Returns ``correct``."""
    correct = bool(checks) and all(c.ok for c in checks) and failed == 0
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAIL'}")
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    print(json.dumps(line), flush=True)
    return correct
