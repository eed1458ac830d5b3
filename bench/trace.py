"""The profiler trace of a window, and its reduction to numbers.

``Tracer.window()`` wraps the measured window: with tracing on it runs
JAX's profiler into a temporary directory and marks the window with the
host span ``bench:window``.  ``load`` reads the ``.xplane.pb`` into plain
lists, one per device: the XLA ops and the XLA modules (jitted programs)
with start and duration in nanoseconds, and the host's ``bench:`` spans, on
the trace's one clock.  The functions below reduce those lists; they are
what the per-layer readers in ``bench/metrics/`` call.

Names that the program's trace gives today, matched here and nowhere else:
a jitted program's module is ``jit_<function name>``; an op is named by its
HLO instruction, and the decode-attention Pallas call's instruction is
``decode_attention.<n>``, after the Python function that calls it.
"""
from __future__ import annotations

import contextlib
import glob
import json
import re
import shutil
import tempfile
import time

from .harness import log

#: the program's names, as the trace shows them
MODULE = {"train_step": "jit_train_step",
          "prefill": "jit_prefill_step",
          "decode": "jit_slot_decode_step",
          "install": "jit__paged_install_step"}
KERNEL = {"decode_attention": "decode_attention"}
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW = "bench:window"


class Tracer:
    """The measured window: profiled when ``enabled``, and in every run
    the XLA compilations inside it are counted (there should be none)."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None
        self.trace = None
        self.compiles = 0
        self._open = False

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self._open and event.endswith("backend_compile_duration"):
            self.compiles += 1

    @contextlib.contextmanager
    def window(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        if self.enabled:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)
        self._open = True
        try:
            if self.enabled:
                with jax.profiler.TraceAnnotation(WINDOW):
                    yield
            else:
                yield
        finally:
            self._open = False
            if self.enabled:
                jax.profiler.stop_trace()
            log(f"compilations inside the window: {self.compiles}")

    def load(self) -> "Trace":
        t0 = time.perf_counter()
        try:
            self.trace = load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        self.trace.read_s = time.perf_counter() - t0
        return self.trace


class Trace:
    """``devices``: {device id: {"ops": [[name, start_ns, dur_ns], ...],
    "modules": [...]}}; ``spans``: [[name, start_ns, dur_ns], ...]."""

    def __init__(self, devices: dict, spans: list):
        self.devices = {int(k): v for k, v in devices.items()}
        self.spans = spans
        win = [s for s in spans if s[0] == WINDOW]
        self.window = ((win[0][1], win[0][1] + win[0][2]) if win else
                       _extent(devices))
        self.read_s = 0.0

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(d["devices"], d["spans"])

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _extent(devices: dict) -> tuple:
    evs = [e for d in devices.values() for e in d["ops"]]
    if not evs:
        return (0, 0)
    return (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))


def _short(name: str) -> str:
    """An op event's name is its HLO text, ``%name = type op(...)``: keep
    the instruction's own name."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[0])
    devices, spans = {}, []
    for plane in pd.planes:
        log(f"trace plane {plane.name}: {[ln.name for ln in plane.lines][:8]}")
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            d = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    d[key] += [[_short(e.name), e.start_ns, e.duration_ns]
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events
                          if e.name.startswith("bench:")]
    return Trace(devices, spans)


# -------------------------------------------------------------- reductions

def _clip(events, window):
    lo, hi = window
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_s(trace: Trace, device: int) -> float:
    ops = _clip(trace.devices[device]["ops"], trace.window)
    return sum(b - a for a, b in union((a, b) for _, a, b in ops)) * 1e-9


def idle_share(trace: Trace, device: int | None = None) -> float:
    """1 − busy / window, in %; with ``device`` None, the idlest device."""
    devs = list(trace.devices) if device is None else [device]
    return max(100.0 * (1.0 - busy_s(trace, d) / trace.window_s)
               for d in devs)


def mean_busy_s(trace: Trace) -> float:
    return sum(busy_s(trace, d) for d in trace.devices) / len(trace.devices)


def op_time_s(trace: Trace, device: int, pattern: str) -> tuple[float, int]:
    """Summed device time and count of the ops whose name contains
    ``pattern``, inside the window."""
    ops = [(a, b) for n, a, b in _clip(trace.devices[device]["ops"],
                                       trace.window) if pattern in n]
    return sum(b - a for a, b in ops) * 1e-9, len(ops)


def module_time_s(trace: Trace, device: int, prefix: str) -> tuple[float, int]:
    """Summed time and count of the jitted programs whose module name
    starts with ``prefix``, inside the window."""
    mods = [(a, b) for n, a, b in _clip(trace.devices[device]["modules"],
                                        trace.window) if n.startswith(prefix)]
    return sum(b - a for a, b in mods) * 1e-9, len(mods)


def top_ops(trace: Trace, device: int, n: int = 10) -> list:
    """The ``n`` op names with the most device time in the window."""
    tot: dict[str, int] = {}
    for name, a, b in _clip(trace.devices[device]["ops"], trace.window):
        tot[name] = tot.get(name, 0) + (b - a)
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, device: int, n: int = 10) -> list:
    """Idle time of ``device`` in the window, summed by the harness span
    open on the host at each gap's middle (the innermost one), longest
    first."""
    lo, hi = trace.window
    busy = union((a, b) for _, a, b in _clip(trace.devices[device]["ops"],
                                              trace.window))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [(s, s + d, name[len("bench:"):]) for name, s, d in trace.spans
             if name != WINDOW]
    tot: dict[str, int] = {}
    for a, b in gaps:
        mid = (a + b) // 2
        open_ = [sp for sp in spans if sp[0] <= mid < sp[1]]
        if open_:
            label = max(open_)[2]
        elif a == lo:
            label = "window start, before the first device op"
        else:
            label = "no harness span"
        tot[label] = tot.get(label, 0) + (b - a)
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
