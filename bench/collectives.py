"""Collective operations in a device trace, matched by name here and
nowhere else.

An op event is named by its HLO instruction (``bench/trace.py``).  A
collective's instruction keeps its opcode as its name's stem:
``all-reduce.83``, ``all-gather.75``, and, where XLA makes it asynchronous,
``all-reduce-start.4`` and ``all-reduce-done.4``.  The synchronous ops and
both halves of an asynchronous one are the time a collective holds the
core (an async pair's ``-start`` issues it, its ``-done`` waits for it).
"""
from __future__ import annotations

import re

from .trace import MODULE, Trace, _clip, module_time_s

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")
NAME = re.compile(r"^(%s)(-start|-done)?(\.\d+)*$"
                  % "|".join(re.escape(k) for k in KINDS))


def is_collective(op_name: str) -> bool:
    return NAME.match(op_name) is not None


def collective_s(trace: Trace, device: int) -> tuple[float, int]:
    """Summed time and count of the collective ops on ``device`` inside
    the window."""
    ops = [(a, b) for n, a, b in _clip(trace.devices[device]["ops"],
                                       trace.window) if is_collective(n)]
    return sum(b - a for a, b in ops) * 1e-9, len(ops)


def per_step(trace: Trace) -> tuple[float, float, int] | None:
    """On the first device: (collective time, train-step time, steps) in
    the window, or None where it holds no train step."""
    if trace is None or not trace.devices:
        return None
    dev = min(trace.devices)
    step_s, steps = module_time_s(trace, dev, MODULE["train_step"])
    if not steps:
        return None
    return collective_s(trace, dev)[0], step_s, steps
