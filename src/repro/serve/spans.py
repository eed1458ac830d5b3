"""Profiler spans of the program: the serving engine's step phases and the
sharded QFT set-up (``launch/train.ShardedQFT``).

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``repro:<name>`` while a profiler session runs, so the host phases land in
the profiler's own trace, on the clock of the device's ops.  The
arguments are host integers the caller already holds; they travel as the
event's stats.  With no session running it returns one shared context that
does nothing: no annotation is built and no name is formatted.

Arguments known only at the end of a phase are attached with
``set_metadata(**args)`` on the entered span, which both kinds accept.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

#: what every span of the program is named after
PREFIX = "repro:"


class _Off:
    """The span while no profiler session runs."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **args: int) -> None:
        pass


_OFF = _Off()


def span(name: str, **args: int):
    """A context manager that records ``repro:<name>`` with ``args`` while
    the profiler runs, and nothing otherwise."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return TraceAnnotation(PREFIX + name, **args)
