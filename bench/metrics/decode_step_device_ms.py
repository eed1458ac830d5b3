"""Device time of one slot-decode program, from the trace's XLA module
events, averaged over the decode steps in the window (first device)."""
from bench.trace import MODULE, module_time_s


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    t, n = module_time_s(tr, min(tr.devices), MODULE["decode"])
    return 1e3 * t / n if n else None
