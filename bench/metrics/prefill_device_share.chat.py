"""The prefill and install programs' share of the device's busy time in
the window, in %."""
from bench.trace import MODULE, busy_s, module_time_s


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    dev = min(tr.devices)
    busy = busy_s(tr, dev)
    pre, n = module_time_s(tr, dev, MODULE["prefill"])
    ins, m = module_time_s(tr, dev, MODULE["install"])
    return 100.0 * (pre + ins) / busy if busy > 0 and (n or m) else None
