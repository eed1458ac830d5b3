"""1 - (union of device op intervals / traced window), in %; on several
chips, the idlest.  One reader for every kind of traffic
(``device_idle_share.<traffic>``)."""
from bench.trace import idle_share


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None or not tr.devices else idle_share(tr)
