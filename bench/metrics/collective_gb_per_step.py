"""Bytes the step's collectives carry per device and step, in GB: the
program's counter (``ShardedQFT.collective_bytes``, read from the compiled
step's HLO), summed over the kinds of collective."""


def read(ctx):
    counts = ctx["counts"].get("collective_bytes")
    return None if not counts else sum(counts.values()) / 1e9
