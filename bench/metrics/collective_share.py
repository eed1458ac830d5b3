"""The collective ops' share of the train step's device time on the first
device, in %: ``collective_ms`` over the mean ``jit_train_step`` time."""
from bench.collectives import per_step


def read(ctx):
    got = per_step(ctx["trace"])
    return None if got is None or got[1] <= 0 else 100.0 * got[0] / got[1]
