"""Time the collective ops hold the first device's core, per train step,
in ms: synchronous collectives and the ``-start``/``-done`` halves of
asynchronous ones (names matched in ``bench/collectives.py``), summed
inside the traced window, over the ``jit_train_step`` programs in it."""
from bench.collectives import per_step


def read(ctx):
    got = per_step(ctx["trace"])
    return None if got is None else 1e3 * got[0] / got[2]
