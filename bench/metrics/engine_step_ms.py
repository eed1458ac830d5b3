"""Mean wall time of the harness's ``Engine.step`` calls in the window
(admission, prefill chunks, installs, one decode step and its host sync)."""


def read(ctx):
    steps = ctx["spans"].get("engine.step")
    return 1e3 * sum(steps) / len(steps) if steps else None
