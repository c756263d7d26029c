"""Peak device memory after the window, in GB, fullest chip."""

from benchmark import run


def reduce(spec, ctx):
    peak = run.memory_peak(ctx["devices"])
    return peak / 1e9 if peak else None
