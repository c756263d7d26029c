"""Share of the traced window in which no operation ran, in percent, worst chip."""

from benchmark import trace_reduce as tr


def reduce(spec, ctx):
    t = ctx["trace"]

    def idle(chip):
        start, end = t.window(chip)
        return 100.0 * (1.0 - tr.length(t.busy(chip)) / (end - start))

    return max(t.per_chip(idle))
