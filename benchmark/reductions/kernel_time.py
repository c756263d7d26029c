"""Device self time, in ms per step, of operations whose HLO name matches ``pattern``; worst chip."""

import re


def time_ps(spec, ctx):
    pattern, t = re.compile(spec["pattern"]), ctx["trace"]
    return max(t.per_chip(lambda c: sum(
        op.self_ps for op in t.chips[c]["ops"] if pattern.search(op.name))))


def reduce(spec, ctx):
    ps = time_ps(spec, ctx)
    return ps * 1e-9 / ctx["steps"] if ps else None
