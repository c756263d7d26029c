"""Device self time, in ms per step, of operations one of whose trace stats
(``stat``: for example XLA's category, or the framework name that carries the
``jax.named_scope`` path) matches ``pattern``; worst chip."""

import re


def reduce(spec, ctx):
    pattern, t, stat = re.compile(spec["pattern"]), ctx["trace"], spec["stat"]
    ps = max(t.per_chip(lambda c: sum(
        op.self_ps for op in t.chips[c]["ops"]
        if pattern.search(str(op.stats.get(stat, ""))))))
    return ps * 1e-9 / ctx["steps"] if ps else None
