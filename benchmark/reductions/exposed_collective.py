"""Collective time not hidden behind compute, in ms per step, worst chip.

A collective's interval is its operation on the ``XLA Ops`` line (the ``-start``
and ``-done`` halves of an asynchronous one included; on a ``TPU v5 lite`` a
synchronous all-reduce is recorded as an event that *encloses* the operations
the chip ran meanwhile) and its start-to-done span on the ``Async XLA Ops``
line. Exposed is the part of those intervals in which no other operation ran
on that chip."""

from benchmark import trace_reduce as tr


def reduce(spec, ctx):
    t = ctx["trace"]

    def exposed(chip):
        # by XLA's category where the trace gives one (a jax psum is named
        # %psum.N, category all-reduce), else by the HLO name
        is_coll = lambda op: bool(tr.COLLECTIVE.match(
            str(op.stats.get("hlo_category") or op.name.lstrip("%"))))
        coll = [(o.start, o.end) for o in t.chips[chip]["ops"] if is_coll(o)]
        coll += [(o.start, o.end) for o in t.chips[chip]["async"] if is_coll(o)]
        if not coll:
            return None
        compute = tr.union((o.start, o.end) for o in t.leaves(chip, lambda o: not is_coll(o)))
        return tr.length(tr.subtract(tr.union(coll), compute))

    per_chip = [x for x in t.per_chip(exposed) if x is not None]
    return max(per_chip) * 1e-9 / ctx["steps"] if per_chip else None
