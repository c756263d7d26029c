"""Device self time, in ms per step, of the trace's operations **whose framework
name (``tf_op``) is empty**: the instructions the compiler made (layout copies,
a ``concatenate`` turned ``dynamic-update-slice``, zero fills, ``slice-start`` /
``slice-done`` prefetches, nameless fusions), which no ``monitor.spans.span``
can reach. Each is looked up by its HLO name in the program's own ledger of the
compiled step (``monitor.program_ops()``: one record an instruction, and for a
nameless one the scopes of the nearest named instruction that feeds it, its
``producer``, and that it feeds, its ``consumer``); worst chip.

Other programs of the process hold a ``%copy.1`` too (``pool``, ``update``,
``first_grad``), but only the step runs in the traced window and only
``donate_step`` entries are on the ledger, which in a cell is the step alone.
The trace names an operation's program by a number (``program_id``), never by
the module's name, so where two entries on the ledger hold one name the one
noted last is taken.

From the metric's file, either or both (a regular expression each, searched):
``owner`` — the operation's owner matches, the owner being its ``consumer``
and, where it has none, its ``producer`` (an operation the ledger does not
hold, or one with no named neighbour on either side, is owned by ``""``);
``either`` — its ``producer`` or its ``consumer`` matches. An operation the
trace leaves nameless and the compiled text names (a ``while``: the profiler
gives control flow no framework name) is its own producer and consumer. The
names and patterns live in the metric files, none here.

A program without the ledger, or one whose ledger holds no step, gives
nothing; a ledger in which nothing matches gives 0."""

import re


def lookup(records):
    """``find(op) -> record or None`` over the ledger's ``records``."""
    by_name = {r["name"]: r for r in records}
    return lambda op: by_name.get(op.name)


def nameless(ops, find):
    """``[(op, record or None)]`` of the ``ops`` whose framework name is empty."""
    return [(op, find(op)) for op in ops if not str(op.stats.get("tf_op", ""))]


def neighbours(record):
    """``(producer, consumer)`` of the operation whose ledger record is ``record``."""
    if record is None:
        return "", ""
    if record["scope"]:
        return record["scope"], record["scope"]
    return record["producer"], record["consumer"]


def kept(spec, record):
    """Whether the metric ``spec`` reads the operation whose ledger record is
    ``record`` (``None``: the ledger does not hold it)."""
    producer, consumer = neighbours(record)
    if "owner" in spec and not re.search(spec["owner"], consumer or producer):
        return False
    if "either" in spec and not (re.search(spec["either"], producer)
                                 or re.search(spec["either"], consumer)):
        return False
    return True


def reduce(spec, ctx):
    try:
        from beforeholiday_tpu.monitor import program_ops
    except ImportError:
        return None
    records = program_ops()
    if not records:
        return None
    find, t = lookup(records), ctx["trace"]
    ps = max(t.per_chip(lambda c: sum(
        op.self_ps for op, record in nameless(t.chips[c]["ops"], find)
        if kept(spec, record))))
    return ps * 1e-9 / ctx["steps"]
