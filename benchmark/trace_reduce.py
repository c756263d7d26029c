"""From a ``jax.profiler`` trace to what the per-layer metrics read.

One device plane per chip (``/device:TPU:<n>``). Its ``XLA Ops`` line holds the
operations the chip ran, control flow (``while``) as events that enclose their
bodies' operations; ``Async XLA Ops`` holds the spans of asynchronous copies
and collectives from start to done. The host plane's ``python`` line holds the
harness's ``TraceAnnotation`` spans (``next_batch``, ``dispatch``, ``fence``).
All times are picoseconds on one clock.

Busy time is the union of the intervals in which an operation ran, so enclosing
events add nothing; time by operation is *self* time, an enclosing event's
duration less its children's.
"""

import collections
import glob
import os
import re

from benchmark import xplane

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE, _ASYNC_LINE, _HOST_PLANE, _HOST_LINE = "XLA Ops", "Async XLA Ops", "/host:CPU", "python"
_HOST_SPANS = ("next_batch", "dispatch", "fence")
COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")

Op = collections.namedtuple("Op", "name start end self_ps leaf stats")


def short_name(hlo_text):
    """``%fusion.157`` of ``%fusion.157 = (bf16[...]) fusion(...)``."""
    return hlo_text.split(" = ", 1)[0].strip()


def union(intervals):
    """Disjoint sorted ``[(start, end)]`` covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, holes):
    """The parts of disjoint sorted ``intervals`` not covered by disjoint sorted ``holes``."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(holes) and holes[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(holes) and holes[k][0] < e:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def nest(events):
    """``[Op]`` of one line's events with self time and whether each is a leaf."""
    order = sorted(events, key=lambda ev: (ev.start_ps, -ev.duration_ps))
    child_ps, has_child, stack = [0] * len(order), [False] * len(order), []
    for i, ev in enumerate(order):
        while stack and order[stack[-1]].start_ps + order[stack[-1]].duration_ps <= ev.start_ps:
            stack.pop()
        if stack:
            child_ps[stack[-1]] += ev.duration_ps
            has_child[stack[-1]] = True
        stack.append(i)
    return [Op(short_name(ev.name), ev.start_ps, ev.start_ps + ev.duration_ps,
               max(ev.duration_ps - child_ps[i], 0), not has_child[i], ev.stats)
            for i, ev in enumerate(order)]


class Trace:
    """The traced window as the reductions see it."""

    def __init__(self, planes, chips):
        self.chips = []            # per chip: {"ops": [Op], "async": [Op]}
        numbered = sorted((int(m.group(1)), p) for p in planes
                          for m in [_DEVICE_PLANE.match(p.name)] if m)
        for _, plane in numbered:
            lines = {ln.name: ln.events for ln in plane.lines}
            if lines.get(_OPS_LINE):
                self.chips.append({"ops": nest(lines[_OPS_LINE]),
                                   "async": nest(lines.get(_ASYNC_LINE, []))})
        if len(self.chips) < chips:
            raise RuntimeError(
                f"the trace has {len(self.chips)} device plane(s) with operations, "
                f"the cell uses {chips}: planes {[p.name for p in planes]}")
        self.chips = self.chips[:chips]
        self.host = []
        for plane in planes:
            if plane.name == _HOST_PLANE:
                for ln in plane.lines:
                    if ln.name == _HOST_LINE:
                        self.host = [ev for ev in ln.events if ev.name in _HOST_SPANS]

    # per chip ------------------------------------------------------------
    def window(self, chip):
        ops = self.chips[chip]["ops"]
        return min(o.start for o in ops), max(o.end for o in ops)

    def busy(self, chip):
        return union((o.start, o.end) for o in self.chips[chip]["ops"])

    def leaves(self, chip, match=None):
        return [o for o in self.chips[chip]["ops"] if o.leaf and (match is None or match(o))]

    def per_chip(self, fn):
        return [fn(c) for c in range(len(self.chips))]

    # what the result line carries ------------------------------------------
    def busy_s(self):
        return sum(self.per_chip(lambda c: length(self.busy(c)))) / len(self.chips) * 1e-12

    def window_s(self):
        spans = self.per_chip(self.window)
        return sum(e - s for s, e in spans) / len(spans) * 1e-12

    def breakdown(self, top=10):
        by_op = collections.Counter()
        for op in self.chips[0]["ops"]:
            # the HLO name alone says little: add XLA's category and the
            # framework name's last parts, which carry the named scope
            scope = "/".join(str(op.stats.get("tf_op", "")).rstrip(":").split("/")[-3:])
            by_op[f"{op.name} [{op.stats.get('hlo_category', '?')}] {scope}"[:120]] += op.self_ps
        start, end = self.window(0)
        gaps = subtract([(start, end)], self.busy(0))
        by_span = collections.Counter()
        for s, e in gaps:
            best, best_ps = "host:other", 0
            for ev in self.host:
                overlap = min(e, ev.start_ps + ev.duration_ps) - max(s, ev.start_ps)
                if overlap > best_ps:
                    best, best_ps = "host:" + ev.name, overlap
            by_span[best] += e - s
        return {"device_ops": [[n, ps * 1e-12] for n, ps in by_op.most_common(top)],
                "idle_gaps": [[n, ps * 1e-12] for n, ps in by_span.most_common(top)]}


def load(trace_dir, chips):
    """The newest ``.xplane.pb`` under ``trace_dir``, as a :class:`Trace`."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return Trace(xplane.read(files[-1]), chips)
