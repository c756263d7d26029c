"""Host-side timeline recorder + Chrome-trace/Perfetto ``trace.json`` export.

``monitor/spans.py`` labels the HLO (``jax.named_scope``) so device activity
shows up in XProf; this module is the HOST half — a wall-clock event recorder
whose output loads directly in Perfetto / ``chrome://tracing`` (the JSON
Trace Event Format: https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU).

What the timeline shows: host-side activity — tracing/compilation of jitted
entry points, dispatch, and between-step host work. Spans opened inside a
jitted function measure TRACE time (the function body runs once, when XLA
builds the program), not device execution; device-side timelines remain
XProf's job (``monitor.spans.trace``). The two views compose: the recorder
timestamps where the HOST went, the comms ledger instants mark which
collectives each traced region issued.

Layout: one Chrome-trace *process* row per rank (``pid`` = rank; process
metadata names the row), one *thread* row per recording host thread. Spans
are ``B``/``E`` begin/end pairs (they nest per pid/tid), instants are ``i``
events.

Usage::

    with monitor.timeline("trace.json") as rec:
        step(params, batch)          # spans/comms instants land in rec
        rec.instant("ckpt_saved")
    # exported on exit; open trace.json in Perfetto

The **host ledger** below the recorder is the same clock kept always: a
process-global, bounded record of where the host's time went — compile phases
and compile-cache traffic (fed by ``monitor/compile.py``'s listener), host time
under every ``monitor.spans.span``, and the garbage collector's pauses (both
fed by ``monitor/spans.py``) — read by ``host_records()``. While a recorder is
active every ledger event also lands on it (``TraceRecorder.complete``), so
``trace.json`` and the ledger are one system seen twice.

``export`` is the module's ONE file-write path and is the only function the
no-host-sync AST scan sanctions for this file (it writes host dicts — it
still never reads a device value).
"""

from __future__ import annotations

import collections
import contextlib
import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "TraceRecorder",
    "active_recorder",
    "book",
    "held",
    "host_records",
    "outermost",
    "reset_host_ledger",
    "span_intervals",
    "tally",
    "timeline",
]


class TraceRecorder:
    """Append-only host event recorder in Chrome trace-event form.

    Thread-safe; timestamps are ``time.perf_counter_ns`` microseconds
    relative to construction (Chrome traces want microseconds)."""

    def __init__(self, *, process_name: str = "beforeholiday_tpu"):
        self._t0 = time.perf_counter_ns()
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._pids: Dict[int, int] = {}  # rank -> pid (identity; dedup only)
        self._tids: Dict[int, int] = {}  # thread ident -> small tid
        self._named_threads: set = set()  # (pid, tid) rows already named
        self._process_name = process_name
        # spans handed over whole (``complete``), merged in by ``events``
        self._completed: collections.deque = collections.deque()

    # ------------------------------------------------------------- internals
    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    def _pid_tid(self, rank: int, ident: Optional[int] = None):
        """Register (and name) the rank's process row and the thread's
        (default: this thread's) row on first use. Caller holds no lock."""
        if ident is None:
            ident = threading.get_ident()
        with self._lock:
            if rank not in self._pids:
                self._pids[rank] = rank
                self._events.append({
                    "ph": "M", "name": "process_name", "pid": rank, "tid": 0,
                    "args": {"name": f"{self._process_name} rank {rank}"},
                })
                self._events.append({
                    "ph": "M", "name": "process_sort_index", "pid": rank,
                    "tid": 0, "args": {"sort_index": rank},
                })
            if ident not in self._tids:
                self._tids[ident] = len(self._tids)
            tid = self._tids[ident]
            if (rank, tid) not in self._named_threads:
                # name every (process, thread) row so multi-rank traces load
                # with deterministic, human-readable rows in Perfetto (tid 0
                # is each rank's main recording thread)
                self._named_threads.add((rank, tid))
                self._events.append({
                    "ph": "M", "name": "thread_name", "pid": rank,
                    "tid": tid, "args": {"name": f"host-thread-{tid}"},
                })
                self._events.append({
                    "ph": "M", "name": "thread_sort_index", "pid": rank,
                    "tid": tid, "args": {"sort_index": tid},
                })
        return rank, tid

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            self._events.append(ev)

    # ------------------------------------------------------------ recording
    def begin(self, name: str, *, rank: int = 0,
              args: Optional[Dict[str, Any]] = None) -> None:
        pid, tid = self._pid_tid(rank)
        ev = {"ph": "B", "name": name, "pid": pid, "tid": tid,
              "ts": self._now_us()}
        if args:
            ev["args"] = dict(args)
        self._append(ev)

    def end(self, *, rank: int = 0) -> None:
        pid, tid = self._pid_tid(rank)
        self._append({"ph": "E", "pid": pid, "tid": tid, "ts": self._now_us()})

    @contextlib.contextmanager
    def span(self, name: str, *, rank: int = 0,
             args: Optional[Dict[str, Any]] = None):
        """Nested host span (``B``/``E`` pair). ``monitor.spans.span`` routes
        here automatically while a recorder is active."""
        self.begin(name, rank=rank, args=args)
        try:
            yield
        finally:
            self.end(rank=rank)

    def complete(self, name: str, start_ns: int, end_ns: int, *,
                 rank: int = 0, args: Optional[Dict[str, Any]] = None) -> None:
        """A span that is already over, by its ``time.perf_counter_ns``
        readings (the host ledger's compile phases and collector pauses are
        known only at their end). Takes no lock and allocates no container
        besides its own tuple: the collector's callback calls this, and a
        collection can start inside any allocation, the recorder's own under
        its lock among them. ``events`` merges these in at their times."""
        self._completed.append(
            (name, start_ns, end_ns, threading.get_ident(), rank, args))

    def instant(self, name: str, *, rank: int = 0,
                args: Optional[Dict[str, Any]] = None) -> None:
        """Zero-duration marker (the comms ledger mirrors collective records
        here as ``kind:site`` instants)."""
        pid, tid = self._pid_tid(rank)
        ev = {"ph": "i", "name": name, "pid": pid, "tid": tid,
              "ts": self._now_us(), "s": "t"}
        if args:
            ev["args"] = dict(args)
        self._append(ev)

    def counter(self, name: str, value: Any, *, rank: int = 0) -> None:
        """Counter-track sample (``C`` event): Perfetto renders one stacked
        area chart per (pid, name) from these — the serving telemetry books
        page occupancy, batch fill, and admission-queue depth this way.
        ``value`` may be a number or a dict of series-name → number (multi-
        series counters stack)."""
        pid, tid = self._pid_tid(rank)
        series = dict(value) if isinstance(value, dict) else {"value": value}
        self._append({
            "ph": "C", "name": name, "pid": pid, "tid": tid,
            "ts": self._now_us(),
            "args": {k: float(v) for k, v in series.items()},
        })

    # -------------------------------------------------------------- queries
    def events(self) -> List[Dict[str, Any]]:
        """Snapshot of the event list in recording order (host dicts; no
        device values). When spans were handed over whole (``complete``) their
        ``B``/``E`` pairs are merged in at their timestamps: metadata rows
        first, then every timed event in timestamp order."""
        whole = []
        while self._completed:
            name, start_ns, end_ns, ident, rank, args = self._completed.popleft()
            pid, tid = self._pid_tid(rank, ident)
            begin = {"ph": "B", "name": name, "pid": pid, "tid": tid,
                     "ts": max(start_ns - self._t0, 0) / 1e3}
            if args:
                begin["args"] = dict(args)
            whole += [begin, {"ph": "E", "pid": pid, "tid": tid,
                              "ts": max(end_ns - self._t0, 0) / 1e3}]
        with self._lock:
            if whole:
                timed = [e for e in self._events + whole if e["ph"] != "M"]
                timed.sort(key=lambda e: e["ts"])    # stable: live events keep their order
                self._events = [e for e in self._events if e["ph"] == "M"] + timed
            return [dict(e) for e in self._events]

    def _export_events(self) -> List[Dict[str, Any]]:
        """Deterministic export order: all ``M`` metadata rows first, sorted
        by (pid, tid, name) so Perfetto assigns process/thread rows the same
        order on every load, then the timed events sorted by timestamp
        (stable — simultaneous events keep recording order)."""
        events = self.events()
        meta = [e for e in events if e.get("ph") == "M"]
        timed = [e for e in events if e.get("ph") != "M"]
        meta.sort(key=lambda e: (e.get("pid", 0), e.get("tid", 0),
                                 e.get("name", "")))
        timed.sort(key=lambda e: e.get("ts", 0.0))
        return meta + timed

    # --------------------------------------------------------------- export
    def export(self, path: str) -> None:
        """Write ``{"traceEvents": [...]}`` — loads in Perfetto /
        ``chrome://tracing`` as-is (metadata rows first, timed events in
        timestamp order — see ``_export_events``). The module's one
        sanctioned write path (host-side data only; there is nothing to
        read back)."""
        payload = {
            "traceEvents": self._export_events(),
            "displayTimeUnit": "ms",
        }
        with open(path, "w") as f:
            json.dump(payload, f)


# ------------------------------------------------------- active recorder
# Process-global by design, like warn_once: spans and the comms ledger fire
# from deep inside library code that cannot thread a recorder handle.
_ACTIVE: Optional[TraceRecorder] = None
_ACTIVE_LOCK = threading.Lock()


def active_recorder() -> Optional[TraceRecorder]:
    """The recorder installed by ``timeline`` (None when not recording) —
    the hook ``spans.span`` and ``comms.record`` consult."""
    return _ACTIVE


@contextlib.contextmanager
def timeline(path: Optional[str] = None, *,
             recorder: Optional[TraceRecorder] = None):
    """Install a recorder as process-active for the block; export to ``path``
    on exit when given. Yields the recorder. Re-entrant (the previous
    recorder is restored), though nested timelines record independently."""
    global _ACTIVE
    rec = recorder if recorder is not None else TraceRecorder()
    with _ACTIVE_LOCK:
        prev = _ACTIVE
        _ACTIVE = rec
    try:
        yield rec
    finally:
        with _ACTIVE_LOCK:
            _ACTIVE = prev
        if path is not None:
            rec.export(path)


# ------------------------------------------------------ interval extraction
def span_intervals(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Match ``B``/``E`` pairs per (pid, tid) into closed intervals:
    ``{"name", "start", "end", "pid", "tid", "depth"}`` (timestamps in the
    recorder's microseconds; depth 0 = outermost). Unclosed spans are
    dropped — a crash mid-span must not fabricate a duration."""
    stacks: Dict[Tuple[Any, Any], List[Dict[str, Any]]] = {}
    out: List[Dict[str, Any]] = []
    for ev in events:
        ph = ev.get("ph")
        if ph not in ("B", "E"):
            continue
        key = (ev.get("pid", 0), ev.get("tid", 0))
        stack = stacks.setdefault(key, [])
        if ph == "B":
            stack.append({
                "name": ev.get("name", ""),
                "start": ev["ts"],
                "pid": key[0],
                "tid": key[1],
                "depth": len(stack),
            })
        elif stack:
            iv = stack.pop()
            iv["end"] = ev["ts"]
            out.append(iv)
    out.sort(key=lambda iv: (iv["pid"], iv["tid"], iv["start"]))
    return out


# ------------------------------------------------------------ host ledger
# Always on, process-global, host-only, like the comms ledger; every time is
# a ``time.perf_counter_ns`` reading, the recorder's clock. What feeds it:
#
#   compile.trace / compile.lower / compile.backend, cache.hit / cache.miss /
#   cache.load          monitor/compile.py's ``jax.monitoring`` listeners
#   span                monitor/spans.py ``span``: host time under the span
#                       (inside ``jit`` a span runs at TRACE time only)
#   gc                  monitor/spans.py's ``gc.callbacks`` entry: collections
#                       of at least ``GC_MIN_NS``; shorter ones are counted
#
# ``book`` takes no lock: the collector's callback books from inside whatever
# allocation started the collection, so a lock here could be one the same
# thread already holds. A ``deque`` append is one step under the interpreter
# lock; readers copy and retry.
SETUP_CAP = 1 << 14        # compile.* and cache.* events kept: the oldest
RING = 1 << 15             # span and gc events kept: the newest
GC_MIN_NS = 1_000_000
_PER_STEP = ("span", "gc")

_SETUP: collections.deque = collections.deque()
_STEPS: collections.deque = collections.deque(maxlen=RING)
# ("gc.short", "gc.gen0") / ("dropped", kind) -> [count, ns, first start, last end]
_TALLY: Dict[Tuple[str, str], List[int]] = {}
_HELD: Optional[int] = None    # the thread ``held`` keeps off the ledger


@contextlib.contextmanager
def held():
    """Book nothing this thread does in the block, a collection's pause
    excepted (it happened): for a ledger that asks JAX for a program the
    process already has (``monitor.program_ops``), so that set-up's account
    stays set-up's. One thread at a time."""
    global _HELD
    prev, _HELD = _HELD, threading.get_ident()
    try:
        yield
    finally:
        _HELD = prev


def book(kind: str, name: str, start_ns: int, end_ns: int, **extra: Any) -> None:
    """One event of the host ledger, and the same span on the active
    timeline recorder when there is one (``span`` events excepted: the span
    itself is already on it as a live ``B``/``E`` pair)."""
    extra = extra or None
    tid = threading.get_ident()
    if tid == _HELD and kind != "gc":
        return
    event = (kind, name, start_ns, end_ns, tid, extra)
    if kind in _PER_STEP:
        _STEPS.append(event)
    elif len(_SETUP) < SETUP_CAP:
        _SETUP.append(event)
    else:
        tally("dropped", kind, start_ns, end_ns)
    rec = _ACTIVE
    if rec is not None and kind != "span":
        rec.complete(name if kind == "gc" else f"{kind}:{name}", start_ns, end_ns,
                     args=extra)


def tally(kind: str, name: str, start_ns: int, end_ns: int) -> None:
    """Count an event the ledger does not keep (a short collection, an event
    past ``SETUP_CAP``): how many, their summed time, first start, last end."""
    row = _TALLY.get((kind, name))
    if row is None:
        row = _TALLY[kind, name] = [0, 0, start_ns, end_ns]
    row[0] += 1
    row[1] += end_ns - start_ns
    row[3] = end_ns


def _copy(events: collections.deque) -> list:
    while True:
        try:
            return list(events)
        except RuntimeError:       # appended to while it was copied
            continue


def host_records() -> List[Dict[str, Any]]:
    """Snapshot of the host ledger, by start time: ``{"kind", "name", "start",
    "end", "tid"}`` per event (nanoseconds of ``time.perf_counter_ns``; ``tid``
    the thread's ident), with what the feeder added (a collection's
    ``collected``). Events the ledger only counts come last, one row a kind and
    name with ``count`` and their summed ``ns``: ``gc.short`` (collections
    under ``GC_MIN_NS``) and ``dropped`` (set-up events past ``SETUP_CAP``)."""
    rows = [dict(extra or {}, kind=kind, name=name, start=start, end=end, tid=tid)
            for kind, name, start, end, tid, extra in _copy(_SETUP) + _copy(_STEPS)]
    rows.sort(key=lambda r: (r["start"], -r["end"]))
    for (kind, name), (count, ns, first, last) in sorted(dict(_TALLY).items()):
        rows.append({"kind": kind, "name": name, "start": first, "end": last,
                     "tid": None, "count": count, "ns": ns})
    return rows


def outermost(records: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Those of ``records`` that no other of them encloses on the same thread.
    A nested ``jit`` is traced inside its caller's trace and a kernel's span
    opens inside its layer's, so summing a kind's events counts that time
    twice; summing these does not."""
    out: List[Dict[str, Any]] = []
    reach: Dict[Any, int] = {}        # tid -> end of the enclosing event
    for r in sorted(records, key=lambda r: (r["start"], -r["end"])):
        if r["end"] > reach.get(r["tid"], -1):
            out.append(r)
            reach[r["tid"]] = r["end"]
    return out


def reset_host_ledger() -> None:
    """Forget every event and count. The listeners stay registered."""
    _SETUP.clear()
    _STEPS.clear()
    _TALLY.clear()
