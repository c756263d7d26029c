"""Recompile sentinel — count compilations per jitted entry point and warn on
the silent TPU performance killer: the recompilation storm.

jax caches compiled executables by ABSTRACT signature (pytree structure +
leaf shapes/dtypes + static argument values), so a jitted entry point
recompiles exactly when it is called with a signature it has not seen.
``track_compiles`` exploits that: it computes the same signature key on the
HOST at every call (cheap — shapes and treedefs only, no device work) and
counts distinct keys per entry point. distinct-signatures == compilations,
with no dependence on jax internals.

A fluctuating-shape data pipeline or a Python scalar smuggled into a traced
argument shows up here as an entry with ``signatures > 1`` — and a single
``warn_once`` per entry names the entry and both signatures the moment the
SECOND one appears, when the cause is still on screen.

Usage::

    @monitor.track_compiles("train_step")
    @jax.jit
    def train_step(params, batch): ...

    monitor.compile_summary()   # [{"entry": "train_step", "signatures": 1,
                                #   "calls": 400}]

Wrap ABOVE ``jax.jit`` (the sentinel must see the concrete arguments, not
tracers). Like the comms ledger, state is process-global and host-only;
``reset_compile_counts`` clears it (and re-arms the warning) between
benchmark configurations.

**The seconds.** The sentinel counts signatures; what a compilation COSTS the
host comes from JAX itself. Importing this module registers, once a process,
two ``jax.monitoring`` listeners that book every trace, lowering and backend
compile (an executable loaded from the persistent cache, or compiled) and
every compile-cache hit, miss and load into the host ledger
(``monitor.host_records()``), under the jitted function's name and on the
timeline's clock; ``compile_summary`` rolls them up by entry. The events fire
only when JAX traces, lowers or compiles: a step served from the jit cache
costs nothing here.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from beforeholiday_tpu.monitor.trace import book, host_records, outermost
from beforeholiday_tpu.utils.logging import reset_warn_once, warn_once

__all__ = [
    "BucketGateError",
    "compile_counts",
    "compile_summary",
    "reset_compile_counts",
    "track_compiles",
]


class BucketGateError(RuntimeError):
    """A strict-mode entry point was called with an abstract signature beyond
    its declared bucket budget — the recompile storm the sentinel warns about,
    promoted to a hard failure for serving-class entry points."""

_LOCK = threading.Lock()
# entry name -> {"signatures": {sig: first-call index}, "calls": n}
_ENTRIES: Dict[str, Dict[str, Any]] = {}

_WARN_PREFIX = "monitor.compile"


def _leaf_sig(leaf: Any):
    """Hashable abstract signature of one argument leaf: (shape, dtype) for
    anything array-like, the VALUE for hashable Python statics (a changed
    static is a recompile too), else the type name."""
    if isinstance(leaf, (jax.Array, np.ndarray)) or hasattr(leaf, "shape"):
        return ("array", jnp.shape(leaf), np.dtype(jnp.result_type(leaf)).name)
    try:
        hash(leaf)
    except TypeError:
        return ("unhashable", type(leaf).__name__)
    return ("static", leaf)


def _sig_of(args: Tuple, kwargs: Dict[str, Any]):
    treedef = jax.tree_util.tree_structure((args, kwargs))
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    return (str(treedef), tuple(_leaf_sig(x) for x in leaves))


def _describe(sig) -> str:
    """Short human rendering of a signature for the warning message."""
    return ", ".join(
        f"{s[1]}{{{s[2]}}}" if s[0] == "array" else repr(s[1]) for s in sig[1]
    )


def track_compiles(entry: str, *, strict: bool = False,
                   max_signatures: int | None = None):
    """Decorator: count abstract-signature changes of a jitted entry point.

    Apply OUTSIDE ``jax.jit`` so the wrapper sees concrete arguments. The
    first signature is the expected compile; each NEW signature thereafter
    increments the entry's compile count and (once per entry, via
    ``warn_once``) logs a recompile warning naming the old and new shapes.

    ``strict=True`` with ``max_signatures=N`` promotes the sentinel to a
    HARD GATE: the N declared bucket signatures compile normally, but a call
    whose signature would be the (N+1)-th raises :class:`BucketGateError`
    BEFORE dispatch (and before registering the signature, so retries keep
    failing rather than laundering the overflow into the known set). This is
    the serving-path contract — a finite bucket set is declared up front and
    an out-of-bucket shape is a bug, not a warning."""
    if strict and max_signatures is None:
        raise ValueError("strict=True requires max_signatures")

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            sig = _sig_of(args, kwargs)
            with _LOCK:
                row = _ENTRIES.setdefault(
                    entry, {"signatures": {}, "calls": 0}
                )
                row["calls"] += 1
                known = row["signatures"]
                is_new = sig not in known
                if (
                    is_new
                    and strict
                    and len(known) >= max_signatures
                ):
                    raise BucketGateError(
                        f"entry {entry!r}: signature outside the declared "
                        f"bucket set (budget {max_signatures}, already "
                        f"compiled {len(known)}): {_describe(sig)} — pad to "
                        f"a declared bucket or widen the bucket set"
                    )
                if is_new:
                    known[sig] = row["calls"]
                n_sigs = len(known)
            if is_new and n_sigs > 1 and not strict:
                warn_once(
                    (_WARN_PREFIX, entry),
                    "recompile sentinel: entry %r compiled %d distinct "
                    "signatures (latest: %s) — fluctuating input shapes or "
                    "statics defeat the jit cache; pad batches or hoist the "
                    "changing value out of the traced arguments",
                    entry,
                    n_sigs,
                    _describe(sig),
                )
            return fn(*args, **kwargs)

        return wrapper

    return deco


def compile_counts() -> Dict[str, Dict[str, int]]:
    """Raw per-entry counters: ``{entry: {"signatures": n, "calls": m}}``.
    ``signatures`` is the compile count (distinct abstract signatures)."""
    with _LOCK:
        return {
            name: {"signatures": len(row["signatures"]),
                   "calls": row["calls"]}
            for name, row in _ENTRIES.items()
        }


def compile_summary() -> List[Dict[str, object]]:
    """`dispatch_summary`-style rollup: one sorted row per entry, ``{"entry",
    "signatures", "calls", "recompiled"}`` from the sentinel and, from the host
    ledger, the seconds JAX spent on the jitted function of that name:
    ``trace_s`` (of which ``trace_outer_s`` was not inside another function's
    trace: sum THAT over entries), ``lower_s``, ``backend_s``, ``compiles``
    (backend compiles or cache loads) and ``cache_hits`` / ``cache_misses``
    (JAX raises a miss when it WRITES an entry: a program under the cache's
    size or compile-time threshold is neither, so ``compiles - cache_hits`` is
    what was compiled here). An entry only one side knows has zeros for the
    other."""
    rows: Dict[str, Dict[str, object]] = {}

    def row(name):
        return rows.setdefault(name, {
            "entry": name, "signatures": 0, "calls": 0, "recompiled": False,
            "trace_s": 0.0, "trace_outer_s": 0.0, "lower_s": 0.0,
            "backend_s": 0.0, "compiles": 0, "cache_hits": 0, "cache_misses": 0})

    for name, c in compile_counts().items():
        row(name).update(signatures=c["signatures"], calls=c["calls"],
                         recompiled=c["signatures"] > 1)
    traces = []
    for r in host_records():
        if r["kind"] in _SECONDS:
            row(r["name"])[_SECONDS[r["kind"]]] += (r["end"] - r["start"]) / 1e9
        if r["kind"] in _COUNTS:
            row(r["name"])[_COUNTS[r["kind"]]] += 1
        if r["kind"] == "compile.trace":
            traces.append(r)
    for r in outermost(traces):
        row(r["name"])["trace_outer_s"] += (r["end"] - r["start"]) / 1e9
    return [rows[name] for name in sorted(rows)]


def reset_compile_counts(entry: Optional[str] = None) -> None:
    """Forget tracked entries and re-arm their recompile warnings. Counting
    restarts at the next call — an already-cached executable re-counts as
    one signature but does NOT recompile on the device.

    With ``entry``, the reset is SCOPED: only that entry's signature set,
    call counter, and armed warning are cleared, every other entry keeps
    counting. A caller that re-traces one entry on purpose resets its own
    scope this way — a global reset would silently zero the training
    step's recompile evidence and disarm warnings the user still wants."""
    with _LOCK:
        if entry is not None:
            _ENTRIES.pop(entry, None)
            names = [entry]
        else:
            names = list(_ENTRIES)
            _ENTRIES.clear()
    for name in names:
        reset_warn_once((_WARN_PREFIX, name))


# ------------------------------------------------- the host ledger's feeders
_PHASES = {   # jax/_src/dispatch.py: each fires with ``fun_name=``
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {   # jax/_src/compiler.py, compilation_cache.py: no name
    "/jax/compilation_cache/cache_hits": "cache.hit",
    "/jax/compilation_cache/cache_misses": "cache.miss",
}
_SECONDS = {"compile.trace": "trace_s", "compile.lower": "lower_s",
            "compile.backend": "backend_s"}
_COUNTS = {"compile.backend": "compiles", "cache.hit": "cache_hits",
           "cache.miss": "cache_misses"}


class _Pending(threading.local):
    """This thread's cache events that wait for their entry's name."""

    def __init__(self):
        self.events: list = []


_PENDING = _Pending()


def _on_event(event: str, **kw: Any) -> None:
    kind = _CACHE_EVENTS.get(event)
    if kind is not None:
        now = time.perf_counter_ns()
        _PENDING.events.append((kind, now, now))


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    """End = receipt, start = end - duration. JAX names the cache's events
    after nothing, but raises them inside the backend compile they belong to,
    on its thread: they are booked under that entry's name when it ends."""
    kind = _PHASES.get(event)
    if kind is None and event != _CACHE_LOAD:
        return
    end = time.perf_counter_ns()
    start = end - int(duration * 1e9)
    if kind is None:
        _PENDING.events.append(("cache.load", start, end))
        return
    name = str(kw.get("fun_name", ""))
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]          # lowering and compiling say ``jit(step)``
    if kind == "compile.backend":
        waiting = _PENDING.events
        for cache_kind, s, e in waiting:
            book(cache_kind, name if s >= start else "", s, e)
        del waiting[:]
    book(kind, name, start, end)


_on_event._host_ledger = _on_duration._host_ledger = True


def _listen_once() -> None:
    """Register the two listeners unless this process already has them (the
    module imported under a second name, or reloaded)."""
    from jax._src import monitoring

    def missing(getter):
        have = getattr(monitoring, getter, lambda: ())()
        return not any(getattr(cb, "_host_ledger", False) for cb in have)

    if missing("get_event_listeners"):
        jax.monitoring.register_event_listener(_on_event)
    if missing("get_event_duration_listeners"):
        jax.monitoring.register_event_duration_secs_listener(_on_duration)


_listen_once()
