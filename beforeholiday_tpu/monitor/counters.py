"""Queryable guard-dispatch counters — the monitor-side window onto
``guard/dispatch.py``'s probe cache.

Every ``checked_impl`` call is trace-time dispatch telemetry: did this
(op, backend, shapes/dtypes, statics) key take the pallas kernel or degrade
to the jnp oracle, and was a probe actually built? The raw counts live in
``guard.dispatch`` (under its verdict lock); this module shapes them for
operators — per-key rows plus an op-level rollup suitable for a JSON line
or a health dashboard.

``tile_records`` shows, per traced flash kernel, how much of the score square
its tile plan computes and how much of that goes through a mask.

Imports of ``guard.dispatch`` are deferred into the functions: the package
import chain (utils → monitor.spans → monitor/__init__ → here) must not
re-enter ``guard`` mid-import.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

__all__ = [
    "book_tiles",
    "dispatch_counters",
    "dispatch_records",
    "dispatch_summary",
    "reset_counters",
    "reset_dispatch_counters",
    "tile_records",
]


def _ratio(pallas: int, jnp: int) -> float:
    total = pallas + jnp
    return round(pallas / total, 4) if total else 0.0


def dispatch_counters() -> Dict[Tuple, Dict[str, int]]:
    """Per-key snapshot: ``{key: {"pallas": n, "jnp": n, "probes": n}}``.
    ``pallas``/``jnp`` count trace-time dispatches by chosen impl; ``probes``
    counts actual probe builds (so cache hits = pallas + jnp - probes)."""
    from beforeholiday_tpu.guard import dispatch as _dispatch

    return _dispatch.dispatch_counters()


def reset_dispatch_counters() -> None:
    from beforeholiday_tpu.guard import dispatch as _dispatch

    _dispatch.reset_dispatch_counters()


def reset_counters() -> None:
    """Full dispatch-telemetry reset: zero the per-key counters AND re-arm
    every probe-failure warning the dispatcher has emitted.
    ``reset_dispatch_counters`` alone leaves stale warn-once state behind
    (``clear_probe_cache`` only resets keys still holding a verdict), which
    leaks across long sessions — this is the one-call clean slate between
    benchmark configurations."""
    from beforeholiday_tpu.guard import dispatch as _dispatch

    _dispatch.reset_dispatch_counters()
    _dispatch.reset_probe_warnings()


def dispatch_records() -> List[Dict[str, object]]:
    """Per-key JSON-ready rows (one per (op, backend, shapes, statics) key):
    ``{"op", "key", "pallas", "jnp", "probes", "pallas_ratio", "degraded"}``
    — ``pallas_ratio`` is this key's pallas-hit fraction of its dispatches."""
    from beforeholiday_tpu.guard import dispatch as _dispatch

    failed = set(_dispatch.probe_failures())
    return sorted(
        (
            {
                "op": key[0],
                "key": repr(key[1:]),
                "pallas": c["pallas"],
                "jnp": c["jnp"],
                "probes": c["probes"],
                "pallas_ratio": _ratio(c["pallas"], c["jnp"]),
                "degraded": key in failed,
            }
            for key, c in _dispatch.dispatch_counters().items()
        ),
        key=lambda r: (r["op"], r["key"]),
    )


def tile_records() -> List[Dict[str, object]]:
    """Per traced kernel JSON-ready rows, beside :func:`dispatch_records`:
    ``{"op", "kernel", "key", "traces", "total", "live", "masked"}`` — the
    tile plan the kernel was built with (``guard.dispatch.count_tiles``).
    ``live < total``: tiles above the causal diagonal are not computed;
    ``masked < live``: tiles wholly below it take the mask-free path. A call
    whose ``live == total == masked`` did not engage either. A flash forward's
    row also holds its grid a head: the ``steps`` it takes and the K + V blocks
    it ``copies`` (``steps`` under the square's blocks: no step above the
    causal diagonal; ``copies < steps``: steps that name a block again)."""
    from beforeholiday_tpu.guard import dispatch as _dispatch

    return sorted(
        (
            {"op": op, "kernel": kernel, "key": repr(statics), **counts}
            for (op, kernel, statics), counts in _dispatch.tile_counters().items()
        ),
        key=lambda r: (r["op"], r["key"], r["kernel"]),
    )


def book_tiles(op: str, kernel: str, statics: Tuple, *, total: int, live: int,
               masked: int) -> None:
    """Book one trace of a tiled op for :func:`tile_records`, for callers
    that may not import ``guard`` (``moe``): ``guard.dispatch.count_tiles``."""
    from beforeholiday_tpu.guard import dispatch as _dispatch

    _dispatch.count_tiles(op, kernel, statics, total=total, live=live, masked=masked)


def dispatch_summary() -> List[Dict[str, object]]:
    """Op-level rollup, one JSON-ready row per op name:
    ``{"op", "keys", "pallas", "jnp", "probes", "pallas_ratio",
    "degraded_keys"}`` (``pallas_ratio`` = fraction of the op's dispatches that took the
    kernel; 1.0 is a fully-healthy op, 0.0 a fully-degraded one)."""
    from beforeholiday_tpu.guard import dispatch as _dispatch

    per_key = _dispatch.dispatch_counters()
    failed = set(_dispatch.probe_failures())
    by_op: Dict[str, Dict[str, object]] = {}
    for key, c in per_key.items():
        row = by_op.setdefault(
            key[0],
            {"op": key[0], "keys": 0, "pallas": 0, "jnp": 0, "probes": 0,
             "degraded_keys": 0},
        )
        row["keys"] += 1
        row["pallas"] += c["pallas"]
        row["jnp"] += c["jnp"]
        row["probes"] += c["probes"]
        if key in failed:
            row["degraded_keys"] += 1
    for row in by_op.values():
        row["pallas_ratio"] = _ratio(row["pallas"], row["jnp"])
    return sorted(by_op.values(), key=lambda r: r["op"])
