"""Guarded Pallas dispatch — probe once per static key, degrade to jnp.

``_pallas_util.resolve_impl`` picks ``pallas`` wherever the traced program owns
one device per shard, but has no recourse if the kernel then fails to build for
an odd shape/dtype (the reference's per-extension ``is_kernel_available`` gates,
fused_softmax.py:164, only check shapes they anticipated). :func:`checked_impl`
closes that hole: before the first pallas call for a given
(op, backend, shapes/dtypes, statics) key, the kernel is probe-built in a
throwaway trace; on failure the op degrades to its jnp oracle with ONE
structured warning via :mod:`beforeholiday_tpu.utils.logging` instead of
raising. The verdict is cached, so the happy path after the first call is a
dict lookup at trace time — nothing enters the compiled step, and no host sync.

Probe depth:

* ``"trace"``   — ``jax.eval_shape`` over ShapeDtypeStructs: catches BlockSpec /
  tiling / shape-contract errors (the failure class reachable on CPU, where the
  Pallas interpreter has no Mosaic stage). Cheap; safe inside an outer trace.
* ``"compile"`` — full ``jit(...).lower(...).compile()``: additionally catches
  Mosaic lowering errors on a real TPU backend. Only attempted outside any
  ambient trace (a probe compile inside ``shard_map`` tracing would not see the
  per-shard lowering context and could mis-verdict).
* ``"off"``     — trust the kernel (no probe).

The default ``"auto"`` resolves to ``compile`` on a clean-trace TPU backend and
``trace`` everywhere else. Inside a ``jit`` trace on TPU that means the probe
cannot see a Mosaic failure — and does not need to: the enclosing program's
own compile then raises it, loudly, instead of degrading.

Fault injection (:func:`beforeholiday_tpu.testing.faults.force_probe_failure`)
registers op names in :data:`_FORCED_FAILURES`; the probe consults it first, so
the degradation path is exercisable on any backend.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional, Set, Tuple

import jax
from jax._src import core as _jax_core

from beforeholiday_tpu.utils.logging import get_logger, reset_warn_once, warn_once

logger = get_logger(__name__)

# key -> None (probe passed) | str (failure summary; already warned)
_VERDICTS: Dict[Tuple, Optional[str]] = {}
_VERDICTS_LOCK = threading.Lock()
_FORCED_FAILURES: Set[str] = set()
_PROBE_MODE = "auto"  # "auto" | "compile" | "trace" | "off"

# key -> {"pallas": n, "jnp": n, "probes": n} — trace-time dispatch
# telemetry (every checked_impl call counts under the impl it returned;
# "probes" counts actual probe builds, so hits = total - probes). Guarded by
# _VERDICTS_LOCK; queried via monitor.counters / dispatch_counters().
_COUNTERS: Dict[Tuple, Dict[str, int]] = {}

# every warn_once key this module has fired — clear_probe_cache only resets
# keys still holding a verdict, so without this record a key warned and then
# dropped (or a full-registry reset) leaks stale warn-once state in long
# sessions. Guarded by _VERDICTS_LOCK; drained by reset_probe_warnings().
_WARNED_KEYS: Set[Tuple] = set()


# (op, kernel, statics) -> {"traces", "total", "live", "masked", ...}: the tile plan
# a kernel was built with, booked when the kernel is traced (the plan is
# static per call). Beside the dispatch counters: those say WHICH
# implementation a key took, these how much of the score square that
# implementation computes for it. Guarded by _VERDICTS_LOCK.
_TILES: Dict[Tuple, Dict[str, int]] = {}


def _count(key: Tuple, outcome: str, probed: bool = False) -> None:
    # caller holds _VERDICTS_LOCK
    c = _COUNTERS.setdefault(key, {"pallas": 0, "jnp": 0, "probes": 0})
    c[outcome] += 1
    if probed:
        c["probes"] += 1


def dispatch_counters() -> Dict[Tuple, Dict[str, int]]:
    """Snapshot of per-key dispatch counts: how many trace-time dispatches
    took the pallas path vs degraded to jnp, and how many ran a probe."""
    with _VERDICTS_LOCK:
        return {k: dict(v) for k, v in _COUNTERS.items()}


def reset_dispatch_counters() -> None:
    with _VERDICTS_LOCK:
        _COUNTERS.clear()
        _TILES.clear()


def count_tiles(op_name: str, kernel: str, statics: Tuple, *, total: int,
                live: int, masked: int, **grid: int) -> None:
    """Book one trace of ``op_name``'s ``kernel`` for the static key
    ``statics``: tiles in the score square, tiles the kernel computes, tiles
    it computes through a mask. ``live < total`` is a causal plan skipping the
    tiles above the diagonal; ``masked < live`` is tiles taking the mask-free
    path. ``grid``: further counts of the kernel's grid (the flash forward's
    ``copies`` and ``steps`` a head). Telemetry only, like :func:`count_forced`."""
    with _VERDICTS_LOCK:
        row = _TILES.setdefault((op_name, kernel, tuple(statics)), {"traces": 0})
        row["traces"] += 1
        row.update(total=total, live=live, masked=masked, **grid)


def tile_counters() -> Dict[Tuple, Dict[str, int]]:
    """Snapshot per ``(op, kernel, statics)``: ``{"traces", "total", "live",
    "masked"}`` — tile counts of one head of one call."""
    with _VERDICTS_LOCK:
        return {k: dict(v) for k, v in _TILES.items()}


class InjectedProbeFailure(RuntimeError):
    """Raised by the probe when a fault injector forced this op to fail."""


def set_probe_mode(mode: str) -> str:
    """Set the probe depth globally; returns the previous mode."""
    global _PROBE_MODE
    if mode not in ("auto", "compile", "trace", "off"):
        raise ValueError(f"probe mode must be auto/compile/trace/off, got {mode!r}")
    prev, _PROBE_MODE = _PROBE_MODE, mode
    return prev


def clear_probe_cache(op_name: Optional[str] = None) -> None:
    """Drop cached verdicts (all, or one op's) — next call re-probes (and may
    warn again: the matching warn_once keys are reset too). Dispatch counters
    are cumulative telemetry and are NOT cleared; use
    :func:`reset_dispatch_counters`."""
    with _VERDICTS_LOCK:
        if op_name is None:
            dropped = list(_VERDICTS)
            _VERDICTS.clear()
        else:
            dropped = [k for k in _VERDICTS if k[0] == op_name]
            for key in dropped:
                del _VERDICTS[key]
        for key in dropped:
            _WARNED_KEYS.discard(("guard.dispatch",) + key)
    for key in dropped:
        reset_warn_once(("guard.dispatch",) + key)


def reset_probe_warnings() -> None:
    """Re-arm EVERY probe-failure warning this module has ever emitted —
    including keys whose verdicts were already dropped, which
    :func:`clear_probe_cache` cannot reach. ``monitor.reset_counters`` calls
    this so a counter reset leaves no stale warn-once state behind."""
    with _VERDICTS_LOCK:
        warned = list(_WARNED_KEYS)
        _WARNED_KEYS.clear()
    for full_key in warned:
        reset_warn_once(full_key)


def probe_failures() -> Dict[Tuple, str]:
    """Snapshot of keys that failed their probe (key -> failure summary)."""
    with _VERDICTS_LOCK:
        return {k: v for k, v in _VERDICTS.items() if v is not None}


def _is_arrayish(x: Any) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype")


def _trace_clean() -> bool:
    """True outside any jit/grad/shard_map trace. ``trace_state_clean`` left
    the public ``jax.core`` namespace; the private module still carries it.
    No fallback: if it moves again the import above fails loudly instead of
    pinning the ``"auto"`` probe to ``"trace"`` forever."""
    return _jax_core.trace_state_clean()


def _probe(op_name: str, fn: Callable, args: tuple, kw: dict) -> None:
    """Build ``fn(*args, **kw)`` in a throwaway trace; raise on failure.

    Array args and kwargs (including tracers from an enclosing trace) are
    replaced by ShapeDtypeStructs so the probe never touches live values;
    everything else passes through as statics.
    """
    if op_name in _FORCED_FAILURES:
        raise InjectedProbeFailure(f"probe failure injected for {op_name!r}")
    mode = _PROBE_MODE
    if mode == "off":
        return
    if mode == "auto":
        mode = (
            "compile"
            if jax.default_backend() == "tpu" and _trace_clean()
            else "trace"
        )
    structs, spots = [], []
    for i, a in enumerate(args):
        if _is_arrayish(a):
            structs.append(jax.ShapeDtypeStruct(a.shape, a.dtype))
            spots.append(i)
    kw_spots = sorted(k for k, v in kw.items() if _is_arrayish(v))
    structs.extend(jax.ShapeDtypeStruct(kw[k].shape, kw[k].dtype) for k in kw_spots)

    def probe_fn(*arrays):
        full = list(args)
        for i, x in zip(spots, arrays):
            full[i] = x
        full_kw = dict(kw)
        for k, x in zip(kw_spots, arrays[len(spots):]):
            full_kw[k] = x
        return fn(*full, **full_kw)

    if mode == "compile" and _trace_clean():
        jax.jit(probe_fn).lower(*structs).compile()
    else:
        jax.eval_shape(probe_fn, *structs)


def _key_of(op_name: str, args: tuple, kw: dict, statics: Tuple) -> Tuple:
    sig = lambda a: (a.shape, str(a.dtype)) if _is_arrayish(a) else repr(a)
    return (
        op_name,
        jax.default_backend(),
        tuple(sig(a) for a in args),
        tuple(sorted((k, sig(v)) for k, v in kw.items())),
        tuple(repr(s) for s in statics),
    )


def count_forced(
    op_name: str,
    impl: str,
    *args: Any,
    statics: Tuple = (),
    **kw: Any,
) -> None:
    """Book a dispatch that BYPASSED the probe under the same counter-key
    shape as :func:`checked_impl` — for ops with no viable oracle at this
    shape (e.g. flash attention backward at S=8192, where materializing the
    jnp scores is uncompilable), where degradation would be worse than
    failing loudly. Telemetry only: no probe, no verdict, no downgrade."""
    key = _key_of(op_name, args, kw, statics)
    with _VERDICTS_LOCK:
        _count(key, impl)


def checked_impl(
    op_name: str,
    impl: str,
    fn: Callable,
    *args: Any,
    statics: Tuple = (),
    **kw: Any,
) -> str:
    """Downgrade ``impl`` 'pallas' -> 'jnp' when the kernel probe fails.

    ``fn(*args, **kw)`` must be the exact pallas path the caller is about to
    take; array args contribute (shape, dtype) to the cache key, everything
    else (plus ``statics``) is keyed by repr. Returns the impl to use. Never
    raises from the probe: any probe exception caches a failed verdict, emits
    exactly one structured warning, and selects the oracle.
    """
    if impl != "pallas":
        return impl
    key = _key_of(op_name, args, kw, statics)
    with _VERDICTS_LOCK:
        if key in _VERDICTS:
            chosen = "jnp" if _VERDICTS[key] is not None else "pallas"
            _count(key, chosen)
            return chosen
    try:
        _probe(op_name, fn, args, kw)
    except Exception as e:  # noqa: BLE001 — degradation IS the contract
        summary = f"{type(e).__name__}: {e}"
        with _VERDICTS_LOCK:
            _VERDICTS.setdefault(key, summary)
            _count(key, "jnp", probed=True)
            _WARNED_KEYS.add(("guard.dispatch",) + key)
        # warn_once dedups per key (clear_probe_cache resets it with the
        # verdict, so a re-probe of the same key may warn again)
        warn_once(
            ("guard.dispatch",) + key,
            "guarded dispatch: op=%s key=%s probe failed (%s); "
            "degrading to the jnp oracle for this key",
            op_name, key[2], summary,
            logger=logger,
        )
        return "jnp"
    with _VERDICTS_LOCK:
        _VERDICTS.setdefault(key, None)
        _count(key, "pallas", probed=True)
    return "pallas"
