"""Trace spans and wall-clock timers — the observability layer's host/trace
annotation half (``beforeholiday_tpu.utils`` re-exports the public names).

Ref: apex/transformer/pipeline_parallel/_timers.py:83 ``_Timers`` (named
start/stop timers that ``torch.cuda.synchronize()``) and the NVTX ranges gated
by ``prof`` in DDP (apex/parallel/distributed.py:360-361). TPU equivalents:

* ``span`` / ``annotate`` — ``jax.named_scope`` labels. They surface in
  XProf / tensorboard traces the way NVTX ranges surface in nsight, cost
  nothing at runtime (they only label the HLO), and are safe inside jit —
  which is why the amp step, the model, the kernels' entries, the pipeline
  schedules, the DDP reducer, and the fused optimizers carry them
  unconditionally. The same call is a ``jax.profiler.TraceAnnotation``: while
  a profiler session is on, a span run on the host (``donate_step.call``)
  lands on the host plane's ``python`` line, on the device planes' clock.
  The scope names are an interface: the benchmark's per-layer metrics
  (``benchmark/layer_metrics/*.json``) match them in the device trace.
  Every span also books the host time under it into the host ledger
  (``monitor.host_records()``, kind ``span``): inside ``jit`` the body runs
  when the function is TRACED, so that is what the scope and everything under
  it cost set-up; on the host (``donate_step.call``) it is a step's dispatch.
* the collector's pauses — a ``gc.callbacks`` entry, registered once when
  this module is imported, books every collection of a millisecond or more
  into the same ledger (kind ``gc``, name ``gc.gen<n>``) and counts the
  shorter ones; while a profiler session is on each is a ``TraceAnnotation``
  too, so that a pause lies on the host line beside the step it delayed.
* ``Timers`` — host-side wall-clock timers whose device barrier is
  ``jax.block_until_ready`` on a token array (the ``cuda.synchronize``
  analogue). Between-steps tooling; never call inside a jitted step.
* ``trace`` / ``start_trace`` / ``stop_trace`` — thin wrappers over
  ``jax.profiler`` trace collection.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import time
from typing import Dict, Optional

import jax

# the full dotted path: the package attribute ``trace`` is rebound to THIS
# module's profiler function, so only this form reliably reaches the submodule
from beforeholiday_tpu.monitor.trace import GC_MIN_NS, active_recorder, book, tally

__all__ = [
    "Timers",
    "annotate",
    "nvtx_range",
    "span",
    "start_trace",
    "stop_trace",
    "trace",
]


@contextlib.contextmanager
def span(name: str, enabled: bool = True):
    """Named trace span (the NVTX-range idiom, gated like the reference's
    ``prof`` flag). On the device it only labels the traced HLO. On the host
    it is a ``jax.profiler.TraceAnnotation`` (one flag test while no profiler
    session is on), and when a ``monitor.timeline`` recorder is active the
    span ALSO lands on that timeline (a ``B``/``E`` pair in the exported
    ``trace.json``) — same label, all views."""
    if not enabled:
        yield
        return
    rec = active_recorder()
    start = time.perf_counter_ns()
    try:
        if rec is None:
            with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
                yield
        else:
            with rec.span(name), jax.profiler.TraceAnnotation(name), jax.named_scope(name):
                yield
    finally:
        book("span", name, start, time.perf_counter_ns())


# the pre-monitor name; same contract, kept importable forever
nvtx_range = span


def annotate(name: str):
    """Decorator: wrap a function's trace in a named scope (the NVTX-range
    idiom, ref: distributed.py ``torch.cuda.nvtx.range_push``)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return wrapped

    return deco


_GC_NAMES = ("gc.gen0", "gc.gen1", "gc.gen2")
_gc_open = [0, None]       # the collection under way: its start, its annotation


def _on_collection(phase, info):
    """The ``gc.callbacks`` entry: ``start`` / ``stop`` of every collection,
    on the host ledger's clock. It runs inside whatever allocation started
    the collection, so it takes no lock and must not raise."""
    if phase == "start":
        if jax.profiler.TraceAnnotation.is_enabled():      # a profiler session is on
            _gc_open[1] = jax.profiler.TraceAnnotation(_GC_NAMES[info["generation"]])
            _gc_open[1].__enter__()
        _gc_open[0] = time.perf_counter_ns()
        return
    end = time.perf_counter_ns()
    start, annotation = _gc_open
    _gc_open[:] = 0, None
    if annotation is not None:
        annotation.__exit__(None, None, None)
    if not start:              # registered while a collection was under way
        return
    if end - start >= GC_MIN_NS:
        book("gc", _GC_NAMES[info["generation"]], start, end, collected=info["collected"])
    else:
        tally("gc.short", _GC_NAMES[info["generation"]], start, end)


_on_collection._host_ledger = True
if not any(getattr(cb, "_host_ledger", False) for cb in gc.callbacks):
    gc.callbacks.append(_on_collection)     # once a process, however imported


def start_trace(log_dir: str, **kw) -> None:
    """Begin an XProf trace (view in tensorboard's profile tab)."""
    jax.profiler.start_trace(log_dir, **kw)


def stop_trace() -> None:
    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(log_dir: Optional[str]):
    """Trace the enclosed block when ``log_dir`` is set; no-op otherwise —
    so trainers can take a ``--profile-dir`` flag and leave the call in."""
    if log_dir:
        jax.profiler.start_trace(log_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    else:
        yield


class _Timer:
    def __init__(self, name: str):
        self.name = name
        self._elapsed = 0.0
        self._started = False
        self._start_time = 0.0

    def start(self, barrier_on=None):
        assert not self._started, f"timer {self.name} already started"
        if barrier_on is not None:
            jax.block_until_ready(barrier_on)
        self._start_time = time.perf_counter()
        self._started = True

    def stop(self, barrier_on=None):
        assert self._started, f"timer {self.name} not started"
        if barrier_on is not None:
            jax.block_until_ready(barrier_on)
        self._elapsed += time.perf_counter() - self._start_time
        self._started = False

    def reset(self):
        self._elapsed = 0.0
        self._started = False

    def elapsed(self, reset: bool = True) -> float:
        running = self._started
        if running:
            self.stop()
        value = self._elapsed
        if reset:
            self.reset()
        if running:
            self.start()
        return value


class Timers:
    """Group of named timers (ref: _timers.py:120 ``Timers``)."""

    def __init__(self):
        self._timers: Dict[str, _Timer] = {}

    def __call__(self, name: str) -> _Timer:
        if name not in self._timers:
            self._timers[name] = _Timer(name)
        return self._timers[name]

    def log(self, names, normalizer: float = 1.0, reset: bool = True) -> str:
        for name in names:
            # a typo'd timer name must be loud, not silently dropped
            assert name in self._timers, f"timer {name!r} was never started"
        parts = [
            f"{name}: {self._timers[name].elapsed(reset=reset) * 1000.0 / normalizer:.2f}ms"
            for name in names
        ]
        return "time (ms) | " + " | ".join(parts)
