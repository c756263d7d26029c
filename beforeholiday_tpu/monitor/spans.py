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
* ``Timers`` — host-side wall-clock timers whose device barrier is
  ``jax.block_until_ready`` on a token array (the ``cuda.synchronize``
  analogue). Between-steps tooling; never call inside a jitted step.
* ``trace`` / ``start_trace`` / ``stop_trace`` — thin wrappers over
  ``jax.profiler`` trace collection.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Dict, Optional

import jax

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
    # deferred, full-dotted-path import: the package attribute ``trace`` is
    # rebound to THIS module's profiler function, so only the dotted form
    # reliably reaches the submodule
    from beforeholiday_tpu.monitor.trace import active_recorder

    rec = active_recorder()
    with contextlib.ExitStack() as stack:
        if rec is not None:
            stack.enter_context(rec.span(name))
        stack.enter_context(jax.profiler.TraceAnnotation(name))
        stack.enter_context(jax.named_scope(name))
        yield


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
