"""jit-safe training observability.

Split by which side of the device boundary each piece lives on:

* :mod:`beforeholiday_tpu.monitor.metrics`  — ``TrainMonitor`` + the
  ``Metrics`` pytree: device-side counters/gauges/EMAs updated with pure jnp
  inside the jitted step, with a ``lax.psum``-based cross-rank ``aggregate``.
* :mod:`beforeholiday_tpu.monitor.export`   — ``MetricsLogger``: host-side
  drain at a configurable cadence, one readback per logged step (JSONL / CSV
  / callback).
* :mod:`beforeholiday_tpu.monitor.spans`    — trace spans and wall-clock
  timers; a span names the device ops it encloses, marks the profiler's
  host line and books the host time under it; the collector's pauses.
* :mod:`beforeholiday_tpu.monitor.counters` — queryable guard-dispatch
  hit/degrade counters, and the tile plan each traced flash kernel was
  built with (``tile_records``).
* :mod:`beforeholiday_tpu.monitor.comms`    — trace-time collective-traffic
  ledger (op kind / axis / dtype / bytes / call-site, subsystem rollup).
* :mod:`beforeholiday_tpu.monitor.trace`    — host timeline recorder +
  Chrome-trace/Perfetto ``trace.json`` exporter (``timeline``), and the
  always-on **host ledger** on the same clock (``host_records``): compile
  phases and compile-cache traffic by jitted function, host time under
  every span, collector pauses. For the operator: ``compile_summary()``
  after set-up says which entry's trace, lowering or compile took the
  seconds and whether the cache was warm; after a stall,
  ``host_records()`` (or ``trace.json``, which carries the same events
  while a timeline is on) says whether a collection or a compile lay in it.
* :mod:`beforeholiday_tpu.monitor.compile`  — recompile sentinel
  (``track_compiles``: count signatures per jitted entry, warn on storms)
  and the ``jax.monitoring`` listeners that feed the ledger its seconds.
* :mod:`beforeholiday_tpu.monitor.memory`   — per-jit memory ledger
  (``track_memory``: AOT ``memory_analysis()`` bytes per entry/signature).
* :mod:`beforeholiday_tpu.monitor.program`  — the program ledger
  (``program_ops``: every instruction of a ``donate_step`` entry's compiled
  module with its scope, or for one the compiler made, the scopes of what
  feeds it and what it feeds; nothing is lowered or parsed until asked).
* :mod:`beforeholiday_tpu.monitor.roofline` — published chip peaks
  (``ChipSpec`` and its registry, keyed by ``device_kind``).
* :mod:`beforeholiday_tpu.monitor.flight`   — crash flight recorder
  (ring buffer of drained steps, dumped on StepGuard rollback / crash).
"""

# NOTE on the name ``trace``: importing the ``monitor.trace`` SUBMODULE below
# sets the package attribute ``trace`` to the module; the spans import after
# it deliberately rebinds ``trace`` to the profiler context manager (the
# pre-existing public name). Internal code reaches the submodule via the full
# dotted path (``from beforeholiday_tpu.monitor.trace import ...``), which is
# unaffected by the rebinding.
from beforeholiday_tpu.monitor.trace import (  # noqa: F401
    TraceRecorder,
    active_recorder,
    host_records,
    reset_host_ledger,
    span_intervals,
    timeline,
)
from beforeholiday_tpu.monitor.spans import (  # noqa: F401
    Timers,
    annotate,
    nvtx_range,
    span,
    start_trace,
    stop_trace,
    trace,
)
from beforeholiday_tpu.monitor.metrics import (  # noqa: F401
    Metrics,
    TrainMonitor,
    global_norm,
)
from beforeholiday_tpu.monitor.export import MetricsLogger  # noqa: F401
from beforeholiday_tpu.monitor.counters import (  # noqa: F401
    book_tiles,
    dispatch_counters,
    dispatch_records,
    dispatch_summary,
    reset_counters,
    reset_dispatch_counters,
    tile_records,
)
from beforeholiday_tpu.monitor.comms import (  # noqa: F401
    comms_records,
    comms_summary,
    ledger_scope,
    reset_comms_ledger,
)
from beforeholiday_tpu.monitor.compile import (  # noqa: F401
    BucketGateError,
    compile_counts,
    compile_summary,
    reset_compile_counts,
    track_compiles,
)
from beforeholiday_tpu.monitor.memory import (  # noqa: F401
    measure_memory,
    memory_records,
    memory_summary,
    reset_memory_ledger,
    track_memory,
)
from beforeholiday_tpu.monitor.program import (  # noqa: F401
    program_ops,
    reset_program_ledger,
)
from beforeholiday_tpu.monitor.roofline import (  # noqa: F401
    ChipSpec,
    chip_specs,
    get_chip_spec,
    register_chip_spec,
)
from beforeholiday_tpu.monitor.flight import (  # noqa: F401
    FlightRecorder,
    active_flight_recorder,
)
from beforeholiday_tpu.monitor.histo import Histogram  # noqa: F401
from beforeholiday_tpu.monitor.goodput import (  # noqa: F401
    classify_span,
    goodput_report,
)

__all__ = [
    "BucketGateError",
    "ChipSpec",
    "FlightRecorder",
    "Histogram",
    "Metrics",
    "MetricsLogger",
    "Timers",
    "TraceRecorder",
    "TrainMonitor",
    "active_flight_recorder",
    "active_recorder",
    "annotate",
    "book_tiles",
    "chip_specs",
    "classify_span",
    "comms_records",
    "comms_summary",
    "compile_counts",
    "compile_summary",
    "dispatch_counters",
    "dispatch_records",
    "dispatch_summary",
    "get_chip_spec",
    "global_norm",
    "goodput_report",
    "host_records",
    "ledger_scope",
    "measure_memory",
    "memory_records",
    "memory_summary",
    "nvtx_range",
    "program_ops",
    "register_chip_spec",
    "reset_comms_ledger",
    "reset_compile_counts",
    "reset_counters",
    "reset_dispatch_counters",
    "reset_host_ledger",
    "reset_memory_ledger",
    "reset_program_ledger",
    "span",
    "span_intervals",
    "start_trace",
    "stop_trace",
    "tile_records",
    "timeline",
    "trace",
    "track_compiles",
    "track_memory",
]
