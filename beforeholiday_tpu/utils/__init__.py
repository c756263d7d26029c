"""Shared utilities: logging, pytree helpers; timers/profiling live in
``beforeholiday_tpu.monitor.spans`` (re-exported here)."""

from beforeholiday_tpu.utils.logging import get_logger, reset_warn_once, warn_once
from beforeholiday_tpu.monitor.spans import Timers, annotate, nvtx_range, trace

__all__ = [
    "get_logger",
    "Timers",
    "annotate",
    "nvtx_range",
    "reset_warn_once",
    "trace",
    "warn_once",
]
