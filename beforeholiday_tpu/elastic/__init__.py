"""Elastic training: async shard checkpoints, live resharding, preemption
survival.

Production TPU jobs get preempted, lose hosts, and resume at different
world sizes. This package composes the repo's shipped mechanisms — ZeRO-3
per-rank shard checkpoints with bitwise resharding, StepGuard health state,
the replication tripwire, and the flight recorder — into a survivable loop:

* :class:`~beforeholiday_tpu.elastic.checkpoint.CheckpointManager` — async
  overlapped generation checkpoints (non-blocking device→host snapshot,
  background serialize + atomic write, bounded-queue backpressure), every
  stall booked to the ``ckpt`` ledger (:func:`ckpt_summary`); with
  ``hosts=N`` the write partitions across simulated hosts (per-host
  manifests, durable only when ALL hosts stamped).
* :class:`~beforeholiday_tpu.elastic.trainer.ElasticTrainer` — the loop
  that treats a tripwire mismatch, a (simulated or signal-delivered)
  preemption, or a watchdog-flagged hang as a resize event: drain, reload
  the last durable manifest, ``reshard_state`` to the surviving world on a
  freshly carved mesh, continue bitwise. Shrink AND grow: with
  ``grow_when_available`` the trainer reclaims returned capacity at
  checkpoint boundaries.
* :class:`~beforeholiday_tpu.elastic.signals.PreemptionNotice` — the real
  preemption bridge: a SIGTERM/SIGUSR1 handler sets a host flag the loop
  polls once per step; composes with the flight recorder's
  ``arm_preemption_dump`` (dump first, then graceful drain).
* :class:`~beforeholiday_tpu.elastic.watchdog.HangWatchdog` — liveness for
  the rank that hangs rather than dies: per-rank heartbeats, a monitor
  thread, and :class:`~beforeholiday_tpu.elastic.watchdog.RankHangError`
  raised into the loop's poll.

Drills live in ``testing/drills.py`` (SIGKILL a training subprocess
mid-run, assert bitwise-correct resume; randomized multi-fault schedules,
each bitwise vs an uninterrupted reference) and are driven by
``tests/test_elastic.py`` / ``tests/test_chaos.py``.
"""

from beforeholiday_tpu.elastic.checkpoint import (
    CheckpointManager,
    ckpt_records,
    ckpt_summary,
    latest_generation,
    list_generations,
    reset_ckpt_ledger,
)
from beforeholiday_tpu.elastic.signals import PreemptionNotice
from beforeholiday_tpu.elastic.trainer import (
    ElasticTrainer,
    ResizeEvent,
    guard_state_specs,
    zero3_state_specs,
)
from beforeholiday_tpu.elastic.watchdog import (
    HangWatchdog,
    RankHangError,
    reset_watchdog_ledger,
    watchdog_records,
)

__all__ = [
    "CheckpointManager",
    "ElasticTrainer",
    "HangWatchdog",
    "PreemptionNotice",
    "RankHangError",
    "ResizeEvent",
    "ckpt_records",
    "ckpt_summary",
    "guard_state_specs",
    "latest_generation",
    "list_generations",
    "reset_ckpt_ledger",
    "reset_watchdog_ledger",
    "watchdog_records",
    "zero3_state_specs",
]
