"""Deterministic, seedable fault injectors for the guardrail test-suite.

Every guardrail in :mod:`beforeholiday_tpu.guard` must be exercisable under
``JAX_PLATFORMS=cpu`` tier-1 tests; these injectors produce the faults. All are
deterministic given their ``seed`` (leaf selection happens host-side with a
private :class:`random.Random`, so injection sites are static under jit and the
same seed always poisons the same leaves).

* :func:`poison_grads`       — NaN/Inf N leaves of a grad pytree (the overflow
  the amp sentinel must catch);
* :func:`force_probe_failure` — make guarded dispatch's probe fail for an op
  (the kernel-build failure the jnp degradation must absorb);
* :func:`perturb_rank_grads` — perturb ONE rank's grads inside ``shard_map``
  (the silent divergence ``reduce_gradients(check_consistency=True)`` must
  flag);
* :func:`preempt_after`     — raise :class:`SimulatedPreemption` on the n-th
  tick (the in-process preemption notice the elastic trainer must survive);
* :func:`kill_rank`         — SIGKILL/SIGTERM a subprocess rank (the hard
  host loss the preemption drills inject for real);
* :func:`hang_rank`         — silence ONE rank's heartbeats on a
  :class:`~beforeholiday_tpu.elastic.watchdog.HangWatchdog` (the rank that
  hangs rather than dies — no exception, no exit, just silence);
* :func:`tear_host_generation` — remove one host's manifest from a durable
  multi-host checkpoint generation (the single-host storage loss a restore
  must tolerate by falling back to the last generation durable on ALL
  hosts).
"""

from __future__ import annotations

import contextlib
import os
import random
import signal
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp

from beforeholiday_tpu.elastic.signals import SimulatedPreemption  # noqa: F401 — re-exported


def poison_grads(
    grads: Any,
    *,
    n: int = 1,
    value: float = float("nan"),
    seed: int = 0,
    whole_leaf: bool = False,
) -> Any:
    """Return ``grads`` with ``n`` inexact leaves poisoned by ``value``.

    By default one element per chosen leaf is poisoned — enough to trip any
    correct non-finite sentinel while keeping the fault realistic (a single
    overflowed activation, not a wiped tensor); ``whole_leaf=True`` floods the
    leaf. Plugs directly into the ``reduce_grads`` hook of
    ``scaled_value_and_grad`` / ``StepGuard.value_and_grad``.
    """
    if n < 0:
        raise ValueError(f"n must be >= 0, got {n}")
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    candidates = [
        i for i, l in enumerate(leaves)
        if jnp.issubdtype(jnp.asarray(l).dtype, jnp.inexact)
    ]
    if not candidates:
        raise ValueError("no inexact leaves to poison")
    picks = random.Random(seed).sample(candidates, min(n, len(candidates)))
    for i in picks:
        leaf = jnp.asarray(leaves[i])
        if whole_leaf:
            leaves[i] = jnp.full_like(leaf, value)
        else:
            flat = jnp.ravel(leaf).at[0].set(value)
            leaves[i] = flat.reshape(leaf.shape)
    return jax.tree_util.tree_unflatten(treedef, leaves)


@contextlib.contextmanager
def force_probe_failure(*op_names: str) -> Iterator[None]:
    """Force guarded dispatch's probe to fail for ``op_names`` in this scope.

    Cached verdicts for the ops are dropped on entry (so an earlier clean probe
    cannot mask the injection) AND on exit (so the forced failure does not
    outlive the scope as a cached degradation).
    """
    from beforeholiday_tpu.guard import dispatch

    if not op_names:
        raise ValueError("force_probe_failure needs at least one op name")
    added = [op for op in op_names if op not in dispatch._FORCED_FAILURES]
    for op in op_names:
        dispatch.clear_probe_cache(op)
        dispatch._FORCED_FAILURES.add(op)
    try:
        yield
    finally:
        for op in added:
            dispatch._FORCED_FAILURES.discard(op)
        for op in op_names:
            dispatch.clear_probe_cache(op)


def perturb_rank_grads(
    grads: Any,
    axis_name: str,
    rank: int = 0,
    *,
    eps: float = 1e-3,
    value: Optional[float] = None,
) -> Any:
    """Inside ``shard_map``: corrupt ONE rank's inexact grad leaves.

    Default adds ``eps`` (a realistic silent divergence — e.g. a rank that
    dropped a microbatch); ``value=`` overwrites instead (e.g. ``float('nan')``
    for a rank whose backward blew up). Other ranks pass through untouched, so
    a consistency fingerprint across ``axis_name`` must disagree.
    """
    idx = jax.lax.axis_index(axis_name)

    def _corrupt(g):
        g = jnp.asarray(g)
        if not jnp.issubdtype(g.dtype, jnp.inexact):
            return g
        bad = jnp.full_like(g, value) if value is not None else g + jnp.asarray(
            eps, g.dtype
        )
        return jnp.where(idx == rank, bad, g)

    return jax.tree_util.tree_map(_corrupt, grads)


def preempt_after(n_steps: int, *,
                  surviving_world: Optional[int] = None
                  ) -> Callable[[], None]:
    """Deterministic in-process preemption: a ``tick()`` whose ``n_steps``-th
    call raises :class:`SimulatedPreemption` (once — later calls pass, so a
    trainer that survives the event keeps running).

    Host-side by design: call it once per step OUTSIDE the traced function
    (``ElasticTrainer.run(..., preemption=preempt_after(7))``), exactly
    where a real preemption-notice callback would interrupt the loop.
    ``surviving_world`` rides the exception for the trainer's resize.
    """
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    calls = {"n": 0}

    def tick() -> None:
        calls["n"] += 1
        if calls["n"] == n_steps:
            raise SimulatedPreemption(
                f"simulated preemption on tick {n_steps}",
                surviving_world=surviving_world,
            )

    return tick


def kill_rank(proc, *, sig: int = signal.SIGKILL,
              timeout: float = 30.0) -> int:
    """Deliver ``sig`` to a subprocess rank and reap it; returns the exit
    code (negative signal number on POSIX).

    ``SIGKILL`` (default) is the hard host loss — no cleanup runs, so an
    in-flight checkpoint generation is torn and a resume must fall back to
    the last durable one. ``SIGTERM`` instead exercises graceful-notice
    paths like ``FlightRecorder.arm_preemption_dump``. ``proc`` is a
    ``subprocess.Popen`` (the drills spawn each rank as its own process;
    in-process simulated ranks use :func:`preempt_after`).
    """
    proc.send_signal(sig)
    return proc.wait(timeout=timeout)


def hang_rank(watchdog, rank: int, *, after_step: int = 0) -> Callable:
    """Silence ``rank``'s heartbeats on ``watchdog`` once the global step
    reaches ``after_step`` — the rank that HANGS rather than dies.

    Unlike :func:`kill_rank` nothing exits and nothing raises: the rank
    simply stops reporting while the rest of the job keeps stepping, which
    is exactly the failure a liveness monitor (not an exception handler)
    must catch. Installs a suppressor on the watchdog's heartbeat ledger
    (``HangWatchdog.beat`` consults it) and returns it, so a test can
    ``watchdog.remove_suppressor(...)`` to "un-hang" the rank.
    """
    if not 0 <= rank < watchdog.world:
        raise ValueError(
            f"rank {rank} out of range for watchdog world {watchdog.world}"
        )
    if after_step < 0:
        raise ValueError(f"after_step must be >= 0, got {after_step}")

    def suppress(r: int, step: int) -> bool:
        return r == rank and step >= after_step

    watchdog.add_suppressor(suppress)
    return suppress


def tear_host_generation(gen_path: str, host: int) -> str:
    """Tear ONE simulated host's slice out of a durable multi-host
    checkpoint generation: remove its per-host manifest (host-manifest
    presence is that host's durability stamp, mirroring the top-level
    rule), leaving the generation durable on every OTHER host but not on
    ALL hosts — ``elastic.latest_generation`` must now fall back to the
    previous fully-durable generation. Returns the removed path."""
    from beforeholiday_tpu.optimizers import zero3

    path = zero3.host_manifest_path(gen_path, host)
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no host manifest for host {host} under {gen_path!r} — either "
            "the generation is single-host (hosts=1 writes none) or it is "
            "already torn"
        )
    os.remove(path)
    return path
