"""ElasticTrainer — the loop that turns shipped mechanisms into survivable
training: async shard checkpoints behind the step, failure detection, and
live resharding to the surviving world size.

Composition, not new physics — every piece already exists in the repo:

* ZeRO-3 shard state + bitwise resharding (``optimizers/zero3``);
* StepGuard skip/rollback on the shard triplet
  (``guard.StepGuard.apply_sharded_update``);
* the replication tripwire (``parallel.check_replicated_consistency``) —
  a traced ``mismatch`` flag in the step's metrics row;
* the preemption exception, :class:`~beforeholiday_tpu.elastic.signals.
  SimulatedPreemption` (raised by a real signal's notice or by an injector);
* the async :class:`~beforeholiday_tpu.elastic.checkpoint.CheckpointManager`.

A RESIZE EVENT (tripwire mismatch, ``SimulatedPreemption``, or a real
preemption notice routed to the same exception) is handled as:

1. drain — ``CheckpointManager.wait()`` makes every submitted generation
   durable;
2. reload — ``latest_generation`` finds the last durable manifest
   (``save_shard_files`` stamps it last, so a torn generation is invisible);
3. reshard — ``zero3.reshard_state`` re-slices the arena bitwise for the
   surviving world;
4. recarve — a fresh 1-D mesh over the surviving devices
   (``parallel_state.carve_data_mesh``) and a freshly built step function;
5. continue — ``global_step`` rolls back to the checkpointed step and the
   loop replays forward. The continued loss trajectory is bitwise identical
   to an uninterrupted run at the new world size from the same checkpoint
   (``tests/test_elastic.py::TestElasticTrainerDrills`` pins this).

The user supplies ``make_step(mesh, world) -> step`` where
``step(state, gstate, batch) -> (state, gstate, row)``; ``row`` is a dict of
REPLICATED scalars containing ``"loss"`` and optionally ``"mismatch"``
(nonzero trips the tripwire path — the step's new state is DISCARDED, not
checkpointed, and the trainer reloads from the last durable generation).
``gstate`` is the StepGuard state (None without a guard) and rides the
generation manifest via ``StepGuard.state_dict`` in ``extra``.

The run loop is host orchestration BETWEEN steps: it drains the row once per
step like the examples do (``np.asarray``), never inside a traced function.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

_shard_map = functools.partial(jax.shard_map, check_vma=False)

from beforeholiday_tpu.elastic import checkpoint as ckpt
from beforeholiday_tpu.elastic.signals import SimulatedPreemption
from beforeholiday_tpu.elastic.watchdog import RankHangError
from beforeholiday_tpu.monitor.trace import active_recorder
from beforeholiday_tpu.optimizers import zero3
from beforeholiday_tpu.parallel.parallel_state import (
    DATA_AXIS,
    carve_data_mesh,
)
from beforeholiday_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _span(name: str):
    """Book ``name`` on the active timeline recorder (no-op otherwise) —
    the goodput classifier's raw material (``monitor.goodput``). The loop
    books ``step`` around productive work and ``elastic:drain`` /
    ``elastic:restore`` / ``elastic:reshard`` / ``elastic:hang`` around the
    resize machinery; the checkpoint ledger books its own ``ckpt:*`` phase
    spans."""
    rec = active_recorder()
    if rec is None:
        return contextlib.nullcontext()
    return rec.span(name)


__all__ = [
    "ElasticTrainer",
    "ResizeEvent",
    "guard_state_specs",
    "zero3_state_specs",
]


def zero3_state_specs(axis_name: str = DATA_AXIS) -> Dict[str, P]:
    """PartitionSpecs for the ZeRO-3 state dict: the flat arenas shard on
    ``axis_name``, the step counter is replicated."""
    return {
        "master": P(axis_name),
        "exp_avg": P(axis_name),
        "exp_avg_sq": P(axis_name),
        "step": P(),
    }


def guard_state_specs(guard, axis_name: str = DATA_AXIS):
    """PartitionSpecs for a gstate produced by ``guard.init(<zero3 state>)``:
    scaler/health leaves are replicated scalars (or the replicated amax
    history under O6); the rollback snapshot, when armed, IS the shard
    triplet and shards like it."""
    from beforeholiday_tpu.guard.step import _HEALTH_KEYS

    specs: Dict[str, Any] = {
        "scaler": jax.tree_util.tree_map(
            lambda _: P(), guard.scaler.init()
        ),
        "health": {k: P() for k in _HEALTH_KEYS},
    }
    if guard.rollback_after:
        specs["snapshot"] = zero3_state_specs(axis_name)
    return specs


@dataclasses.dataclass(frozen=True)
class ResizeEvent:
    """One elastic resize (or graceful drain), as it happened."""

    reason: str          # "preemption" | "tripwire" | "hang" | "grow" |
                         # "manual" | "preemption_drain"
    at_step: int         # global step when the event fired
    old_world: int
    new_world: int
    resumed_from: int    # generation step the trainer reloaded
    stall_s: float = 0.0  # wall time the loop spent on drain+reload+reshard


class ElasticTrainer:
    """Survivable ZeRO-3 training loop with async generation checkpoints.

    Parameters
    ----------
    opt: a ``ZeRO3FusedAdam`` (its state dict is what gets checkpointed).
    layout: ``zero3.layout_of(params)`` — topology-independent, reused
        across resizes.
    make_step: ``(mesh, world) -> step`` factory; rebuilt on every resize.
    directory: checkpoint root (generations land in ``gen_<step>``).
    guard: optional ``StepGuard`` — its state rides the manifest ``extra``.
    checkpoint_every: submit a generation every N committed steps (0 off).
    survivor_policy: world -> surviving world when an event does not name
        one (default halve).
    min_world: resizing below this raises instead of limping on.
    hosts: simulated multi-host checkpoint partition — each host writes
        only its rank subset + a per-host manifest; a resized world keeps
        the largest compatible partition (``zero3.effective_hosts``).
    notice: a :class:`~beforeholiday_tpu.elastic.signals.PreemptionNotice`
        (installed by the caller) polled once per step; its raised
        ``SimulatedPreemption`` takes the same resize/drain path as the
        injected one.
    watchdog: a :class:`~beforeholiday_tpu.elastic.watchdog.HangWatchdog`
        — the loop heartbeats every rank after each committed step and
        polls :meth:`~HangWatchdog.check`; a flagged hang resizes like a
        tripwire. Heartbeat state rides the manifest ``extra``.
    capacity_probe: ``() -> available device count``, polled at checkpoint
        boundaries when ``grow_when_available`` is on; when capacity
        allows a larger valid world the trainer resizes UP from the
        generation it just submitted (no committed step is lost).
    grow_when_available: enable grow-back (and permit resize targets
        above the current world).
    """

    def __init__(
        self,
        opt,
        layout,
        make_step: Callable[[Any, int], Callable],
        *,
        directory: str,
        guard=None,
        checkpoint_every: int = 5,
        queue_depth: int = 2,
        keep: int = 2,
        devices=None,
        axis_name: str = DATA_AXIS,
        min_world: int = 1,
        survivor_policy: Optional[Callable[[int], int]] = None,
        hosts: int = 1,
        notice=None,
        watchdog=None,
        capacity_probe: Optional[Callable[[], int]] = None,
        grow_when_available: bool = False,
    ):
        self.opt = opt
        self.layout = layout
        self.make_step = make_step
        self.directory = directory
        self.guard = guard
        self.checkpoint_every = int(checkpoint_every)
        self.queue_depth = int(queue_depth)
        self.keep = int(keep)
        self.axis_name = axis_name
        self.min_world = int(min_world)
        self.survivor_policy = survivor_policy or (lambda w: w // 2)
        if hosts < 1:
            raise ValueError(f"hosts must be >= 1, got {hosts}")
        self.hosts = int(hosts)
        self.notice = notice
        self.watchdog = watchdog
        self.capacity_probe = capacity_probe
        self.grow_when_available = bool(grow_when_available)
        self._devices = np.asarray(
            jax.devices() if devices is None else devices
        ).ravel()
        self.world: Optional[int] = None
        self.mesh = None
        self.global_step = 0
        self.events: List[ResizeEvent] = []
        self.history: List[Dict[str, Any]] = []
        self._state = None
        self._gstate = None
        self._step_fn = None
        self._manager: Optional[ckpt.CheckpointManager] = None

    @property
    def state(self):
        """Live ZeRO-3 state dict (global sharded arrays on the current mesh)."""
        return self._state

    @property
    def gstate(self):
        """Live StepGuard state (None without a guard)."""
        return self._gstate

    # ------------------------------------------------------------- lifecycle
    def init(self, params, *, world: Optional[int] = None) -> None:
        """Fresh start: carve the mesh, shard ``opt.init(params)`` onto it,
        seed the guard state from the shard triplet (the rollback snapshot
        is shard-sized, never model-sized)."""
        self._install_world(world or len(self._devices))
        specs = zero3_state_specs(self.axis_name)
        init_fn = jax.jit(_shard_map(
            lambda p: self.opt.init(p),
            mesh=self.mesh, in_specs=(P(),), out_specs=specs,
        ))
        self._state = init_fn(params)
        self._gstate = (
            self.guard.init(self._state) if self.guard is not None else None
        )
        self.global_step = 0

    def restore(self, *, world: int,
                directory: Optional[str] = None) -> int:
        """Resume from the last DURABLE generation at ``world`` ranks:
        load shards, ``reshard_state`` (bitwise), place the arena on a
        freshly carved mesh, rebuild the step, and reload guard/scaler
        state from the manifest ``extra``. Returns the generation step the
        trainer resumed from (``global_step`` is rolled back to it)."""
        src = directory or self.directory
        gen = ckpt.latest_generation(src)
        if gen is None:
            raise FileNotFoundError(
                f"no durable checkpoint generation under {src!r}"
            )
        step, path = gen
        manifest, shards = zero3.load_shard_files(path)
        resharded = zero3.reshard_state(shards, manifest, world)
        self._install_world(world)
        state: Dict[str, Any] = {}
        for key in manifest["state_keys"]:
            full = np.concatenate([r[key] for r in resharded])
            state[key] = jax.device_put(
                full, NamedSharding(self.mesh, P(self.axis_name))
            )
        state["step"] = jax.device_put(
            jnp.asarray(resharded[0]["step"], jnp.int32),
            NamedSharding(self.mesh, P()),
        )
        self._state = state
        if self.guard is not None:
            sd = (manifest.get("extra") or {}).get("guard")
            if sd is None:
                self._gstate = self.guard.init(self._state)
            else:
                self._gstate = self.guard.load_state_dict(
                    sd,
                    params=(
                        self._state if self.guard.rollback_after else None
                    ),
                )
        if self.watchdog is not None:
            hb = (manifest.get("extra") or {}).get("heartbeats")
            if hb is not None and int(hb.get("world", -1)) == world:
                # same topology: restore last-heard steps (clocks re-arm at
                # now inside load_state_dict — a restore must never inherit
                # a pre-crash silence window). A resharded world keeps the
                # fresh ledger _install_world already armed; PR-12
                # manifests carry no heartbeats key and default the same.
                self.watchdog.load_state_dict(hb)
        self.global_step = int(manifest.get("step", step))
        return self.global_step

    def close(self) -> None:
        if self._manager is not None:
            self._manager.close()
            self._manager = None

    def __enter__(self) -> "ElasticTrainer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -------------------------------------------------------------- the loop
    def run(self, n_steps: int, batch_fn: Callable[[int], Any], *,
            preemption: Optional[Callable[[], None]] = None
            ) -> List[Dict[str, Any]]:
        """Advance ``n_steps`` COMMITTED steps past the current
        ``global_step``, surviving resize events along the way (replayed
        steps after a reload count toward the same target, exactly like a
        real resumed run re-earning lost steps).

        ``batch_fn(global_step)`` returns the GLOBAL batch (host arrays) —
        key it on the step so a replay after reload sees identical data and
        the continued trajectory stays bitwise. ``preemption`` is an
        injector called once per step (``faults.preempt_after``); a
        ``SimulatedPreemption`` from it — or from anywhere in the step —
        becomes a resize event. Returns the history rows appended by this
        call (``{"step", "world", "loss"}``)."""
        if self._step_fn is None:
            raise RuntimeError("call init() or restore() before run()")
        target = self.global_step + int(n_steps)
        appended = len(self.history)
        while self.global_step < target:
            try:
                if preemption is not None:
                    preemption()
                if self.notice is not None:
                    self.notice.tick()
                if self.watchdog is not None:
                    self.watchdog.check()
                with _span("step"):
                    batch = batch_fn(self.global_step)
                    new_state, new_gstate, row = self._step_fn(
                        self._state, self._gstate, batch
                    )
                    fetched = {k: np.asarray(v) for k, v in row.items()}
            except SimulatedPreemption as e:
                if e.drain:
                    # graceful notice: this process is going away — make
                    # the state durable and hand control back (exit 0),
                    # instead of resizing a world that is being evicted
                    t0 = time.perf_counter()
                    with _span("elastic:drain"):
                        self.checkpoint_now(wait=True)
                    self.events.append(ResizeEvent(
                        reason="preemption_drain", at_step=self.global_step,
                        old_world=self.world, new_world=self.world,
                        resumed_from=self.global_step,
                        stall_s=time.perf_counter() - t0,
                    ))
                    logger.warning(
                        "graceful drain at step %d (%s): generation durable, "
                        "returning", self.global_step, e,
                    )
                    return self.history[appended:]
                surviving = (
                    e.surviving_world
                    if e.surviving_world is not None
                    else self.survivor_policy(self.world)
                )
                self._resize(surviving, reason="preemption")
                continue
            except RankHangError as e:
                # a silent rank is a lost rank that never said so: same
                # recovery as the tripwire — the last committed state is
                # durable, drop to the survivor world and replay
                logger.warning(
                    "hang watchdog fired at step %d (%s); resharding",
                    self.global_step, e,
                )
                self._resize(
                    self.survivor_policy(self.world), reason="hang"
                )
                continue
            mism = fetched.get("mismatch")
            if mism is not None and bool(np.any(mism)):
                # a replicated-by-construction value diverged across ranks:
                # the step's output is poisoned — discard it and reload
                logger.warning(
                    "consistency tripwire fired at step %d; resharding",
                    self.global_step,
                )
                self._resize(
                    self.survivor_policy(self.world), reason="tripwire"
                )
                continue
            self._state, self._gstate = new_state, new_gstate
            self.global_step += 1
            if self.watchdog is not None:
                # every simulated rank that stepped is alive by
                # construction; injected hangs suppress individual beats
                self.watchdog.beat_all(self.global_step)
            loss = fetched["loss"]
            self.history.append({
                "step": self.global_step,
                "world": self.world,
                "loss": float(loss),
            })
            if (
                self._manager is not None
                and self.checkpoint_every
                and self.global_step % self.checkpoint_every == 0
            ):
                self._submit_checkpoint()
                self._maybe_grow()
        return self.history[appended:]

    def checkpoint_now(self, *, wait: bool = False) -> str:
        """Submit a generation for the current state immediately; with
        ``wait=True`` block until it is durable (what the drills' reference
        runs do at every lineage boundary)."""
        path = self._submit_checkpoint()
        if wait:
            self._manager.wait()
        return path

    # ------------------------------------------------------------- internals
    def _submit_checkpoint(self) -> str:
        extra: Dict[str, Any] = {}
        if self.guard is not None:
            extra["guard"] = self.guard.state_dict(self._gstate)
        if self.watchdog is not None:
            extra["heartbeats"] = self.watchdog.state_dict()
        return self._manager.submit(
            self.global_step, self._state, extra=extra or None
        )

    def _maybe_grow(self) -> None:
        """Checkpoint-boundary grow-back: when the capacity probe reports
        room for a larger valid world, resize UP from the generation just
        submitted — ``global_step`` equals its step, so the restore loses
        no committed work and the continued trajectory is bitwise the
        new-world trajectory from that checkpoint."""
        if not (self.grow_when_available and self.capacity_probe):
            return
        cap = int(self.capacity_probe())
        target = self._grow_target(cap)
        if target is None:
            return
        logger.warning(
            "capacity probe reports %d devices available at step %d; "
            "growing %d -> %d", cap, self.global_step, self.world, target,
        )
        self._resize(target, reason="grow")

    def _grow_target(self, capacity: int) -> Optional[int]:
        """Largest world > the current one that divides the device count
        and fits ``capacity`` (None when capacity allows no growth)."""
        ndev = int(self._devices.size)
        for w in range(min(capacity, ndev), self.world, -1):
            if ndev % w == 0:
                return w
        return None

    def _validate_resize_target(self, new_world: int, *,
                                reason: str) -> None:
        """A survivor policy (or event payload) naming a bad world must
        fail loudly, not limp into a nonsense mesh carve or a silent
        no-op."""
        ndev = int(self._devices.size)
        if new_world < 1:
            raise ValueError(
                f"resize target must be >= 1, got {new_world} "
                f"(reason={reason!r})"
            )
        if ndev % new_world:
            raise ValueError(
                f"resize target {new_world} does not divide the device "
                f"count {ndev} — the ZeRO-3 arena reshards only onto "
                f"worlds that tile the slice (reason={reason!r})"
            )
        if new_world == self.world:
            raise ValueError(
                f"resize target {new_world} equals the current world "
                f"(reason={reason!r}) — a resize must change the world; "
                "grow-back reclaims returned capacity at checkpoint "
                "boundaries instead of re-resizing in place"
            )
        if new_world > self.world and not (
            self.grow_when_available or reason == "manual"
        ):
            raise ValueError(
                f"resize target {new_world} grows past the current world "
                f"{self.world} but grow_when_available is off "
                f"(reason={reason!r})"
            )

    def _resize(self, new_world: int, *, reason: str) -> None:
        new_world = int(new_world)
        self._validate_resize_target(new_world, reason=reason)
        if new_world < max(1, self.min_world):
            raise RuntimeError(
                f"resize to world={new_world} is below min_world="
                f"{self.min_world}; cannot continue"
            )
        old_world, at = self.world, self.global_step
        t0 = time.perf_counter()
        outer = "elastic:hang" if reason == "hang" else "elastic:reshard"
        with _span(outer):
            if self._manager is not None:
                # drain in-flight generations so the newest submitted one is
                # durable before we go looking for it
                with _span("elastic:drain"):
                    self._manager.wait()
            with _span("elastic:restore"):
                resumed = self.restore(world=new_world)
        self.events.append(ResizeEvent(
            reason=reason, at_step=at, old_world=old_world,
            new_world=new_world, resumed_from=resumed,
            stall_s=time.perf_counter() - t0,
        ))
        logger.warning(
            "elastic resize (%s) at step %d: world %d -> %d, resumed from "
            "generation %d", reason, at, old_world, new_world, resumed,
        )

    def _install_world(self, world: int) -> None:
        if self._manager is not None:
            self._manager.close()
        self.world = int(world)
        self.mesh = carve_data_mesh(
            self.world, devices=self._devices, axis_name=self.axis_name
        )
        self._step_fn = self.make_step(self.mesh, self.world)
        manifest = zero3.shard_manifest(
            self.layout, self.world,
            hosts=zero3.effective_hosts(self.world, self.hosts),
        )
        self._manager = ckpt.CheckpointManager(
            self.directory, manifest,
            queue_depth=self.queue_depth, keep=self.keep,
        )
        if self.watchdog is not None:
            # fresh beat clocks for the new world — a resize must not
            # inherit the silence window that triggered it
            self.watchdog.reset(self.world)
