"""Kill/resume, chaos and goodput drills for the elastic stack — the harness
``tests/test_elastic.py``, ``tests/test_chaos.py`` and
``tests/test_telemetry.py`` drive, on the 8-device virtual CPU mesh.

These are safety drills, not measurements: every one ends in a BITWISE
comparison against an independent, uninterrupted reference (or, for the
goodput run, an exact integer sum), and nothing here reads a clock for a
result.

* **Fixtures** — a tiny ZeRO-3 engine (``_engine``), a batch keyed on the
  global step (``_batch_fn``: a replay after reload sees identical data,
  which is what makes a continued trajectory bitwise) and its parameters.
* **The train child** — ``python -m beforeholiday_tpu.testing.drills --role
  train`` trains with async generation checkpoints and, by flag, SIGKILLs
  itself mid-run (rank loss the hard way: no atexit, no flush — the writer
  thread dies wherever it stands), self-delivers a REAL SIGTERM with the
  flight recorder armed and a ``PreemptionNotice`` installed (the graceful
  drain), or resumes from the last durable generation. Not for direct use:
  ``_spawn_train_child`` starts it under ``JAX_PLATFORMS=cpu``.
* **The preemption drill** (``_run_drill``) — the child dies by SIGKILL at
  world 8; the parent finds the last DURABLE generation (a torn one scans as
  manifest-less and is skipped), resumes at world 4 and runs to the target.
  The oracle is an INDEPENDENT reference: a fresh world-8 run recomputes the
  checkpointed step from scratch, checkpoints synchronously, reshards to 4
  and runs the same steps — loss trajectory and final master arena must
  match the resumed run BITWISE. That proves both halves at once: the async
  snapshot captured the true state, and resharding + resume replay the
  exact trajectory.
* **The chaos soak** — PR 12 proved SINGLE-fault recovery bitwise;
  production preemptible slices deliver fault SEQUENCES — a SIGTERM notice
  while a generation is in flight, a host lost right after capacity grew
  back, a hung rank discovered mid-shrink. ``generate_schedule`` composes
  the whole fault menagerie into seeded random schedules and
  ``run_schedule`` holds every one to the same oracle: the run must end with
  the master arena BITWISE-EQUAL to an uninterrupted reference.
  ``growback_drill`` is the deterministic 4→8 grow-back; ``run_soak`` is all
  of ``SCHEDULE_SEEDS`` plus the grow drill.
* **The goodput run** (``_goodput_run``) — a seeded preempt 8→4 / grow-back
  4→8 schedule under a live timeline; ``goodput_report`` must sum its
  integer-microsecond breakdown EXACTLY to wall time.

Fault kinds (all injectors live in :mod:`beforeholiday_tpu.testing.faults`
or ride the elastic subsystem's own hooks):

* ``shrink``  — in-process ``SimulatedPreemption`` naming half the world;
* ``signal``  — a REAL ``SIGUSR1`` through the OS into
  :class:`~beforeholiday_tpu.elastic.signals.PreemptionNotice`;
* ``grow``    — the capacity probe reports the full slice back; the
  trainer grows at the next checkpoint boundary;
* ``torn``    — one simulated host's manifest torn out of the newest
  durable generation (restore must fall back);
* ``hang``    — one rank's heartbeats suppressed; the
  :class:`~beforeholiday_tpu.elastic.watchdog.HangWatchdog` flags it;
* ``sigkill`` / ``sigterm`` (spawn legs) — a subprocess child killed hard
  mid-run, or gracefully drained (flight-recorder dump + notice handoff,
  rc 0) by a real SIGTERM.

**The lineage-replay oracle.** Every recovery rolls ``global_step`` back
to a durable generation and replays, so the FINAL trajectory is fully
described by the run's resize events: keep, in occurrence order, each
``(resumed_from, new_world)``, dropping earlier entries whose segment
start was replayed over (``start >= resumed_from``). The reference then
replays that lineage forward-only — run to each boundary, checkpoint
synchronously, restore at the new world — with no faults at all. Final
master arena, per-step loss, and per-step world must all match bitwise.
Detection timing (watchdog wall clocks) may vary run to run; the oracle
keys on OBSERVED events, so a hang that fires late (or not at all) still
yields a consistent comparison.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import random
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

WORLD = 8
RESUME_WORLD = 4
CKPT_EVERY = 2
SCHEDULE_SEEDS = (0, 1, 2, 3, 4, 5)

_IN_PROCESS_KINDS = ("shrink", "signal", "grow", "torn", "hang")


def _geometry(quick: bool):
    """(dim, layers, rows) for the drill model — rows divisible by both the
    full and the surviving world so the same global batch shards either way."""
    return (32, 4, 16) if quick else (64, 8, 16)


def _params(dim: int, layers: int):
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    return {
        f"w{i:02d}": jnp.asarray(
            (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        )
        for i in range(layers)
    }


def _batch_fn(rows: int, dim: int):
    """Global batch keyed on the global step — a replay after reload sees
    identical data, which is what makes the continued trajectory bitwise."""
    import jax.numpy as jnp

    def batch(step: int):
        rng = np.random.RandomState(10_000 + int(step))
        return jnp.asarray(rng.randn(rows, dim).astype(np.float32))

    return batch


def _engine(dim: int, layers: int):
    """(params, layout, opt, make_step) — the pieces ElasticTrainer wants."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from beforeholiday_tpu.elastic import zero3_state_specs
    from beforeholiday_tpu.monitor import comms as mon_comms
    from beforeholiday_tpu.optimizers import ZeRO3FusedAdam, zero3

    import functools

    _shmap = functools.partial(jax.shard_map, check_vma=False)

    params = _params(dim, layers)
    layout = zero3.layout_of(params)
    opt = ZeRO3FusedAdam(
        lr=1e-2, weight_decay=0.02, impl="jnp",
        prefetch=1, param_residency="keep",
    )
    specs = zero3_state_specs()

    def make_step(mesh, world):
        def body(state, batch):
            def loss_fn(master):
                p = opt.gather_params(master, layout)
                y = batch
                for k in sorted(p):
                    y = jnp.tanh(y @ p[k])
                return jnp.sum(y)

            local_loss, g = jax.value_and_grad(loss_fn)(state["master"])
            new_state = opt.step(g, state)
            loss = mon_comms.psum(local_loss, "data", site="elastic.loss")
            return new_state, loss

        inner = jax.jit(_shmap(
            body, mesh=mesh, in_specs=(specs, P("data")),
            out_specs=(specs, P()),
        ))

        def step(state, gstate, batch):
            new_state, loss = inner(state, batch)
            return new_state, gstate, {"loss": loss}

        return step

    return params, layout, opt, make_step


def _require_mesh():
    import jax

    if len(jax.devices()) < WORLD or jax.default_backend() != "cpu":
        raise RuntimeError(
            f"the drills need a >= {WORLD}-device CPU platform, "
            f"got {len(jax.devices())} x {jax.default_backend()}"
        )


# --------------------------------------------------------------- drill child
def _train_role(args) -> None:
    """The drill child. Three shapes, picked by flags:

    * ``--kill-at N`` (default drill): train with async checkpoints, then
      SIGKILL the whole process right after committing N steps — whatever
      generation is in flight stays torn on disk.
    * ``--term-at N [--arm-notice --dump PATH]``: self-deliver a REAL
      SIGTERM after committing N steps with the flight recorder's
      preemption dump armed and a ``PreemptionNotice`` installed — the
      handler dumps the black box, hands off to the notice (no signal
      re-delivery), the run loop drains, and the child exits 0 printing a
      JSON line (``_spawn_leg``'s graceful-drain drill).
    * ``--resume``: restore from the last durable generation in ``--dir``
      at ``--world`` ranks instead of ``init`` (the post-fault child).
    """
    _require_mesh()
    import contextlib

    from beforeholiday_tpu.elastic import ElasticTrainer, PreemptionNotice
    from beforeholiday_tpu.monitor.flight import FlightRecorder

    dim, layers, rows = _geometry(args.quick)
    params, layout, opt, make_step = _engine(dim, layers)
    batch = _batch_fn(rows, dim)
    world = args.world or WORLD
    notice = None
    if args.arm_notice:
        notice = PreemptionNotice((signal.SIGTERM,)).install()
    trainer = ElasticTrainer(
        opt, layout, make_step, directory=args.dir,
        checkpoint_every=args.ckpt_every, queue_depth=2, keep=2,
        hosts=args.hosts, notice=notice,
    )
    rec = FlightRecorder(path=args.dump) if args.dump else None
    drained = False
    with rec if rec is not None else contextlib.nullcontext():
        if rec is not None:
            # armed AFTER the notice installed: the recorder's handler owns
            # the signal, dumps first, then finds the notice registered as
            # the graceful consumer — drain instead of re-delivery
            rec.arm_preemption_dump(signal.SIGTERM)
        if args.resume:
            trainer.restore(world=world)
        else:
            trainer.init(params, world=world)
        while trainer.global_step < args.total:
            trainer.run(1, batch)
            if trainer.events and trainer.events[-1].reason == (
                "preemption_drain"
            ):
                # leave the recorder context BEFORE exiting: a sys.exit
                # inside it would dump again (exception:SystemExit) over
                # the preemption dump we are about to report
                drained = True
                break
            if args.kill_at and trainer.global_step == args.kill_at:
                os.kill(os.getpid(), signal.SIGKILL)
            if args.term_at and trainer.global_step == args.term_at:
                os.kill(os.getpid(), signal.SIGTERM)
        if args.kill_at and not drained:
            raise RuntimeError(
                f"train child survived to step {trainer.global_step} "
                f"without being killed (kill_at={args.kill_at})"
            )
    trainer.close()
    if drained:
        print(json.dumps({
            "drained_at": trainer.global_step,
            "world": trainer.world,
            "dumps": list(rec.dumps) if rec is not None else [],
        }))
        sys.exit(0)
    print(json.dumps({
        "finished_at": trainer.global_step, "world": trainer.world,
    }))


def _child_env() -> dict:
    """Env for a drill child: CPU platform, 8 virtual devices,
    repo root importable."""
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={WORLD}"
    )
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _spawn_train_child(ckpt_dir: str, *, quick: bool,
                       extra_args: list = (), timeout: float = 300.0):
    """Run a ``--role train`` child with ``extra_args`` appended; returns
    the ``CompletedProcess`` (callers assert on rc/stdout — ``_spawn_leg``
    reuses this for its SIGTERM/SIGKILL legs)."""
    cmd = [
        sys.executable, "-m", "beforeholiday_tpu.testing.drills",
        "--role", "train", "--dir", ckpt_dir,
    ] + list(extra_args)
    if quick:
        cmd.append("--quick")
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout,
        env=_child_env(),
    )


def _spawn_killed_child(ckpt_dir: str, *, quick: bool, total: int,
                        kill_at: int, ckpt_every: int) -> int:
    """Run the drill child to its SIGKILL; returns the (negative) rc."""
    proc = _spawn_train_child(
        ckpt_dir, quick=quick, extra_args=[
            "--total", str(total), "--kill-at", str(kill_at),
            "--ckpt-every", str(ckpt_every),
        ],
    )
    if proc.returncode != -signal.SIGKILL:
        raise AssertionError(
            f"drill child was supposed to die by SIGKILL, got rc="
            f"{proc.returncode}\nstdout: {proc.stdout[-2000:]}\n"
            f"stderr: {proc.stderr[-2000:]}"
        )
    return proc.returncode


# ------------------------------------------------------- preemption drill
def _run_drill(tmp: str, quick: bool):
    from beforeholiday_tpu import elastic
    from beforeholiday_tpu.elastic import ElasticTrainer

    dim, layers, rows = _geometry(quick)
    params, layout, opt, make_step = _engine(dim, layers)
    batch = _batch_fn(rows, dim)
    # with queue_depth=2, submit N returning means generation N-6 finished
    # (the bounded queue is the proof): killing after the step-10 submit
    # guarantees at least gens 2 and 4 are durable, whatever the writer's
    # fsync pace — the kill still usually tears whatever is in flight
    total, kill_at, ckpt_every = 16, 11, 2

    child_dir = os.path.join(tmp, "drill")
    killed_rc = _spawn_killed_child(
        child_dir, quick=quick, total=total, kill_at=kill_at,
        ckpt_every=ckpt_every,
    )

    gen = elastic.latest_generation(child_dir)
    if gen is None:
        gens = elastic.list_generations(child_dir)
        raise AssertionError(
            f"no durable generation survived the SIGKILL; saw {gens}"
        )
    resumed_from, _ = gen
    replay = total - resumed_from
    if not 0 < replay < total:
        raise AssertionError(
            f"drill resumed from step {resumed_from} (kill at {kill_at}) — "
            "the checkpoint cadence is broken"
        )

    # resume the survivors at the smaller world
    with ElasticTrainer(
        opt, layout, make_step, directory=child_dir, checkpoint_every=0,
    ) as resumed:
        got = resumed.restore(world=RESUME_WORLD)
        if got != resumed_from:
            raise AssertionError(
                f"restore landed on step {got}, latest durable is "
                f"{resumed_from}"
            )
        resumed_hist = resumed.run(replay, batch)
        resumed_master = np.asarray(resumed.state["master"])

    # independent reference: recompute the checkpointed step from scratch,
    # checkpoint synchronously, reshard, run the same steps
    ref_dir = os.path.join(tmp, "reference")
    with ElasticTrainer(
        opt, layout, make_step, directory=ref_dir, checkpoint_every=0,
    ) as ref:
        ref.init(params, world=WORLD)
        ref.run(resumed_from, batch)
        ref.checkpoint_now(wait=True)
        ref.restore(world=RESUME_WORLD)
        ref_hist = ref.run(replay, batch)
        ref_master = np.asarray(ref.state["master"])

    if [r["step"] for r in resumed_hist] != [r["step"] for r in ref_hist]:
        raise AssertionError("resumed and reference step ids diverged")
    for a, b in zip(resumed_hist, ref_hist):
        if a["loss"] != b["loss"]:
            raise AssertionError(
                f"loss trajectory diverged at step {a['step']}: resumed "
                f"{a['loss']!r} vs reference {b['loss']!r}"
            )
    if resumed_master.dtype != ref_master.dtype or not np.array_equal(
        resumed_master, ref_master
    ):
        raise AssertionError(
            "final master arena of the resumed run is not bitwise equal to "
            "the uninterrupted reference at the same world size"
        )
    return {
        "killed_rc": killed_rc,
        "resumed_from_step": resumed_from,
        "drill_steps_replayed": replay,
    }


# ------------------------------------------------------------ goodput run
def _goodput_run(tmpdir: str):
    """One seeded fault-schedule run (preempt 8->4, grow back 4->8) under a
    live timeline; returns the exact-sum goodput report."""
    from beforeholiday_tpu import elastic
    from beforeholiday_tpu.elastic import ElasticTrainer
    from beforeholiday_tpu.monitor import compile_counts, goodput_report
    from beforeholiday_tpu.monitor.trace import timeline
    from beforeholiday_tpu.testing.faults import preempt_after

    dim, layers, rows = _geometry(True)
    params, layout, opt, make_step = _engine(dim, layers)
    elastic.reset_ckpt_ledger()
    trainer = ElasticTrainer(
        opt, layout, make_step, directory=tmpdir,
        checkpoint_every=2, queue_depth=2, keep=3,
        capacity_probe=lambda: WORLD, grow_when_available=True,
    )
    with timeline() as rec:
        trainer.init(params, world=WORLD)
        # preempt on the 5th tick -> resize to the survivor world; the
        # capacity probe reports the full world at every checkpoint
        # boundary after that, so the next boundary grows back to 8
        trainer.run(
            10, _batch_fn(rows, dim),
            preemption=preempt_after(5, surviving_world=RESUME_WORLD),
        )
        trainer.close()
    events = rec.events()
    report = goodput_report(
        events,
        resize_events=trainer.events,
        ckpt=elastic.ckpt_summary(),
        compile_counts=compile_counts(),
    )
    # the classifier's contract: the integer breakdown sums to wall EXACTLY
    parts = sum(report[k] for k in (
        "productive_us", "checkpoint_us", "drain_us", "restore_us",
        "hang_us", "reshard_us", "compile_us", "other_us",
    ))
    assert parts == report["wall_us"], (parts, report["wall_us"])
    # both resizes really happened and their machinery was booked
    reasons = [e.reason for e in trainer.events]
    assert reasons == ["preemption", "grow"], reasons
    assert report["restore_us"] > 0 and report["reshard_us"] > 0, report
    assert report["productive_us"] > 0
    # checkpoint badput is the ledger's exposed time as seen from the run
    # loop: never more than what the ckpt ledger itself booked (writer
    # thread excluded on both sides), and present once generations exist
    assert report["checkpoint_s"] <= report["ckpt_exposed_s"] + 0.05, report
    return report, trainer.events


# ------------------------------------------------------------- chaos soak


@dataclasses.dataclass(frozen=True)
class Fault:
    """One scheduled fault: ``kind`` fires once ``at_step`` commits.
    ``arg`` seeds kind-specific choices (hung rank, torn host)."""

    kind: str
    at_step: int
    arg: int = 0


@dataclasses.dataclass(frozen=True)
class FaultSchedule:
    """A seeded multi-fault run: optional subprocess ``spawn`` leg
    (``sigkill``/``sigterm`` at ``spawn_at``), then in-process ``faults``
    against the resumed trainer, ending at committed step ``total``."""

    seed: int
    total: int
    faults: Tuple[Fault, ...]
    spawn: Optional[str] = None    # None | "sigkill" | "sigterm"
    spawn_at: int = 5

    @property
    def kinds(self) -> Tuple[str, ...]:
        base = tuple(f.kind for f in self.faults)
        return ((self.spawn,) + base) if self.spawn else base


def generate_schedule(seed: int, *, spawn: Optional[str] = None
                      ) -> FaultSchedule:
    """Deterministic composition from ``seed``: 2–3 in-process faults with
    ≥ 2 distinct kinds overall, steps spaced so every fault lands after a
    durable generation exists and before the run ends.

    Constraints the generator enforces by simulating the expected world:
    ``grow`` only after capacity was lost (so it actually fires), ``torn``
    immediately paired with a shrink (so the fallback is exercised while
    the tear is still the newest generation), nothing scheduled below
    world 1. The runner re-checks world validity at apply time — watchdog
    detection timing can shift the actual world — and skips a fault whose
    precondition vanished; the oracle keys on observed events, so a
    skipped fault never breaks the comparison."""
    rng = random.Random(0xC4A05 + seed)
    w = 4 if spawn == "sigkill" else WORLD   # sigkill leg resumes at 4
    # sigkill must land AFTER the bounded queue has proven earlier
    # generations durable (submit N returning means N-6 finished with
    # queue_depth=2) — same timing argument as ``_run_drill``'s; a
    # graceful drain needs no such margin, it waits the writer itself
    spawn_at = 11 if spawn == "sigkill" else 5
    step = (spawn_at + 5 if spawn else 0) + rng.randint(3, 5)
    faults: List[Fault] = []
    n = rng.randint(2, 3)
    while len(faults) < n or len(set(f.kind for f in faults)) < 2:
        allowed = []
        if w > 1:
            allowed += ["shrink", "signal", "hang"]
            allowed += ["torn"]   # pairs with a shrink below
        if w < WORLD:
            allowed += ["grow"]
        kind = rng.choice(allowed)
        faults.append(Fault(kind, step, arg=rng.randrange(WORLD)))
        if kind == "torn":
            # the tear only matters while the torn generation is still
            # the newest — pair it with an immediate shrink
            faults.append(Fault("shrink", step + 1, arg=0))
            w //= 2
        elif kind in ("shrink", "signal", "hang"):
            w //= 2
        elif kind == "grow":
            w = WORLD
        step += rng.randint(4, 6)
    total = step + 6
    return FaultSchedule(
        seed=seed, total=total, faults=tuple(faults), spawn=spawn,
        spawn_at=spawn_at,
    )


def final_lineage(initial, events) -> List[Tuple[int, int]]:
    """Collapse a run's resize events into the lineage of its FINAL
    trajectory: ``[(start_step, world), ...]`` with strictly increasing
    starts. ``initial`` seeds the lineage (``[(0, world0)]``, plus the
    subprocess leg's resume boundary when there was one). Each event rolls
    back to ``resumed_from`` and replays, so any earlier entry starting at
    or past that step was replayed over and is dropped; graceful drains
    roll nothing back."""
    lineage: List[Tuple[int, int]] = [(int(s), int(w)) for s, w in initial]
    for ev in events:
        if ev.reason == "preemption_drain":
            continue
        r = int(ev.resumed_from)
        lineage = [e for e in lineage if e[0] < r] + [(r, int(ev.new_world))]
    return lineage


def replay_reference(lineage, total: int, directory: str, *,
                     engine, batch_fn):
    """Run the lineage forward with NO faults: advance to each boundary,
    checkpoint synchronously, restore at the segment's world. Returns the
    (closed) reference trainer's final master arena and history."""
    from beforeholiday_tpu.elastic import ElasticTrainer

    params, layout, opt, make_step = engine
    with ElasticTrainer(
        opt, layout, make_step, directory=directory, checkpoint_every=0,
    ) as ref:
        ref.init(params, world=lineage[0][1])
        for start, w in lineage[1:]:
            if start > ref.global_step:
                ref.run(start - ref.global_step, batch_fn)
            if start != ref.global_step:
                raise AssertionError(
                    f"lineage boundary {start} unreachable: reference is "
                    f"at {ref.global_step}"
                )
            ref.checkpoint_now(wait=True)
            ref.restore(world=w)
        if total > ref.global_step:
            ref.run(total - ref.global_step, batch_fn)
        return np.asarray(ref.state["master"]), list(ref.history)


def _assert_bitwise(trainer, ref_master, ref_history, total: int, *,
                    start: int = 0) -> None:
    """Final-trajectory oracle: last-written row per step (replays
    overwrite) must match the reference row in loss AND world, and the
    final master arena must be bitwise equal. ``start`` skips steps a
    subprocess leg ran (the parent trainer's history begins at its
    resume boundary); the arena comparison is global regardless."""
    final_rows: Dict[int, Dict[str, Any]] = {}
    for row in trainer.history:
        final_rows[row["step"]] = row
    ref_rows = {row["step"]: row for row in ref_history}
    for s in range(start + 1, total + 1):
        a, b = final_rows.get(s), ref_rows.get(s)
        if a is None or b is None:
            raise AssertionError(f"step {s} missing from a trajectory")
        if a["loss"] != b["loss"] or a["world"] != b["world"]:
            raise AssertionError(
                f"final trajectory diverged at step {s}: chaos "
                f"(world {a['world']}, loss {a['loss']!r}) vs reference "
                f"(world {b['world']}, loss {b['loss']!r})"
            )
    got = np.asarray(trainer.state["master"])
    if got.dtype != ref_master.dtype or not np.array_equal(got, ref_master):
        raise AssertionError(
            "chaos run's final master arena is not bitwise equal to the "
            "lineage-replay reference"
        )


# ----------------------------------------------------------------- the runner


def _spawn_leg(sched: FaultSchedule, ckpt_dir: str, tmp: str,
               quick: bool) -> Dict[str, Any]:
    """Run the subprocess leg of a schedule; returns resume info for the
    in-process continuation."""
    from beforeholiday_tpu import elastic

    if sched.spawn == "sigkill":
        proc = _spawn_train_child(
            ckpt_dir, quick=quick, extra_args=[
                "--total", str(sched.spawn_at + 6),
                "--kill-at", str(sched.spawn_at),
                "--ckpt-every", str(CKPT_EVERY), "--hosts", "2",
            ],
        )
        if proc.returncode != -signal.SIGKILL:
            raise AssertionError(
                f"chaos SIGKILL child should die by signal, got rc="
                f"{proc.returncode}\nstdout: {proc.stdout[-2000:]}\n"
                f"stderr: {proc.stderr[-2000:]}"
            )
        return {"rc": proc.returncode, "resume_world": 4, "dump": None}
    dump = os.path.join(tmp, f"dump_{sched.seed}.json")
    proc = _spawn_train_child(
        ckpt_dir, quick=quick, extra_args=[
            "--total", str(sched.spawn_at + 10),
            "--term-at", str(sched.spawn_at),
            "--ckpt-every", str(CKPT_EVERY), "--hosts", "2",
            "--arm-notice", "--dump", dump,
        ],
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"SIGTERM drill child should drain gracefully (rc 0), got rc="
            f"{proc.returncode}\nstdout: {proc.stdout[-2000:]}\n"
            f"stderr: {proc.stderr[-2000:]}"
        )
    info = json.loads(proc.stdout.strip().splitlines()[-1])
    if info.get("drained_at") != sched.spawn_at:
        raise AssertionError(
            f"child drained at {info.get('drained_at')}, expected "
            f"{sched.spawn_at}"
        )
    if not (info.get("dumps") and os.path.isfile(dump)):
        raise AssertionError(
            "armed SIGTERM drill left no flight-recorder dump — the "
            "graceful-drain handoff did not run"
        )
    gen = elastic.latest_generation(ckpt_dir)
    if gen is None or gen[0] != sched.spawn_at:
        raise AssertionError(
            f"drained child's generation is not durable at step "
            f"{sched.spawn_at}: {gen}"
        )
    return {"rc": proc.returncode, "resume_world": WORLD, "dump": dump}


def run_schedule(sched: FaultSchedule, tmp: str, quick: bool
                 ) -> Dict[str, Any]:
    """Execute one schedule end to end and assert the bitwise oracle.
    Returns summary facts (kinds applied, events, grow stalls, spawn rc)."""
    from beforeholiday_tpu import elastic
    from beforeholiday_tpu.elastic import (
        ElasticTrainer,
        HangWatchdog,
        PreemptionNotice,
    )
    from beforeholiday_tpu.testing import faults as flt

    dim, layers, rows = _geometry(quick)
    engine = _engine(dim, layers)
    params, layout, opt, make_step = engine
    base_bf = _batch_fn(rows, dim)
    needs_pace = any(f.kind == "hang" for f in sched.faults)

    def bf(step):
        if needs_pace:
            # give the watchdog wall-clock room between steps; data stays
            # keyed on the step, so pacing never touches determinism
            time.sleep(0.015)
        return base_bf(step)

    ckpt_dir = os.path.join(tmp, f"chaos_{sched.seed}")
    lineage0: List[Tuple[int, int]] = [(0, WORLD)]
    spawn_info: Optional[Dict[str, Any]] = None
    if sched.spawn:
        spawn_info = _spawn_leg(sched, ckpt_dir, tmp, quick)

    # capacity starts at whatever survives the spawn leg (a SIGKILL *is*
    # the capacity loss); only an explicit grow fault hands it back
    cap = {"n": spawn_info["resume_world"] if spawn_info else WORLD}
    wd = (
        HangWatchdog(WORLD, hang_timeout_s=0.25, poll_interval_s=0.025)
        if needs_pace else None
    )
    notice = PreemptionNotice((signal.SIGUSR1,), drain=False)
    inject: Dict[str, Any] = {"exc": None}

    def injected():
        exc, inject["exc"] = inject["exc"], None
        if exc is not None:
            raise exc

    suppressors: List[Any] = []
    applied: List[str] = []

    def apply_fault(f: Fault, trainer) -> None:
        w = trainer.world
        if f.kind == "shrink":
            if w <= 1:
                return
            cap["n"] = w // 2
            inject["exc"] = flt.SimulatedPreemption(
                f"chaos shrink at step {trainer.global_step}",
                surviving_world=w // 2,
            )
        elif f.kind == "signal":
            if w <= 1:
                return
            cap["n"] = w // 2
            notice.surviving_world = w // 2
            os.kill(os.getpid(), signal.SIGUSR1)
        elif f.kind == "grow":
            cap["n"] = WORLD
        elif f.kind == "torn":
            if trainer._manager is not None:
                # drain the writer so the generation about to be torn has
                # actually been stamped durable (a tear of a still-in-flight
                # generation would test nothing)
                trainer._manager.wait()
            gens = [
                (s, p) for s, p, d in elastic.list_generations(ckpt_dir) if d
            ]
            if len(gens) < 2:
                return   # never tear the only restorable generation
            _, path = gens[-1]
            try:
                flt.tear_host_generation(path, f.arg % 2)
            except FileNotFoundError:
                return   # single-host generation (world degraded to 1)
        elif f.kind == "hang":
            if wd is None or w <= 1:
                return
            cap["n"] = w // 2
            suppressors.append(
                flt.hang_rank(wd, f.arg % w, after_step=trainer.global_step)
            )
        else:  # pragma: no cover — generator emits only known kinds
            raise ValueError(f"unknown fault kind {f.kind!r}")
        applied.append(f.kind)

    with contextlib.ExitStack() as stack:
        stack.enter_context(notice)
        if wd is not None:
            stack.enter_context(wd)
        trainer = stack.enter_context(ElasticTrainer(
            opt, layout, make_step, directory=ckpt_dir,
            checkpoint_every=CKPT_EVERY, hosts=2,
            survivor_policy=lambda w: w // 2,
            grow_when_available=True, capacity_probe=lambda: cap["n"],
            watchdog=wd, notice=notice,
        ))
        if spawn_info is not None:
            resumed = trainer.restore(world=spawn_info["resume_world"])
            lineage0.append((resumed, spawn_info["resume_world"]))
        else:
            trainer.init(params, world=WORLD)
        pending = sorted(sched.faults, key=lambda f: f.at_step)
        seen_events = 0
        while trainer.global_step < sched.total:
            while pending and pending[0].at_step <= trainer.global_step:
                apply_fault(pending.pop(0), trainer)
            trainer.run(1, bf, preemption=injected)
            # watchdog-driven resizes land asynchronously: once one fires,
            # the hung rank is gone — drop its suppressor and pin capacity
            # so grow-back waits for an explicit grow fault
            for ev in trainer.events[seen_events:]:
                if ev.reason == "hang":
                    cap["n"] = min(cap["n"], ev.new_world)
                    for s in suppressors:
                        with contextlib.suppress(ValueError):
                            wd.remove_suppressor(s)
                    suppressors.clear()
            seen_events = len(trainer.events)

        events = list(trainer.events)
        lineage = final_lineage(lineage0, events)
        ref_master, ref_history = replay_reference(
            lineage, sched.total, os.path.join(tmp, f"ref_{sched.seed}"),
            engine=engine, batch_fn=base_bf,
        )
        _assert_bitwise(
            trainer, ref_master, ref_history, sched.total,
            start=(lineage0[-1][0] if sched.spawn else 0),
        )
        grow_stalls = [
            ev.stall_s for ev in events if ev.reason == "grow"
        ]
        return {
            "seed": sched.seed,
            "kinds": sorted(set(
                ([sched.spawn] if sched.spawn else []) + applied
            )),
            "n_events": len(events),
            "event_reasons": [ev.reason for ev in events],
            "lineage": lineage,
            "grow_stalls_s": grow_stalls,
            "spawn_rc": spawn_info["rc"] if spawn_info else None,
            "spawn_dump": spawn_info["dump"] if spawn_info else None,
            "bitwise": 1.0,
        }


# ------------------------------------------------------- dedicated grow drill


def growback_drill(tmp: str, quick: bool) -> Dict[str, Any]:
    """The deterministic 4→8 grow-back: train at half capacity, probe
    reports the full slice back, the trainer grows at the next checkpoint
    boundary, and the continued run must be bitwise the world-8 run from
    that same generation."""
    from beforeholiday_tpu.elastic import ElasticTrainer

    dim, layers, rows = _geometry(quick)
    params, layout, opt, make_step = _engine(dim, layers)
    bf = _batch_fn(rows, dim)
    cap = {"n": 4}
    # capacity returns right after step 6 commits — step 6's boundary
    # already probed cap=4, so the grow lands at the NEXT boundary, step 8
    grow_at, grow_boundary, total = 6, 8, 12

    with ElasticTrainer(
        opt, layout, make_step, directory=os.path.join(tmp, "grow"),
        checkpoint_every=CKPT_EVERY, hosts=2, grow_when_available=True,
        capacity_probe=lambda: cap["n"],
    ) as tr:
        tr.init(params, world=4)
        tr.run(grow_at, bf)
        cap["n"] = WORLD
        tr.run(total - grow_at, bf)
        if [ev.reason for ev in tr.events] != ["grow"]:
            raise AssertionError(
                f"expected exactly one grow event, saw {tr.events}"
            )
        ev = tr.events[0]
        if (ev.old_world, ev.new_world, ev.resumed_from) != (
                4, WORLD, grow_boundary):
            raise AssertionError(f"grow event off: {ev}")
        if tr.world != WORLD or tr.global_step != total:
            raise AssertionError(
                f"grow drill ended at world {tr.world} step "
                f"{tr.global_step}"
            )
        master = np.asarray(tr.state["master"])
        history = list(tr.history)
        stall = ev.stall_s

    ref_master, ref_history = replay_reference(
        [(0, 4), (grow_boundary, WORLD)], total,
        os.path.join(tmp, "grow_ref"),
        engine=_engine(dim, layers), batch_fn=bf,
    )
    final_rows = {}
    for row in history:
        final_rows[row["step"]] = row
    for row in ref_history:
        mine = final_rows[row["step"]]
        if mine["loss"] != row["loss"] or mine["world"] != row["world"]:
            raise AssertionError(
                f"grow drill trajectory diverged at step {row['step']}"
            )
    if not np.array_equal(master, ref_master):
        raise AssertionError("grow drill master arena not bitwise")
    return {"growback_resume_bitwise": 1.0, "growback_stall_s": stall}


# ------------------------------------------------------------------ the soak


def run_soak(tmp: str, quick: bool) -> Dict[str, Any]:
    """Every seeded schedule plus the grow drill; each asserts its bitwise
    oracle, so returning at all means all survived. Returns the grow drill's
    facts and one ``run_schedule`` summary per schedule."""
    _require_mesh()

    schedules = [
        generate_schedule(s, spawn=(
            "sigkill" if s == 0 else "sigterm" if s == 1 else None
        ))
        for s in SCHEDULE_SEEDS
    ]
    # the acceptance shape, asserted before any run burns time: ≥ 6
    # schedules, each ≥ 2 distinct kinds, ≥ 1 with SIGKILL, ≥ 1 with grow
    if len(schedules) < 6:
        raise AssertionError("need at least 6 chaos schedules")
    for s in schedules:
        if len(set(s.kinds)) < 2:
            raise AssertionError(
                f"schedule {s.seed} composes < 2 distinct kinds: {s.kinds}"
            )
    if not any(s.spawn == "sigkill" for s in schedules):
        raise AssertionError("no schedule includes SIGKILL")
    if not any("grow" in s.kinds for s in schedules):
        raise AssertionError("no schedule includes grow-back")

    grow = growback_drill(tmp, quick)
    results = [run_schedule(sched, tmp, quick) for sched in schedules]

    survived = sum(1 for r in results if r["bitwise"] == 1.0)
    if survived != len(schedules):
        raise AssertionError(
            f"only {survived}/{len(schedules)} schedules survived"
        )
    return {"growback": grow, "schedules": results}


def _cli():
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", choices=("train",), required=True)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--dir", required=True)
    ap.add_argument("--total", type=int, default=16)
    ap.add_argument("--kill-at", dest="kill_at", type=int, default=0)
    ap.add_argument("--term-at", dest="term_at", type=int, default=0)
    ap.add_argument("--ckpt-every", dest="ckpt_every", type=int, default=2)
    ap.add_argument("--world", type=int, default=0)
    ap.add_argument("--hosts", type=int, default=1)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--arm-notice", dest="arm_notice", action="store_true")
    ap.add_argument("--dump", default=None)
    _train_role(ap.parse_args())


if __name__ == "__main__":
    _cli()
