"""Elastic-training rungs: the preemption drill and the checkpoint-stall
meter — on the virtual CPU mesh.

Two claims from the elastic ISSUE, each pinned the only way the 1-core CI
host allows (same philosophy as ``zero3_bench``):

* **Preemption drill** — a CHILD process trains at world=8 with async
  generation checkpoints and ``SIGKILL``s itself mid-run (rank loss, the
  hard way: no atexit, no flush — the writer thread dies wherever it
  stands). The parent asserts the child died by signal, finds the last
  DURABLE generation (a torn one scans as manifest-less and is skipped),
  resumes at world=4 via ``ElasticTrainer.restore`` and runs to the target.
  The oracle is an INDEPENDENT reference: a fresh world-8 run recomputes
  the checkpointed step from scratch, checkpoints synchronously, reshards
  to 4, and runs the same steps — loss trajectory and final master arena
  must match the resumed run BITWISE. That proves both halves at once: the
  async snapshot captured the true state, and resharding + resume replay
  the exact trajectory. Asserted before anything is printed.
* **Stall meter** — an async run (checkpoint every step) and a synchronous
  baseline (``checkpoint_now(wait=True)`` every step) over the same model,
  both booked to the ``ckpt`` ledger. The child asserts the async run's
  ``hidden_fraction`` is STRICTLY positive (exposed stall < background
  write time) and strictly above the sync baseline's, and emits the
  interval-exact ``overlap_report`` fraction from a live timeline
  (``ckpt:*`` spans classify as wire time) ungated.

Gated keys: ``ckpt_timeline_overlap_fraction`` (interval-exact, re-measured
in ``pass2`` — a program-structure fact that repeats) and
``elastic_resume_bitwise`` (1.0; a second drill would dominate runtime, so
``pass2`` re-asserts the already-verified value). The ledger's
``ckpt_stall_hidden_fraction`` is a wall-clock lower bound whose exposed
tail rides fsync variance — asserted strictly positive on BOTH passes and
strictly above the sync baseline, but not held to the ±10% gate.

Run as ``python -m beforeholiday_tpu.testing.elastic_bench`` (``--quick``
shrinks sizes) under ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``; prints one JSON line.
The ``--role train`` entry is the drill child — not for direct use.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile

import numpy as np

WORLD = 8
RESUME_WORLD = 4


def _geometry(quick: bool):
    """(dim, layers, rows) for the drill model — rows divisible by both the
    full and the surviving world so the same global batch shards either way."""
    return (32, 4, 16) if quick else (64, 8, 16)


def _stall_geometry(quick: bool):
    """Bigger arena AND a batch heavy enough that the step outlasts a
    generation write: per-generation serialize+write must be measurable
    against the step's compute, and the step must be long enough that the
    writer keeps pace (little backpressure) — that is the regime where
    hiding is possible at all."""
    return (96, 8, 256) if quick else (192, 16, 256)


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
            f"elastic_bench needs a >= {WORLD}-device CPU platform, "
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
      JSON line (``chaos_bench``'s graceful-drain drill).
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
    the ``CompletedProcess`` (callers assert on rc/stdout — ``chaos_bench``
    reuses this for its SIGTERM/SIGKILL legs)."""
    cmd = [
        sys.executable, "-m", "beforeholiday_tpu.testing.elastic_bench",
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


# --------------------------------------------------------------------- rungs
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


def _run_stall(tmp: str, tag: str, quick: bool):
    """One async-checkpoint run; returns (ckpt_summary, timeline fraction)."""
    from beforeholiday_tpu import elastic
    from beforeholiday_tpu.elastic import ElasticTrainer
    from beforeholiday_tpu.monitor import overlap
    # monitor re-exports spans.trace under the submodule's name; go through
    # the module path so we get trace.timeline, not the nvtx shim
    from beforeholiday_tpu.monitor.trace import timeline

    dim, layers, rows = _stall_geometry(quick)
    params, layout, opt, make_step = _engine(dim, layers)
    batch = _batch_fn(rows, dim)
    n_steps, drain_steps = (6, 6) if quick else (10, 8)

    elastic.reset_ckpt_ledger()
    with ElasticTrainer(
        opt, layout, make_step,
        directory=os.path.join(tmp, tag), checkpoint_every=1,
        queue_depth=3, keep=2,
    ) as tr:
        tr.init(params, world=WORLD)
        with timeline() as rec:
            for _ in range(n_steps):
                with rec.span("step"):
                    with rec.span("train"):
                        tr.run(1, batch)
            # non-checkpointing tail: the writer drains UNDER compute, so
            # close() finds an empty queue and books ~no exposed wait
            tr.checkpoint_every = 0
            for _ in range(drain_steps):
                with rec.span("step"):
                    with rec.span("train"):
                        tr.run(1, batch)
        events = rec.events()
    summary = elastic.ckpt_summary()
    rep = overlap.overlap_report(events)
    return summary, rep["overlap_fraction"]


def _run_stall_sync(tmp: str, quick: bool):
    """Synchronous baseline: submit + wait every step — everything exposed."""
    from beforeholiday_tpu import elastic
    from beforeholiday_tpu.elastic import ElasticTrainer

    dim, layers, rows = _stall_geometry(quick)
    params, layout, opt, make_step = _engine(dim, layers)
    batch = _batch_fn(rows, dim)
    n_steps = 6 if quick else 10

    elastic.reset_ckpt_ledger()
    with ElasticTrainer(
        opt, layout, make_step,
        directory=os.path.join(tmp, "sync"), checkpoint_every=0,
    ) as tr:
        tr.init(params, world=WORLD)
        for _ in range(n_steps):
            tr.run(1, batch)
            tr.checkpoint_now(wait=True)
    return elastic.ckpt_summary()


def main(quick: bool = False):
    _require_mesh()

    with tempfile.TemporaryDirectory(prefix="elastic_bench_") as tmp:
        drill = _run_drill(tmp, quick)

        async_summ, timeline_frac = _run_stall(tmp, "stall", quick)
        sync_summ = _run_stall_sync(tmp, quick)
        hf = async_summ["hidden_fraction"]
        sync_hf = sync_summ["hidden_fraction"] or 0.0
        if hf is None or not hf > 0.0:
            raise AssertionError(
                f"async checkpointing hid nothing: hidden_fraction={hf!r} "
                f"(exposed {async_summ['exposed_s']:.4f}s vs background "
                f"{async_summ['background_s']:.4f}s)"
            )
        if not async_summ["exposed_s"] < async_summ["background_s"]:
            raise AssertionError(
                "async run exposed more stall than the writer worked — "
                "the overlap machinery is lying"
            )
        if not hf > sync_hf:
            raise AssertionError(
                f"async hidden_fraction {hf:.4f} is not above the "
                f"synchronous baseline {sync_hf:.4f}"
            )

        # pass 2: re-measure the stall meter on a fresh run; the drill's
        # bitwise oracle was already asserted above (a second SIGKILL drill
        # would dominate runtime for no extra information). The GATED key is
        # the interval-exact timeline fraction — ckpt span time under
        # concurrent compute spans, a program-structure fact that repeats;
        # the ledger's hidden_fraction is a wall-clock lower bound whose
        # exposed tail rides fsync variance, so it is asserted (> 0, above
        # sync) but not gated.
        async2, timeline_frac2 = _run_stall(tmp, "stall2", quick)
        hf2 = async2["hidden_fraction"]
        if hf2 is None or not hf2 > 0.0:
            raise AssertionError(
                f"pass-2 async run hid nothing: hidden_fraction={hf2!r}"
            )

    out = {
        "elastic_resume_bitwise": 1.0,
        "killed_rc": drill["killed_rc"],
        "resumed_from_step": drill["resumed_from_step"],
        "drill_steps_replayed": drill["drill_steps_replayed"],
        "resumed_world": RESUME_WORLD,
        "ckpt_stall_hidden_fraction": round(hf, 4),
        "ckpt_sync_hidden_fraction": round(sync_hf, 4),
        "ckpt_exposed_s": round(async_summ["exposed_s"], 6),
        "ckpt_background_s": round(async_summ["background_s"], 6),
        "ckpt_generations": async_summ["generations"],
        "ckpt_timeline_overlap_fraction": (
            round(timeline_frac, 4) if timeline_frac is not None else None
        ),
        "ckpt_pass2_hidden_fraction": (
            round(hf2, 4) if hf2 is not None else None
        ),
        "pass2": {
            "ckpt_timeline_overlap_fraction": (
                round(timeline_frac2, 4)
                if timeline_frac2 is not None else None
            ),
            "elastic_resume_bitwise": 1.0,
        },
        "config": (
            f"world={WORLD} resume_world={RESUME_WORLD} "
            f"drill_geom={_geometry(quick)} stall_geom={_stall_geometry(quick)}"
        ),
    }
    print(json.dumps(out))
    return out


def _cli():
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", choices=("bench", "train"), default="bench")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--dir", default=None)
    ap.add_argument("--total", type=int, default=16)
    ap.add_argument("--kill-at", dest="kill_at", type=int, default=0)
    ap.add_argument("--term-at", dest="term_at", type=int, default=0)
    ap.add_argument("--ckpt-every", dest="ckpt_every", type=int, default=2)
    ap.add_argument("--world", type=int, default=0)
    ap.add_argument("--hosts", type=int, default=1)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--arm-notice", dest="arm_notice", action="store_true")
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()
    if args.role == "train":
        if args.dir is None:
            ap.error("--role train needs --dir")
        _train_role(args)
    else:
        main(quick=args.quick)


if __name__ == "__main__":
    _cli()
