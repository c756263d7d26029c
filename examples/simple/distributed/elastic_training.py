"""Elastic ZeRO-3 training — survive a preemption mid-run, resume smaller.

``zero3_fully_sharded.py`` ends with the save-at-8/restore-at-4 round trip;
this script wires that mechanism into a LIVE loop via
``beforeholiday_tpu.elastic``:

* an async ``CheckpointManager`` snapshots the shard triplet every
  ``--checkpoint-every`` committed steps — the device→host copy is initiated
  non-blocking behind the step, serialization and the atomic (temp file +
  fsync + rename, manifest stamped last) writes happen on a background
  thread, and every stall the training thread DOES eat is booked to the
  ``ckpt`` ledger;
* at ``--preempt-at-step`` a ``SimulatedPreemption`` fires (the in-process
  stand-in for a preemption notice / lost rank) naming
  ``--resume-world`` survivors: the trainer drains in-flight generations,
  reloads the last DURABLE one, reshards the arena bitwise onto a freshly
  carved survivor mesh, rolls ``global_step`` back, and replays forward;
* the script then proves the headline guarantee: an INDEPENDENT
  uninterrupted run, resharded from the same generation, matches the
  survived run loss-by-loss and arena-bitwise.

Run (any machine — 8 virtual CPU devices stand in for a TPU slice):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python elastic_training.py --preempt-at-step 8 --resume-world 4
"""

import argparse
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from beforeholiday_tpu.elastic import (
    ElasticTrainer,
    ckpt_summary,
    reset_ckpt_ledger,
    zero3_state_specs,
)
from beforeholiday_tpu.optimizers import ZeRO3FusedAdam, zero3
from beforeholiday_tpu.testing.faults import preempt_after

import functools

_shard_map = functools.partial(jax.shard_map, check_vma=False)

D, LAYERS, ROWS = 64, 4, 16  # width, depth, global batch rows


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--steps", type=int, default=12,
                   help="committed steps to train (replays after the resize "
                        "count toward the same target)")
    p.add_argument("--preempt-at-step", type=int, default=8,
                   help="step attempt on which the simulated preemption "
                        "notice fires (0 = never)")
    p.add_argument("--resume-world", type=int, default=4,
                   help="world size that survives the preemption")
    p.add_argument("--checkpoint-every", type=int, default=2,
                   help="submit an async generation every N committed steps")
    p.add_argument("--grow-back-at-step", type=int, default=0,
                   help="committed step at which the capacity probe reports "
                        "the full slice available again; the trainer grows "
                        "back at the next checkpoint boundary (0 = never)")
    return p.parse_args(argv)


def build_engine():
    """(params, layout, opt, make_step) — the pieces ElasticTrainer wants.

    ``make_step(mesh, world)`` returns ``step(state, gstate, batch) ->
    (state, gstate, row)``; the trainer rebuilds it on every resize, so the
    same factory serves the full and the survivor world."""
    rng = np.random.RandomState(0)
    params = {
        f"w{i}": jnp.asarray(
            rng.randn(D, D) / np.sqrt(D), jnp.float32)
        for i in range(LAYERS)
    }
    layout = zero3.layout_of(params)
    opt = ZeRO3FusedAdam(
        lr=1e-2, weight_decay=0.01, impl="jnp",
        prefetch=1, param_residency="keep",
    )
    specs = zero3_state_specs()

    def make_step(mesh, world):
        def body(state, xb):
            def loss_fn(master_shard):
                p = opt.gather_params(master_shard, layout)
                h = xb
                for i in range(LAYERS):
                    h = jnp.tanh(h @ p[f"w{i}"])
                return jnp.sum(h)

            loss, g = jax.value_and_grad(loss_fn)(state["master"])
            state = opt.step(g, state)
            return state, jax.lax.psum(loss, "data")

        inner = jax.jit(_shard_map(
            body, mesh=mesh, in_specs=(specs, P("data")), out_specs=(specs, P()),
        ))

        def step(state, gstate, batch):
            state, loss = inner(state, batch)
            return state, gstate, {"loss": loss}

        return step

    return params, layout, opt, make_step


def batch_fn(step: int):
    """Global batch keyed on the step — a replay after reload sees identical
    data, which is what keeps the continued trajectory bitwise."""
    rng = np.random.RandomState(10_000 + int(step))
    return jnp.asarray(rng.randn(ROWS, D).astype(np.float32))


def main(argv=None):
    args = parse_args(argv)
    world = len(jax.devices())
    params, layout, opt, make_step = build_engine()
    reset_ckpt_ledger()

    preemption = (
        preempt_after(args.preempt_at_step,
                      surviving_world=args.resume_world)
        if args.preempt_at_step else None
    )

    # the capacity probe models the slice scheduler: after the preemption
    # only --resume-world devices exist, until --grow-back-at-step when the
    # full slice returns; the trainer reclaims it at a checkpoint boundary
    box = {}
    def capacity_probe():
        tr = box.get("tr")
        if tr is not None and tr.global_step >= args.grow_back_at_step:
            return world
        return args.resume_world

    with tempfile.TemporaryDirectory() as root:
        with ElasticTrainer(
            opt, layout, make_step, directory=f"{root}/live",
            checkpoint_every=args.checkpoint_every,
            grow_when_available=bool(args.grow_back_at_step),
            capacity_probe=(
                capacity_probe if args.grow_back_at_step else None
            ),
        ) as tr:
            box["tr"] = tr
            tr.init(params, world=world)
            tr.run(args.steps, batch_fn, preemption=preemption)
            for ev in tr.events:
                print(f"resize ({ev.reason}) at step {ev.at_step}: "
                      f"world {ev.old_world} -> {ev.new_world}, resumed "
                      f"from generation {ev.resumed_from}")
            for row in tr.history:
                print(f"  step {row['step']:3d}  world {row['world']}  "
                      f"loss {row['loss']:+.6f}")
            survived = np.asarray(tr.state["master"])
            # collapse the resize events into the FINAL trajectory's
            # lineage: each event rolls back to resumed_from and replays,
            # erasing any earlier segment that started at or past it
            lineage = [(0, world)]
            for ev in tr.events:
                if ev.reason == "preemption_drain":
                    continue
                r = ev.resumed_from
                lineage = (
                    [e for e in lineage if e[0] < r] + [(r, ev.new_world)]
                )
            final_rows = {}
            for r in tr.history:      # last occurrence wins (replays)
                final_rows[r["step"]] = r
            tail = [
                final_rows[s]
                for s in range(lineage[-1][0] + 1, args.steps + 1)
            ]
            final_world = tr.world

        summary = ckpt_summary()
        hf = summary["hidden_fraction"]
        print(f"ckpt ledger: {summary['generations']} generation(s), "
              f"exposed {summary['exposed_s'] * 1e3:.1f} ms, background "
              f"{summary['background_s'] * 1e3:.1f} ms"
              + (f", hidden fraction {hf:.2f}" if hf is not None else ""))

        if len(lineage) == 1:
            return

        # the guarantee, demonstrated: a fault-free reference replaying the
        # same lineage (run to each boundary, checkpoint synchronously,
        # reshard to the segment's world) matches the survived run
        with ElasticTrainer(
            opt, layout, make_step, directory=f"{root}/ref",
            checkpoint_every=0,
        ) as ref:
            ref.init(params, world=lineage[0][1])
            for start, w in lineage[1:]:
                if start > ref.global_step:
                    ref.run(start - ref.global_step, batch_fn)
                ref.checkpoint_now(wait=True)
                ref.restore(world=w)
            ref_rows = ref.run(args.steps - ref.global_step, batch_fn)
            assert [r["loss"] for r in tail] == [
                r["loss"] for r in ref_rows
            ], "survived trajectory diverged from the uninterrupted reference"
            assert np.array_equal(
                survived, np.asarray(ref.state["master"])
            ), "survived master arena diverged"
        print(f"verified: the survived run (final world {final_world}) is "
              "bitwise identical to a fault-free replay of the same lineage")


if __name__ == "__main__":
    main()
