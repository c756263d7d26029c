"""Elastic-training tests: crash-safe shard saves, fault injectors, the
async CheckpointManager, preemption-dump wiring, and the ElasticTrainer's
live-resharding drills (preemption + tripwire) — all on the 8-device
virtual CPU mesh from conftest.

The bitwise oracle used throughout: a run resumed from a durable generation
at a smaller world must reproduce, loss by loss and arena by arena, an
independent uninterrupted run resharded from the same generation — that
pins both the snapshot (captured the true state) and the reshard (bitwise
re-slice) at once.
"""

from __future__ import annotations

import functools
import json
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from beforeholiday_tpu.amp.scaler import LossScaler
from beforeholiday_tpu.elastic import (
    CheckpointManager,
    ElasticTrainer,
    ckpt_summary,
    guard_state_specs,
    latest_generation,
    list_generations,
    reset_ckpt_ledger,
    zero3_state_specs,
)
from beforeholiday_tpu.elastic import checkpoint as ckpt_mod
from beforeholiday_tpu.guard.step import (
    SKIP_GRAD_OVERFLOW,
    SKIP_ROLLBACK,
    StepGuard,
)
from beforeholiday_tpu.optimizers import ZeRO3FusedAdam, zero3
from beforeholiday_tpu.ops.quantized import amax_of_tree
from beforeholiday_tpu.parallel import (
    carve_data_mesh,
    check_replicated_consistency,
)
from beforeholiday_tpu.testing import drills
from beforeholiday_tpu.testing import faults

pytestmark = pytest.mark.elastic

_shmap = functools.partial(jax.shard_map, check_vma=False)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _subprocess_env():
    """Env for drill children (same pattern as the perf-attr crash tests):
    CPU backend, repo importable."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO_ROOT
    return env


def _tiny_manifest(world: int = 2):
    """(manifest, shard-builder) for a host-only 8x8 single-param layout."""
    params = {"w": np.zeros((8, 8), np.float32)}
    layout = zero3.layout_of(params)
    manifest = zero3.shard_manifest(layout, world)

    def shards(tag: float):
        sl = manifest["shard_len"]
        return [
            {
                **{
                    k: np.full((sl,), tag * 10 + r, np.float32)
                    for k in manifest["state_keys"]
                },
                "step": np.int64(tag),
            }
            for r in range(world)
        ]

    return manifest, shards


# ---------------------------------------------------------------------------
# satellite 1: crash-safe save_shard_files
# ---------------------------------------------------------------------------


class TestAtomicSave:
    def test_manifest_lands_last_and_only_via_rename(self, tmp_path,
                                                     monkeypatch):
        """Every file lands through the atomic-rename seam, destinations are
        final paths (never ``*.tmp``), and the manifest is stamped LAST —
        the invariant that makes manifest presence mean durability."""
        manifest, shards = _tiny_manifest(world=2)
        landed = []
        real = zero3._rename

        def recording(src, dst):
            landed.append(dst)
            real(src, dst)

        monkeypatch.setattr(zero3, "_rename", recording)
        zero3.save_shard_files(str(tmp_path / "gen"), shards(1), manifest)
        assert len(landed) == 3  # 2 shards + manifest
        assert landed[-1].endswith(zero3._MANIFEST_NAME)
        assert not any(d.endswith(".tmp") for d in landed)
        back_manifest, back = zero3.load_shard_files(str(tmp_path / "gen"))
        assert back_manifest["world"] == 2
        np.testing.assert_array_equal(back[1]["master"], shards(1)[1]["master"])

    def test_torn_save_previous_generation_loads(self, tmp_path,
                                                 monkeypatch):
        """A writer dying mid-save (rename seam raises after the first shard
        lands) leaves a manifest-less generation: the scan marks it
        non-durable, ``latest_generation`` falls back to the previous
        generation, and that one loads bitwise."""
        manifest, shards = _tiny_manifest(world=2)
        d = str(tmp_path)
        zero3.save_shard_files(
            ckpt_mod.generation_dir(d, 2), shards(2), dict(manifest, step=2)
        )

        calls = {"n": 0}
        real = zero3._rename

        def dying(src, dst):
            calls["n"] += 1
            if calls["n"] > 1:
                raise RuntimeError("simulated writer death mid-save")
            real(src, dst)

        monkeypatch.setattr(zero3, "_rename", dying)
        with pytest.raises(RuntimeError, match="writer death"):
            zero3.save_shard_files(
                ckpt_mod.generation_dir(d, 4), shards(4),
                dict(manifest, step=4),
            )
        monkeypatch.setattr(zero3, "_rename", real)

        torn = ckpt_mod.generation_dir(d, 4)
        assert not os.path.exists(os.path.join(torn, zero3._MANIFEST_NAME))
        gens = {s: durable for s, _, durable in list_generations(d)}
        assert gens == {2: True, 4: False}
        latest = latest_generation(d)
        assert latest is not None and latest[0] == 2
        back_manifest, back = zero3.load_shard_files(latest[1])
        assert back_manifest["step"] == 2
        np.testing.assert_array_equal(back[0]["master"], shards(2)[0]["master"])
        with pytest.raises(FileNotFoundError):
            zero3.load_shard_files(torn)

    def test_sigkill_writer_mid_save_subprocess(self, tmp_path):
        """The real thing: a child process is SIGKILLed between file
        landings of generation 4 (no cleanup, no atexit). The parent must
        still find generation 2 durable and loadable."""
        d = str(tmp_path)
        script = f"""
import os, signal
import numpy as np
from beforeholiday_tpu.optimizers import zero3
from beforeholiday_tpu.elastic import checkpoint as ckpt

d = {d!r}
params = {{"w": np.zeros((8, 8), np.float32)}}
layout = zero3.layout_of(params)
manifest = zero3.shard_manifest(layout, 2)
sl = manifest["shard_len"]

def shards(tag):
    return [
        {{**{{k: np.full((sl,), tag * 10 + r, np.float32)
             for k in manifest["state_keys"]}},
          "step": np.int64(tag)}}
        for r in range(2)
    ]

zero3.save_shard_files(
    ckpt.generation_dir(d, 2), shards(2), dict(manifest, step=2))
real = zero3._rename
calls = {{"n": 0}}

def killing(src, dst):
    calls["n"] += 1
    if calls["n"] > 1:
        os.kill(os.getpid(), signal.SIGKILL)
    real(src, dst)

zero3._rename = killing
zero3.save_shard_files(
    ckpt.generation_dir(d, 4), shards(4), dict(manifest, step=4))
"""
        proc = subprocess.run(
            [sys.executable, "-c", script],
            env=_subprocess_env(), capture_output=True, text=True,
            timeout=300,
        )
        assert proc.returncode == -signal.SIGKILL, proc.stderr
        latest = latest_generation(d)
        assert latest is not None and latest[0] == 2
        manifest, back = zero3.load_shard_files(latest[1])
        assert manifest["step"] == 2
        np.testing.assert_array_equal(
            back[1]["master"], np.full((manifest["shard_len"],), 21.0)
        )
        torn = ckpt_mod.generation_dir(d, 4)
        assert not os.path.exists(os.path.join(torn, zero3._MANIFEST_NAME))


# ---------------------------------------------------------------------------
# satellite 2: fault injectors
# ---------------------------------------------------------------------------


class TestFaultInjectors:
    def test_preempt_after_fires_exactly_once(self):
        tick = faults.preempt_after(3, surviving_world=4)
        tick()
        tick()
        with pytest.raises(faults.SimulatedPreemption) as ei:
            tick()
        assert ei.value.surviving_world == 4
        # the n-th call raised ONCE; a trainer that survived keeps ticking
        for _ in range(5):
            tick()

    def test_preempt_after_defers_world_to_policy(self):
        tick = faults.preempt_after(1)
        with pytest.raises(faults.SimulatedPreemption) as ei:
            tick()
        assert ei.value.surviving_world is None

    def test_preempt_after_validates(self):
        with pytest.raises(ValueError, match="n_steps"):
            faults.preempt_after(0)

    @pytest.mark.parametrize("sig", [signal.SIGKILL, signal.SIGTERM])
    def test_kill_rank_reaps_signal_death(self, sig):
        proc = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(120)"]
        )
        rc = faults.kill_rank(proc, sig=sig)
        assert rc == -sig


# ---------------------------------------------------------------------------
# satellite 3: flight-recorder preemption dump
# ---------------------------------------------------------------------------


class TestFlightPreemptionDump:
    def test_sigterm_dumps_ring_and_last_checkpoint(self, tmp_path):
        """An armed recorder SIGTERM'd from outside (well — by itself, which
        delivers the same way) dumps the black box with the preemption
        reason and the last durable generation id, then re-delivers the
        signal: the process still dies a signal death."""
        dump = str(tmp_path / "preempt.json")
        script = f"""
import os, signal
from beforeholiday_tpu.monitor.flight import FlightRecorder

rec = FlightRecorder(capacity=8, path={dump!r})
rec.note_checkpoint(6, "/ckpt/gen_00000006")
rec.arm_preemption_dump()
os.kill(os.getpid(), signal.SIGTERM)
raise SystemExit("unreachable: SIGTERM must have killed us")
"""
        proc = subprocess.run(
            [sys.executable, "-c", script],
            env=_subprocess_env(), capture_output=True, text=True,
            timeout=300,
        )
        assert proc.returncode == -signal.SIGTERM, proc.stderr
        with open(dump) as f:
            payload = json.load(f)
        assert payload["reason"] == "preemption:SIGTERM"
        assert payload["last_checkpoint"]["generation"] == 6
        assert payload["last_checkpoint"]["path"] == "/ckpt/gen_00000006"

    def test_arm_disarm_restores_disposition(self):
        from beforeholiday_tpu.monitor.flight import FlightRecorder

        prev = signal.getsignal(signal.SIGUSR1)
        rec = FlightRecorder(capacity=2, path="unused.json")
        rec.arm_preemption_dump(signal.SIGUSR1)
        try:
            assert signal.getsignal(signal.SIGUSR1) is not prev
            rec.arm_preemption_dump(signal.SIGUSR1)  # idempotent
        finally:
            rec.disarm_preemption_dump()
        assert signal.getsignal(signal.SIGUSR1) is prev
        rec.disarm_preemption_dump()  # no-op when not armed


# ---------------------------------------------------------------------------
# tentpole: CheckpointManager (host-level, no mesh needed)
# ---------------------------------------------------------------------------


def _arena_state(manifest, *, seed: int = 0, step: int = 7):
    n = manifest["world"] * manifest["shard_len"]
    rng = np.random.RandomState(seed)
    state = {
        k: rng.randn(n).astype(np.float32) for k in manifest["state_keys"]
    }
    state["step"] = np.int64(step)
    return state


class TestCheckpointManager:
    def test_submit_wait_roundtrip_and_ledger(self, tmp_path):
        reset_ckpt_ledger()
        manifest, _ = _tiny_manifest(world=2)
        state = _arena_state(manifest, step=7)
        extra = {"guard": {"scale": 256.0, "health": {"skipped_total": 1}}}
        with CheckpointManager(str(tmp_path), manifest) as mgr:
            gen = mgr.submit(3, state, extra=extra)
            mgr.wait()
            assert mgr.last_durable == (3, gen)
        back_manifest, shards = zero3.load_shard_files(gen)
        assert back_manifest["step"] == 3
        assert back_manifest["extra"] == extra
        full = np.concatenate([s["master"] for s in shards])
        np.testing.assert_array_equal(full, state["master"])
        assert all(int(s["step"]) == 7 for s in shards)

        summary = ckpt_summary()
        assert summary["generations"] == 1
        assert summary["bytes"] > 0
        booked = {r["phase"]: r["side"] for r in summary["phases"]}
        assert booked["submit"] == "exposed"
        assert booked["wait"] == "exposed"
        assert booked["serialize"] == "background"
        assert booked["write"] == "background"

    def test_array_extra_is_jsonized(self, tmp_path):
        """The guard state_dict carries the fp8 amax history as an ndarray;
        the manifest is JSON — submit must not choke on it."""
        manifest, _ = _tiny_manifest(world=2)
        hist = np.arange(8, dtype=np.float32).reshape(2, 4)
        with CheckpointManager(str(tmp_path), manifest) as mgr:
            gen = mgr.submit(
                1, _arena_state(manifest),
                extra={"guard": {"amax_history": hist}},
            )
            mgr.wait()
        back, _ = zero3.load_shard_files(gen)
        np.testing.assert_array_equal(
            np.asarray(back["extra"]["guard"]["amax_history"]), hist
        )

    def test_prune_keeps_last_k_durable(self, tmp_path):
        manifest, _ = _tiny_manifest(world=2)
        with CheckpointManager(str(tmp_path), manifest, keep=2) as mgr:
            for step in (1, 2, 3, 4):
                mgr.submit(step, _arena_state(manifest))
                mgr.wait()
        gens = list_generations(str(tmp_path))
        assert [(s, d) for s, _, d in gens] == [(3, True), (4, True)]

    def test_latest_generation_skips_torn(self, tmp_path):
        manifest, _ = _tiny_manifest(world=2)
        with CheckpointManager(str(tmp_path), manifest) as mgr:
            mgr.submit(5, _arena_state(manifest))
            mgr.wait()
        torn = ckpt_mod.generation_dir(str(tmp_path), 9)
        os.makedirs(torn)
        with open(os.path.join(torn, "shard_00000.npz"), "wb") as f:
            f.write(b"torn")
        latest = latest_generation(str(tmp_path))
        assert latest is not None and latest[0] == 5

    def test_writer_error_surfaces_on_wait(self, tmp_path):
        manifest, _ = _tiny_manifest(world=2)
        bad = _arena_state(manifest)
        bad["master"] = np.zeros(
            (manifest["world"] * manifest["shard_len"] + 3,), np.float32
        )
        mgr = CheckpointManager(str(tmp_path), manifest)
        mgr.submit(1, bad)
        with pytest.raises(RuntimeError, match="writer thread failed"):
            mgr.wait()
        mgr.close()  # error was surfaced and cleared; close is clean

    def test_validation(self, tmp_path):
        manifest, _ = _tiny_manifest(world=2)
        with pytest.raises(ValueError, match="queue_depth"):
            CheckpointManager(str(tmp_path), manifest, queue_depth=0)
        with pytest.raises(ValueError, match="keep"):
            CheckpointManager(str(tmp_path), manifest, keep=0)
        with pytest.raises(ValueError, match="manifest format"):
            CheckpointManager(str(tmp_path), {"format": "bogus"})

    def test_close_idempotent_and_rejects_submit(self, tmp_path):
        manifest, _ = _tiny_manifest(world=2)
        mgr = CheckpointManager(str(tmp_path), manifest)
        mgr.close()
        mgr.close()
        with pytest.raises(RuntimeError, match="closed"):
            mgr.submit(1, _arena_state(manifest))


# ---------------------------------------------------------------------------
# mesh carving + tripwire primitive
# ---------------------------------------------------------------------------


class TestCarveAndConsistency:
    def test_carve_data_mesh(self, devices8):
        mesh = carve_data_mesh(3, devices=devices8)
        assert mesh.shape == {"data": 3}
        assert list(mesh.devices.ravel()) == list(devices8[:3])
        with pytest.raises(ValueError, match="world must be in"):
            carve_data_mesh(0, devices=devices8)
        with pytest.raises(ValueError, match="world must be in"):
            carve_data_mesh(9, devices=devices8)

    @pytest.mark.parametrize("perturb_rank", [None, 2])
    def test_check_replicated_consistency(self, devices8, perturb_rank):
        mesh = carve_data_mesh(8, devices=devices8)

        def f(x):
            tree = {"g": x, "h": x * 2.0}
            if perturb_rank is not None:
                tree = faults.perturb_rank_grads(
                    tree, "data", rank=perturb_rank, eps=1e-3
                )
            return check_replicated_consistency(tree, "data")

        fn = jax.jit(_shmap(f, mesh=mesh, in_specs=(P(),), out_specs=P()))
        mismatch = np.asarray(fn(jnp.arange(4, dtype=jnp.float32)))
        assert bool(mismatch) == (perturb_rank is not None)


# ---------------------------------------------------------------------------
# guard: sharded update semantics (unit, world=1 mesh)
# ---------------------------------------------------------------------------


def _sharded_fixture(devices, world, guard, *, dim=16, layers=2):
    """(mesh, opt, layout, state, gstate, grads_of) on a world-sized mesh."""
    mesh = carve_data_mesh(world, devices=devices)
    params = drills._params(dim, layers)
    layout = zero3.layout_of(params)
    opt = ZeRO3FusedAdam(lr=1e-2, impl="jnp", param_residency="keep")
    specs = zero3_state_specs()
    init_fn = jax.jit(_shmap(
        lambda p: opt.init(p), mesh=mesh, in_specs=(P(),), out_specs=specs,
    ))
    state = init_fn(params)
    gstate = guard.init(state) if guard is not None else None
    return mesh, opt, layout, state, gstate


class TestApplyShardedUpdate:
    def _step_fn(self, mesh, opt, guard, *, poison):
        specs = zero3_state_specs()
        gspecs = guard_state_specs(guard)

        def body(state, gstate):
            g = jax.tree_util.tree_map(
                lambda a: jnp.ones_like(a) * 1e-3, state["master"]
            )
            if poison:
                g = jax.tree_util.tree_map(
                    lambda a: jnp.full_like(a, jnp.nan), g
                )
            loss = jnp.float32(1.0)
            verdict = guard.check_grads(loss, g)
            plain = opt.step(g, state)
            guarded, new_gstate = guard.apply_sharded_update(
                opt, state, g, gstate, verdict
            )
            return plain, guarded, new_gstate

        return jax.jit(_shmap(
            body, mesh=mesh, in_specs=(specs, gspecs),
            out_specs=(specs, specs, gspecs),
        ))

    def test_clean_step_matches_bare_opt(self, devices8):
        guard = StepGuard(LossScaler(init_scale=4.0), check_params=True)
        mesh, opt, _, state, gstate = _sharded_fixture(devices8, 1, guard)
        plain, guarded, new_gstate = self._step_fn(
            mesh, opt, guard, poison=False
        )(state, gstate)
        for k in ("master", "exp_avg", "exp_avg_sq", "step"):
            np.testing.assert_array_equal(
                np.asarray(plain[k]), np.asarray(guarded[k])
            )
        assert float(np.asarray(new_gstate["scaler"]["scale"])) == 4.0
        assert int(np.asarray(
            new_gstate["health"]["consecutive_overflows"]
        )) == 0
        # the step actually moved
        assert not np.array_equal(
            np.asarray(guarded["master"]), np.asarray(state["master"])
        )

    def test_poisoned_step_holds_triplet_and_halves_scale(self, devices8):
        guard = StepGuard(LossScaler(init_scale=4.0), check_params=True)
        mesh, opt, _, state, gstate = _sharded_fixture(devices8, 1, guard)
        _, guarded, new_gstate = self._step_fn(
            mesh, opt, guard, poison=True
        )(state, gstate)
        for k in ("master", "exp_avg", "exp_avg_sq", "step"):
            np.testing.assert_array_equal(
                np.asarray(guarded[k]), np.asarray(state[k])
            )
        assert float(np.asarray(new_gstate["scaler"]["scale"])) == 2.0
        health = {
            k: int(np.asarray(v)) for k, v in new_gstate["health"].items()
        }
        assert health["consecutive_overflows"] == 1
        assert health["skipped_total"] == 1
        assert health["last_skip_reason"] == SKIP_GRAD_OVERFLOW

    def test_rollback_restores_snapshot_at_min_scale(self, devices8):
        guard = StepGuard(
            LossScaler(init_scale=2.0, min_loss_scale=2.0),
            rollback_after=2, check_params=True,
        )
        mesh, opt, _, state, gstate = _sharded_fixture(devices8, 1, guard)
        step = self._step_fn(mesh, opt, guard, poison=True)
        _, state1, gstate1 = step(state, gstate)
        _, state2, gstate2 = step(state1, gstate1)
        health = {
            k: int(np.asarray(v)) for k, v in gstate2["health"].items()
        }
        assert health["rollbacks_total"] == 1
        assert health["consecutive_overflows"] == 0
        assert health["last_skip_reason"] == SKIP_ROLLBACK
        np.testing.assert_array_equal(
            np.asarray(state2["master"]),
            np.asarray(gstate["snapshot"]["master"]),
        )


# ---------------------------------------------------------------------------
# tentpole: ElasticTrainer drills (in-process)
# ---------------------------------------------------------------------------


class TestElasticTrainerDrills:
    DIM, LAYERS, ROWS = 32, 2, 8

    def _pieces(self):
        return drills._engine(self.DIM, self.LAYERS)

    def test_preemption_resize_is_bitwise(self, tmp_path):
        """In-process preemption drill: a SimulatedPreemption on the 8th
        tick resizes 8 -> 4 from the last durable generation; the continued
        run is bitwise identical to an independent reference that trained
        to the same generation, checkpointed synchronously, and resharded
        to 4."""
        params, layout, opt, make_step = self._pieces()
        batch = drills._batch_fn(self.ROWS, self.DIM)

        d1 = str(tmp_path / "drill")
        with ElasticTrainer(
            opt, layout, make_step, directory=d1, checkpoint_every=2,
        ) as tr:
            tr.init(params, world=8)
            tr.run(10, batch, preemption=faults.preempt_after(
                8, surviving_world=4
            ))
            assert tr.global_step == 10
            assert tr.world == 4
            assert len(tr.events) == 1
            ev = tr.events[0]
            assert ev.reason == "preemption"
            assert (ev.old_world, ev.new_world) == (8, 4)
            assert ev.at_step == 7          # 7 steps committed before tick 8
            assert ev.resumed_from == 6     # gens 2,4,6 submitted + drained
            drill_tail = [
                r for r in tr.history if r["world"] == 4
            ]
            drill_master = np.asarray(tr.state["master"])

        # independent reference: recompute generation 6 from scratch at
        # world 8, checkpoint synchronously, reshard to 4, run the tail
        d2 = str(tmp_path / "ref")
        with ElasticTrainer(
            opt, layout, make_step, directory=d2, checkpoint_every=0,
        ) as ref:
            ref.init(params, world=8)
            ref.run(6, batch)
            ref.checkpoint_now(wait=True)
        with ElasticTrainer(
            opt, layout, make_step, directory=d2, checkpoint_every=0,
        ) as ref4:
            assert ref4.restore(world=4) == 6
            ref_tail = ref4.run(4, batch)
            ref_master = np.asarray(ref4.state["master"])

        assert [r["step"] for r in drill_tail] == [7, 8, 9, 10]
        assert [r["loss"] for r in drill_tail] == [
            r["loss"] for r in ref_tail
        ]
        np.testing.assert_array_equal(drill_master, ref_master)

    def test_tripwire_resize_discards_poisoned_step(self, tmp_path):
        """A replicated-by-construction row value corrupted on ONE rank
        (post-collective, keyed on a host call counter so a reload does not
        re-fire) trips ``check_replicated_consistency``: the step's output
        is discarded — never committed, never checkpointed — and the
        trainer reshards to the survivor policy's world."""
        params, layout, opt, _ = self._pieces()
        specs = zero3_state_specs()
        calls = {"n": 0}
        TRIP_AT = 4  # 4th step attempt overall (global_step 3 at world 8)

        def make_step(mesh, world):
            def body(state, x, trip):
                def loss_fn(master):
                    p = opt.gather_params(master, layout)
                    y = x
                    for k in sorted(p):
                        y = jnp.tanh(y @ p[k])
                    return jnp.sum(y)

                local_loss, g = jax.value_and_grad(loss_fn)(state["master"])
                new_state = opt.step(g, state)
                loss = jax.lax.psum(local_loss, "data")
                # corrupt the replicated loss on rank 0 only when tripped
                rank = jax.lax.axis_index("data")
                seen = jnp.where(
                    (trip > 0) & (rank == 0), loss + 1.0, loss
                )
                mism = check_replicated_consistency(
                    {"loss": seen}, "data", site="elastic.tripwire"
                )
                return new_state, {"loss": loss, "mismatch": mism}

            inner = jax.jit(_shmap(
                body, mesh=mesh, in_specs=(specs, P("data"), P()),
                out_specs=(specs, P()),
            ))

            def step(state, gstate, batch_):
                calls["n"] += 1
                trip = jnp.float32(1.0 if calls["n"] == TRIP_AT else 0.0)
                new_state, row = inner(state, batch_, trip)
                return new_state, gstate, row

            return step

        batch = drills._batch_fn(self.ROWS, self.DIM)
        with ElasticTrainer(
            opt, layout, make_step, directory=str(tmp_path),
            checkpoint_every=2,
        ) as tr:
            tr.init(params, world=8)
            rows = tr.run(6, batch)
            assert tr.global_step == 6
            assert tr.world == 4
            assert len(tr.events) == 1
            ev = tr.events[0]
            assert ev.reason == "tripwire"
            assert (ev.old_world, ev.new_world) == (8, 4)
            assert ev.at_step == 3
            assert ev.resumed_from == 2
            # the poisoned attempt (would-be step 4 at world 8) was
            # discarded: step 4 only ever committed at the survivor world
            worlds_at_4 = {r["world"] for r in rows if r["step"] == 4}
            assert worlds_at_4 == {4}

    def test_resize_below_min_world_refuses(self, tmp_path):
        params, layout, opt, make_step = self._pieces()
        batch = drills._batch_fn(self.ROWS, self.DIM)
        with ElasticTrainer(
            opt, layout, make_step, directory=str(tmp_path),
            checkpoint_every=1, min_world=4,
        ) as tr:
            tr.init(params, world=8)
            with pytest.raises(RuntimeError, match="below min_world"):
                tr.run(4, batch, preemption=faults.preempt_after(
                    3, surviving_world=2
                ))

    def test_run_before_init_refuses(self, tmp_path):
        params, layout, opt, make_step = self._pieces()
        with ElasticTrainer(
            opt, layout, make_step, directory=str(tmp_path),
        ) as tr:
            with pytest.raises(RuntimeError, match="init\\(\\) or restore"):
                tr.run(1, drills._batch_fn(self.ROWS, self.DIM))


# ---------------------------------------------------------------------------
# satellite 4: resharding with in-flight guard/scaler state
# ---------------------------------------------------------------------------


class TestGuardStateAcrossReshard:
    DIM, LAYERS, ROWS = 32, 2, 8

    def _guard_engine(self, guard):
        """Engine whose grads are NaN-poisoned when the batch says so, with
        the O6 amax observations threaded into the guarded update — the
        full in-flight scaler surface (scale, consecutive_overflows, amax
        history) rides the gstate."""
        params = drills._params(self.DIM, self.LAYERS)
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(lr=1e-2, impl="jnp", param_residency="keep")
        specs = zero3_state_specs()
        gspecs = guard_state_specs(guard)

        def make_step(mesh, world):
            def body(state, gstate, x, poison):
                def loss_fn(master):
                    p = opt.gather_params(master, layout)
                    y = x
                    for k in sorted(p):
                        y = jnp.tanh(y @ p[k])
                    return jnp.sum(y)

                local_loss, g = jax.value_and_grad(loss_fn)(
                    state["master"]
                )
                bad = jnp.where(poison > 0, jnp.nan, 0.0).astype(
                    jnp.float32
                )
                g = jax.tree_util.tree_map(
                    lambda a: a + bad.astype(a.dtype), g
                )
                verdict = guard.check_grads(local_loss, g)
                verdict["amax"] = (
                    amax_of_tree(state["master"]), amax_of_tree(g)
                )
                new_state, new_gstate = guard.apply_sharded_update(
                    opt, state, g, gstate, verdict
                )
                loss = jax.lax.psum(local_loss, "data")
                return new_state, new_gstate, {"loss": loss}

            inner = jax.jit(_shmap(
                body, mesh=mesh,
                in_specs=(specs, gspecs, P("data"), P()),
                out_specs=(specs, gspecs, P()),
            ))

            def step(state, gstate, batch_):
                x, poison = batch_
                return inner(state, gstate, x, poison)

            return step

        return params, layout, opt, make_step

    def test_scale_health_and_amax_survive_reshard(self, tmp_path):
        guard = StepGuard(
            LossScaler(
                init_scale=2.0**8, quantized=True, amax_history_len=4
            ),
            check_params=True,
        )
        params, layout, opt, make_step = self._guard_engine(guard)
        raw_batch = drills._batch_fn(self.ROWS, self.DIM)

        def batch(step):
            poison = np.float32(1.0 if step in (4, 5) else 0.0)
            return raw_batch(step), poison

        d = str(tmp_path)
        with ElasticTrainer(
            opt, layout, make_step, directory=d, guard=guard,
            checkpoint_every=0,
        ) as tr:
            tr.init(params, world=8)
            tr.run(6, batch)  # steps 4 and 5 overflow
            sd_before = guard.state_dict(tr.gstate)
            tr.checkpoint_now(wait=True)

        # two halvings from 2**8, two consecutive skips, history populated
        assert sd_before["loss_scale"] == 2.0**6
        assert sd_before["health"]["consecutive_overflows"] == 2
        assert sd_before["health"]["skipped_total"] == 2
        assert sd_before["health"]["last_skip_reason"] == SKIP_GRAD_OVERFLOW
        assert np.any(np.asarray(sd_before["amax_history"]) > 0)

        with ElasticTrainer(
            opt, layout, make_step, directory=d, guard=guard,
            checkpoint_every=0,
        ) as tr4:
            assert tr4.restore(world=4) == 6
            sd_after = guard.state_dict(tr4.gstate)
            assert sd_after["loss_scale"] == sd_before["loss_scale"]
            assert sd_after["health"] == sd_before["health"]
            np.testing.assert_array_equal(
                np.asarray(sd_after["amax_history"]),
                np.asarray(sd_before["amax_history"]),
            )
            # the trajectory CONTINUES: one clean step at the new world
            # resets the consecutive counter but keeps the totals
            tr4.run(1, batch)
            sd_cont = guard.state_dict(tr4.gstate)
            assert sd_cont["loss_scale"] == sd_before["loss_scale"]
            assert sd_cont["health"]["consecutive_overflows"] == 0
            assert sd_cont["health"]["skipped_total"] == 2

    def test_rollback_snapshot_reseeds_from_resharded_state(self, tmp_path):
        """With rollback armed the snapshot is deliberately NOT
        checkpointed twice; restore re-seeds it from the resharded triplet
        (ElasticTrainer passes params= through load_state_dict)."""
        guard = StepGuard(
            LossScaler(init_scale=2.0**8), rollback_after=3,
            check_params=True,
        )
        params, layout, opt, make_step = self._guard_engine(guard)
        raw_batch = drills._batch_fn(self.ROWS, self.DIM)

        def batch(step):
            return raw_batch(step), np.float32(0.0)

        d = str(tmp_path)
        with ElasticTrainer(
            opt, layout, make_step, directory=d, guard=guard,
            checkpoint_every=0,
        ) as tr:
            tr.init(params, world=8)
            tr.run(3, batch)
            tr.checkpoint_now(wait=True)

        with ElasticTrainer(
            opt, layout, make_step, directory=d, guard=guard,
            checkpoint_every=0,
        ) as tr4:
            tr4.restore(world=4)
            np.testing.assert_array_equal(
                np.asarray(tr4.gstate["snapshot"]["master"]),
                np.asarray(tr4.state["master"]),
            )
            tr4.run(1, batch)  # the re-seeded snapshot is usable
            assert tr4.global_step == 4


# ---------------------------------------------------------------------------
# multi-host checkpoint I/O (hosts=N partitioned writes, torn-host fallback)
# ---------------------------------------------------------------------------


class TestMultiHostCheckpoint:
    def test_host_helpers(self):
        assert zero3.host_rank_range(8, 2, 0) == range(0, 4)
        assert zero3.host_rank_range(8, 2, 1) == range(4, 8)
        with pytest.raises(ValueError, match="divide"):
            zero3.host_rank_range(8, 3, 0)
        with pytest.raises(ValueError, match="host"):
            zero3.host_rank_range(8, 2, 2)
        assert zero3.effective_hosts(8, 2) == 2
        assert zero3.effective_hosts(1, 2) == 1
        assert zero3.effective_hosts(6, 4) == 3

    def test_hosts_must_divide_world(self, tmp_path):
        params = {"w": np.zeros((8, 8), np.float32)}
        layout = zero3.layout_of(params)
        with pytest.raises(ValueError, match="divide"):
            zero3.shard_manifest(layout, 4, hosts=3)
        manifest = zero3.shard_manifest(layout, 4)
        with pytest.raises(ValueError, match="divide"):
            CheckpointManager(str(tmp_path), manifest, hosts=3)
        with pytest.raises(ValueError, match="hosts"):
            CheckpointManager(str(tmp_path), manifest, hosts=0)

    def test_two_host_write_stamps_host_manifests(self, tmp_path):
        params = {"w": np.zeros((8, 8), np.float32)}
        layout = zero3.layout_of(params)
        manifest = zero3.shard_manifest(layout, 4, hosts=2)
        assert manifest["manifest_version"] == 2
        assert zero3.manifest_hosts(manifest) == 2
        state = _arena_state(manifest)
        with CheckpointManager(str(tmp_path), manifest) as mgr:
            gen = mgr.submit(3, state)
            mgr.wait()
        for h in (0, 1):
            assert os.path.isfile(zero3.host_manifest_path(gen, h))
        back, shards = zero3.load_shard_files(gen)
        assert zero3.manifest_hosts(back) == 2
        full = np.concatenate([s["master"] for s in shards])
        np.testing.assert_array_equal(full, state["master"])

    def test_torn_host_demotes_generation(self, tmp_path):
        """Losing ONE host's manifest makes the generation non-durable:
        list_generations demotes it, latest_generation falls back to the
        last generation durable on ALL hosts, and a direct load of the
        torn generation refuses loudly."""
        params = {"w": np.zeros((8, 8), np.float32)}
        layout = zero3.layout_of(params)
        manifest = zero3.shard_manifest(layout, 4, hosts=2)
        with CheckpointManager(str(tmp_path), manifest) as mgr:
            g1 = mgr.submit(2, _arena_state(manifest))
            mgr.wait()
            g2 = mgr.submit(5, _arena_state(manifest))
            mgr.wait()
        assert latest_generation(str(tmp_path))[0] == 5
        removed = faults.tear_host_generation(g2, 1)
        assert not os.path.exists(removed)
        durable = [(s, d) for s, _, d in list_generations(str(tmp_path))]
        assert durable == [(2, True), (5, False)]
        assert latest_generation(str(tmp_path))[0] == 2
        with pytest.raises(FileNotFoundError, match="torn"):
            zero3.load_shard_files(g2)
        with pytest.raises(FileNotFoundError):
            faults.tear_host_generation(g2, 1)   # already removed
        back, _ = zero3.load_shard_files(g1)
        assert back["step"] == 2

    def test_v1_manifest_loads_with_defaults(self, tmp_path):
        """PR-12 generations predate manifest_version/hosts: a manifest
        without either key must keep loading (hosts defaults to 1, no
        host manifests expected) — forward-compat is one-directional."""
        params = {"w": np.zeros((8, 8), np.float32)}
        layout = zero3.layout_of(params)
        manifest = zero3.shard_manifest(layout, 2)
        del manifest["manifest_version"], manifest["hosts"]
        assert zero3.manifest_hosts(manifest) == 1
        state = _arena_state(manifest)
        with CheckpointManager(str(tmp_path), manifest) as mgr:
            gen = mgr.submit(4, state)
            mgr.wait()
        assert not os.path.exists(zero3.host_manifest_path(gen, 0))
        assert latest_generation(str(tmp_path))[0] == 4
        back, shards = zero3.load_shard_files(gen)
        assert zero3.manifest_hosts(back) == 1
        full = np.concatenate([s["master"] for s in shards])
        np.testing.assert_array_equal(full, state["master"])

    def test_single_host_layout_is_v1_compatible(self, tmp_path):
        """hosts=1 writes NO host manifests — byte-layout identical to the
        PR-12 format, so old readers keep working on new writers."""
        params = {"w": np.zeros((8, 8), np.float32)}
        layout = zero3.layout_of(params)
        manifest = zero3.shard_manifest(layout, 2, hosts=1)
        with CheckpointManager(str(tmp_path), manifest) as mgr:
            gen = mgr.submit(1, _arena_state(manifest))
            mgr.wait()
        assert sorted(os.listdir(gen)) == [
            "manifest.json", "shard_00000.npz", "shard_00001.npz",
        ]


class TestWriterErrorNamesGeneration:
    def test_failure_names_generation_and_previous_stays_restorable(
            self, tmp_path):
        """A writer-thread failure surfacing on the NEXT submit/wait must
        name the generation that failed — and the previous durable
        generation must still restore."""
        manifest, _ = _tiny_manifest(world=2)
        good = _arena_state(manifest, step=1)
        bad = _arena_state(manifest)
        bad["master"] = np.zeros(
            (manifest["world"] * manifest["shard_len"] + 3,), np.float32
        )
        mgr = CheckpointManager(str(tmp_path), manifest)
        g1 = mgr.submit(2, good)
        mgr.wait()
        mgr.submit(5, bad)
        with pytest.raises(RuntimeError) as ei:
            mgr.wait()
        msg = str(ei.value)
        assert "writer thread failed" in msg
        assert "gen_00000005" in msg
        assert "previous durable" in msg
        mgr.close()
        assert latest_generation(str(tmp_path)) == (2, g1)
        back, _ = zero3.load_shard_files(g1)
        assert back["step"] == 2


# ---------------------------------------------------------------------------
# resize-target validation + grow-back
# ---------------------------------------------------------------------------


class TestResizeValidationAndGrowback:
    DIM, LAYERS, ROWS = 32, 2, 8

    def _trainer(self, tmp_path, **kw):
        params, layout, opt, make_step = drills._engine(self.DIM, self.LAYERS)
        tr = ElasticTrainer(
            opt, layout, make_step, directory=str(tmp_path),
            checkpoint_every=2, **kw,
        )
        return params, tr

    def test_invalid_targets_refuse_with_reasons(self, tmp_path):
        params, tr = self._trainer(tmp_path)
        with tr:
            tr.init(params, world=4)
            tr.run(2, drills._batch_fn(self.ROWS, self.DIM))
            with pytest.raises(ValueError, match=">= 1"):
                tr._resize(0, reason="manual")
            with pytest.raises(ValueError, match="divide"):
                tr._resize(3, reason="manual")
            with pytest.raises(ValueError, match="equals the current"):
                tr._resize(4, reason="manual")
            with pytest.raises(ValueError, match="grow_when_available"):
                tr._resize(8, reason="tripwire")
            assert tr.world == 4   # nothing moved

    def test_hosts_validation(self, tmp_path):
        params, layout, opt, make_step = drills._engine(self.DIM, self.LAYERS)
        with pytest.raises(ValueError, match="hosts"):
            ElasticTrainer(
                opt, layout, make_step, directory=str(tmp_path), hosts=0,
            )

    def test_growback_at_checkpoint_boundary_is_bitwise(self, tmp_path):
        """Capacity returns mid-run; the trainer grows 4 -> 8 at the next
        checkpoint boundary and the continued run matches a reference that
        resharded the same generation."""
        out = drills.growback_drill(str(tmp_path), quick=True)
        assert out["growback_resume_bitwise"] == 1.0
        assert out["growback_stall_s"] > 0.0

    def test_grow_target_picks_largest_divisor(self, tmp_path):
        params, tr = self._trainer(
            tmp_path, grow_when_available=True, capacity_probe=lambda: 8,
        )
        with tr:
            tr.init(params, world=2)
            assert tr._grow_target(8) == 8
            assert tr._grow_target(7) == 4   # 7,6,5 don't divide 8
            assert tr._grow_target(2) is None
            assert tr._grow_target(1) is None


# ---------------------------------------------------------------------------
# the kill/resume drill and the real-signal drain drill (subprocess; slow)
# ---------------------------------------------------------------------------


class TestKillResumeDrill:
    def test_sigkill_at_8_resumes_at_4_bitwise(self, tmp_path):
        """The preemption drill: a child training at world 8 with async
        checkpoints SIGKILLs itself mid-run; the last durable generation
        resumes at world 4 and must match, loss by loss and arena for arena,
        an independent uninterrupted reference resharded from the same step
        (asserted inside ``_run_drill``)."""
        out = drills._run_drill(str(tmp_path), quick=True)
        assert out["killed_rc"] == -signal.SIGKILL
        assert 0 < out["resumed_from_step"] < 11    # the kill came after 11
        assert out["drill_steps_replayed"] == 16 - out["resumed_from_step"]


@pytest.mark.slow
class TestGracefulDrainDrill:
    def test_sigterm_drains_instead_of_redelivery(self, tmp_path):
        """A REAL SIGTERM into an armed child: the flight recorder dumps
        first, the preemption notice drains the writer, and the child exits
        0 with the generation at the drained step durable — no re-raised
        signal, no torn tail."""
        ckpt = str(tmp_path / "ck")
        dump = str(tmp_path / "dump.json")
        proc = drills._spawn_train_child(ckpt, quick=True, extra_args=[
            "--total", "15", "--term-at", "5", "--ckpt-every", "2",
            "--hosts", "2", "--arm-notice", "--dump", dump,
        ])
        assert proc.returncode == 0, proc.stderr[-3000:]
        info = json.loads(proc.stdout.strip().splitlines()[-1])
        assert info["drained_at"] == 5
        assert info["dumps"] == [dump]
        assert os.path.isfile(dump)
        with open(dump) as f:
            payload = json.load(f)
        assert payload["reason"].startswith("preemption:SIGTERM")
        assert latest_generation(ckpt)[0] == 5
