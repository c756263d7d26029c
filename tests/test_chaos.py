"""Chaos-hardening tests (``-m chaos``): the real-signal preemption bridge,
the hang watchdog, the fault-schedule generator, and the lineage-replay
oracle — units fast, the soak legs ``slow``.

The division of labor with ``tests/test_elastic.py``: that suite proves the
MECHANISMS (async generations, resharding, single-fault drills); this one
proves they stay bitwise when faults ARRIVE THROUGH THE REAL CHANNELS
(signals, wall-clock silence) and in COMPOSITION (seeded multi-fault
schedules vs a fault-free reference replay of the same lineage).
"""

from __future__ import annotations

import os
import signal
import time

import pytest

from beforeholiday_tpu.elastic import (
    HangWatchdog,
    PreemptionNotice,
    RankHangError,
    reset_watchdog_ledger,
    watchdog_records,
)
from beforeholiday_tpu.elastic.signals import _signame
from beforeholiday_tpu.testing import drills
from beforeholiday_tpu.testing.faults import SimulatedPreemption, hang_rank

pytestmark = pytest.mark.chaos



# ---------------------------------------------------------------------------
# the preemption bridge
# ---------------------------------------------------------------------------


class TestPreemptionNotice:
    def test_tick_is_noop_until_notified(self):
        n = PreemptionNotice(surviving_world=4)
        assert not n.triggered
        n.tick()   # nothing pending — must not raise

    def test_notify_then_tick_raises_once(self):
        n = PreemptionNotice(surviving_world=4)
        n._notify(signal.SIGTERM)
        assert n.triggered
        with pytest.raises(SimulatedPreemption) as ei:
            n.tick()
        assert ei.value.surviving_world == 4
        assert not ei.value.drain
        assert not n.triggered
        n.tick()   # flag consumed — a second tick is a no-op

    def test_drain_defaults_on_when_no_surviving_world(self):
        assert PreemptionNotice().drain is True
        assert PreemptionNotice(surviving_world=4).drain is False
        assert PreemptionNotice(surviving_world=4, drain=True).drain is True
        with pytest.raises(SimulatedPreemption) as ei:
            n = PreemptionNotice()
            n._notify(signal.SIGUSR1)
            n.tick()
        assert ei.value.drain and ei.value.surviving_world is None

    def test_real_signal_delivery_and_disposition_restore(self):
        prev = signal.getsignal(signal.SIGUSR1)
        with PreemptionNotice((signal.SIGUSR1,), surviving_world=2) as n:
            os.kill(os.getpid(), signal.SIGUSR1)
            # delivery is synchronous for a self-kill on the main thread
            assert n.triggered
            with pytest.raises(SimulatedPreemption):
                n.tick()
        assert signal.getsignal(signal.SIGUSR1) == prev

    def test_install_idempotent(self):
        n = PreemptionNotice((signal.SIGUSR1,))
        try:
            assert n.install() is n
            handler = signal.getsignal(signal.SIGUSR1)
            n.install()
            assert signal.getsignal(signal.SIGUSR1) == handler
        finally:
            n.uninstall()

    def test_uninstall_leaves_foreign_handler_alone(self):
        n = PreemptionNotice((signal.SIGUSR1,))
        n.install()
        sentinel = lambda s, f: None   # noqa: E731
        signal.signal(signal.SIGUSR1, sentinel)
        n.uninstall()   # someone re-owned the signal after us — hands off
        assert signal.getsignal(signal.SIGUSR1) == sentinel
        signal.signal(signal.SIGUSR1, signal.SIG_DFL)

    def test_signame(self):
        assert _signame(signal.SIGTERM) == "SIGTERM"
        assert _signame(10**6) == str(10**6)


# ---------------------------------------------------------------------------
# the hang watchdog
# ---------------------------------------------------------------------------


class TestHangWatchdog:
    def test_validation(self):
        with pytest.raises(ValueError, match="world"):
            HangWatchdog(0)
        with pytest.raises(ValueError, match="hang_timeout_s"):
            HangWatchdog(2, hang_timeout_s=0)
        wd = HangWatchdog(2, hang_timeout_s=1.0)
        with pytest.raises(ValueError, match="rank"):
            wd.beat(2, 0)

    def test_suppressor_eats_beat(self):
        wd = HangWatchdog(4, hang_timeout_s=1.0)
        sup = hang_rank(wd, 2, after_step=5)
        assert wd.beat(2, 4)          # before after_step: lands
        assert not wd.beat(2, 5)      # suppressed
        assert wd.beat(1, 5)          # other ranks unaffected
        assert wd.beat_all(6) == 3
        wd.remove_suppressor(sup)
        assert wd.beat(2, 7)

    def test_single_silent_rank_flags_and_check_raises(self):
        reset_watchdog_ledger()
        with HangWatchdog(4, hang_timeout_s=0.08,
                          poll_interval_s=0.01) as wd:
            hang_rank(wd, 3, after_step=0)
            deadline = time.monotonic() + 2.0
            while not wd.hung_ranks and time.monotonic() < deadline:
                wd.beat_all(1)        # peers keep beating; rank 3 is eaten
                time.sleep(0.01)
            assert wd.hung_ranks == [3]
            with pytest.raises(RankHangError) as ei:
                wd.check()
            assert ei.value.rank == 3
            assert ei.value.stalled_for_s >= 0.08
            wd.check()                # flags consumed — no re-raise
        rows = watchdog_records()
        assert rows and rows[0]["rank"] == 3
        assert rows[0]["timeout_s"] == pytest.approx(0.08)

    def test_whole_world_silence_never_flags(self):
        """The peer-witness rule: when EVERY rank is quiet the coordinator
        is stalled (compile, trace, I/O) — flagging would cascade resizes
        off recompiles. Only a rank silent WHILE PEERS ADVANCE is a hang."""
        with HangWatchdog(4, hang_timeout_s=0.05,
                          poll_interval_s=0.01) as wd:
            wd.beat_all(1)
            time.sleep(0.2)           # everyone silent — no peer witness
            assert wd.hung_ranks == []
            wd.check()

    def test_world_one_never_flags(self):
        with HangWatchdog(1, hang_timeout_s=0.05,
                          poll_interval_s=0.01) as wd:
            time.sleep(0.2)
            assert wd.hung_ranks == []

    def test_reset_clears_flags_keeps_suppressors(self):
        wd = HangWatchdog(4, hang_timeout_s=1.0)
        hang_rank(wd, 1, after_step=0)
        wd._hung.append({"rank": 1, "last_step": 0,
                         "stalled_for_s": 2.0, "timeout_s": 1.0})
        wd.reset(2)
        assert wd.world == 2
        assert wd.hung_ranks == []
        assert not wd.beat(1, 0)      # suppressor survived the reset
        wd.check()

    def test_state_roundtrip(self):
        wd = HangWatchdog(4, hang_timeout_s=9.0)
        wd.beat_all(7)
        sd = wd.state_dict()
        assert sd == {"world": 4, "last_step": [7, 7, 7, 7],
                      "hang_timeout_s": 9.0}
        wd2 = HangWatchdog(2, hang_timeout_s=9.0)
        wd2.load_state_dict(sd)
        assert wd2.world == 4 and wd2._last_step == [7, 7, 7, 7]
        with pytest.raises(ValueError, match="ranks"):
            wd2.load_state_dict({"world": 3, "last_step": [1, 2]})


# ---------------------------------------------------------------------------
# the schedule generator and the lineage oracle (pure host-side units)
# ---------------------------------------------------------------------------


class TestScheduleGenerator:
    def test_deterministic(self):
        assert drills.generate_schedule(3) == drills.generate_schedule(3)
        assert (drills.generate_schedule(0, spawn="sigkill")
                == drills.generate_schedule(0, spawn="sigkill"))

    def test_acceptance_shape_of_the_soak_set(self):
        """The exact composition ``run_soak`` requires: >= 6 schedules, each
        composing >= 2 distinct fault kinds, >= 1 with SIGKILL, >= 1 with
        grow-back — pinned here so a generator edit that silently weakens
        the soak fails a fast unit, not a slow soak."""
        schedules = [
            drills.generate_schedule(s, spawn=(
                "sigkill" if s == 0 else "sigterm" if s == 1 else None
            ))
            for s in drills.SCHEDULE_SEEDS
        ]
        assert len(schedules) >= 6
        for sch in schedules:
            assert len(set(sch.kinds)) >= 2, sch
            for f in sch.faults:
                assert f.kind in drills._IN_PROCESS_KINDS
                # every fault lands after the first durable generation can
                # exist and before the run's tail
                assert drills.CKPT_EVERY < f.at_step < sch.total
        assert any(s.spawn == "sigkill" for s in schedules)
        assert any(s.spawn == "sigterm" for s in schedules)
        assert any("grow" in s.kinds for s in schedules)

    def test_torn_is_always_paired_with_a_shrink(self):
        for seed in range(20):
            sch = drills.generate_schedule(seed)
            faults = sorted(sch.faults, key=lambda f: f.at_step)
            for i, f in enumerate(faults):
                if f.kind == "torn":
                    after = [g.kind for g in faults[i + 1:]]
                    assert "shrink" in after or "signal" in after, sch


class _Ev:
    def __init__(self, reason, resumed_from, new_world):
        self.reason = reason
        self.resumed_from = resumed_from
        self.new_world = new_world


class TestFinalLineage:
    def test_empty(self):
        assert drills.final_lineage([(0, 8)], []) == [(0, 8)]

    def test_simple_shrink_chain(self):
        evs = [_Ev("preemption", 4, 4), _Ev("hang", 10, 2)]
        assert drills.final_lineage([(0, 8)], evs) == [(0, 8), (4, 4), (10, 2)]

    def test_rollback_replays_over_earlier_segments(self):
        """A resize that resumes from an OLDER generation than a previous
        event's boundary erases that segment from the final trajectory."""
        evs = [_Ev("preemption", 8, 4), _Ev("tripwire", 6, 2)]
        assert drills.final_lineage([(0, 8)], evs) == [(0, 8), (6, 2)]

    def test_drain_rolls_nothing_back(self):
        evs = [_Ev("preemption_drain", 5, 8), _Ev("grow", 6, 8)]
        assert drills.final_lineage([(0, 4)], evs) == [(0, 4), (6, 8)]

    def test_spawn_leg_initial_lineage(self):
        evs = [_Ev("grow", 12, 8)]
        assert drills.final_lineage([(0, 8), (10, 4)], evs) == [
            (0, 8), (10, 4), (12, 8),
        ]

    def test_starts_strictly_increase(self):
        evs = [_Ev("preemption", 4, 4), _Ev("preemption", 4, 2)]
        lin = drills.final_lineage([(0, 8)], evs)
        assert lin == [(0, 8), (4, 2)]
        assert all(a[0] < b[0] for a, b in zip(lin, lin[1:]))


# ---------------------------------------------------------------------------
# soak legs (slow): one live schedule, then the full set (drills.run_soak)
# ---------------------------------------------------------------------------


def _mesh_or_skip():
    import jax

    if len(jax.devices()) < 8 or jax.default_backend() != "cpu":
        pytest.skip("needs the 8-device CPU mesh")


@pytest.mark.slow
class TestChaosSoak:
    def test_growback_drill_bitwise(self, tmp_path):
        _mesh_or_skip()
        out = drills.growback_drill(str(tmp_path), quick=True)
        assert out["growback_resume_bitwise"] == 1.0
        assert out["growback_stall_s"] > 0.0

    def test_one_schedule_in_process_bitwise(self, tmp_path):
        """The grow-back composition (shrink -> grow) live: events observed,
        lineage collapsed, reference replayed, bitwise asserted inside
        run_schedule."""
        _mesh_or_skip()
        sched = drills.generate_schedule(3)
        assert {"shrink", "grow"} <= set(sched.kinds)
        out = drills.run_schedule(sched, str(tmp_path), quick=True)
        assert out["bitwise"] == 1.0
        assert "grow" in out["event_reasons"]

    def test_full_soak(self, tmp_path):
        """The whole soak: six seeded schedules (one behind a SIGKILLed
        child, one behind a SIGTERM-drained child) + the grow drill, every
        one bitwise or ``run_soak`` raises."""
        _mesh_or_skip()
        out = drills.run_soak(str(tmp_path), quick=True)
        runs = out["schedules"]
        assert len(runs) == len(drills.SCHEDULE_SEEDS) >= 6
        assert all(r["bitwise"] == 1.0 for r in runs)
        (sigkill,) = [r for r in runs if "sigkill" in r["kinds"]]
        (sigterm,) = [r for r in runs if "sigterm" in r["kinds"]]
        assert sigkill["spawn_rc"] == -signal.SIGKILL
        assert sigterm["spawn_rc"] == 0
        assert sigterm["spawn_dump"] and os.path.isfile(sigterm["spawn_dump"])
        assert out["growback"]["growback_resume_bitwise"] == 1.0


# ---------------------------------------------------------------------------
# liveness surfaces: flight-dump rendering + heartbeat persistence
# ---------------------------------------------------------------------------


class TestLivenessSurfaces:
    def test_health_summary_renders_liveness_keys(self):
        from beforeholiday_tpu.guard.step import health_summary

        row = {"skipped_total": 2, "last_skip_reason": 0,
               "world": 4, "mismatch": 1, "loss": -3.5}
        out = health_summary(row)
        assert out["world"] == 4 and out["mismatch"] == 1
        assert "loss" not in out          # only health + liveness keys
        assert health_summary({"skipped_total": 0}) == {"skipped_total": 0}

    def test_restore_reloads_heartbeats_at_same_world(self, tmp_path):
        """Heartbeat steps ride the manifest extra; a same-world restore
        gets them back (clocks re-armed), a resharded world keeps the
        fresh ledger."""
        import jax

        if len(jax.devices()) < 8 or jax.default_backend() != "cpu":
            pytest.skip("needs the 8-device CPU mesh")
        from beforeholiday_tpu.elastic import ElasticTrainer
        params, layout, opt, make_step = drills._engine(32, 2)
        bf = drills._batch_fn(8, 32)
        d = str(tmp_path)
        wd = HangWatchdog(4, hang_timeout_s=30.0)
        with ElasticTrainer(
            opt, layout, make_step, directory=d, checkpoint_every=0,
            watchdog=wd,
        ) as tr:
            tr.init(params, world=4)
            tr.run(3, bf)
            assert wd._last_step == [3, 3, 3, 3]
            tr.checkpoint_now(wait=True)

        wd2 = HangWatchdog(4, hang_timeout_s=30.0)
        with ElasticTrainer(
            opt, layout, make_step, directory=d, checkpoint_every=0,
            watchdog=wd2,
        ) as tr2:
            assert tr2.restore(world=4) == 3
            assert wd2._last_step == [3, 3, 3, 3]

        wd8 = HangWatchdog(4, hang_timeout_s=30.0)
        with ElasticTrainer(
            opt, layout, make_step, directory=d, checkpoint_every=0,
            watchdog=wd8,
        ) as tr8:
            tr8.restore(world=8)          # resharded: fresh ledger
            assert wd8.world == 8
            assert wd8._last_step == [-1] * 8
