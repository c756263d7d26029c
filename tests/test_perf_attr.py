"""Perf-attribution engine (ISSUE 6 acceptance contracts; the cost ledger's
cases went with the ledger in PR 52, ``tests/test_program_ledger.py`` holds
its successor's):

* the chip peaks are the published ones, by ``device_kind``;
* ``span_intervals`` rebuilds nested and per-rank spans from a constructed
  timeline;
* a forced StepGuard rollback trip, drained through TrainMonitor ->
  MetricsLogger -> FlightRecorder, dumps a structured JSON black box with
  the last-N snapshots and the loss-scale trajectory;
* a run killed mid-step still leaves a partial metrics log (atexit flush)
  and a crash dump (chained excepthook) on disk — the satellite-1 contract;
* ``dispatch_summary`` carries per-key pallas-hit ratios and
  ``reset_counters`` re-arms the probe-failure warn-once registry.
"""

import json
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu import monitor
from beforeholiday_tpu.amp.scaler import LossScaler
from beforeholiday_tpu.guard import StepGuard, checked_impl, clear_probe_cache
from beforeholiday_tpu.guard import dispatch as guard_dispatch
from beforeholiday_tpu.optimizers import FusedSGD
from beforeholiday_tpu.testing.faults import force_probe_failure
from beforeholiday_tpu.utils.logging import reset_warn_once

pytestmark = pytest.mark.perf_attr

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_perf_state():
    def _reset():
        monitor.reset_comms_ledger()
        monitor.reset_compile_counts()
        monitor.reset_counters()
        clear_probe_cache()
        reset_warn_once()

    _reset()
    yield
    _reset()


class _Capture(logging.Handler):
    """propagate=False on the repo loggers — capture with a direct handler."""

    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


# -------------------------------------------------------------------------------
# chip specs
# -------------------------------------------------------------------------------


class TestChipSpec:
    def test_published_table_registered(self):
        """TPU peaks are the PUBLISHED figures, keyed by the string
        ``device_kind`` prints; no fp8 rate where none is published."""
        specs = monitor.chip_specs()
        assert "cpu_proxy" in specs
        v5e = specs["TPU v5 lite"]
        assert (v5e.peak_tflops, v5e.hbm_gbs) == (197.0, 819.0)
        assert v5e.fp8_peak_tflops is None

    def test_default_resolves_by_device_kind_and_unknown_kind_raises(
            self, monkeypatch):
        from beforeholiday_tpu.monitor import roofline

        assert roofline._resolve_chip(None).name == "cpu_proxy"  # off-TPU

        class _Dev:
            device_kind = "TPU v5 lite"

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
        assert roofline._resolve_chip(None).peak_tflops == 197.0
        _Dev.device_kind = "TPU v99"
        with pytest.raises(KeyError, match="TPU v99"):
            roofline._resolve_chip(None)

    def test_register_get_roundtrip_and_ridge(self):
        spec = monitor.register_chip_spec(
            name="test_chip", peak_tflops=100.0, hbm_gbs=1000.0
        )
        assert monitor.get_chip_spec("test_chip") == spec
        # ridge: 100e12 flops/s over 1000e9 B/s = 100 flops/byte
        np.testing.assert_allclose(spec.ridge_flops_per_byte, 100.0)

    def test_invalid_specs_rejected(self):
        with pytest.raises(ValueError):
            monitor.register_chip_spec(name="bad", peak_tflops=0.0, hbm_gbs=1.0)
        with pytest.raises(ValueError):
            monitor.register_chip_spec(name="bad")  # missing fields
        with pytest.raises(KeyError):
            monitor.get_chip_spec("never_registered")


# -------------------------------------------------------------------------------
# span intervals: constructed-timeline oracles
# -------------------------------------------------------------------------------


def _span(name, start, end, pid=0, tid=0):
    return [
        {"ph": "B", "name": name, "pid": pid, "tid": tid, "ts": float(start)},
        {"ph": "E", "pid": pid, "tid": tid, "ts": float(end)},
    ]


class TestSpanIntervals:
    def test_nested_spans_match_and_depth(self):
        events = [
            {"ph": "B", "name": "outer", "pid": 0, "tid": 0, "ts": 0.0},
            {"ph": "B", "name": "inner", "pid": 0, "tid": 0, "ts": 10.0},
            {"ph": "E", "pid": 0, "tid": 0, "ts": 20.0},
            {"ph": "E", "pid": 0, "tid": 0, "ts": 100.0},
        ]
        ivs = monitor.span_intervals(events)
        by_name = {iv["name"]: iv for iv in ivs}
        assert by_name["inner"]["depth"] == 1
        assert by_name["inner"]["end"] == 20.0
        assert by_name["outer"]["depth"] == 0
        assert by_name["outer"]["end"] == 100.0

    def test_unclosed_span_dropped(self):
        events = [
            {"ph": "B", "name": "crashed", "pid": 0, "tid": 0, "ts": 0.0},
            *_span("done", 0.0, 5.0, tid=1),
        ]
        ivs = monitor.span_intervals(events)
        assert [iv["name"] for iv in ivs] == ["done"]

    def test_per_pid_tid_stacks_are_independent(self):
        events = (
            _span("a", 0.0, 10.0, pid=0) + _span("a", 5.0, 25.0, pid=1)
        )
        ivs = monitor.span_intervals(events)
        assert len(ivs) == 2
        assert {iv["pid"] for iv in ivs} == {0, 1}


# -------------------------------------------------------------------------------
# flight recorder
# -------------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        fl = monitor.FlightRecorder(capacity=3, auto_dump_on_rollback=False)
        for s in range(5):
            fl.record(s, {"loss": float(s)})
        assert len(fl) == 3
        assert [s["step"] for s in fl.snapshots()] == [2, 3, 4]

    def test_invalid_capacity_rejected(self):
        with pytest.raises(ValueError):
            monitor.FlightRecorder(capacity=0)

    def test_rollback_increment_triggers_dump(self, tmp_path):
        path = str(tmp_path / "flight.json")
        fl = monitor.FlightRecorder(capacity=8, path=path)
        fl.record(1, {"loss": 1.0, "rollbacks_total": 0})
        fl.record(2, {"loss": 2.0, "rollbacks_total": 0})
        assert fl.dumps == []
        fl.record(3, {"loss": 9.0, "rollbacks_total": 1})
        assert fl.dumps == [path]
        payload = json.load(open(path))
        assert payload["reason"] == "stepguard_rollback"
        assert payload["n_snapshots"] == 3

    def test_dump_structure(self, tmp_path):
        path = str(tmp_path / "flight.json")
        fl = monitor.FlightRecorder(capacity=4, path=path)
        fl.record(7, {"loss": 0.5, "loss_scale": 1024.0,
                      "last_skip_reason": 4, "rollbacks_total": 1,
                      "skipped_total": 2, "consecutive_overflows": 0})
        fl.dump(reason="manual")
        payload = json.load(open(path))
        for k in ("reason", "created_unix", "capacity", "n_snapshots",
                  "snapshots", "loss_scale_trajectory", "last_health",
                  "dispatch_summary", "comms_summary", "compile_summary",
                  "probe_failures"):
            assert k in payload, k
        assert payload["loss_scale_trajectory"] == [1024.0]
        assert payload["last_health"]["last_skip_reason_name"] == "rollback"
        snap = payload["snapshots"][0]
        assert snap["step"] == 7
        assert "dispatch_pallas" in snap["counters"]
        assert "comms_bytes" in snap["counters"]

    def test_attach_chains_logger_callback(self, tmp_path):
        mon = monitor.TrainMonitor()
        seen = []
        log = monitor.MetricsLogger(
            mon, callback=lambda step, row: seen.append(step)
        )
        fl = monitor.FlightRecorder(
            capacity=4, path=str(tmp_path / "f.json")
        ).attach(log)
        m = mon.update(mon.init(), loss=jnp.float32(1.5))
        log.log(mon.pack(m), 1)
        assert seen == [1]  # previous callback still runs
        assert len(fl) == 1
        assert fl.snapshots()[0]["metrics"]["loss"] == 1.5

    def test_context_manager_dumps_on_exception(self, tmp_path):
        path = str(tmp_path / "flight.json")
        fl = monitor.FlightRecorder(capacity=4, path=path)
        assert monitor.active_flight_recorder() is None
        with pytest.raises(ValueError):
            with fl:
                assert monitor.active_flight_recorder() is fl
                fl.record(1, {"loss": 1.0})
                raise ValueError("boom")
        assert monitor.active_flight_recorder() is None
        payload = json.load(open(path))
        assert payload["reason"] == "exception:ValueError"

    def test_clean_exit_does_not_dump(self, tmp_path):
        path = str(tmp_path / "flight.json")
        with monitor.FlightRecorder(capacity=4, path=path) as fl:
            fl.record(1, {"loss": 1.0})
        assert not os.path.exists(path)

    def test_arm_disarm_restores_excepthook(self):
        prev = sys.excepthook
        fl = monitor.FlightRecorder(capacity=2)
        fl.arm_crash_dump()
        assert sys.excepthook is not prev
        fl.arm_crash_dump()  # idempotent
        fl.disarm_crash_dump()
        assert sys.excepthook is prev


class TestStepGuardTripEndToEnd:
    def test_forced_rollback_produces_flight_dump(self, tmp_path):
        """Acceptance: StepGuard rollback trip -> flight JSON with the last-N
        snapshots, drained through TrainMonitor -> MetricsLogger."""
        params = {"w": jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)}
        opt = FusedSGD(lr=0.1)
        guard = StepGuard(
            LossScaler(init_scale=2.0, min_loss_scale=1.0), rollback_after=2
        )
        gstate = guard.init(params)
        ostate = opt.init(params)
        vg = guard.value_and_grad(lambda p, x: jnp.sum(p["w"] * x))
        mon = monitor.TrainMonitor()

        metrics_path = str(tmp_path / "metrics.jsonl")
        flight_path = str(tmp_path / "flight.json")
        log = monitor.MetricsLogger(mon, path=metrics_path)
        fl = monitor.FlightRecorder(capacity=8, path=flight_path).attach(log)

        @jax.jit
        def step(params, ostate, gstate, m, x):
            loss, grads, verdict = vg(params, gstate, x)
            p, o, g = guard.apply_update(
                opt, params, grads, ostate, gstate, verdict
            )
            m = mon.update(
                m, loss=loss, grads=grads,
                scaler_state=g["scaler"], health=g["health"],
            )
            return p, o, g, m, mon.pack(m)

        m = mon.init()
        good = jnp.asarray([1.0, -1.0, 0.5, 2.0], jnp.float32)
        bad = jnp.asarray([jnp.nan, 1.0, 1.0, 1.0], jnp.float32)
        # clean step, then two overflows: scale 2 -> 1 (floor), then the
        # second consecutive overflow at min scale trips the rollback
        for i, x in enumerate((good, bad, bad), start=1):
            params, ostate, gstate, m, packed = step(
                params, ostate, gstate, m, x
            )
            log.log(packed, i)
        log.close()

        assert fl.dumps == [flight_path]
        payload = json.load(open(flight_path))
        assert payload["reason"] == "stepguard_rollback"
        assert payload["n_snapshots"] == 3
        assert payload["loss_scale_trajectory"] == [2.0, 1.0, 1.0]
        assert payload["last_health"]["rollbacks_total"] == 1
        assert payload["last_health"]["last_skip_reason_name"] == "rollback"
        # the partial metrics log exists alongside the black box
        rows = [json.loads(l) for l in open(metrics_path)]
        assert [r["step"] for r in rows] == [1, 2, 3]
        assert rows[-1]["rollbacks_total"] == 1


class TestCrashFlush:
    def test_killed_run_leaves_partial_log_and_flight_dump(self, tmp_path):
        """Satellite 1: a run dying mid-step must leave (a) the drained rows
        on disk — the atexit flush covers the every=N stdio buffer — and
        (b) the excepthook's crash dump."""
        metrics_path = str(tmp_path / "metrics.jsonl")
        flight_path = str(tmp_path / "flight.json")
        script = f"""
import jax.numpy as jnp
from beforeholiday_tpu import monitor

mon = monitor.TrainMonitor()
log = monitor.MetricsLogger(mon, path={metrics_path!r}, every=2)
fl = monitor.FlightRecorder(capacity=8, path={flight_path!r}).attach(log)
fl.arm_crash_dump()
m = mon.init()
for step in range(1, 7):
    m = mon.update(m, loss=jnp.float32(step))
    log.log(mon.pack(m), step)
raise RuntimeError("killed mid-run")
"""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = _REPO_ROOT
        out = subprocess.run(
            [sys.executable, "-c", script], env=env,
            capture_output=True, text=True, timeout=300,
        )
        assert out.returncode != 0
        assert "killed mid-run" in out.stderr

        rows = [json.loads(l) for l in open(metrics_path)]
        assert [r["step"] for r in rows] == [2, 4, 6]  # every=2 cadence
        payload = json.load(open(flight_path))
        assert payload["reason"] == "exception:RuntimeError"
        assert payload["n_snapshots"] == 3
        assert [s["step"] for s in payload["snapshots"]] == [2, 4, 6]


# -------------------------------------------------------------------------------
# counters: pallas-hit ratio + reset re-arms warn-once (satellite 3)
# -------------------------------------------------------------------------------


class TestCounters:
    def test_dispatch_summary_carries_pallas_ratio(self):
        x = jnp.ones((4, 4))
        checked_impl("ratio_op", "pallas", lambda v: v * 2, x)
        with force_probe_failure("ratio_op"):
            checked_impl(
                "ratio_op", "pallas", lambda v: v * 2, jnp.ones((3, 4))
            )
        (row,) = monitor.dispatch_summary()
        assert row["op"] == "ratio_op"
        np.testing.assert_allclose(row["pallas_ratio"], 0.5)
        recs = monitor.dispatch_records()
        assert {r["pallas_ratio"] for r in recs} == {1.0, 0.0}

    def test_reset_counters_clears_and_rearms_warn_once(self):
        """The leak this pins: a probe-failure warning is once-per-key, and
        clearing the counters/probe cache used to leave the warn-once
        registry stale — a REPEATED failure after a reset went silent."""
        h = _Capture()
        guard_dispatch.logger.addHandler(h)
        try:
            x = jnp.ones((4, 4))
            with force_probe_failure("reset_op"):
                checked_impl("reset_op", "pallas", lambda v: v, x)
            warns = [r for r in h.records if r.levelno == logging.WARNING]
            assert len(warns) == 1
            assert monitor.dispatch_counters()  # non-empty

            monitor.reset_counters()
            clear_probe_cache()
            assert monitor.dispatch_counters() == {}
            assert monitor.dispatch_summary() == []

            with force_probe_failure("reset_op"):
                checked_impl("reset_op", "pallas", lambda v: v, x)
            warns = [r for r in h.records if r.levelno == logging.WARNING]
            assert len(warns) == 2, "second failure after reset must re-warn"
        finally:
            guard_dispatch.logger.removeHandler(h)

    def test_clear_probe_cache_alone_rearms_warning(self):
        """clear_probe_cache discards the warned keys for the ops it drops —
        re-probing a still-broken op warns again instead of leaking the
        stale once-flag."""
        h = _Capture()
        guard_dispatch.logger.addHandler(h)
        try:
            x = jnp.ones((2, 2))
            with force_probe_failure("leak_op"):
                checked_impl("leak_op", "pallas", lambda v: v, x)
                clear_probe_cache("leak_op")
                checked_impl("leak_op", "pallas", lambda v: v, x)
            warns = [r for r in h.records if r.levelno == logging.WARNING]
            assert len(warns) == 2
        finally:
            guard_dispatch.logger.removeHandler(h)
