"""The observability subsystem (ISSUE 2 acceptance contracts):

* ``Metrics`` is pure device state: updates are plain jnp, the pytree
  survives ``jax.jit`` and ``shard_map``, and cross-rank aggregation matches
  a NumPy oracle on the 8-device CPU mesh;
* a monitored, logged training loop performs ONE device->host readback per
  logged step and ZERO on off-cadence steps (counted through
  ``MetricsLogger._fetch``);
* exporters: JSONL/CSV rows + callback, cadence semantics, overflow-streak
  warning once per incident;
* ``warn_once`` rate-limits by key and the guard probe warning rides it;
* dispatch counters expose the guard probe cache per key and per op;
* spans/timers moved to ``monitor/`` with intact ``utils`` back-compat;
* amp ``state_dict`` carries the metrics pytree and pre-monitor checkpoints
  still load.
"""

import functools
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P


def shard_map(f=None, **kw):
    kw.setdefault("check_vma", False)
    if f is None:
        return lambda g: jax.shard_map(g, **kw)
    return jax.shard_map(f, **kw)


from beforeholiday_tpu import monitor
from beforeholiday_tpu.guard import checked_impl, clear_probe_cache
from beforeholiday_tpu.guard import dispatch as guard_dispatch
from beforeholiday_tpu.monitor import (
    MetricsLogger,
    TrainMonitor,
    dispatch_summary,
    global_norm,
    reset_dispatch_counters,
)
from beforeholiday_tpu.monitor import export as monitor_export
from beforeholiday_tpu.utils.logging import reset_warn_once, warn_once

pytestmark = pytest.mark.monitor


@pytest.fixture(autouse=True)
def _fresh_warn_and_probe_state():
    clear_probe_cache()
    reset_warn_once()
    reset_dispatch_counters()
    yield
    clear_probe_cache()
    reset_warn_once()
    reset_dispatch_counters()


@pytest.fixture
def data_mesh(devices8):
    return Mesh(np.asarray(devices8).reshape(8), ("data",))


class _Capture(logging.Handler):
    """propagate=False on the repo loggers — capture with a direct handler."""

    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


# -------------------------------------------------------------------------------
# device-side metrics
# -------------------------------------------------------------------------------


class TestTrainMonitor:
    def test_update_matches_numpy(self):
        mon = TrainMonitor(ema_decay=0.9)
        rng = np.random.RandomState(0)
        g = {"a": rng.randn(4, 3).astype(np.float32),
             "b": rng.randn(5).astype(np.float32)}
        p = {"a": rng.randn(4, 3).astype(np.float32),
             "b": rng.randn(5).astype(np.float32)}
        p2 = {k: v - 0.01 * g[k] for k, v in p.items()}

        m = mon.update(
            mon.init(),
            loss=jnp.float32(2.5),
            grads=jax.tree.map(jnp.asarray, g),
            params=jax.tree.map(jnp.asarray, p),
            new_params=jax.tree.map(jnp.asarray, p2),
        )
        gn = np.sqrt(sum((x ** 2).sum() for x in g.values()))
        pn = np.sqrt(sum((x ** 2).sum() for x in p.values()))
        un = np.sqrt(sum(((p2[k] - p[k]) ** 2).sum() for k in p))
        assert float(m["loss"]) == 2.5
        np.testing.assert_allclose(float(m["grad_norm"]), gn, rtol=1e-5)
        np.testing.assert_allclose(float(m["param_norm"]), pn, rtol=1e-5)
        np.testing.assert_allclose(float(m["update_norm"]), un, rtol=1e-4)
        np.testing.assert_allclose(
            float(m["update_ratio"]), un / pn, rtol=1e-4)
        assert int(m["steps"]) == 1

    def test_ema_seeds_then_decays(self):
        mon = TrainMonitor(ema_decay=0.9)
        m = mon.update(mon.init(), loss=jnp.float32(10.0))
        # step 1 seeds the EMA with the observation, no decay-from-zero bias
        assert float(m["loss_ema"]) == 10.0
        m = mon.update(m, loss=jnp.float32(20.0))
        np.testing.assert_allclose(
            float(m["loss_ema"]), 0.9 * 10.0 + 0.1 * 20.0, rtol=1e-6)

    def test_grad_norm_max_is_running_max(self):
        mon = TrainMonitor()
        m = mon.init()
        for v in (3.0, 7.0, 2.0):
            m = mon.update(m, grads={"g": jnp.full((1,), v)})
        np.testing.assert_allclose(float(m["grad_norm_max"]), 7.0, rtol=1e-6)
        np.testing.assert_allclose(float(m["grad_norm"]), 2.0, rtol=1e-6)

    def test_folds_scaler_and_health(self):
        mon = TrainMonitor()
        health = {
            "consecutive_overflows": jnp.int32(2),
            "skipped_total": jnp.int32(5),
            "last_skip_reason": jnp.int32(1),
            "rollbacks_total": jnp.int32(1),
        }
        m = mon.update(
            mon.init(), scaler_state={"scale": jnp.float32(4096.0)},
            health=health)
        assert float(m["loss_scale"]) == 4096.0
        assert int(m["skipped_total"]) == 5
        assert int(m["consecutive_overflows"]) == 2
        assert int(m["rollbacks_total"]) == 1
        assert int(m["last_skip_reason"]) == 1

    def test_survives_jit(self):
        mon = TrainMonitor()

        @jax.jit
        def step(m, x):
            g = {"w": x}
            return mon.update(m, loss=jnp.sum(x), grads=g)

        m = step(mon.init(), jnp.ones((3,)))
        m = step(m, 2.0 * jnp.ones((3,)))
        assert int(m["steps"]) == 2
        np.testing.assert_allclose(float(m["loss"]), 6.0, rtol=1e-6)

    def test_pack_unpack_roundtrip(self):
        mon = TrainMonitor()
        m = mon.update(
            mon.init(), loss=jnp.float32(1.25), grads={"g": jnp.ones((2,))})
        vec = mon.pack(m)
        assert vec.shape == (len(mon.keys),)
        row = mon.unpack_host(np.asarray(vec))
        assert row["loss"] == 1.25
        assert row["steps"] == 1 and isinstance(row["steps"], int)
        assert set(row) == set(mon.keys)

    def test_state_dict_roundtrip_and_drift_tolerance(self):
        mon = TrainMonitor()
        m = mon.update(mon.init(), loss=jnp.float32(3.0),
                       grads={"g": jnp.ones((4,))})
        sd = mon.state_dict(m)
        assert sd["steps"] == 1 and isinstance(sd["steps"], int)
        m2 = mon.load_state_dict(sd)
        for k in mon.keys:
            np.testing.assert_allclose(
                np.asarray(m2[k]), np.asarray(m[k]), rtol=1e-6)
        # drift both ways: unknown keys ignored, missing keys zero-filled
        m3 = mon.load_state_dict({"loss": 9.0, "not_a_metric": 123})
        assert float(m3["loss"]) == 9.0
        assert int(m3["steps"]) == 0

    def test_global_norm_empty_tree(self):
        assert float(global_norm({})) == 0.0


class TestAggregate:
    def test_cross_rank_aggregation_matches_numpy_oracle(self, data_mesh):
        """8 ranks with different local metrics; psum/pmax/pmin aggregate must
        match the NumPy reduction per each key's declared semantics."""
        mon = TrainMonitor()
        rng = np.random.RandomState(1)
        losses = rng.rand(8).astype(np.float32) * 5
        gvals = rng.rand(8, 4).astype(np.float32)
        skips = np.arange(8, dtype=np.int32) % 3

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh,
            in_specs=(P("data"), P("data"), P("data")), out_specs=P(),
        )
        def run(loss, g, skip):
            m = mon.update(
                mon.init(),
                loss=loss[0],
                grads={"g": g[0]},
                scaler_state={"scale": 2.0 ** skip[0].astype(jnp.float32)},
                health={"skipped_total": skip[0]},
            )
            agg = mon.aggregate(m, "data")
            return mon.pack(agg)

        row = mon.unpack_host(np.asarray(
            run(jnp.asarray(losses), jnp.asarray(gvals), jnp.asarray(skips))))

        per_rank_gn = np.sqrt((gvals ** 2).sum(axis=1))
        np.testing.assert_allclose(row["loss"], losses.mean(), rtol=1e-5)
        np.testing.assert_allclose(
            row["grad_norm"], per_rank_gn.mean(), rtol=1e-5)
        np.testing.assert_allclose(
            row["grad_norm_max"], per_rank_gn.max(), rtol=1e-5)
        np.testing.assert_allclose(
            row["loss_scale"], float(2.0 ** skips.min()), rtol=1e-6)
        assert row["skipped_total"] == int(skips.max())
        assert row["steps"] == 1

    def test_aggregate_is_identity_when_ranks_agree(self, data_mesh):
        mon = TrainMonitor()

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(),), out_specs=P())
        def run(x):
            m = mon.update(mon.init(), loss=jnp.sum(x), grads={"g": x})
            return mon.pack(mon.aggregate(m, "data"))

        x = jnp.ones((4,), jnp.float32)
        row = mon.unpack_host(np.asarray(run(x)))
        np.testing.assert_allclose(row["loss"], 4.0, rtol=1e-5)
        np.testing.assert_allclose(row["grad_norm"], 2.0, rtol=1e-5)


# -------------------------------------------------------------------------------
# export: one readback per logged step, writers, cadence
# -------------------------------------------------------------------------------


class _CountingLogger(MetricsLogger):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.fetches = 0

    def _fetch(self, packed):
        self.fetches += 1
        return super()._fetch(packed)


class TestMetricsLogger:
    def _loop(self, logger, mon, n_steps):
        """A monitored train loop shaped like production: ONE jitted step
        returning (new_state, packed) — the packed vector is the step's only
        monitor output, and the logger is the only reader."""

        @jax.jit
        def step(p, m, x):
            loss, g = jax.value_and_grad(
                lambda p: jnp.mean((x @ p["w"]) ** 2))(p)
            p2 = jax.tree.map(lambda a, b: a - 0.1 * b, p, g)
            m = mon.update(m, loss=loss, grads=g, params=p, new_params=p2)
            return p2, m, mon.pack(m)

        p = {"w": jnp.ones((3, 3), jnp.float32) * 0.5}
        m = mon.init()
        x = jnp.ones((2, 3), jnp.float32)
        rows = []
        for i in range(1, n_steps + 1):
            p, m, packed = step(p, m, x)
            row = logger.log(packed, step=i)
            if row is not None:
                rows.append(row)
        return rows

    def test_one_readback_per_logged_step(self):
        mon = TrainMonitor()
        lg = _CountingLogger(mon, every=2, warn_overflow_streak=0)
        rows = self._loop(lg, mon, 10)
        # steps 2,4,6,8,10 drained; 1,3,5,7,9 cost zero fetches
        assert lg.fetches == 5
        assert [r["step"] for r in rows] == [2, 4, 6, 8, 10]
        assert rows[-1]["steps"] == 10  # device counter agrees with the loop

    def test_every_step_cadence_is_one_fetch_each(self):
        mon = TrainMonitor()
        lg = _CountingLogger(mon, every=1, warn_overflow_streak=0)
        rows = self._loop(lg, mon, 4)
        assert lg.fetches == 4 and len(rows) == 4
        # losses decrease: the loop actually trains and the metrics track it
        assert rows[-1]["loss"] < rows[0]["loss"]

    def test_jsonl_writer(self, tmp_path):
        mon = TrainMonitor()
        path = tmp_path / "m.jsonl"
        with MetricsLogger(mon, path=str(path), fmt="jsonl") as lg:
            m = mon.update(mon.init(), loss=jnp.float32(1.5))
            lg.drain(m, step=3)
        lines = path.read_text().strip().splitlines()
        assert len(lines) == 1
        row = json.loads(lines[0])
        assert row["step"] == 3 and row["loss"] == 1.5

    def test_csv_writer(self, tmp_path):
        import csv as _csv

        mon = TrainMonitor()
        path = tmp_path / "m.csv"
        with MetricsLogger(mon, path=str(path), fmt="csv") as lg:
            m = mon.init()
            for i in (1, 2):
                m = mon.update(m, loss=jnp.float32(i))
                lg.drain(m, step=i)
        rows = list(_csv.DictReader(open(path)))
        assert len(rows) == 2
        assert rows[1]["loss"] == "2.0"
        assert set(rows[0]) == {"step", *mon.keys}

    def test_callback_hook(self):
        mon = TrainMonitor()
        seen = []
        lg = MetricsLogger(mon, callback=lambda step, row: seen.append((step, row)))
        lg.drain(mon.init(), step=7)
        assert len(seen) == 1 and seen[0][0] == 7
        assert seen[0][1]["steps"] == 0

    def test_drain_accepts_dict_or_packed(self):
        mon = TrainMonitor()
        m = mon.update(mon.init(), loss=jnp.float32(2.0))
        lg = MetricsLogger(mon)
        assert lg.drain(m, step=1)["loss"] == 2.0
        assert lg.drain(mon.pack(m), step=1)["loss"] == 2.0

    def test_overflow_streak_warns_once_per_incident(self):
        mon = TrainMonitor()
        lg = MetricsLogger(mon, warn_overflow_streak=3)
        h = _Capture()
        monitor_export.logger.addHandler(h)
        try:
            def drain_with_streak(streak, step):
                m = mon.update(
                    mon.init(),
                    health={"consecutive_overflows": jnp.int32(streak)})
                lg.drain(m, step=step)

            drain_with_streak(3, 1)   # incident 1: warns
            drain_with_streak(4, 2)   # same incident: silent
            drain_with_streak(0, 3)   # recovered
            drain_with_streak(5, 4)   # incident 2: warns again
            warnings = [r for r in h.records if r.levelno == logging.WARNING]
            assert len(warnings) == 2
            assert "overflow streak" in warnings[0].getMessage()
        finally:
            monitor_export.logger.removeHandler(h)


# -------------------------------------------------------------------------------
# warn_once
# -------------------------------------------------------------------------------


class TestWarnOnce:
    def test_rate_limits_by_key(self):
        from beforeholiday_tpu.utils import logging as ulog

        h = _Capture()
        lg = ulog.get_logger("beforeholiday_tpu.test_warn_once")
        lg.addHandler(h)
        try:
            assert warn_once("k1", "first %d", 1, logger=lg) is True
            assert warn_once("k1", "second", logger=lg) is False
            assert warn_once("k2", "other key", logger=lg) is True
            assert len(h.records) == 2
            assert h.records[0].getMessage() == "first 1"
            reset_warn_once("k1")
            assert warn_once("k1", "after reset", logger=lg) is True
        finally:
            lg.removeHandler(h)

    def test_guard_probe_warning_routed_through_warn_once(self):
        """The dispatch warning must fire once per key even across re-entry,
        and again after clear_probe_cache resets the verdict + warn key."""
        h = _Capture()
        guard_dispatch.logger.addHandler(h)
        try:
            def broken(x):
                raise RuntimeError("boom")

            x = jnp.ones((2, 2))
            for _ in range(4):
                assert checked_impl("op_wo", "pallas", broken, x) == "jnp"
            warnings = [r for r in h.records if r.levelno == logging.WARNING]
            assert len(warnings) == 1
            assert "op_wo" in warnings[0].getMessage()
            clear_probe_cache("op_wo")
            assert checked_impl("op_wo", "pallas", broken, x) == "jnp"
            warnings = [r for r in h.records if r.levelno == logging.WARNING]
            assert len(warnings) == 2  # re-probe after cache clear warns anew
        finally:
            guard_dispatch.logger.removeHandler(h)


# -------------------------------------------------------------------------------
# dispatch counters
# -------------------------------------------------------------------------------


class TestDispatchCounters:
    def test_per_key_hit_and_probe_counts(self):
        def fine(x):
            return x * 2

        x = jnp.ones((4, 4))
        for _ in range(3):
            assert checked_impl("op_cnt", "pallas", fine, x) == "pallas"
        counters = monitor.dispatch_counters()
        (key,) = [k for k in counters if k[0] == "op_cnt"]
        assert counters[key] == {"pallas": 3, "jnp": 0, "probes": 1}

    def test_degrade_counts_under_jnp(self):
        def broken(x):
            raise RuntimeError("no tiling")

        x = jnp.ones((2, 2))
        for _ in range(2):
            checked_impl("op_deg", "pallas", broken, x)
        counters = monitor.dispatch_counters()
        (key,) = [k for k in counters if k[0] == "op_deg"]
        assert counters[key] == {"pallas": 0, "jnp": 2, "probes": 1}

    def test_summary_rolls_up_by_op(self):
        def fine(x):
            return x + 1

        def broken(x):
            raise RuntimeError("nope")

        checked_impl("op_a", "pallas", fine, jnp.ones((2, 2)))
        checked_impl("op_a", "pallas", fine, jnp.ones((4, 4)))  # second key
        checked_impl("op_b", "pallas", broken, jnp.ones((2, 2)))
        rows = dispatch_summary()
        by_op = {r["op"]: r for r in rows}
        assert by_op["op_a"]["keys"] == 2
        assert by_op["op_a"]["pallas"] == 2
        assert by_op["op_a"]["degraded_keys"] == 0
        assert by_op["op_b"]["jnp"] == 1
        assert by_op["op_b"]["degraded_keys"] == 1

    def test_summary_row_shape_is_json_ready(self):
        checked_impl("op_shape", "pallas", lambda x: x, jnp.ones((2,)))
        rows = dispatch_summary()
        assert rows and set(rows[0]) == {
            "op", "keys", "pallas", "jnp", "probes", "degraded_keys",
            "pallas_ratio"}
        json.dumps(rows)

    def test_reset_clears_counters_but_cache_clear_does_not(self):
        def fine(x):
            return x

        checked_impl("op_r", "pallas", fine, jnp.ones((2,)))
        clear_probe_cache("op_r")
        assert any(k[0] == "op_r" for k in monitor.dispatch_counters())
        reset_dispatch_counters()
        assert monitor.dispatch_counters() == {}


# -------------------------------------------------------------------------------
# spans + back-compat
# -------------------------------------------------------------------------------


class TestSpans:
    def test_utils_shims_are_the_same_objects(self):
        from beforeholiday_tpu import utils
        from beforeholiday_tpu.monitor import spans

        # the package-level surface IS monitor.spans (the shim modules are gone)
        assert utils.Timers is spans.Timers
        assert utils.annotate is spans.annotate
        assert utils.nvtx_range is spans.nvtx_range
        assert utils.trace is spans.trace

    def test_span_and_annotate_work_under_jit(self):
        @jax.jit
        def f(x):
            with monitor.span("test_region"):
                y = x * 2
            return monitor.annotate("test_fn")(lambda z: z + 1)(y)

        np.testing.assert_allclose(np.asarray(f(jnp.ones((2,)))), 3.0)

    def test_span_disabled_is_noop(self):
        with monitor.span("off", enabled=False):
            pass

    def test_timers_still_time(self):
        t = monitor.Timers()
        t("tick").start()
        t("tick").stop()
        out = t.log(["tick"])
        assert out.startswith("time (ms) | tick:")

    def test_spanned_library_paths_still_compute(self, data_mesh):
        """The span-wrapped DDP reduce and fused optimizer steps must be
        numerically unchanged (named_scope only labels the HLO)."""
        from beforeholiday_tpu.optimizers import FusedAdam
        from beforeholiday_tpu.parallel import reduce_gradients

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P("data"),), out_specs=P())
        def reduce(g):
            return reduce_gradients({"g": g[0]})["g"]

        g = jnp.arange(8, dtype=jnp.float32)
        np.testing.assert_allclose(float(reduce(g)[()]), g.mean(), rtol=1e-6)

        opt = FusedAdam(lr=0.1)
        p = {"w": jnp.ones((4,))}
        st = opt.init(p)
        p2, _ = jax.jit(lambda p, g, s: opt.step(p, g, s))(
            p, {"w": jnp.ones((4,))}, st)
        assert np.all(np.asarray(p2["w"]) < 1.0)


# -------------------------------------------------------------------------------
# amp checkpoint integration
# -------------------------------------------------------------------------------


class TestAmpCheckpoint:
    def _model(self):
        from beforeholiday_tpu import amp
        from beforeholiday_tpu.optimizers import FusedSGD

        params = {"w": jnp.ones((4, 4), jnp.float32)}
        return amp.initialize(
            lambda p, x: x @ p["w"], params, FusedSGD(lr=0.1), "O2")

    def test_metrics_roundtrip_through_amp_state_dict(self):
        from beforeholiday_tpu.guard import StepGuard

        model = self._model()
        mon = TrainMonitor()
        guard = StepGuard(model.scaler)
        gstate = guard.init(model.params)
        m = mon.update(
            mon.init(), loss=jnp.float32(0.5),
            grads={"w": jnp.ones((4, 4))},
            scaler_state=gstate["scaler"], health=gstate["health"])

        sd = model.state_dict(gstate, metrics=m)
        assert "loss_scaler0" in sd and "health0" in sd and "monitor" in sd
        assert isinstance(sd["monitor"]["steps"], int)
        sd = json.loads(json.dumps(sd))  # must be JSON-serializable

        restored_scaler = model.load_state_dict(sd)
        assert set(restored_scaler) == {"scaler", "health"}
        restored_m = model.load_metrics(sd, mon)
        for k in mon.keys:
            np.testing.assert_allclose(
                np.asarray(restored_m[k]), np.asarray(m[k]), rtol=1e-6)

    def test_pre_monitor_checkpoints_still_load(self):
        """Backcompat both directions: a checkpoint written WITHOUT metrics
        (the PR-1 format) loads fine, and load_metrics reports None."""
        model = self._model()
        sstate = model.scaler.init()
        old_sd = model.state_dict(sstate)  # no metrics kwarg: old format
        assert "monitor" not in old_sd
        restored = model.load_state_dict(old_sd)
        assert "scale" in restored
        assert model.load_metrics(old_sd) is None

    def test_load_metrics_default_monitor(self):
        model = self._model()
        mon = TrainMonitor()
        m = mon.update(mon.init(), loss=jnp.float32(1.0))
        sd = model.state_dict(model.scaler.init(), metrics=m)
        restored = model.load_metrics(sd)  # constructs its own TrainMonitor
        assert float(restored["loss"]) == 1.0
