"""Telemetry layer tests (ISSUE 18 acceptance contracts):

* the mergeable log-spaced :class:`Histogram` keeps every quantile within
  its ANALYTIC error bound (``10**(1/k) - 1``) against a numpy-sort oracle
  at several geometries, merges bitwise (bucket-count addition), and its
  pure-``jnp`` ``bucketize`` path produces the exact host-path counts;
* ``MetricsLogger.drain`` splits Histogram values out of a metrics dict
  into ``<name>_p50/_p95/_p99`` columns without breaking readers of
  pre-histogram logs (jsonl rows stay self-describing, csv schema fixed at
  the first row);
* ``goodput_report`` classifies a constructed timeline by priority claiming
  and the integer-microsecond breakdown sums EXACTLY to wall time — then
  the same contract on a real seeded fault-schedule ElasticTrainer run
  (preempt 8→4, grow back 4→8) with checkpoint badput consistent with the
  ckpt ledger;
* :class:`ServingTelemetry` lifecycle accounting is exact under a fake
  clock (TTFT/ITL/e2e, preemption replays, per-request Perfetto tracks,
  scheduler counter tracks), threads through the real ContinuousBatcher
  without perturbing the token schedule, and the SLO multi-window burn
  rate fires the flight-recorder dump ONCE (latched) with the offending
  request records attached;
* the hierarchical MoE dispatch splits its comms payload per interconnect
  tier in ``comms_summary()["by_tier"]`` (slice stage on DCN, intra stage
  on ICI, exact bytes each) while the flat dispatch books a single tier.
"""

from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.infer import Request, ServingTelemetry, SLOPolicy
from beforeholiday_tpu.moe import MoEConfig, init_experts, moe_layer
from beforeholiday_tpu.monitor import (
    Histogram,
    MetricsLogger,
    TrainMonitor,
    classify_span,
    goodput_report,
)
from beforeholiday_tpu.monitor import comms
from beforeholiday_tpu.monitor.flight import FlightRecorder
from beforeholiday_tpu.monitor.trace import timeline
from beforeholiday_tpu.parallel.parallel_state import EXPERT_AXIS

pytestmark = pytest.mark.telemetry



@pytest.fixture(autouse=True)
def _fresh_ledgers():
    from beforeholiday_tpu import elastic
    from beforeholiday_tpu.monitor.comms import reset_comms_ledger
    from beforeholiday_tpu.monitor.compile import reset_compile_counts

    reset_comms_ledger()
    reset_compile_counts()
    elastic.reset_ckpt_ledger()
    yield


def _smap(fn, mesh, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


# ------------------------------------------------------------------ histogram


def _sort_oracle(values, q: float) -> float:
    """The exact quantile under the histogram's rank convention."""
    s = np.sort(np.asarray(values, dtype=np.float64).reshape(-1))
    n = s.size
    rank = 0 if q <= 0.0 else min(n - 1, int(np.ceil(q * n)) - 1)
    return float(s[rank])


class TestHistogram:
    @pytest.mark.parametrize("k", [8, 20, 40])
    def test_quantile_within_analytic_bound(self, k):
        """At every geometry, every quantile estimate overestimates the
        sort oracle by at most ``10**(1/k) - 1`` — exact, not statistical."""
        rng = np.random.RandomState(7)
        data = rng.lognormal(mean=-4.0, sigma=1.5, size=20_000)
        h = Histogram(lo=1e-6, decades=9, bins_per_decade=k)
        h.update(data)
        assert h.count == data.size
        bound = h.quantile_error_bound
        assert bound == pytest.approx(10.0 ** (1.0 / k) - 1.0)
        for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
            est, exact = h.quantile(q), _sort_oracle(data, q)
            rel = est / exact - 1.0
            # upper-edge estimate: never below the sample, never more than
            # one bucket's growth above it
            assert -1e-12 <= rel <= bound * (1.0 + 1e-9), (k, q, rel)

    def test_merge_is_bitwise_bucket_addition(self):
        rng = np.random.RandomState(11)
        a = rng.lognormal(mean=-3.0, sigma=1.0, size=5_000)
        b = rng.lognormal(mean=-5.0, sigma=2.0, size=3_000)
        geo = dict(lo=1e-6, decades=9, bins_per_decade=20)
        ha, hb, hall = Histogram(**geo), Histogram(**geo), Histogram(**geo)
        ha.update(a)
        hb.update(b)
        hall.update(np.concatenate([a, b]))
        merged = ha.merge(hb)
        assert merged is ha  # in-place, returns self
        assert np.array_equal(ha.counts(), hall.counts())
        for q in (0.5, 0.95, 0.99):
            assert ha.quantile(q) == hall.quantile(q)

    def test_device_bucketize_matches_host_path(self):
        rng = np.random.RandomState(3)
        data = rng.lognormal(mean=-4.0, sigma=1.2, size=4_096).astype(
            np.float32
        )
        h_dev = Histogram(lo=1e-5, decades=8, bins_per_decade=20)
        h_host = Histogram(lo=1e-5, decades=8, bins_per_decade=20)
        counts = jax.jit(h_dev.bucketize)(jnp.asarray(data))
        h_dev.add_counts(np.asarray(counts))
        h_host.update(data)
        assert np.array_equal(h_dev.counts(), h_host.counts())

    def test_out_of_range_samples_clamp_not_drop(self):
        h = Histogram(lo=1e-3, decades=3, bins_per_decade=10)  # [1e-3, 1)
        h.update([1e-6, 5e2, 7e3])
        assert h.count == 3
        assert h.counts()[0] == 1                 # underflow slot
        assert h.counts()[-1] == 2                # overflow slot
        assert h.quantile(0.0) == pytest.approx(1e-3)   # reported as lo
        assert h.quantile(1.0) == pytest.approx(1.0)    # clamped to top edge

    def test_empty_and_reset(self):
        h = Histogram()
        assert h.count == 0
        assert np.isnan(h.quantile(0.5))
        h.update([1e-3])
        assert h.count == 1
        h.reset()
        assert h.count == 0

    def test_geometry_mismatch_and_type_errors(self):
        h = Histogram(bins_per_decade=20)
        with pytest.raises(ValueError, match="geometry mismatch"):
            h.merge(Histogram(bins_per_decade=40))
        with pytest.raises(TypeError):
            h.merge([1, 2, 3])
        with pytest.raises(ValueError, match="slots"):
            h.add_counts(np.zeros(3, np.int64))
        with pytest.raises(ValueError):
            Histogram(lo=0.0)


# ------------------------------------------------------- MetricsLogger drain


class TestMetricsLoggerHistogramDrain:
    def test_drain_emits_quantile_columns(self, tmp_path):
        mon = TrainMonitor()
        h = Histogram(lo=1e-5, decades=8, bins_per_decade=20)
        h.update(np.random.RandomState(0).lognormal(-4.0, 1.0, 500))
        path = tmp_path / "m.jsonl"
        with MetricsLogger(mon, path=str(path)) as lg:
            row = lg.drain({**mon.init(), "latency_s": h}, step=3)
        for q, tag in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
            assert row[f"latency_s_{tag}"] == h.quantile(q)
        assert "loss" in row and row["step"] == 3  # base schema intact
        (logged,) = [json.loads(l) for l in path.read_text().splitlines()]
        assert logged == row

    def test_pre_histogram_jsonl_readers_unaffected(self, tmp_path):
        """A reader loop over a pre-histogram log and a histogram-bearing
        log is the same code: jsonl rows are self-describing."""
        mon = TrainMonitor()
        old, new = tmp_path / "old.jsonl", tmp_path / "new.jsonl"
        with MetricsLogger(mon, path=str(old)) as lg:
            lg.drain(mon.init(), step=0)
        h = Histogram()
        h.update([1e-3])
        with MetricsLogger(mon, path=str(new)) as lg:
            lg.drain({**mon.init(), "ttft_s": h}, step=0)
        rows = [json.loads(l) for p in (old, new)
                for l in p.read_text().splitlines()]
        assert all(r["loss"] == 0.0 for r in rows)      # old reader code path
        assert "ttft_s_p99" not in rows[0]              # old log unchanged
        assert rows[1]["ttft_s_p99"] == h.quantile(0.99)

    def test_csv_schema_fixed_at_first_row_includes_quantiles(self, tmp_path):
        import csv

        mon = TrainMonitor()
        h = Histogram()
        h.update([2e-3, 4e-3])
        path = tmp_path / "m.csv"
        with MetricsLogger(mon, path=str(path), fmt="csv") as lg:
            lg.drain({**mon.init(), "itl_s": h}, step=0)
            lg.drain({**mon.init(), "itl_s": h}, step=1)
        rows = list(csv.DictReader(path.open()))
        assert len(rows) == 2
        assert float(rows[0]["itl_s_p50"]) == h.quantile(0.50)
        assert float(rows[1]["itl_s_p99"]) == h.quantile(0.99)


# -------------------------------------------------------------- goodput ledger


def _ev(ph: str, name: str, ts: int, pid: int = 0, tid: int = 0):
    return {"ph": ph, "name": name, "ts": ts, "pid": pid, "tid": tid}


def _oracle_timeline():
    """step[0,100] with a nested exposed ckpt:wait[50,80], the full resize
    machinery [100,200], step[200,380], then 20 µs of unclaimed tail."""
    return [
        _ev("B", "step", 0),
        _ev("B", "ckpt:wait", 50), _ev("E", "ckpt:wait", 80),
        _ev("E", "step", 100),
        _ev("B", "elastic:drain", 100), _ev("E", "elastic:drain", 130),
        _ev("B", "elastic:restore", 130), _ev("E", "elastic:restore", 180),
        _ev("B", "elastic:reshard", 180), _ev("E", "elastic:reshard", 200),
        _ev("B", "step", 200), _ev("E", "step", 380),
    ]


class TestGoodputLedger:
    def test_classify_span(self):
        assert classify_span("step") == "productive"
        assert classify_span("ckpt:wait") == "checkpoint"
        assert classify_span("ckpt:submit") == "checkpoint"
        assert classify_span("ckpt:backpressure") == "checkpoint"
        assert classify_span("ckpt:serialize") is None   # writer thread work
        assert classify_span("elastic:drain") == "drain"
        assert classify_span("elastic:restore") == "restore"
        assert classify_span("elastic:hang") == "hang"
        assert classify_span("elastic:reshard") == "reshard"
        assert classify_span("compile") == "compile"
        assert classify_span("compile:train_step") == "compile"
        assert classify_span("fwd") is None
        assert classify_span("train", step_span="train") == "productive"

    def test_constructed_oracle_sums_exactly(self):
        rep = goodput_report(_oracle_timeline(), wall_us=(0, 400))
        # checkpoint outranks productive: the exposed wait eats 30 µs out
        # of the first step, the tail past the last span is "other"
        assert rep["wall_us"] == 400
        assert rep["checkpoint_us"] == 30
        assert rep["productive_us"] == 250
        assert rep["drain_us"] == 30
        assert rep["restore_us"] == 50
        assert rep["reshard_us"] == 20
        assert rep["hang_us"] == 0
        assert rep["compile_us"] == 0
        assert rep["other_us"] == 20
        parts = sum(rep[f"{c}_us"] for c in (
            "productive", "checkpoint", "drain", "restore", "hang",
            "reshard", "compile", "other",
        ))
        assert parts == rep["wall_us"]          # EXACT, integer arithmetic
        assert rep["badput_us"] == 150
        assert rep["goodput_fraction"] == 250 / 400

    def test_default_wall_is_the_step_tracks_extent(self):
        rep = goodput_report(_oracle_timeline())
        assert rep["wall_us"] == 380            # [first ts, last ts]
        assert rep["other_us"] == 0

    def test_other_tracks_are_hidden_work_not_badput(self):
        """Writer-thread ckpt spans and other ranks' steps never book —
        classification is confined to the step-owning track."""
        events = _oracle_timeline() + [
            _ev("B", "ckpt:serialize", 0, tid=1),
            _ev("E", "ckpt:serialize", 390, tid=1),
            _ev("B", "ckpt:wait", 0, pid=1), _ev("E", "ckpt:wait", 400, pid=1),
        ]
        rep = goodput_report(events, wall_us=(0, 400))
        assert rep["checkpoint_us"] == 30
        assert rep["productive_us"] == 250

    def test_resize_and_ckpt_metadata_fold_in(self):
        class _Resize:
            reason, stall_s = "preemption", 0.25

        rep = goodput_report(
            _oracle_timeline(), wall_us=(0, 400),
            resize_events=[_Resize()],
            ckpt={"exposed_s": 0.03, "hidden_s": 1.5},
            compile_counts={"train": {"signatures": 2}},
        )
        assert rep["resize_by_reason"]["preemption"] == {
            "events": 1, "stall_s": 0.25,
        }
        assert rep["ckpt_exposed_s"] == 0.03
        assert rep["ckpt_hidden_s"] == 1.5
        assert rep["compile_signatures"] == 2

    def test_real_fault_schedule_run(self, devices8, tmp_path):
        """The seeded goodput drill: preempt 8→4 mid-run, grow
        back 4→8 at the next checkpoint boundary, under a live timeline.
        ``_goodput_run`` asserts the exact sum, the resize reasons, the
        restore/reshard booking, and ckpt-ledger consistency internally."""
        from beforeholiday_tpu.testing.drills import _goodput_run

        report, events = _goodput_run(str(tmp_path))
        assert 0.0 < report["goodput_fraction"] < 1.0
        assert report["wall_us"] > 0
        assert report["resize_by_reason"]["preemption"]["events"] == 1
        assert report["resize_by_reason"]["grow"]["events"] == 1
        assert report["ckpt_exposed_s"] >= 0.0


# --------------------------------------------------------- serving telemetry


def _req(rid: int, arrival: float, prompt_len: int = 4,
         max_new: int = 4) -> Request:
    return Request(rid=rid, prompt=list(range(1, prompt_len + 1)),
                   max_new_tokens=max_new, arrival=arrival)


class TestServingTelemetry:
    def test_lifecycle_accounting_under_fake_clock(self):
        tel = ServingTelemetry()
        r = _req(0, arrival=1.0, max_new=3)
        tel.on_enqueue(r, 1.0)
        r.first_token_time = 1.1
        tel.on_admit([r], 1.1, prefill_s=0.08)
        tel.on_decode_tick([r], 1.2)
        tel.on_decode_tick([r], 1.35)
        r.out = [5, 6, 7]
        tel.on_retire([r], 1.4)
        tel.on_step(1.4, free_pages=10, active=0, waiting=0, max_batch=4)

        rec = tel.records[0]
        assert rec.enqueue == 1.0 and rec.admit == 1.1
        assert rec.ttft_s == pytest.approx(0.1)
        assert rec.e2e_s == pytest.approx(0.4)
        assert rec.tokens == 3 and rec.prefill_s == pytest.approx(0.08)

        rep = tel.serving_report()
        assert rep["requests"] == 1 and rep["finished"] == 1
        assert rep["steps"] == 1
        assert rep["tokens"] == 3 and rep["tokens_delivered"] == 3
        assert rep["wall_s"] == pytest.approx(0.4)
        assert rep["goodput_tokens_per_s"] == pytest.approx(3 / 0.4)
        bound = rep["quantile_error_bound"]
        # upper-edge estimates: within one bucket of the true latencies
        assert 100.0 <= rep["ttft_p50_ms"] <= 100.0 * (1 + bound) + 1e-9
        assert 400.0 <= rep["e2e_p99_ms"] <= 400.0 * (1 + bound) + 1e-9
        # ITL gaps were 0.1 and 0.15
        assert 150.0 <= rep["itl_p99_ms"] <= 150.0 * (1 + bound) + 1e-9

    def test_preemption_replay_keeps_first_admit_and_ttft(self):
        tel = ServingTelemetry()
        r = _req(0, arrival=0.0)
        tel.on_enqueue(r, 0.0)
        r.first_token_time = 0.1
        tel.on_admit([r], 0.1, prefill_s=0.05)
        tel.on_preempt(r, 0.2)
        tel.on_admit([r], 0.5, prefill_s=0.07)  # replay re-prefill
        rec = tel.records[0]
        assert rec.admit == 0.1                 # FIRST admission wins
        assert rec.first_token == 0.1
        assert rec.preemptions == 1 and rec.replays == 1
        rep = tel.serving_report()
        assert rep["preemptions"] == 1 and rep["prefill_replays"] == 1

    def test_request_tracks_and_counter_tracks(self):
        with timeline() as rec:
            tel = ServingTelemetry()
            r = _req(7, arrival=0.0)
            tel.on_enqueue(r, 0.0)
            r.first_token_time = 0.1
            tel.on_admit([r], 0.1, prefill_s=0.05)
            tel.on_preempt(r, 0.2)
            tel.on_admit([r], 0.3, prefill_s=0.05)
            r.out = [1, 2, 3, 4]
            tel.on_retire([r], 0.4)
            tel.on_step(0.4, free_pages=9, active=2, waiting=3, max_batch=4)
        events = rec.events()
        # the request's own track (pid = rid): a flat, balanced span chain
        # queued -> active -> (preempt) queued -> active, with the TTFT
        # instant riding it
        track = [e for e in events if e["pid"] == 7 and e["ph"] in "BEi"]
        assert [(e["ph"], e.get("name")) for e in track] == [
            ("B", "req:queued"), ("E", None),
            ("B", "req:active"), ("i", "first_token"),
            ("E", None), ("B", "req:queued"),
            ("E", None), ("B", "req:active"),
            ("E", None),
        ]
        gauges = {e["name"]: e["args"] for e in events if e["ph"] == "C"}
        assert gauges["pages_free"] == {"value": 9.0}
        assert gauges["batch_fill"] == {"value": 0.5}
        assert gauges["queue_depth"] == {"value": 3.0}

    def test_no_recorder_means_no_span_state(self):
        tel = ServingTelemetry()
        r = _req(0, arrival=0.0)
        tel.on_enqueue(r, 0.0)
        r.first_token_time = 0.1
        tel.on_admit([r], 0.1, prefill_s=0.0)
        assert tel._open_span == {}             # zero-cost without a timeline

    def test_policy_validation(self):
        with pytest.raises(ValueError, match="objective"):
            SLOPolicy(ttft_ms=1.0, objective=1.0)
        with pytest.raises(ValueError, match="short_window_s"):
            SLOPolicy(ttft_ms=1.0, short_window_s=10.0, long_window_s=1.0)
        assert SLOPolicy(ttft_ms=5.0, e2e_ms=100.0).targets() == {
            "ttft_ms": 5.0, "e2e_ms": 100.0,
        }

    def _burn(self, tel, n, ttft_s, t0=0.0, dt=0.2):
        """Feed n requests whose TTFT is ``ttft_s``, spread dt apart."""
        for i in range(n):
            t = t0 + i * dt
            r = _req(100 + i, arrival=t)
            tel.on_enqueue(r, t)
            r.first_token_time = t + ttft_s
            tel.on_admit([r], t + ttft_s, prefill_s=ttft_s)
            r.out = [1]
            tel.on_retire([r], t + ttft_s)

    def test_slo_burn_rate_breach_dumps_flight_once(self, tmp_path):
        policy = SLOPolicy(ttft_ms=10.0, objective=0.5, short_window_s=1.0,
                           long_window_s=4.0, burn_threshold=1.5,
                           min_events=4)
        tel = ServingTelemetry(slo=policy)
        fr = FlightRecorder(16, path=str(tmp_path / "slo.json"),
                            auto_dump_on_rollback=False)
        with fr:
            self._burn(tel, 10, ttft_s=0.05)    # 50 ms >> the 10 ms target
            assert tel.breached == {"ttft_ms": True}
            n_dumps = len(fr.dumps)
            assert n_dumps == 1
            self._burn(tel, 10, ttft_s=0.05, t0=3.0)
            assert len(fr.dumps) == n_dumps     # latched: one dump per run
        payload = json.loads(pathlib.Path(fr.dumps[0]).read_text())
        assert payload["reason"] == "slo_breach:ttft_ms"
        snap = payload["snapshots"][-1]
        offenders = snap["extra"]["requests"]
        assert offenders and all(
            o["observed_ttft_ms"] == pytest.approx(50.0) for o in offenders
        )
        assert snap["metrics"]["slo_target_ttft_ms"] == 10.0
        assert snap["metrics"]["slo_burn_short_ttft_ms"] > 1.5

    def test_slo_quiet_when_target_met(self, tmp_path):
        policy = SLOPolicy(ttft_ms=100.0, objective=0.5, short_window_s=1.0,
                           long_window_s=4.0, min_events=4)
        tel = ServingTelemetry(slo=policy)
        fr = FlightRecorder(16, path=str(tmp_path / "quiet.json"),
                            auto_dump_on_rollback=False)
        with fr:
            self._burn(tel, 10, ttft_s=0.05)    # 50 ms meets 100 ms
        assert tel.breached == {"ttft_ms": False}
        assert fr.dumps == []

    def test_threads_through_real_batcher_without_perturbing_tokens(self):
        from beforeholiday_tpu.infer import (
            ContinuousBatcher, EngineConfig, InferenceEngine,
        )
        from beforeholiday_tpu.testing import gpt

        cfg = gpt.GPTConfig(vocab_size=64, seq_len=64, d_model=32,
                            n_heads=2, n_layers=2, dtype=jnp.float32)
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        ecfg = EngineConfig(
            max_seq_len=32, page_size=8, num_pages=17, batch_buckets=(2, 4),
            prefill_seq_buckets=(8, 16), entry_prefix="telemetry_test",
        )
        engine = InferenceEngine(params, cfg, ecfg)
        rng = np.random.RandomState(0)

        def _run(telemetry):
            engine.reset_cache()
            bat = ContinuousBatcher(engine, telemetry=telemetry)
            for i in range(6):
                bat.submit(Request(
                    rid=i,
                    prompt=list(map(int, rng.randint(1, 64, 4 + i % 3))),
                    max_new_tokens=3 + i % 4,
                ))
            rng.seed(0)
            return bat.run()

        plain = _run(None)
        tel = ServingTelemetry()
        observed = _run(tel)
        # greedy decode on the same prompts: the observer is invisible
        assert [r.out for r in sorted(observed, key=lambda r: r.rid)] == \
            [r.out for r in sorted(plain, key=lambda r: r.rid)]
        rep = tel.serving_report()
        assert rep["requests"] == rep["finished"] == 6
        assert rep["tokens_delivered"] == sum(3 + i % 4 for i in range(6))
        assert all(r.finish is not None for r in tel.records.values())
        assert rep["ttft_p50_ms"] > 0.0


# -------------------------------------------------------- comms tier rollup


class TestCommsByTier:
    def _run_moe(self, devices, axis_names, expert_axis, hierarchical):
        cfg = MoEConfig(n_experts=8, top_k=2, capacity_factor=8.0)
        D, T = 32, 16
        params = init_experts(jax.random.PRNGKey(0), cfg.n_experts, D, 64)
        w_router = jnp.asarray(
            np.random.RandomState(0).randn(D, cfg.n_experts).astype(
                np.float32
            ) * 0.1
        )
        groups = 8 if hierarchical else 4
        x = jnp.asarray(np.random.RandomState(5).randn(
            groups * T, D).astype(np.float32))
        C = cfg.capacity(T)
        mesh = (
            Mesh(np.asarray(devices).reshape(2, 4), axis_names)
            if hierarchical else Mesh(np.asarray(devices[:4]), axis_names)
        )
        ax = axis_names if hierarchical else axis_names[0]
        f = jax.jit(_smap(
            lambda xl, w, p: moe_layer(
                xl, w, p, cfg, expert_axis=ax, capacity=C,
                hierarchical=hierarchical,
            )[0],
            mesh, (P(ax), P(), P(ax)), P(ax),
        ))
        f(x, w_router, params)
        return cfg.n_experts * C * D * 4   # one a2a payload, fp32 bytes

    def test_flat_dispatch_books_single_ici_tier(self, devices8):
        payload = self._run_moe(devices8, (EXPERT_AXIS,), EXPERT_AXIS, False)
        (row,) = [r for r in comms.comms_summary()
                  if r["subsystem"] == "moe"]
        assert set(row["by_tier"]) == {"ici"}
        tier = row["by_tier"]["ici"]
        # dispatch out + combine back, the analytic (E, C, D) payload each
        assert tier["bytes"] == row["bytes"] == 2 * payload
        assert tier["calls"] == row["calls"]
        assert tier["compression_ratio"] == 1.0
        sites = {r["site"] for r in comms.comms_records()
                 if r["site"].startswith("moe.")}
        assert sites == {"moe.dispatch", "moe.combine"}

    def test_hierarchical_dispatch_splits_dcn_and_ici(self, devices8):
        payload = self._run_moe(
            devices8, ("slice", "intra"), ("slice", "intra"), True
        )
        (row,) = [r for r in comms.comms_summary()
                  if r["subsystem"] == "moe"]
        assert set(row["by_tier"]) == {"dcn", "ici"}
        # the slice stage (dispatch + combine) rides DCN, the intra stage
        # rides ICI — the full (E, C, D) payload once per a2a per direction
        assert row["by_tier"]["dcn"]["bytes"] == 2 * payload
        assert row["by_tier"]["ici"]["bytes"] == 2 * payload
        assert (row["by_tier"]["dcn"]["bytes"]
                + row["by_tier"]["ici"]["bytes"]) == row["bytes"]
        by_site = {r["site"]: r for r in comms.comms_records()}
        for site, tier in [
            ("moe.dispatch.slice", "dcn"), ("moe.combine.slice", "dcn"),
            ("moe.dispatch.intra", "ici"), ("moe.combine.intra", "ici"),
        ]:
            assert by_site[site]["tier"] == tier
            assert by_site[site]["bytes"] == payload
