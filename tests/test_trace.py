"""Trace layer (ISSUE 3 acceptance contracts):

* comms ledger byte counts match hand-computed oracles for the DDP allreduce
  and the TP all-gather / sequence-parallel reduce-scatter on the 8-device
  CPU mesh, and ``ledger_scope`` attributes records to the issuing layer;
* ``timeline`` exports a ``trace.json`` that parses as Chrome trace-event
  format with balanced, properly nested ``B``/``E`` spans per (pid, tid),
  and both ``monitor.span`` and the comms ledger mirror into the active
  recorder;
* the recompile sentinel counts distinct abstract signatures per entry and
  warns EXACTLY once per entry on a forced shape change;
* the pipeline bubble accounting matches the closed form ``(p-1)/(m+p-1)``
  for plain 1F1B and the phase counts obey the 1F1B warmup arithmetic.
"""

import json
import logging
import threading

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
from beforeholiday_tpu.monitor import comms
from beforeholiday_tpu.monitor.trace import active_recorder
from beforeholiday_tpu.parallel.distributed import reduce_gradients
from beforeholiday_tpu.transformer import pipeline_parallel as pp
from beforeholiday_tpu.transformer.pipeline_parallel import schedules
from beforeholiday_tpu.transformer.tensor_parallel import mappings
from beforeholiday_tpu.utils.logging import get_logger, reset_warn_once

pytestmark = pytest.mark.trace


@pytest.fixture(autouse=True)
def _fresh_trace_state():
    monitor.reset_comms_ledger()
    monitor.reset_compile_counts()
    reset_warn_once()
    yield
    monitor.reset_comms_ledger()
    monitor.reset_compile_counts()
    reset_warn_once()


@pytest.fixture
def data_mesh(devices8):
    return Mesh(np.asarray(devices8).reshape(8), ("data",))


@pytest.fixture
def tensor_mesh(devices8):
    return Mesh(np.asarray(devices8).reshape(8), ("tensor",))


class _Capture(logging.Handler):
    """propagate=False on the repo loggers — capture with a direct handler."""

    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


def _site_rows(site):
    return [r for r in monitor.comms_records() if r["site"] == site]


# -------------------------------------------------------------------------------
# comms ledger: byte-count oracles
# -------------------------------------------------------------------------------


class TestCommsLedgerOracles:
    def test_ddp_allreduce_byte_oracle(self, data_mesh):
        """reduce_gradients psums each leaf once per trace; the ledger must
        show the per-rank local payload: sum over leaves of size*itemsize."""
        grads = {
            "w": jnp.ones((8, 4, 8), jnp.float32),  # sharded over data
            "b": jnp.ones((8, 16), jnp.float32),
        }

        @jax.jit
        @shard_map(mesh=data_mesh, in_specs=(P("data"),), out_specs=P("data"))
        def ddp_reduce(g):
            return reduce_gradients(g, axis_name="data")

        jax.block_until_ready(ddp_reduce(grads))

        rows = _site_rows("ddp.reduce_gradients")
        assert rows, "no ledger rows for the DDP allreduce site"
        assert {r["kind"] for r in rows} == {"psum"}
        assert {r["axis"] for r in rows} == {"data"}
        assert {r["dtype"] for r in rows} == {"float32"}
        # two leaves, each recorded once at trace time; local shards are
        # (4, 8) f32 and (16,) f32 -> 128 + 64 bytes
        assert sum(r["calls"] for r in rows) == 2
        assert sum(r["bytes"] for r in rows) == 4 * 8 * 4 + 16 * 4

    def test_tp_all_gather_byte_oracle(self, tensor_mesh):
        """TP gather's forward all-gather records the LOCAL shard bytes (the
        quantity each rank hands to the interconnect)."""
        x = jnp.ones((4, 8 * 16), jnp.float32)  # last dim sharded over tensor

        @jax.jit
        @shard_map(mesh=tensor_mesh, in_specs=(P(None, "tensor"),),
                   out_specs=P())
        def gather(x):
            return mappings.gather_from_tensor_model_parallel_region(
                x, "tensor")

        out = jax.block_until_ready(gather(x))
        assert out.shape == (4, 8 * 16)

        rows = _site_rows("tp.gather_from_region")
        assert len(rows) == 1
        r = rows[0]
        assert (r["kind"], r["axis"], r["dtype"]) == (
            "all_gather", "tensor", "float32")
        # one trace-time record of the local (4, 16) f32 shard
        assert r["calls"] == 1
        assert r["bytes"] == 4 * 16 * 4

    def test_sp_reduce_scatter_byte_oracle(self, tensor_mesh):
        """The SP reduce-scatter's input is the FULL per-rank partial (each
        rank contributes every row) — the oracle is the unsharded operand."""
        x = jnp.ones((16, 4), jnp.float32)  # replicated partials, dim 0 scatters

        @jax.jit
        @shard_map(mesh=tensor_mesh, in_specs=(P(),),
                   out_specs=P("tensor"))
        def rs(x):
            return mappings.reduce_scatter_to_sequence_parallel_region(
                x, "tensor")

        out = jax.block_until_ready(rs(x))
        # psum over 8 ranks of ones, scattered: every element is 8.0
        np.testing.assert_allclose(np.asarray(out), 8.0)

        rows = _site_rows("sp.reduce_scatter_to_region")
        assert len(rows) == 1
        r = rows[0]
        assert (r["kind"], r["dtype"]) == ("psum_scatter", "float32")
        assert r["calls"] == 1
        assert r["bytes"] == 16 * 4 * 4

    def test_ledger_scope_attribution_and_rollup(self):
        with comms.ledger_scope("column_parallel_linear"):
            comms.record("psum", "tensor", jnp.zeros((4, 8), jnp.bfloat16),
                         site="tp.reduce_from_region")
        comms.record("ppermute", "pipe", jnp.zeros((2, 2), jnp.float32),
                     site="pp.fwd_ring")

        rows = monitor.comms_records()
        scoped = [r for r in rows if r["scope"] == "column_parallel_linear"]
        assert len(scoped) == 1
        assert scoped[0]["dtype"] == "bfloat16"
        assert scoped[0]["bytes"] == 4 * 8 * 2

        summary = {s["subsystem"]: s for s in monitor.comms_summary()}
        assert set(summary) == {"tp", "pp"}
        assert summary["tp"]["bytes"] == 64
        assert summary["tp"]["sites"] == 1
        assert summary["pp"]["by_kind"]["ppermute"]["calls"] == 1

    def test_trace_time_not_run_time_accounting(self, data_mesh):
        """jit caching: re-running a compiled step must NOT re-record."""
        g = {"w": jnp.ones((8, 4), jnp.float32)}

        @jax.jit
        @shard_map(mesh=data_mesh, in_specs=(P("data"),), out_specs=P("data"))
        def step(g):
            return reduce_gradients(g, axis_name="data")

        jax.block_until_ready(step(g))
        first = sum(r["calls"] for r in _site_rows("ddp.reduce_gradients"))
        jax.block_until_ready(step(g))
        jax.block_until_ready(step(g))
        again = sum(r["calls"] for r in _site_rows("ddp.reduce_gradients"))
        assert first == again == 1


# -------------------------------------------------------------------------------
# timeline: trace.json validity + span nesting
# -------------------------------------------------------------------------------


def _check_nesting(events):
    """B/E pairs must balance per (pid, tid) with stack discipline and
    non-decreasing timestamps per thread."""
    stacks = {}
    last_ts = {}
    for ev in events:
        ph = ev["ph"]
        if ph == "M":
            continue
        key = (ev["pid"], ev["tid"])
        assert ev["ts"] >= last_ts.get(key, 0.0)
        last_ts[key] = ev["ts"]
        if ph == "B":
            stacks.setdefault(key, []).append(ev["name"])
        elif ph == "E":
            assert stacks.get(key), f"E with no open span on {key}"
            stacks[key].pop()
        elif ph == "i":
            assert ev.get("s") in ("t", "p", "g")
        elif ph == "C":
            # counter samples carry numeric series in args and never touch
            # the span stack
            assert ev["args"], "counter event with no series"
            assert all(isinstance(v, (int, float))
                       for v in ev["args"].values())
        else:
            raise AssertionError(f"unexpected phase {ph!r}")
    assert all(not s for s in stacks.values()), f"unclosed spans: {stacks}"


class TestTimeline:
    def test_trace_json_valid_chrome_trace(self, tmp_path):
        path = tmp_path / "trace.json"
        with monitor.timeline(str(path)) as rec:
            with rec.span("step"):
                with rec.span("forward"):
                    rec.instant("ckpt_marker")
                with rec.span("backward", rank=1):
                    pass
        data = json.loads(path.read_text())
        assert set(data) == {"traceEvents", "displayTimeUnit"}
        events = data["traceEvents"]
        # per-rank process metadata rows for ranks 0 and 1
        meta = [e for e in events if e["ph"] == "M"]
        assert {e["pid"] for e in meta if e["name"] == "process_name"} == {0, 1}
        names = [e.get("name") for e in events if e["ph"] == "B"]
        assert names == ["step", "forward", "backward"]
        _check_nesting(events)

    def test_monitor_span_routes_to_active_recorder(self):
        with monitor.timeline() as rec:
            assert active_recorder() is rec
            with monitor.span("host_work"):
                pass
        assert active_recorder() is None
        phases = [(e["ph"], e.get("name")) for e in rec.events()
                  if e["ph"] in ("B", "E")]
        assert ("B", "host_work") in phases
        assert phases.count(("E", None)) == 1
        # outside a timeline the span is a valid no-recorder context and
        # must not append to the (now inactive) recorder
        n = len(rec.events())
        with monitor.span("untimed"):
            pass
        assert len(rec.events()) == n

    def test_comms_records_mirror_as_instants(self):
        with monitor.timeline() as rec:
            with comms.ledger_scope("vocab_parallel_embedding"):
                comms.record("all_gather", "tensor",
                             jnp.zeros((2, 4), jnp.float32),
                             site="tp.gather_from_region")
        inst = [e for e in rec.events() if e["ph"] == "i"]
        assert len(inst) == 1
        assert inst[0]["name"] == "all_gather:tp.gather_from_region"
        assert inst[0]["args"]["axis"] == "tensor"
        assert inst[0]["args"]["scope"] == "vocab_parallel_embedding"
        assert inst[0]["args"]["float32"] == 2 * 4 * 4
        _check_nesting(rec.events())

    def test_timeline_restores_previous_recorder(self):
        with monitor.timeline() as outer:
            with monitor.timeline() as inner:
                assert active_recorder() is inner
            assert active_recorder() is outer
        assert active_recorder() is None


class TestPerfettoMetadata:
    """Perfetto loads a trace by its metadata rows: every (process, thread)
    pair needs a ``thread_name``/``thread_sort_index`` row or multi-rank
    traces render as anonymous swimlanes in arbitrary order. These pin the
    row naming and the deterministic export ordering."""

    def _cross_rank_trace(self):
        """Nested spans on rank 0 overlapping in wall time with rank 1,
        plus counter samples riding both ranks' tracks."""
        rec = monitor.TraceRecorder()
        rec.begin("step", rank=0)
        rec.counter("pages_free", 61, rank=0)
        rec.begin("fwd", rank=0)
        rec.begin("step", rank=1)          # overlaps rank 0's open spans
        rec.end(rank=0)                    # close fwd
        rec.begin("psum:ddp.grads", rank=1)
        rec.counter("queue", {"waiting": 3, "active": 5.0}, rank=1)
        rec.end(rank=1)
        rec.end(rank=0)                    # close rank 0's step
        rec.counter("pages_free", 64, rank=0)
        rec.end(rank=1)                    # close rank 1's step
        return rec

    def test_every_rank_thread_pair_is_named_once(self):
        rec = self._cross_rank_trace()
        meta = [e for e in rec.events() if e["ph"] == "M"]
        by_name = {}
        for e in meta:
            by_name.setdefault(e["name"], []).append(e)
        # one process_name + process_sort_index per rank, sort_index == pid
        assert {(e["pid"], e["args"]["name"]) for e in by_name["process_name"]} \
            == {(0, "beforeholiday_tpu rank 0"), (1, "beforeholiday_tpu rank 1")}
        assert {(e["pid"], e["args"]["sort_index"])
                for e in by_name["process_sort_index"]} == {(0, 0), (1, 1)}
        # one thread_name/thread_sort_index per (pid, tid) — both ranks
        # record from this test's single host thread, so tid is 0 everywhere
        assert {(e["pid"], e["tid"], e["args"]["name"])
                for e in by_name["thread_name"]} \
            == {(0, 0, "host-thread-0"), (1, 0, "host-thread-0")}
        assert {(e["pid"], e["tid"], e["args"]["sort_index"])
                for e in by_name["thread_sort_index"]} == {(0, 0, 0), (1, 0, 0)}
        # repeated spans must not re-emit metadata
        rec.begin("again", rank=0)
        rec.end(rank=0)
        assert len([e for e in rec.events() if e["ph"] == "M"]) == len(meta)

    def test_second_host_thread_gets_its_own_named_row(self):
        rec = monitor.TraceRecorder()
        with rec.span("main_work"):
            t = threading.Thread(target=lambda: rec.begin("io_work"))
            t.start()
            t.join()
        tids = {e["tid"] for e in rec.events() if e["ph"] == "B"}
        assert tids == {0, 1}
        names = {e["tid"]: e["args"]["name"] for e in rec.events()
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert names == {0: "host-thread-0", 1: "host-thread-1"}

    def test_export_is_deterministic_and_ordered(self, tmp_path):
        rec = self._cross_rank_trace()
        p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
        rec.export(str(p1))
        rec.export(str(p2))
        assert p1.read_bytes() == p2.read_bytes()
        events = json.loads(p1.read_text())["traceEvents"]
        # all metadata rows first, sorted by (pid, tid, name) so Perfetto
        # assigns rows identically on every load ...
        n_meta = sum(1 for e in events if e["ph"] == "M")
        assert all(e["ph"] == "M" for e in events[:n_meta])
        assert all(e["ph"] != "M" for e in events[n_meta:])
        meta_keys = [(e["pid"], e["tid"], e["name"]) for e in events[:n_meta]]
        assert meta_keys == sorted(meta_keys)
        # ... then timed events in nondecreasing timestamp order
        ts = [e["ts"] for e in events[n_meta:]]
        assert ts == sorted(ts)

    def test_counter_events_export_but_stay_out_of_span_analysis(self, tmp_path):
        """'C' rows feed Perfetto counter tracks; the span analyzers must not
        mistake them for B/E pairs and scalars normalise to a float series."""
        rec = self._cross_rank_trace()
        counters = [e for e in rec.events() if e["ph"] == "C"]
        assert [(e["name"], e["pid"]) for e in counters] \
            == [("pages_free", 0), ("queue", 1), ("pages_free", 0)]
        assert counters[0]["args"] == {"value": 61.0}
        assert counters[1]["args"] == {"waiting": 3.0, "active": 5.0}
        # same spans reconstruct with and without the counter rows present
        path = tmp_path / "trace.json"
        rec.export(str(path))
        events = json.loads(path.read_text())["traceEvents"]
        timed = [e for e in events if e["ph"] != "M"]
        ivs_with = monitor.span_intervals(events)
        ivs_without = monitor.span_intervals(
            [e for e in timed if e["ph"] != "C"])
        assert ivs_with == ivs_without

    def test_exported_cross_rank_trace_round_trips_to_analyzers(self, tmp_path):
        """The exported JSON is the analyzers' input format (``span_intervals``,
        goodput): nesting stays valid per rank and the spans
        reconstruct exactly."""
        rec = self._cross_rank_trace()
        path = tmp_path / "trace.json"
        rec.export(str(path))
        events = json.loads(path.read_text())["traceEvents"]
        _check_nesting(events)
        ivs = monitor.span_intervals(events)
        by_rank = {}
        for iv in ivs:
            by_rank.setdefault(iv["pid"], []).append(iv["name"])
        assert sorted(by_rank[0]) == ["fwd", "step"]
        assert sorted(by_rank[1]) == ["psum:ddp.grads", "step"]
        # rank 0's fwd nests inside its step; rank 1's stack is independent
        depths = {(iv["pid"], iv["name"]): iv["depth"] for iv in ivs}
        assert depths[(0, "fwd")] == 1
        assert depths[(0, "step")] == 0
        assert depths[(1, "step")] == 0
        assert depths[(1, "psum:ddp.grads")] == 1


# -------------------------------------------------------------------------------
# recompile sentinel
# -------------------------------------------------------------------------------


class TestRecompileSentinel:
    def test_fires_exactly_once_on_forced_shape_change(self):
        h = _Capture()
        lg = get_logger()
        lg.addHandler(h)
        try:
            @monitor.track_compiles("test.entry")
            @jax.jit
            def f(x):
                return x + 1

            f(jnp.ones((4,)))
            f(jnp.ones((4,)))  # cached — same signature
            assert not [r for r in h.records
                        if "recompile sentinel" in r.getMessage()]

            f(jnp.ones((8,)))   # forced shape change -> 2nd signature
            f(jnp.ones((16,)))  # 3rd signature — warn_once swallows
            warnings = [r for r in h.records
                        if "recompile sentinel" in r.getMessage()]
            assert len(warnings) == 1
            assert "test.entry" in warnings[0].getMessage()

            counts = monitor.compile_counts()["test.entry"]
            assert counts == {"signatures": 3, "calls": 4}
            (row,) = [r for r in monitor.compile_summary()
                      if r["entry"] == "test.entry"]
            assert row["recompiled"] is True
        finally:
            lg.removeHandler(h)

    def test_dtype_and_static_changes_are_signatures_too(self):
        @monitor.track_compiles("test.dtype")
        @jax.jit
        def g(x):
            return x * 2

        g(jnp.ones((4,), jnp.float32))
        g(jnp.ones((4,), jnp.bfloat16))
        assert monitor.compile_counts()["test.dtype"]["signatures"] == 2

    def test_reset_rearms_the_warning(self):
        h = _Capture()
        lg = get_logger()
        lg.addHandler(h)
        try:
            @monitor.track_compiles("test.rearm")
            def f(x):
                return x

            f(jnp.ones((2,)))
            f(jnp.ones((3,)))
            monitor.reset_compile_counts()
            assert monitor.compile_counts() == {}
            f(jnp.ones((2,)))
            f(jnp.ones((3,)))
            warnings = [r for r in h.records
                        if "recompile sentinel" in r.getMessage()]
            assert len(warnings) == 2  # re-armed after reset
        finally:
            lg.removeHandler(h)


# -------------------------------------------------------------------------------
# pipeline bubble accounting (pure host arithmetic — no device needed)
# -------------------------------------------------------------------------------


class TestBubbleAccounting:
    @pytest.mark.parametrize("p", [2, 4, 8])
    @pytest.mark.parametrize("m", [1, 4, 16, 64])
    def test_plain_1f1b_matches_closed_form(self, m, p):
        assert pp.analytic_bubble_fraction(m, p) == pytest.approx(
            (p - 1) / (m + p - 1))

    def test_degenerate_and_interleaved_cases(self):
        assert pp.analytic_bubble_fraction(8, 1) == 0.0
        # interleaving divides the bubble term by v
        v2 = pp.analytic_bubble_fraction(8, 4, virtual_size=2)
        assert v2 == pytest.approx(1.5 / 9.5)
        assert v2 < pp.analytic_bubble_fraction(8, 4)

    def test_phase_counts_1f1b_arithmetic(self):
        m, p = 16, 4
        for r in range(p):
            c = pp.phase_counts(m, p, r)
            assert c["warmup"] == min(p - r - 1, m)
            assert c["warmup"] + c["steady"] == m
            assert c["cooldown"] == c["warmup"]
        assert pp.phase_counts(m, p, p - 1)["warmup"] == 0  # last stage

    def test_schedule_report_fields(self):
        rep = pp.schedule_report(8, 4)
        assert rep["schedule"] == "1f1b"
        assert rep["total_ticks"] == 8 + 4 + 4 - 1
        assert rep["engine_bubble_fraction"] == pytest.approx(
            (rep["total_ticks"] - 8) / rep["total_ticks"])
        assert rep["analytic_bubble_fraction"] == pytest.approx(3 / 11)
        assert [c["rank"] for c in rep["per_rank"]] == [0, 1, 2, 3]
        json.dumps(rep)  # JSON-ready by contract

    def test_record_schedule_stashes_and_mirrors_to_timeline(self):
        rep = pp.schedule_report(4, 2, schedule="1f1b")
        with monitor.timeline() as rec:
            schedules._record_schedule(rep)
        got = pp.last_schedule_report()
        assert got is not None and got["total_ticks"] == rep["total_ticks"]
        inst = [e for e in rec.events() if e["ph"] == "i"]
        assert inst and inst[0]["name"] == "pp.schedule:1f1b"
        assert inst[0]["args"]["analytic_bubble_fraction"] == pytest.approx(
            1 / 5)
