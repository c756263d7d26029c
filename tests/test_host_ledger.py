"""The host ledger (``monitor.host_records()``): compile phases and compile-cache
traffic by jitted function, host time under every span, the collector's pauses,
all on the timeline recorder's clock and, while a recorder is active, on it.

The events behind it fire on any backend, so everything here runs on the CPU:
what a fresh ``jax.jit`` function books and what a second call does not, a
nested ``jit`` enclosed in its caller, the persistent cache's miss then hit and
load, a span on the host and one inside ``jit`` (trace time, once),
``donate_step``'s two spans a step and the ring's bound, a forced collection
over a few million objects against an empty one, every kind on ``trace.json``
with valid nesting, ``reset``, and the listeners registered once however often
the modules are imported.
"""

import gc
import importlib
import importlib.util
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from beforeholiday_tpu import monitor
from beforeholiday_tpu.monitor import compile as compile_mod
from beforeholiday_tpu.monitor import spans as spans_mod
from beforeholiday_tpu.remat import donate_step

from test_trace import _check_nesting

# the package attribute ``trace`` is the profiler function: take the submodule by its path
trace_mod = importlib.import_module("beforeholiday_tpu.monitor.trace")

pytestmark = pytest.mark.trace

PHASES = ("compile.trace", "compile.lower", "compile.backend")


@pytest.fixture(autouse=True)
def _fresh_ledger():
    monitor.reset_host_ledger()
    yield
    monitor.reset_host_ledger()


def _named(name, records=None):
    records = monitor.host_records() if records is None else records
    return [r for r in records if r["name"] == name]


def _phases(name):
    """The three compile phases under ``name``: where the process has a
    persistent cache on, its events are booked under the name too."""
    return [r for r in _named(name) if r["kind"] in PHASES]


def _seconds(records):
    return sum(r["end"] - r["start"] for r in records) / 1e9


def _fresh_jit(name):
    """A jitted function JAX has not seen, named ``name``."""
    def fn(x):
        return jnp.tanh(x) * 3 + 1
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


# ------------------------------------------------------------ compile phases
@pytest.mark.parametrize("kind", PHASES)
def test_a_fresh_jit_books_each_phase_once_under_its_name(kind):
    before = time.perf_counter_ns()
    _fresh_jit("ledger_fresh_" + kind[8:])(jnp.ones((4,))).block_until_ready()
    after = time.perf_counter_ns()
    (event,) = [r for r in _named("ledger_fresh_" + kind[8:]) if r["kind"] == kind]
    # on the recorder's clock, inside the call, on the thread that compiled
    assert before <= event["start"] <= event["end"] <= after
    assert event["tid"] == threading.get_ident()


def test_a_second_call_books_nothing():
    fn = _fresh_jit("ledger_twice")
    fn(jnp.ones((4,))).block_until_ready()
    first = len(_phases("ledger_twice"))
    fn(jnp.ones((4,))).block_until_ready()
    assert len(_phases("ledger_twice")) == first == 3
    fn(jnp.ones((5,))).block_until_ready()          # a new shape is a new program
    assert len(_phases("ledger_twice")) == 6


def test_the_phases_follow_one_another():
    _fresh_jit("ledger_order")(jnp.ones((4,))).block_until_ready()
    by_kind = {r["kind"]: r for r in _named("ledger_order")}
    assert by_kind["compile.trace"]["end"] <= by_kind["compile.lower"]["start"] + 1_000_000
    assert by_kind["compile.lower"]["end"] <= by_kind["compile.backend"]["start"] + 1_000_000


def test_a_nested_jit_is_enclosed_and_the_outermost_sum_counts_it_once():
    inner = _fresh_jit("ledger_inner")

    @jax.jit
    def ledger_outer(x):
        return inner(x) + inner(x * 2)

    ledger_outer(jnp.ones((4,))).block_until_ready()
    traces = [r for r in monitor.host_records() if r["kind"] == "compile.trace"]
    (outer,) = _named("ledger_outer", traces)
    inners = _named("ledger_inner", traces)
    assert len(inners) == 2                       # each call site books; the second is a cache hit
    for r in inners:
        assert outer["start"] <= r["start"] and r["end"] <= outer["end"]
    # only the top level lowers and compiles
    assert {r["kind"] for r in _named("ledger_inner")} == {"compile.trace"}
    kept = trace_mod.outermost([r for r in traces if r["start"] >= outer["start"]])
    assert [r["name"] for r in kept] == ["ledger_outer"]
    assert _seconds(kept) < _seconds([outer] + inners)


def test_outermost_keeps_threads_apart():
    a = {"kind": "compile.trace", "name": "a", "start": 0, "end": 100, "tid": 1}
    b = {"kind": "compile.trace", "name": "b", "start": 10, "end": 20, "tid": 2}
    assert trace_mod.outermost([b, a]) == [a, b]
    assert trace_mod.outermost([dict(b, tid=1), a]) == [a]


def test_compile_summary_carries_the_seconds_beside_the_sentinel():
    inner = _fresh_jit("ledger_sum_inner")

    @monitor.track_compiles("ledger_sum")
    @jax.jit
    def ledger_sum(x):
        return inner(x) - 1

    try:
        ledger_sum(jnp.ones((4,))).block_until_ready()
        ledger_sum(jnp.ones((4,))).block_until_ready()
        rows = {r["entry"]: r for r in monitor.compile_summary()}
    finally:
        monitor.reset_compile_counts("ledger_sum")
    row = rows["ledger_sum"]
    assert (row["signatures"], row["calls"], row["recompiled"], row["compiles"]) == (1, 2, False, 1)
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    assert row["trace_outer_s"] == row["trace_s"]
    nested = rows["ledger_sum_inner"]
    assert nested["trace_s"] > 0 and nested["trace_outer_s"] == 0.0 and nested["compiles"] == 0
    assert nested["signatures"] == 0 and nested["calls"] == 0
    json.dumps(monitor.compile_summary())          # the flight recorder dumps it


# --------------------------------------------------------- the compile cache
@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent cache pointed at ``tmp_path``, every program kept; the
    process's settings are put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    prev = {n: getattr(jax.config, n) for n in names}
    cc.reset_cache()
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        yield
    finally:
        for n, v in prev.items():
            jax.config.update(n, v)
        cc.reset_cache()


@pytest.mark.parametrize("kind", ("cache.miss", "cache.hit", "cache.load"))
def test_the_persistent_cache_books_a_miss_then_a_hit_and_a_load(kind, persistent_cache):
    fn = _fresh_jit("ledger_cached")
    fn(jnp.ones((4,))).block_until_ready()
    cold = _named("ledger_cached")
    if not [r for r in cold if r["kind"] == "cache.miss"]:
        pytest.skip("this backend did not write the executable to the persistent cache")
    assert not [r for r in cold if r["kind"] in ("cache.hit", "cache.load")]
    monitor.reset_host_ledger()
    jax.clear_caches()                      # the in-process caches: the next call compiles again
    fn(jnp.ones((4,))).block_until_ready()
    warm = _named("ledger_cached")
    if not [r for r in warm if r["kind"] == "cache.hit"]:
        # a cache entry that cannot be read back warns and compiles again
        pytest.skip("the persistent cache entry could not be read back here")
    assert not [r for r in warm if r["kind"] == "cache.miss"]
    (backend,) = [r for r in warm if r["kind"] == "compile.backend"]
    picked = [r for r in (cold if kind == "cache.miss" else warm) if r["kind"] == kind]
    assert len(picked) == 1
    if kind != "cache.miss":
        # booked on the backend event that encloses it, under that entry's name
        assert backend["start"] <= picked[0]["start"] and picked[0]["end"] <= backend["end"]
    rows = {r["entry"]: r for r in monitor.compile_summary()}
    assert (rows["ledger_cached"]["cache_hits"], rows["ledger_cached"]["cache_misses"]) == (1, 0)


# ------------------------------------------------------------------- spans
def test_a_span_on_the_host_books_its_seconds():
    with monitor.span("ledger_host_span"):
        time.sleep(0.02)
    (event,) = _named("ledger_host_span")
    assert event["kind"] == "span" and 0.02 <= _seconds([event]) < 1.0
    assert event["tid"] == threading.get_ident()


def test_a_span_books_when_its_body_raises_and_not_when_disabled():
    with pytest.raises(ValueError):
        with monitor.span("ledger_raises"):
            raise ValueError("x")
    assert len(_named("ledger_raises")) == 1
    with monitor.span("ledger_disabled", enabled=False):
        pass
    assert not _named("ledger_disabled")


def test_a_span_inside_jit_books_once_at_trace_time():
    @jax.jit
    def ledger_traced(x):
        with monitor.span("ledger_scope"):
            return jnp.sin(x) * 2

    for _ in range(3):
        ledger_traced(jnp.ones((4,))).block_until_ready()
    (scope,) = _named("ledger_scope")
    (trace,) = [r for r in _named("ledger_traced") if r["kind"] == "compile.trace"]
    assert trace["start"] <= scope["start"] and scope["end"] <= trace["end"]


def test_nested_spans_and_the_outermost_of_a_chosen_few():
    with monitor.span("ledger_layer"):
        with monitor.span("ledger_kernel"):
            with monitor.span("ledger_norm"):
                pass
        with monitor.span("ledger_norm"):
            pass
    records = monitor.host_records()
    chosen = [r for r in records if r["name"] in ("ledger_kernel", "ledger_norm")]
    assert [r["name"] for r in trace_mod.outermost(chosen)] == ["ledger_kernel", "ledger_norm"]
    assert [r["name"] for r in trace_mod.outermost(
        [r for r in records if r["name"].startswith("ledger_")])] == ["ledger_layer"]


def test_donate_step_books_two_spans_a_step():
    step = donate_step(lambda s, x: (s + x, jnp.sum(x)), donate_argnums=(0,))
    state = jnp.zeros((4,))
    for _ in range(5):
        state, out = step(state, jnp.ones((4,)))
    jax.block_until_ready(out)
    calls, prepares = _named("donate_step.call"), _named("donate_step.prepare")
    assert len(calls) == len(prepares) == 5
    for p, c in zip(prepares, calls):
        assert p["end"] <= c["start"]
    # the first call traced and compiled inside its span; the later ones only dispatch
    compiles = [r for r in monitor.host_records() if r["kind"] == "compile.backend"]
    assert any(calls[0]["start"] <= r["start"] and r["end"] <= calls[0]["end"] for r in compiles)
    assert not any(calls[1]["start"] <= r["start"] for r in compiles)


def test_the_ring_is_bounded_and_keeps_the_newest():
    gc.disable()       # a pause booked meanwhile would take a place in the same ring
    try:
        for i in range(trace_mod.RING + 10):
            trace_mod.book("span", "ledger_ring", i, i + 1)
        ring = _named("ledger_ring")
    finally:
        gc.enable()
    assert len(ring) == trace_mod.RING
    assert ring[0]["start"] == 10 and ring[-1]["start"] == trace_mod.RING + 9


def test_set_up_events_keep_the_oldest_and_count_the_rest(monkeypatch):
    monkeypatch.setattr(trace_mod, "SETUP_CAP", 4)
    for i in range(7):
        trace_mod.book("compile.trace", "ledger_cap", 10 * i, 10 * i + 5)
    records = monitor.host_records()
    kept = [r for r in _named("ledger_cap", records) if r["kind"] == "compile.trace"]
    assert [r["start"] for r in kept] == [0, 10, 20, 30]
    (dropped,) = [r for r in records if r["kind"] == "dropped"]
    assert (dropped["name"], dropped["count"], dropped["ns"]) == ("compile.trace", 3, 15)


# ------------------------------------------------------------ the collector
def _garbage(n):
    """``n`` self-referencing lists: only a full collection frees them."""
    junk = [[] for _ in range(n)]
    for item in junk:
        item.append(item)
    return len(junk)


def test_a_collection_over_millions_of_objects_books_a_pause():
    gc.collect()
    monitor.reset_host_ledger()
    _garbage(2_000_000)
    before = time.perf_counter_ns()
    freed = gc.collect()
    after = time.perf_counter_ns()
    forced = [r for r in monitor.host_records() if r["kind"] == "gc"
              and r["name"] == "gc.gen2" and r["start"] >= before]
    assert forced and forced[-1]["end"] <= after
    assert forced[-1]["end"] - forced[-1]["start"] >= trace_mod.GC_MIN_NS
    assert forced[-1]["collected"] == freed >= 2_000_000
    assert forced[-1]["tid"] == threading.get_ident()


def test_an_empty_collection_raises_only_the_short_counter():
    gc.collect()
    monitor.reset_host_ledger()
    gc.collect(0)
    records = monitor.host_records()
    assert not [r for r in records if r["kind"] == "gc"]
    (short,) = [r for r in records if r["kind"] == "gc.short" and r["name"] == "gc.gen0"]
    assert short["count"] >= 1 and 0 < short["ns"] < short["count"] * trace_mod.GC_MIN_NS
    assert short["start"] <= short["end"]


# ------------------------------------------------------------- the timeline
def _timeline_run(tmp_path):
    """Every kind of event under one active recorder; the exported events."""
    path = tmp_path / "trace.json"
    fn = _fresh_jit("ledger_timeline")
    step = donate_step(lambda s, x: (s + fn(x), jnp.sum(x)), donate_argnums=(0,))
    gc.collect()
    with monitor.timeline(str(path)) as rec:
        with monitor.span("ledger_step"):
            state, out = step(jnp.zeros((4,)), jnp.ones((4,)))
            jax.block_until_ready(out)
            _garbage(1_000_000)
            gc.collect()
    return rec, json.loads(path.read_text())["traceEvents"]


@pytest.mark.parametrize("name", (
    "compile.trace:ledger_timeline", "compile.lower:<lambda>", "compile.backend:<lambda>",
    "donate_step.call", "gc.gen2"))
def test_every_kind_lands_on_the_active_timeline_nested(name, tmp_path):
    rec, events = _timeline_run(tmp_path)
    _check_nesting(events)
    _check_nesting(rec.events())
    spans = {iv["name"]: iv for iv in monitor.span_intervals(events)}
    assert name in spans, sorted(spans)
    outer = spans["ledger_step"]
    assert outer["start"] <= spans[name]["start"] and spans[name]["end"] <= outer["end"]
    assert spans[name]["depth"] >= 1
    if name.startswith("compile."):
        # the compile phases lie inside the call that caused them
        call = spans["donate_step.call"]
        assert call["start"] <= spans[name]["start"] and spans[name]["end"] <= call["end"]


def test_the_timeline_and_the_ledger_are_one_clock(tmp_path):
    rec, events = _timeline_run(tmp_path)
    (ledger,) = [r for r in _named("ledger_timeline") if r["kind"] == "compile.trace"]
    (span,) = [iv for iv in monitor.span_intervals(events)
               if iv["name"] == "compile.trace:ledger_timeline"]
    assert span["end"] - span["start"] == pytest.approx((ledger["end"] - ledger["start"]) / 1e3)
    assert span["start"] == pytest.approx((ledger["start"] - rec._t0) / 1e3)


def test_a_span_handed_over_whole_merges_between_live_ones():
    rec = monitor.TraceRecorder()
    rec.begin("live_outer")
    t0 = time.perf_counter_ns()
    rec.begin("live_inner")
    rec.end()
    t1 = time.perf_counter_ns()
    rec.complete("whole", t0, t1, args={"n": 1})
    rec.end()
    events = rec.events()
    _check_nesting(events)
    assert [e.get("name") for e in events if e["ph"] == "B"] == ["live_outer", "whole", "live_inner"]
    assert rec.events() == events                   # merged once, stable afterwards
    depth = {iv["name"]: iv["depth"] for iv in monitor.span_intervals(events)}
    assert depth == {"live_outer": 0, "whole": 1, "live_inner": 2}


def test_no_recorder_no_timeline_events():
    rec = monitor.TraceRecorder()
    _fresh_jit("ledger_untimed")(jnp.ones((4,))).block_until_ready()
    assert _named("ledger_untimed") and not [e for e in rec.events() if e["ph"] != "M"]


# ------------------------------------------------------ reset, registration
def test_reset_clears_events_and_counts():
    _fresh_jit("ledger_reset")(jnp.ones((4,))).block_until_ready()
    with monitor.span("ledger_reset_span"):
        pass
    gc.collect(0)
    assert monitor.host_records()
    monitor.reset_host_ledger()
    assert monitor.host_records() == []
    _fresh_jit("ledger_reset_again")(jnp.ones((4,))).block_until_ready()
    assert len(_phases("ledger_reset_again")) == 3          # still listening


def test_the_listeners_are_registered_once_however_often_imported():
    from jax._src import monitoring

    def ours():
        return (sum(getattr(cb, "_host_ledger", False) for cb in gc.callbacks),
                sum(getattr(cb, "_host_ledger", False)
                    for cb in monitoring.get_event_duration_listeners()),
                sum(getattr(cb, "_host_ledger", False) for cb in monitoring.get_event_listeners()))

    assert ours() == (1, 1, 1)
    # a second copy of each feeder, as a package imported under another name
    # would bring (a reload would hand later tests in this process new classes)
    for module in (compile_mod, spans_mod):
        spec = importlib.util.spec_from_file_location("second_" + module.__name__.rsplit(".", 1)[1],
                                                      module.__file__)
        spec.loader.exec_module(importlib.util.module_from_spec(spec))
    assert ours() == (1, 1, 1)
    _fresh_jit("ledger_reloaded")(jnp.ones((4,))).block_until_ready()
    assert len(_phases("ledger_reloaded")) == 3
