"""The seven per-layer metrics that read the program's host ledger (PR 35).

Each metric file against a ledger built by hand: the window is the last
``steps`` calls of ``donate_step.call``, set-up is what ended before the first
of them, ``exclude`` drops the reference's ``run`` with what it encloses, a
nested trace or span is counted once, a program without the ledger gives
``None`` and a window without a pause gives 0.0. Then the CPU rehearsal: its
traced run still carries no time-derived metric, and the ledger holds the
step's three phases."""

import json
import sys

import pytest

from benchmark import run
from benchmark.reductions import host_ledger

METRICS = ("setup_trace_s", "setup_lower_s", "setup_backend_s", "setup_cache_misses",
           "kernel_trace_s", "host_dispatch_ms", "host_gc_pause_ms")
MS = 1_000_000


def ev(kind, name, start_ms, end_ms, tid=1, **extra):
    return dict(extra, kind=kind, name=name, start=start_ms * MS, end=end_ms * MS, tid=tid)


def ledger():
    """Set-up: the reference ``run`` (trace 0-100 with a nested ``clip``, lower,
    a cold backend compile), then ``step`` under the first ``donate_step.call``
    (trace 1000-1600 enclosing ``_gmm`` 1100-1200 and the spans ``full_mixer`` >
    ``flash_attention`` > ``layer_norm``; lower 1600-1900; backend 1900-2300, a
    cache miss in it), a short eager program on another thread, a long pause
    in set-up. Then four steps of which the last three are the window, a
    2 ms and a 5 ms pause inside it, a 50 ms one after it."""
    rows = [
        ev("compile.trace", "run", 0, 100), ev("compile.trace", "clip", 10, 20),
        ev("compile.lower", "run", 100, 150), ev("compile.backend", "run", 150, 900),
        ev("cache.miss", "run", 890, 890),
        ev("gc", "gc.gen2", 920, 980, collected=7),
        ev("span", "donate_step.call", 990, 2400),
        ev("compile.trace", "step", 1000, 1600), ev("compile.trace", "_gmm", 1100, 1200),
        ev("span", "full_mixer", 1300, 1500), ev("span", "flash_attention", 1310, 1400),
        ev("span", "layer_norm", 1320, 1330), ev("span", "layer_norm", 1450, 1460),
        ev("span", "flash_attention", 2390, 2395),          # eager, outside any trace
        ev("compile.lower", "step", 1600, 1900), ev("compile.backend", "step", 1900, 2300),
        ev("cache.miss", "step", 2290, 2290), ev("cache.hit", "pool", 2500, 2500, tid=2),
        ev("compile.trace", "pool", 2450, 2460, tid=2),
        ev("compile.backend", "pool", 2470, 2510, tid=2),
        ev("cache.load", "pool", 2480, 2500, tid=2),
        {"kind": "gc.short", "name": "gc.gen0", "start": 0, "end": 5000 * MS, "tid": None,
         "count": 900, "ns": 40 * MS},
    ]
    for i, (start, ms) in enumerate(((3000, 1.0), (3100, 0.4), (3200, 0.6), (3300, 0.8))):
        rows.append(ev("span", "donate_step.prepare", start - 1, start))
        rows.append(ev("span", "donate_step.call", start, start + ms))
    rows += [ev("gc", "gc.gen1", 3150, 3152, collected=0), ev("gc", "gc.gen2", 3250, 3255, collected=3),
             ev("gc", "gc.gen2", 3400, 3450, collected=0)]
    return rows


def read(metric, records, steps=3):
    spec = run.load("layer_metrics", metric)
    events = host_ledger.chosen(spec, records, steps)
    return spec, events


EXPECTED = {
    "setup_trace_s": 0.610,          # step 0.6 + pool 0.01; run, clip and _gmm not
    "setup_lower_s": 0.300,
    "setup_backend_s": 0.440,        # step 0.4 + pool 0.04
    "setup_cache_misses": 1.0,       # step's; run's is the reference's
    "kernel_trace_s": 0.100,         # flash 0.09 + the second layer_norm 0.01
    "host_dispatch_ms": 0.6,         # (0.4 + 0.6 + 0.8) / 3: the first of four is not in the window
    "host_gc_pause_ms": 5.0,
}


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reads_the_hand_built_ledger(metric, monkeypatch):
    from beforeholiday_tpu import monitor

    monkeypatch.setattr(monitor, "host_records", ledger)
    spec = run.load("layer_metrics", metric)
    assert spec["reduction"] == "host_ledger" and "family" not in spec
    assert host_ledger.reduce(spec, {"steps": 3}) == pytest.approx(EXPECTED[metric])


def test_the_window_is_the_last_steps_calls():
    spec, events = read("host_dispatch_ms", ledger(), steps=3)
    assert [r["start"] // MS for r in events] == [3100, 3200, 3300]
    spec, events = read("host_dispatch_ms", ledger(), steps=4)
    assert [r["start"] // MS for r in events] == [3000, 3100, 3200, 3300]
    # with the whole run as the window the first call, which compiled, is a step too,
    # and set-up ends before it
    spec, events = read("setup_trace_s", ledger(), steps=5)
    assert events == []


def test_exclude_drops_the_reference_and_what_it_encloses():
    spec, events = read("setup_trace_s", ledger())
    assert sorted(r["name"] for r in events) == ["pool", "step"]
    spec = dict(spec, exclude=[])
    assert sorted(r["name"] for r in host_ledger.chosen(spec, ledger(), 3)) == ["pool", "run", "step"]


def test_a_nested_trace_and_a_nested_span_are_counted_once():
    nested = [r for r in ledger() if r["name"] in ("_gmm", "clip")]
    assert len(nested) == 2
    assert not [r for r in read("setup_trace_s", ledger())[1] if r["name"] in ("_gmm", "clip")]
    # layer_norm inside flash_attention goes with it; the one beside it is read;
    # full_mixer, not a listed span, does not hide the kernels inside it; the
    # eager call outside any trace is no trace time
    _, events = read("kernel_trace_s", ledger())
    assert [(r["name"], r["start"] // MS) for r in events] == [
        ("flash_attention", 1310), ("layer_norm", 1450)]


def test_threads_nest_apart():
    a = ev("compile.trace", "a", 0, 100, tid=1)
    b = ev("compile.trace", "b", 10, 20, tid=2)      # inside a's time, on another thread
    assert host_ledger.outermost([a, b]) == [a, b]
    assert host_ledger.outermost([a, dict(b, tid=1)]) == [a]


@pytest.mark.parametrize("metric", METRICS)
def test_no_ledger_gives_none_and_no_step_gives_none(metric, monkeypatch):
    spec = run.load("layer_metrics", metric)
    monkeypatch.setitem(sys.modules, "beforeholiday_tpu.monitor", None)   # the import fails
    assert host_ledger.reduce(spec, {"steps": 3}) is None
    monkeypatch.undo()
    from beforeholiday_tpu import monitor

    monkeypatch.setattr(monitor, "host_records",
                        lambda: [r for r in ledger() if r["name"] != host_ledger.STEP_CALL])
    assert host_ledger.reduce(spec, {"steps": 3}) is None


def test_a_window_without_a_pause_reads_zero(monkeypatch):
    from beforeholiday_tpu import monitor

    monkeypatch.setattr(monitor, "host_records",
                        lambda: [r for r in ledger() if r["kind"] != "gc"])
    assert host_ledger.reduce(run.load("layer_metrics", "host_gc_pause_ms"), {"steps": 3}) == 0.0
    assert host_ledger.reduce(run.load("layer_metrics", "setup_cache_misses"), {"steps": 3}) == 1.0


def test_the_manifest_lists_the_seven_for_every_cell():
    with open(run._REPO + "/BENCHMARK.json") as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for metric in METRICS:
        assert "workloads" not in entries[metric] and entries[metric]["source"] == "program_counter"
    assert {entries[m]["moves"] for m in METRICS} == {"setup_s", "tokens_per_s", "step_ms_p95"}
    assert {entries[m]["layer"] for m in METRICS} == {
        "set-up (tracing, lowering, the compile cache)", "step wiring (remat/donation.py)"}


def test_the_rehearsal_carries_no_time_and_the_ledger_holds_the_steps_phases(capsys):
    from beforeholiday_tpu import monitor

    monitor.reset_host_ledger()
    assert run.main(["--workload", "tiny-gpt.train", "--seed", "2147483999",
                     "--seconds", "1", "--trace", "1"]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] and result["metrics"] == {}       # the rehearsal rule
    records = monitor.host_records()
    step = {r["kind"] for r in records if r["name"] == "one_chip_step"}
    assert {"compile.trace", "compile.lower", "compile.backend"} <= step
    cell = run.load("workloads", "tiny-gpt.train")
    for metric in METRICS:
        value = host_ledger.reduce(run.load("layer_metrics", metric), {"steps": 2 * cell["pool"]})
        assert value is not None and value >= 0.0, metric
    # the step was traced once, and the kernels' spans opened under that trace
    assert host_ledger.reduce(run.load("layer_metrics", "kernel_trace_s"),
                              {"steps": 2 * cell["pool"]}) > 0.0
    calls = [r for r in records if r["name"] == host_ledger.STEP_CALL]
    assert len(calls) == 3 + 2 * cell["pool"]
