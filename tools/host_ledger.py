#!/usr/bin/env python
"""What the host ledger (``monitor.host_records()``) says about one run of a
benchmark cell, and what the ledger itself costs.

    python tools/host_ledger.py run --workload <cell> --seed <n> [--seconds 33] [--trace 1]
                                    [--host-plane] --out chiprun_out/<name>.json
    python tools/host_ledger.py cost --out chiprun_out/cost.json

``run`` goes through ``benchmark/run.py`` in this process (chip only for a real
cell), then writes: the run's result line; the seven ledger metrics by the
benchmark's own reduction (the window is the run's measured steps, traced or
not); where ``setup_s`` went (the time before the package's import — ``import
jax``, the client — the ledger's three phases, the remainder);
``monitor.compile_summary()``; every step of the window slower than 1.5 x the
median with the ``gc`` / ``compile.*`` / ``cache.*`` events inside its interval;
the ledger itself. With ``--host-plane`` (needs ``--trace 1``) also the trace's
planes and lines by name and the first events of every host line that holds one
of the harness's or the program's host spans: what ``benchmark/trace_reduce.py``
would have to match.

``cost`` times, on this host: ``monitor.span`` enter and exit against the same
body without the ledger's two clock reads and its booking; a collection with
and without the ledger's ``gc.callbacks`` entry."""

import argparse
import contextlib
import gc
import glob
import io
import json
import os
import sys
import time
import timeit

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

PHASES = ("setup_trace_s", "setup_lower_s", "setup_backend_s")
HOST_SPANS = ("next_batch", "dispatch", "fence", "donate_step.prepare", "donate_step.call")


def host_plane(trace_dir):
    """Planes and lines of the newest ``.xplane.pb`` by name, and the first
    events of the host lines that hold a span we know."""
    from benchmark import xplane

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                  key=os.path.getmtime)[-1]
    out = {"planes": []}
    for plane in xplane.read(path):
        lines = []
        for ln in plane.lines:
            names = {ev.name for ev in ln.events}
            row = {"line": ln.name, "events": len(ln.events)}
            known = sorted(n for n in names if n in HOST_SPANS or n.startswith("gc.gen"))
            if known:
                row["known_spans"] = known
                row["first_events"] = [[ev.name, ev.start_ps, ev.duration_ps]
                                       for ev in ln.events[:12]]
                row["first_known"] = [[ev.name, ev.start_ps, ev.duration_ps] for ev in ln.events
                                      if ev.name in known][:12]
            elif not plane.name.startswith("/device:"):
                row["some_names"] = sorted(names)[:8]
            lines.append(row)
        out["planes"].append({"plane": plane.name, "lines": lines})
    return out


def run_cell(args):
    from benchmark import run, trace_reduce          # sets run._T0, as the script's start does
    jax_imported = time.perf_counter()
    from benchmark.reductions import host_ledger

    seen = {"cells": []}
    enable_cache, init, load = run.enable_cache, run.Cell.__init__, trace_reduce.load

    def timed_enable_cache():
        seen["before_package"] = time.perf_counter()     # jax imported, the client up
        try:
            return enable_cache()
        finally:
            seen["package_imported"] = time.perf_counter()

    def keeping_init(self, *a, **kw):
        init(self, *a, **kw)
        seen["cells"].append(self)

    def looking_load(trace_dir, **kw):
        if args.host_plane:
            seen["host_plane"] = host_plane(trace_dir)
        return load(trace_dir, **kw)

    run.enable_cache, run.Cell.__init__, trace_reduce.load = timed_enable_cache, keeping_init, looking_load
    stdout = io.StringIO()

    class Tee(io.TextIOBase):
        def write(self, text):
            sys.__stdout__.write(text)
            return stdout.write(text)

        def flush(self):
            sys.__stdout__.flush()

    with contextlib.redirect_stdout(Tee()):
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", str(args.trace)])
    if rc:
        return rc
    from beforeholiday_tpu import monitor

    lines = stdout.getvalue().strip().splitlines()
    result = json.loads(lines[-1])
    detail = next(json.loads(ln)["detail"] for ln in reversed(lines) if ln.startswith('{"detail"'))
    records = monitor.host_records()
    cell = seen["cells"][-1]
    walls = cell.walls[run._CHECK_STEPS:]
    steps = len(walls)
    metrics = {name: host_ledger.reduce(spec, {"steps": steps})
               for name, spec in sorted(run.load_all("layer_metrics").items())
               if spec["reduction"] == "host_ledger"}

    # where setup_s went
    before = seen["before_package"] - run._T0
    phases = sum(metrics[m] for m in PHASES)
    account = {"setup_s": detail["setup_s"], "reference_s": detail["reference_s"],
               "before_package_s": before, "of_it_import_jax_s": jax_imported - run._T0,
               "package_import_s": seen["package_imported"] - seen["before_package"],
               "phases_s": phases, "remainder_s": detail["setup_s"] - before - phases}
    reference = [r for r in records if r["name"] == "run" and r["kind"].startswith("compile.")]
    account["reference_phases_s"] = {
        k: sum(r["end"] - r["start"] for r in reference if r["kind"] == k) / 1e9
        for k in ("compile.trace", "compile.lower", "compile.backend")}
    first_call = [r for r in records if r["name"] == host_ledger.STEP_CALL][-steps:][0]["start"]
    short = [r for r in records if r["kind"] == "gc.short"]
    account["gc_in_setup_s"] = sum(r["end"] - r["start"] for r in records
                                   if r["kind"] == "gc" and r["end"] <= first_call) / 1e9
    account["gc_short"] = {r["name"]: [r["count"], r["ns"] / 1e9] for r in short}
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir or os.environ.get("JAX_COMPILATION_CACHE_DIR")
    account["cache"] = {
        "dir": cache_dir, "max_size": jax.config.jax_compilation_cache_max_size,
        "bytes": sum(os.path.getsize(os.path.join(d, f))
                     for d, _, files in os.walk(cache_dir or "") for f in files),
        "files": sum(len(files) for _, _, files in os.walk(cache_dir or ""))}

    # slow steps against the ledger: fence k closes step k, on the ledger's clock
    import numpy as np

    ends = cell._last_fence - np.concatenate([np.cumsum(walls[::-1])[::-1][1:], [0.0]])
    median, slow = float(np.median(walls)), []
    watched = [r for r in records if r["kind"] == "gc" or r["kind"].startswith(("compile.", "cache."))]
    wiring = [r for r in records if r["name"].startswith("donate_step.")]
    for i in np.flatnonzero(np.asarray(walls) > 1.5 * median):
        lo, hi = (ends[i] - walls[i]) * 1e9, ends[i] * 1e9
        inside = [[r["kind"], r["name"], (r["end"] - r["start"]) / 1e6] for r in watched
                  if r["start"] < hi and r["end"] > lo]
        slow.append({"step": int(i), "ms": walls[i] * 1e3, "events": inside,
                     # the step's own host spans: a stall inside the dispatch shows here
                     "step_wiring_ms": [[r["name"], (r["end"] - r["start"]) / 1e6] for r in wiring
                                        if r["start"] < hi and r["end"] > lo]})
    in_window = [r for r in watched if r["start"] >= first_call and r["end"] <= ends[-1] * 1e9]
    out = {"workload": args.workload, "seed": args.seed, "trace": args.trace, "result": result,
           "detail": detail, "ledger_metrics": metrics, "account": account,
           "steps": steps, "step_ms_median": median * 1e3, "step_ms_max": max(walls) * 1e3,
           "slow_steps_over_1p5x": slow,
           "watched_events_in_window": [[r["kind"], r["name"], (r["end"] - r["start"]) / 1e6]
                                        for r in in_window],
           "compile_summary": monitor.compile_summary(),
           "host_plane": seen.get("host_plane"), "records": records}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    top = sorted(out["compile_summary"], key=lambda r: -(r["trace_outer_s"] + r["lower_s"] + r["backend_s"]))
    print(json.dumps({"ledger": {
        "workload": args.workload, "seed": args.seed, "metrics": metrics, "account": account,
        "steps": steps, "step_ms_median": median * 1e3, "step_ms_max": max(walls) * 1e3,
        "slow_steps_over_1p5x": slow, "events_in_window": len(in_window),
        "top_entries": [[r["entry"], round(r["trace_outer_s"], 3), round(r["lower_s"], 3),
                         round(r["backend_s"], 3), r["cache_hits"], r["cache_misses"]]
                        for r in top[:8]]}}), flush=True)
    return 0


def cost(args):
    import jax

    from beforeholiday_tpu import monitor

    @contextlib.contextmanager
    def bare_span(name):           # monitor.spans.span less the ledger's part
        with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
            yield

    @contextlib.contextmanager
    def parent_span(name):         # as the span was before this PR
        with contextlib.ExitStack() as stack:
            stack.enter_context(jax.profiler.TraceAnnotation(name))
            stack.enter_context(jax.named_scope(name))
            yield

    def with_ledger():
        with monitor.span("cost_probe"):
            pass

    def without():
        with bare_span("cost_probe"):
            pass

    def as_parent():
        with parent_span("cost_probe"):
            pass

    n = 200_000
    spans = {}
    for label, fn in (("with_ledger", with_ledger), ("without", without),
                      ("as_the_parent_had_it", as_parent)) * 3:
        spans.setdefault(label, []).append(min(timeit.repeat(fn, number=n, repeat=3)) / n * 1e9)
    monitor.reset_host_ledger()

    ours = [cb for cb in gc.callbacks if getattr(cb, "_host_ledger", False)]
    gc.collect()

    def collect():
        gc.collect(0)

    pauses, m = {}, 100_000
    for _ in range(3):
        pauses.setdefault("with_callback", []).append(
            min(timeit.repeat(collect, number=m, repeat=3)) / m * 1e9)
        for cb in ours:
            gc.callbacks.remove(cb)
        try:
            pauses.setdefault("without", []).append(
                min(timeit.repeat(collect, number=m, repeat=3)) / m * 1e9)
        finally:
            gc.callbacks.extend(ours)
    out = {"host": os.uname().nodename, "cpus": os.cpu_count(),
           "span_ns_a_call": {k: min(v) for k, v in spans.items()},
           "span_ns_a_call_all": spans,
           "empty_gen0_collection_ns": {k: min(v) for k, v in pauses.items()},
           "empty_gen0_collection_ns_all": pauses}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(json.dumps({"cost": out}), flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="what", required=True)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--seconds", type=float, default=33.0)
    r.add_argument("--trace", type=int, choices=(0, 1), default=0)
    r.add_argument("--host-plane", action="store_true")
    r.add_argument("--out", required=True)
    c = sub.add_parser("cost")
    c.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    return run_cell(args) if args.what == "run" else cost(args)


if __name__ == "__main__":
    sys.exit(main())
