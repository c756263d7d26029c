#!/usr/bin/env python3
"""The benchmark's one command: run one cell, print its metrics, decide ``correct``.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Nothing here names a model, a cell or a metric. A cell is
``workloads/<cell>.json``; it names a configuration, ``configs/<config>.json``,
whose ``family`` names ``families/<family>.py`` (the program's entry points, the
seeded weights and batches, the operation count) and, through it, the plain
reference under ``reference/``. Per-layer metrics are ``layer_metrics/*.json``,
each naming a reduction under ``reductions/``. See ``README.md``.

A run: refuse any backend but ``tpu``; run the float32 reference through the
first steps; build the program's state from the same seeded weights; drive the
compiled step through those steps and compare; then hand the same step and
state to the measured window of fenced steps over a pool of seeded batches,
each dispatched one ahead of the fence before it.
The last line of stdout is the result object and nothing else.
"""

import time

_T0 = time.perf_counter()          # set-up is counted from here

import argparse
import functools
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
sys.path.insert(0, _REPO)          # the package is not installed

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from benchmark import check, trace_reduce

_DATA_DIRS = (_HERE, os.path.join(_HERE, "tests", "fixtures"))
_COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CHECK_STEPS = 3                   # steps the reference follows


def load(kind, name):
    """``<kind>/<name>.json`` of the benchmark, or of the tests' fixtures."""
    for base in _DATA_DIRS:
        path = os.path.join(base, kind, name + ".json")
        if os.path.isfile(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(f"no {kind}/{name}.json under {_DATA_DIRS}")


def load_all(kind):
    """Every ``<kind>/*.json``, by name; the benchmark's own wins over a fixture."""
    out = {}
    for base in reversed(_DATA_DIRS):
        for path in sorted(glob.glob(os.path.join(base, kind, "*.json"))):
            with open(path) as f:
                out[os.path.basename(path)[:-5]] = json.load(f)
    return out


def enable_cache():
    """The program's rule for where the compile cache lives, and every program
    of a cell kept in it, the small and the quickly compiled ones too, so that
    only a cell's first run in a checkout compiles. Returns the directory."""
    from beforeholiday_tpu.utils.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return enable_compile_cache()


def peak_of(device_kind):
    """Published peaks of ``device_kind``; a kind not in the table is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no published peak for device_kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def dispatch_errors(guarded_ops):
    """Why the guarded kernels cannot be said to have run compiled (copied from
    ``chip_smoke.dispatch_errors``): each op dispatched pallas at least once,
    never jnp, and no probe failed."""
    from beforeholiday_tpu.guard import dispatch

    counters, errors = dispatch.dispatch_counters(), []
    for op in guarded_ops:
        n = {w: sum(v[w] for k, v in counters.items() if k[0] == op)
             for w in ("pallas", "jnp")}
        if n["pallas"] <= 0:
            errors.append(f"{op}: no pallas dispatch (resolved to jnp?)")
        if n["jnp"] != 0:
            errors.append(f"{op}: {n['jnp']} dispatch(es) degraded to jnp")
    for key, why in dispatch.probe_failures().items():
        errors.append(f"probe failed for {key[0]} {key[2]}: {why[:200]}")
    return errors


class CompileCounter:
    """Counts lowerings (each new shape or program) while ``armed``."""

    def __init__(self):

        self.count, self.armed = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.armed and event == _COMPILE_EVENT:
            self.count += 1


class Cell:
    """One cell on its devices: the reference, the program, the check, the window.

    The compiled programs are made once and take the seed as an argument, so
    one process can read many seeds (``start``) without compiling again."""

    def __init__(self, cell, cfg, devices):

        self.cell, self.cfg, self.devices = cell, cfg, devices
        self.family = importlib.import_module(f"benchmark.families.{cfg['family']}")
        self.rows = cell["per_chip_batch"] * cell["chips"]
        per_row = self.family.ITEMS_PER_ROW
        self.items_per_step = self.rows * (cfg[per_row] if per_row else 1)
        self.mesh = None
        self.batch_sharding = SingleDeviceSharding(devices[0])
        self.replicated = self.batch_sharding
        if cell["layout"] == "dp":
            self.mesh = Mesh(np.asarray(devices), ("data",))
            self.batch_sharding = NamedSharding(self.mesh, P("data"))
            self.replicated = NamedSharding(self.mesh, P())
        self.compiles = CompileCounter()
        self.program = self.family.Program(cfg, cell, self._weights, devices, self.mesh)
        self._jitted = {}

    def _jit(self, name, fn, **kw):
        if name not in self._jitted:
            self._jitted[name] = jax.jit(fn, **kw)
        return self._jitted[name]

    # -- seeded inputs -----------------------------------------------------
    def _key(self, seed):
        return jax.random.PRNGKey(seed)

    def _weights(self, seed):
        return self.family.weights(self.cfg, jax.random.fold_in(self._key(seed), 0))

    def start(self, seed):
        """Take ``seed``: its pool of batches, made on the device(s) in one
        call; whatever an earlier seed left is dropped."""
        n = self.cell["pool"]
        self.seed = jax.device_put(np.uint32(seed % 2 ** 32), self.replicated)
        self.state = None
        self.losses, self.found_inf, self.walls = [], [], []

        def pool(seed):
            return [self.family.batch(self.cfg, self.rows,
                                      jax.random.fold_in(self._key(seed), 1 + i))
                    for i in range(n)]

        self.pool = self._jit("pool", pool, out_shardings=self.batch_sharding)(self.seed)

    # -- the reference -----------------------------------------------------
    def reference(self, mode="float32"):
        """The plain reference (or, in a lower ``mode``, the control) through
        the first steps on the seeded weights and the pool's first batches."""
        ref = self.family.reference
        opt_init, opt_step = self.family.reference_optimizer(self.cfg, self.cell)
        loss_fn = functools.partial(ref.loss, cfg=self.cfg, mode=mode)

        def run(seed, batches):
            return check.reference_trajectory(
                loss_fn, opt_init, opt_step, self._weights(seed), batches,
                ref.STACKED_PREFIX)

        batches = jax.tree.map(lambda *xs: jnp.stack(xs), *self.pool[:_CHECK_STEPS])
        losses, first, update = self._jit("reference." + mode, run)(self.seed, batches)
        return {"losses": np.asarray(losses), "first_grad": jax.device_get(first),
                "update": jax.device_get(update)}

    # -- the program -------------------------------------------------------
    def build(self):
        """The program's state, made from the seed in one call."""
        self.state = self._jit("make_state", self.program.make_state,
                               out_shardings=self.replicated)(self.seed)

    def dispatch(self, i):
        """Start step ``i`` on the pool's batch ``i`` and return what its fence
        waits for. The state is donated to the next step, so it is never waited on."""
        annotate = jax.profiler.TraceAnnotation
        with annotate("next_batch"):
            batch = self.pool[i % len(self.pool)]
        with annotate("dispatch"):
            self.state, loss, found_inf = self.program.step(self.state, batch)
        self.losses.append(loss)
        self.found_inf.append(found_inf)
        return loss, found_inf

    def fence(self, outputs):
        with jax.profiler.TraceAnnotation("fence"):
            jax.block_until_ready(outputs)
        now = time.perf_counter()
        self.walls.append(now - self._last_fence)
        self._last_fence = now

    def run_step(self, i):
        """One step, dispatched and fenced: the window's own two calls."""
        self._last_fence = time.perf_counter()
        self.fence(self.dispatch(i))

    def program_numbers(self):
        """Drive the program through the first steps and read what is compared."""
        prog, prefix = self.program, self.family.reference.STACKED_PREFIX

        def first_grad(state, seed):
            return check.leaf_norms(prog.first_gradient(state, self._weights(seed)), prefix)

        def update(state, seed):
            w0, now = self._weights(seed), prog.masters(state)
            return check.leaf_norms({k: now[k] - w0[k] for k in w0}, prefix)

        self.run_step(0)
        first = jax.device_get(self._jit("first_grad", first_grad)(self.state, self.seed))
        for i in range(1, _CHECK_STEPS):
            self.run_step(i)
        return {"losses": np.asarray(jax.device_get(self.losses[:_CHECK_STEPS])),
                "first_grad": first,
                "update": jax.device_get(self._jit("update", update)(self.state, self.seed))}

    # -- the window --------------------------------------------------------
    def window(self, seconds=None, steps=None):
        """Steps for ``seconds`` (or exactly ``steps``), every one fenced, the
        next one dispatched before the fence as a training loop under JAX's
        asynchronous dispatch runs: the chip is never left waiting for the
        host, so the host's scheduling noise stays out of the rate. A step's
        time is the time between its fence and the one before. Returns the
        window's length in seconds, first dispatch to last fence, and the index
        of its first step. Lowerings inside it are counted."""
        first = n = len(self.walls)
        self.compiles.armed = True
        t0 = self._last_fence = time.perf_counter()
        pending = self.dispatch(n)
        while pending is not None:
            n += 1
            more = (n - first < steps) if steps else (time.perf_counter() - t0 < seconds)
            ahead = self.dispatch(n) if more else None
            self.fence(pending)
            pending = ahead
        self.compiles.armed = False
        return self._last_fence - t0, first

    def window_checks(self, first):
        """``(rows, failed)``: what the window itself must show."""
        losses = np.asarray(jax.device_get(self.losses[first:]), np.float64)
        found = np.asarray(jax.device_get(self.found_inf[first:])).astype(bool)
        bad = found | ~np.isfinite(losses)
        n = len(self.pool)
        rows = [{"name": "window.compilations", "value": self.compiles.count, "limit": 0,
                 "ok": self.compiles.count == 0},
                {"name": "window.failed_steps", "value": int(bad.sum()), "limit": 0,
                 "ok": not bad.any()}]
        if len(losses) >= 2 * n:
            head, tail = float(losses[:n].mean()), float(losses[-n:].mean())
            rows.append({"name": "window.loss_last_pass_minus_first", "value": tail - head,
                         "limit": 0.0, "ok": bool(tail < head)})
        disagree = self.program.replicas_disagree(self.state)
        rows.append({"name": "window.replicas_disagree", "value": int(disagree), "limit": 0,
                     "ok": not disagree})
        return rows, int(bad.sum())


def memory_peak(devices):
    """Bytes in use on the fullest chip at their peak. On this runtime
    ``peak_bytes_in_use`` counts live buffers only; the scratch of the loaded
    step program is ``bytes_reserved``, and is most of a training step's
    footprint, so the two are added as they stand when the window closes."""
    def one(d):
        s = d.memory_stats() or {}
        return max(s.get("peak_bytes_in_use", 0),
                   s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0))

    return max(one(d) for d in devices)


def traced_window(cell_run, steps, ctx):
    """Trace ``steps`` steps; returns ``(per-layer metrics, device fields,
    breakdown, window_s, first)``."""
    tmp = tempfile.mkdtemp(prefix="benchmark_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            window_s, first = cell_run.window(steps=steps)
        finally:
            jax.profiler.stop_trace()
        trace = trace_reduce.load(tmp, chips=len(cell_run.devices))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    ctx = dict(ctx, steps=steps, trace=trace)
    metrics = {}
    for name, spec in sorted(load_all("layer_metrics").items()):
        if spec.get("family", ctx["cfg"]["family"]) != ctx["cfg"]["family"]:
            continue
        reduction = importlib.import_module(f"benchmark.reductions.{spec['reduction']}")
        value = reduction.reduce(spec, ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": spec["unit"]}
    device = {"busy_s": trace.busy_s(), "window_s": trace.window_s()}
    return metrics, device, trace.breakdown(), window_s, first


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load("workloads", args.workload)
    cfg = load("configs", cell["config"])
    rehearsal = bool(cell.get("rehearsal"))


    backend = jax.default_backend()
    if backend != "tpu" and not rehearsal:
        print(f"benchmark: cell {args.workload!r} needs the 'tpu' backend, JAX found "
              f"{backend!r}; nothing was compiled", file=sys.stderr)
        return 1
    if len(jax.devices()) < cell["chips"]:
        print(f"benchmark: cell {args.workload!r} needs {cell['chips']} chips, the "
              f"process sees {len(jax.devices())}", file=sys.stderr)
        return 1
    cache_dir = enable_cache()
    devices = jax.devices()[:cell["chips"]]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices())}
    print(f"cell: {args.workload}  config: {cell['config']}  seed: {args.seed}  "
          f"device: {json.dumps(device)}  compile cache: {cache_dir}", flush=True)
    if rehearsal:
        print("REHEARSAL on a fixture cell: counts only, no time-derived metric is reported")

    run = Cell(cell, cfg, devices)
    run.start(args.seed)
    t_ref = time.perf_counter()
    reference = run.reference()
    reference_s = time.perf_counter() - t_ref
    run.build()
    rows = check.compare(run.program_numbers(), reference, cell["limits"])
    if not rehearsal:
        for err in dispatch_errors(run.family.GUARDED_OPS):
            rows.append({"name": "dispatch", "value": err, "limit": "none", "ok": False})
    setup_s = time.perf_counter() - _T0 - reference_s

    flops_per_item = run.family.model_flops_per_item(cfg)
    ctx = {"cfg": cfg, "cell": cell, "family": run.family, "devices": devices,
           "items_per_step": run.items_per_step}
    metrics, breakdown = {}, None
    if args.trace:
        if rehearsal:
            window_s, first = run.window(steps=2 * cell["pool"])
        else:
            ctx["peak"] = peak_of(device["kind"])
            metrics, dev_extra, breakdown, window_s, first = traced_window(
                run, 2 * cell["pool"], ctx)
            device.update(dev_extra)
    else:
        window_s, first = run.window(seconds=args.seconds)
    window_rows, failed = run.window_checks(first)
    rows += window_rows
    attempted = len(run.walls) - first
    device["memory_peak_bytes"] = memory_peak(devices)

    if not args.trace:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        if not rehearsal:
            peak = peak_of(device["kind"])
            rate = attempted * run.items_per_step / window_s
            thr = cfg["throughput_metric"]
            metrics[thr["name"]] = {"value": rate, "unit": thr["unit"]}
            metrics["step_ms_p95"] = {
                "value": float(np.percentile(run.walls[first:], 95)) * 1e3, "unit": "ms"}
            metrics["mfu"] = {
                "value": 100.0 * flops_per_item * rate
                / (cell["chips"] * peak["bf16_flops_per_s"]), "unit": "%"}

    for row in rows:
        print(f"compared: {row['name']} = {row['value']}  limit {row['limit']}  "
              f"{'ok' if row['ok'] else 'FAILED'}  {row.get('at', '')}")
    walls = np.asarray(run.walls[first:])
    print(json.dumps({"detail": {
        "steps_in_window": attempted, "window_s": window_s, "reference_s": reference_s,
        "setup_s": setup_s, "flops_per_item": flops_per_item,
        "items_per_step": run.items_per_step,
        "step_ms_median": float(np.median(walls)) * 1e3,
        "step_ms_max": float(walls.max()) * 1e3,
        "slow_steps": [[int(i), round(float(walls[i]) * 1e3, 2)]
                       for i in np.flatnonzero(walls > 1.01 * np.median(walls))[:24]],
        "losses_first_last": [float(x) for x in jax.device_get(
            [run.losses[0], run.losses[-1]])]}}))
    result = {"correct": all(r["ok"] for r in rows), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
