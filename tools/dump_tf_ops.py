#!/usr/bin/env python
"""Run one benchmark cell traced and write what its per-layer metrics are read
from: every distinct framework name (``tf_op``: the ``monitor.spans`` scope
path) of chip 0's device ops with its self time, and every distinct HLO name
stem (``%flash_attention``, ``%fusion``, ...) with its self time; beside them
what the run dispatched (``monitor.dispatch_summary()``: ``pallas`` / ``jnp`` by
guarded op) and the tile plan of every traced kernel (``monitor.tile_records()``);
and ``nameless``: every op of chip 0 whose ``tf_op`` is empty, by HLO name, with
what the program ledger (``monitor.program_ops()``) says of it — opcode, bytes in
and out, the nearest named instruction that feeds it (``producer``) and that it
feeds (``consumer``), ``hops``; or its own ``scope`` where the compiled text names
what the trace does not, a ``while`` — beside the trace's ``hlo_category`` and its
self time in ps: which of the compiler's own instructions are the stacks, the
gradient pack, a prefetch (what the six ``nameless_*`` metrics are read from);
``stat_names``: the names of the stats the trace's ops carry.

    python tools/dump_tf_ops.py --workload <cell> --seed <n> --out chiprun_out/<cell>.json

The file has the form of ``benchmark/tests/fixtures/tf_ops/*.json`` (PR 24);
``benchmark/tests`` check the metric patterns against such files. Chip only:
it goes through ``benchmark/run.py``, which refuses any other backend for a
real cell."""

import argparse
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

_LEDGER_FIELDS = ("opcode", "scope", "bytes_in", "bytes_out", "producer", "consumer", "hops")


def tables(ops, records):
    """What the metrics' patterns are written against, of one chip's ``ops``:
    self time by ``tf_op`` and by HLO name stem, and the ``nameless`` rows
    (``records``: ``monitor.program_ops()``), largest first."""
    from benchmark.reductions import nameless_time

    by_scope, by_name = collections.Counter(), collections.Counter()
    for op in ops:
        by_scope[str(op.stats.get("tf_op", ""))] += op.self_ps
        by_name[re.sub(r"[.\d]+$", "", op.name)] += op.self_ps
    rows = {}
    for op, record in nameless_time.nameless(ops, nameless_time.lookup(records)):
        row = rows.setdefault(op.name, {
            "name": op.name, "hlo_category": str(op.stats.get("hlo_category", "")),
            "calls": 0, "self_ps": 0,
            **{k: (record or {}).get(k) for k in _LEDGER_FIELDS}})
        row["calls"] += 1
        row["self_ps"] += op.self_ps
    return {"ops": sorted(by_scope.items()), "hlo_names": sorted(by_name.items()),
            "nameless": sorted(rows.values(), key=lambda r: (-r["self_ps"], r["name"])),
            "stat_names": sorted({k for op in ops for k in op.stats})}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from beforeholiday_tpu import monitor
    from benchmark import run, trace_reduce

    kept, load = {}, trace_reduce.load

    def keeping(*a, **kw):
        kept["trace"] = load(*a, **kw)
        return kept["trace"]

    trace_reduce.load = keeping
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", "1", "--trace", "1"])
    if rc or "trace" not in kept:
        return rc or 1
    t = kept["trace"]
    start, end = t.window(0)
    cell = run.load("workloads", args.workload)
    out = {"cell": args.workload, "seed": args.seed, "steps": 2 * cell["pool"],
           "device_kind": jax.devices()[0].device_kind,
           "from": "tools/dump_tf_ops.py on the chip: chip 0, every distinct tf_op of the "
                   "XLA Ops line with its self time in ps; hlo_names: the same by HLO name stem; "
                   "nameless: every op with an empty tf_op by HLO name, with the program "
                   "ledger's record of it (monitor.program_ops())",
           "busy_ps": trace_reduce.length(t.busy(0)), "window_ps": end - start,
           **tables(t.chips[0]["ops"], monitor.program_ops()),
           "dispatch": monitor.dispatch_summary(), "tiles": monitor.tile_records()}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
