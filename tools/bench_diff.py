#!/usr/bin/env python
"""Compare two ``BENCH_r*.json`` runs and flag drift past the stability gate.

``bench.py`` writes one ``BENCH_r<N>.json`` per run: ``{"n", "cmd", "rc",
"tail", "parsed"}`` where ``parsed`` is the last JSON line the bench printed
(the metric tree — or ``null`` when the run died before printing one). This
tool turns the eyeballed perf trajectory into an exit code::

    python tools/bench_diff.py BENCH_r01.json BENCH_r05.json
    python tools/bench_diff.py old.json new.json --tol 0.10

Every NUMERIC leaf under ``parsed`` (flattened to a dotted path) present in
BOTH files is compared; a leaf whose relative change exceeds ``--tol``
(default the bench's own ±10% gate) is flagged and the exit code is 1.
Bookkeeping keys (``n``/``cmd``/``rc``/``tail``) are never compared — they
differ on every run by construction. A side with ``parsed: null`` (a run
that crashed before its metric line) yields no comparable keys: that is a
warning and exit 0 — the crash is the other tooling's problem; this tool
only judges drift between two successfully parsed runs.

Zero baselines compare by absolute difference against ``--tol`` (a relative
change from 0 is undefined); booleans are excluded (True/False flapping is
a correctness signal, not drift).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict


def flatten_numeric(tree: Any, prefix: str = "") -> Dict[str, float]:
    """Dotted-path → value for every numeric leaf (bool excluded)."""
    out: Dict[str, float] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            path = f"{prefix}.{k}" if prefix else str(k)
            out.update(flatten_numeric(v, path))
    elif isinstance(tree, list):
        for i, v in enumerate(tree):
            out.update(flatten_numeric(v, f"{prefix}[{i}]"))
    elif isinstance(tree, (int, float)) and not isinstance(tree, bool):
        out[prefix] = float(tree)
    return out


def diff_runs(old: Dict[str, Any], new: Dict[str, Any],
              tol: float, keys=None) -> Dict[str, Any]:
    """Compare the ``parsed`` subtrees; returns ``{"compared", "regressions",
    "missing_old"/"missing_new" (parsed is null), "added", "removed"}``.
    ``keys`` (a sequence of substrings) restricts the comparison to dotted
    paths containing at least one of them — the ``--keys`` filter."""
    result: Dict[str, Any] = {"compared": 0, "regressions": [],
                              "added": [], "removed": []}
    old_parsed = old.get("parsed")
    new_parsed = new.get("parsed")
    result["missing_old"] = old_parsed is None
    result["missing_new"] = new_parsed is None
    if old_parsed is None or new_parsed is None:
        return result
    a = flatten_numeric(old_parsed)
    b = flatten_numeric(new_parsed)
    if keys:
        subs = [k for k in keys if k]
        a = {k: v for k, v in a.items() if any(s in k for s in subs)}
        b = {k: v for k, v in b.items() if any(s in k for s in subs)}
    result["added"] = sorted(set(b) - set(a))
    result["removed"] = sorted(set(a) - set(b))
    for key in sorted(set(a) & set(b)):
        va, vb = a[key], b[key]
        result["compared"] += 1
        if va == 0.0:
            drift = abs(vb)  # relative-to-zero is undefined; absolute gate
        else:
            drift = abs(vb - va) / abs(va)
        if drift > tol:
            result["regressions"].append({
                "key": key, "old": va, "new": vb,
                "drift": drift,
            })
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("old", help="baseline BENCH_r*.json")
    ap.add_argument("new", help="candidate BENCH_r*.json")
    ap.add_argument("--tol", type=float, default=0.10,
                    help="relative drift gate (default 0.10 = ±10%%)")
    ap.add_argument("--keys", default=None,
                    help="comma-separated substrings; only dotted paths "
                         "containing one of them are compared (e.g. "
                         "--keys gpt_o5,tuned_vs)")
    args = ap.parse_args(argv)

    with open(args.old) as f:
        old = json.load(f)
    with open(args.new) as f:
        new = json.load(f)

    keys = args.keys.split(",") if args.keys else None
    result = diff_runs(old, new, args.tol, keys=keys)
    if result["missing_old"] or result["missing_new"]:
        side = args.old if result["missing_old"] else args.new
        print(f"warning: {side} has parsed=null (run died before its metric "
              f"line) — no comparable keys, nothing to gate")
        return 0
    for key in result["removed"]:
        print(f"note: key disappeared: {key}")
    for key in result["added"]:
        print(f"note: new key: {key}")
    for reg in result["regressions"]:
        print(f"DRIFT {reg['key']}: {reg['old']:.6g} -> {reg['new']:.6g} "
              f"({100.0 * reg['drift']:+.1f}% > ±{100.0 * args.tol:.0f}%)")
    n = result["compared"]
    bad = len(result["regressions"])
    print(f"{n} keys compared, {bad} past the ±{100.0 * args.tol:.0f}% gate")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
