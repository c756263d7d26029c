#!/usr/bin/env python3
"""Read, on the chip, the two numbers each limit of a cell's check is set from.

    python benchmark/read_limits.py --workload <cell> --seeds 101,102,... --control-seeds 101,102,103

For every seed: the sound program's compared numbers against the float32
reference; for the control seeds also the control's (the reference one precision
down, ``reference/precision.py``). All in one process, the compiled programs
shared between seeds. Prints each reading, then for each number the largest the
sound program gave and the smallest the control gave. A limit goes above the
first and below the second, with room on both sides; where the second is under
three times the first, no limit will hold (see "How correct is decided").
Writes nothing into the cell's file: that is for whoever adds the cell.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_NO_LIMIT = {"loss_gap": float("inf"), "first_grad_norm_gap": float("inf"),
             "update_norm_gap": float("inf")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds for the sound program")
    ap.add_argument("--control-seeds", default="", help="seeds (of those) also read under the control")
    ap.add_argument("--control", default="fp8", help="the control's arithmetic mode")
    ap.add_argument("--out", default=None, help="write every reading to this JSON file")
    args = ap.parse_args(argv)

    import jax

    from benchmark import check, run

    run.enable_cache()
    cell = run.load("workloads", args.workload)
    cfg = run.load("configs", cell["config"])
    if jax.default_backend() != "tpu" and not cell.get("rehearsal"):
        print(f"read_limits: {args.workload!r} needs the 'tpu' backend", file=sys.stderr)
        return 1
    c = run.Cell(cell, cfg, jax.devices()[:cell["chips"]])
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    readings = {"program": {}, "control": {}}
    for seed in (int(s) for s in args.seeds.split(",")):
        c.start(seed)
        reference = c.reference()
        if seed in control_seeds:
            readings["control"][seed] = check.compare(c.reference(args.control), reference, _NO_LIMIT)
        c.build()
        readings["program"][seed] = check.compare(c.program_numbers(), reference, _NO_LIMIT)
        for who in ("program", "control"):
            if seed in readings[who]:
                print(who, seed, json.dumps({r["name"]: [r["value"], r["at"]]
                                             for r in readings[who][seed]}), flush=True)
    for name in [r["name"] for r in next(iter(readings["program"].values()))]:
        sound = max(r["value"] for rows in readings["program"].values() for r in rows if r["name"] == name)
        ctl = [r["value"] for rows in readings["control"].values() for r in rows if r["name"] == name]
        print(f"{name}: sound largest {sound:.6g} over {len(readings['program'])} seeds; "
              f"control smallest {min(ctl) if ctl else float('nan'):.6g} over {len(ctl)} seeds")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(readings, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
