#!/usr/bin/env python
"""Compile a benchmark cell's training step for a described v5e 2x2. Nothing runs.

The TPU compiler is installed where no chip is; ``jax.experimental.topologies``
describes the chip and ``jit(...).lower(shapes).compile()`` gives the program
the chip would run. This prints, per cell, what that program says about the
arenas: compile seconds, temp and argument memory, and every **arena-wide
fusion** with its operand count, the compiler's estimated cycles and its
``op_name``. A fusion is arena-wide if its result, an operand or an array inside
it is shaped like a parameter arena of the step's state: ``[n]`` or
``[n / 128, 128]``::

    JAX_PLATFORMS=cpu python tools/offline_step.py gpt2-medium.train gpt2-medium.train-dp4
    JAX_PLATFORMS=cpu python tools/offline_step.py gpt2-medium.train --hlo-dir /root/scratch

A gradient arena that is materialised once reads as a few two- to eight-operand
fusions; one that is recomputed inside its consumers reads as fusions that
each take every leaf cotangent as an operand (PERF.md §6, PR 25). Estimated
cycles are the compiler's own model, not a time: times come from a chip run.

``--nameless`` prints the program ledger's records (``monitor.program_ops``) of
the instructions that carry no ``op_name`` — the compiler's own copies, fills,
prefetches and fusions, which the device trace shows with an empty ``tf_op`` —
largest first: opcode, bytes in and out, ``producer`` -> ``consumer``, hops.
Bytes are a count: bytes / 819 GB/s is a floor, never a measured time; the
times are in ``tools/dump_tf_ops.py``'s ``nameless`` table, from the chip.
"""

from __future__ import annotations

import argparse
import importlib
import math
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from beforeholiday_tpu.monitor.program import parse_instructions, program_ops  # noqa: E402

_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[\w.\-]+)")


def _is_arena(shape: str, arena_elements: set) -> bool:
    """Whether an HLO shape (a tuple counts if any member does) is an arena:
    ``[n]`` or its lane view ``[n / 128, 128]`` for an arena of n elements."""
    for dims in _SHAPE.findall(shape):
        dims = [int(d) for d in dims.split(",")] if dims else []
        if len(dims) == 1 or (len(dims) == 2 and dims[1] == 128):
            if math.prod(dims) in arena_elements:
                return True
    return False


def arena_wide_fusions(hlo: str, arena_elements: set) -> list:
    """``[(name, operand count, estimated cycles, op_name)]`` of the fusions
    that produce, consume or build inside themselves an arena-shaped array."""
    instructions = parse_instructions(hlo)
    shape_of = {i.name: i.shape for i in instructions}
    shapes_in = {}
    for i in instructions:
        shapes_in.setdefault(i.computation, []).append(i.shape)
    found = []
    for _, name, shape, opcode, operands, rest, *_ in instructions:
        if opcode != "fusion":
            continue
        called = _CALLS.search(rest)
        shapes = [shape] + [shape_of.get(operand, "") for operand in operands]
        shapes += shapes_in.get(called.group(1), []) if called else []
        if not any(_is_arena(x, arena_elements) for x in shapes):
            continue
        cycles = _CYCLES.search(rest)
        op_name = _OP_NAME.search(rest)
        found.append((
            name, len(operands), int(cycles.group(1)) if cycles else None,
            op_name.group(1) if op_name else "(no op_name)",
        ))
    return found


def compile_cell(cellname: str, topo):
    """The compiled step of one cell of ``BENCHMARK.json`` and the element
    counts of its state's arenas (the TILE-padded 1-D leaves of the step's state)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from beforeholiday_tpu.ops.arena import TILE
    from benchmark import run as R

    cell = R.load("workloads", cellname)
    cfg = R.load("configs", cell["config"])
    fam = importlib.import_module(f"benchmark.families.{cfg['family']}")
    devices = list(topo.devices)[: cell["chips"]]
    mesh = Mesh(np.asarray(devices), ("data",)) if cell["layout"] == "dp" else None
    rep = NamedSharding(mesh, P()) if mesh else SingleDeviceSharding(devices[0])
    split = NamedSharding(mesh, P("data")) if mesh else rep

    def weights(seed):
        return fam.weights(cfg, jax.random.fold_in(jax.random.PRNGKey(seed), 0))

    prog = fam.Program(cfg, cell, weights, devices, mesh)
    seed = jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        jax.eval_shape(prog.make_state, seed),
    )
    rows = cell["per_chip_batch"] * cell["chips"]
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=split),
        jax.eval_shape(lambda: fam.batch(cfg, rows, jax.random.PRNGKey(0))),
    )
    if cfg["family"] == "resnet":  # its step is a plain function of (state, batch)
        lowered = jax.jit(lambda s, b: prog.step(s, b)).lower(state, batch)
    else:
        lowered = prog.step.jitted.lower(state, batch)
    arenas = {
        int(x.size) for x in jax.tree.leaves(state)
        if x.ndim == 1 and x.size and x.size % TILE == 0  # flatten() pads to TILE
    }
    return lowered.compile(), arenas


_NOT_OPS = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


def print_nameless(cellname: str, hlo: str) -> None:
    """The ledger's records of the nameless instructions of ``hlo``, largest first."""
    rows = [r for r in program_ops(cellname, program=hlo)
            if not r["scope"] and r["opcode"] not in _NOT_OPS]
    print(f"{cellname}: {len(rows)} instructions without an op_name "
          f"({sum(not (r['producer'] or r['consumer']) for r in rows)} with no named neighbour)")
    for r in sorted(rows, key=lambda r: -max(r["bytes_in"], r["bytes_out"])):
        print(f"  {r['name']:44s} {r['opcode']:20s} in {r['bytes_in'] / 1e6:9.1f} MB  "
              f"out {r['bytes_out'] / 1e6:9.1f} MB  hops {r['hops']:2d}  "
              f"{r['producer'] or '-'} -> {r['consumer'] or '-'}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="+", help="names of cells in benchmark/workloads/")
    ap.add_argument("--hlo-dir", help="also write each cell's optimized HLO text here")
    ap.add_argument("--nameless", action="store_true",
                    help="also print the ledger's record of every instruction without an op_name")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"  # steer resolve_impl to the chip's choice
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for cellname in args.cells:
        t0 = time.time()
        compiled, arenas = compile_cell(cellname, topo)
        mem, hlo = compiled.memory_analysis(), compiled.as_text()
        print(
            f"{cellname}: compile_s {time.time() - t0:.1f}  "
            f"temp GiB {mem.temp_size_in_bytes / 2**30:.4f}  "
            f"args GiB {mem.argument_size_in_bytes / 2**30:.4f}  "
            f"tpu_custom_call {hlo.count('tpu_custom_call')}  "
            f"all-reduce {hlo.count(' all-reduce(') + hlo.count(' all-reduce-start(')}  "
            f"arena elements {sorted(arenas)}"
        )
        for name, n_operands, cycles, op_name in arena_wide_fusions(hlo, arenas):
            print(f"  {name:48s} operands {n_operands:3d}  est. cycles {cycles!s:>11}  {op_name}")
        if args.nameless:
            print_nameless(cellname, hlo)
        if args.hlo_dir:
            with open(os.path.join(args.hlo_dir, f"{cellname}.hlo.txt"), "w") as f:
                f.write(hlo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
