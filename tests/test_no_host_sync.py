"""Static no-host-sync check over the library source.

The reference's scaler deliberately defers its only ``.item()`` to scale-update
time (apex/amp/scaler.py:206); the TPU port goes further — NOTHING in the hot
path may read a traced value back to the host, or every step stalls the XLA
pipeline. This test walks the AST of every ``beforeholiday_tpu`` module and
flags the two readback idioms:

* any ``x.item()`` call;
* ``float(...)`` / ``int(...)`` whose argument is a subscript like
  ``state["scale"]`` — the traced-state readback pattern (a subscripted name is
  how device state travels here; ``float(eps)`` on a plain config scalar is
  fine and not flagged).

Sanctioned sync points are ``state_dict``-family methods (checkpointing is
host-side by contract, ref: apex/amp/frontend.py:434-473) — anything inside a
function whose name is in ``_SANCTIONED_FUNCS`` passes. Host-side harnesses
(testing/, models/ input pipelines) are out of scope: they run between steps,
not inside them.
"""

import ast
import pathlib

import beforeholiday_tpu

_PKG_ROOT = pathlib.Path(beforeholiday_tpu.__file__).parent

# functions that are host-side by contract
_SANCTIONED_FUNCS = frozenset({"state_dict", "load_state_dict"})

# directories that are host harnesses, not step code
_SKIP_DIRS = frozenset({"testing", "models"})

# file-scoped sanctioned functions: the monitor exporter's drain path is the
# ONE host-side readback the observability contract allows (one fetch per
# logged step, piggybacking on the step's existing scalar readback), the
# trace recorder's ``export`` is its one file-write path (host dicts only —
# it never reads a device value), and the flight recorder's ``dump`` is the
# crash-dump write path (it serializes already-drained host rows) — nothing
# else in monitor/ may sync. The serving engine's host surface (prefill/
# decode/decode_logits — serving cannot emit a token without reading it
# back) and the batcher's scheduler drive points are the inference
# subsystem's sanctioned boundary; everything below them (the step
# functions, the paged cache ops) must stay sync-free. The elastic
# checkpoint manager's snapshot/serialize entry points (``submit`` initiates
# the async D2H copy, ``wait`` drains, ``_write_generation`` joins the copy
# on the writer thread) are the ONE place checkpointing may touch host
# values; the trainer's run loop gets no sanction — it drains the step row
# the same way the examples do
_SANCTIONED_BY_FILE = {
    "monitor/export.py": frozenset({"drain", "flush", "_fetch"}),
    "monitor/trace.py": frozenset({"export"}),
    "monitor/flight.py": frozenset({"dump"}),
    "infer/engine.py": frozenset({"prefill", "decode", "decode_logits"}),
    "infer/batching.py": frozenset({"step", "static_batched_generate"}),
    "elastic/checkpoint.py": frozenset(
        {"submit", "wait", "_write_generation"}
    ),
    # forward-looking pins: neither file syncs today, and the sanction
    # confines any future readback to the documented host-side entry points
    # (the signal handler and the heartbeat/monitor path run OUTSIDE the
    # step's data path by contract — anywhere else in these files a
    # readback must fail the scan)
    "elastic/signals.py": frozenset({"_handler"}),
    "elastic/watchdog.py": frozenset({"_monitor_loop", "beat"}),
}

# file-scoped waivers for sync points that are part of a documented host-side
# contract but live outside a state_dict method; keep this list SHORT and
# justified — every entry is a reviewed exception, not an escape hatch
_WAIVED = {
    # (relative path, function name): reason
    ("contrib/sparsity.py", "permutation_search"):
        "pure-NumPy host-side channel-permutation search (the reference's "
        "ASP search also runs on host, between steps) — no traced values",
}


def _flag_nodes(tree: ast.AST):
    """Yield (node, idiom) for every host-sync idiom outside a sanctioned
    function."""
    # stack of enclosing function names, updated via a manual walk
    out = []

    def visit(node, func_stack):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            func_stack = func_stack + [node.name]
        if isinstance(node, ast.Call):
            f = node.func
            sanctioned = any(n in _SANCTIONED_FUNCS for n in func_stack)
            if (
                isinstance(f, ast.Attribute)
                and f.attr == "item"
                and not node.args
                and not sanctioned
            ):
                out.append((node, ".item()", func_stack))
            if (
                isinstance(f, ast.Name)
                and f.id in ("float", "int")
                and len(node.args) == 1
                and isinstance(node.args[0], ast.Subscript)
                # x.shape[i] is a static Python int, never a traced value
                and not (
                    isinstance(node.args[0].value, ast.Attribute)
                    and node.args[0].value.attr == "shape"
                )
                and not sanctioned
            ):
                out.append((node, f"{f.id}(<subscript>)", func_stack))
        for child in ast.iter_child_nodes(node):
            visit(child, func_stack)

    visit(tree, [])
    return out


def test_no_host_sync_idioms_in_library():
    offenders = []
    for py in sorted(_PKG_ROOT.rglob("*.py")):
        rel = py.relative_to(_PKG_ROOT)
        if rel.parts and rel.parts[0] in _SKIP_DIRS:
            continue
        tree = ast.parse(py.read_text(), filename=str(py))
        file_sanctioned = _SANCTIONED_BY_FILE.get(rel.as_posix(), frozenset())
        for node, idiom, func_stack in _flag_nodes(tree):
            func = func_stack[-1] if func_stack else "<module>"
            if (str(rel), func) in _WAIVED:
                continue
            if any(n in file_sanctioned for n in func_stack):
                continue
            offenders.append(f"{rel}:{node.lineno} {idiom} in {func}()")
    assert not offenders, (
        "host-sync idioms outside state_dict/load_state_dict "
        "(wrap readbacks in a state_dict-family method, or add a reviewed "
        "waiver):\n  " + "\n  ".join(offenders)
    )


def test_scanner_catches_the_idioms():
    """The checker itself must actually fire on both idioms — guard the guard."""
    src = (
        "def hot(state):\n"
        "    a = state['scale'].item()\n"
        "    b = float(state['scale'])\n"
        "    c = int(state['n'])\n"
        "    d = float(3.5)  # plain scalar: fine\n"
        "def state_dict(state):\n"
        "    return {'scale': float(state['scale'])}  # sanctioned\n"
    )
    flags = _flag_nodes(ast.parse(src))
    idioms = sorted(i for _, i, _ in flags)
    assert idioms == [".item()", "float(<subscript>)", "int(<subscript>)"]


def test_monitor_package_is_scanned():
    """monitor/ must be inside the scanner's reach (not under _SKIP_DIRS),
    and its only file-scoped sanctions are the exporter's drain path and the
    trace recorder's write path."""
    monitor_files = sorted(
        p.relative_to(_PKG_ROOT).as_posix()
        for p in (_PKG_ROOT / "monitor").rglob("*.py")
    )
    assert "monitor/metrics.py" in monitor_files
    assert "monitor/comms.py" in monitor_files
    assert "monitor/trace.py" in monitor_files
    assert "monitor/compile.py" in monitor_files
    assert "monitor" not in _SKIP_DIRS
    assert set(_SANCTIONED_BY_FILE) == {
        "monitor/export.py", "monitor/trace.py", "monitor/flight.py",
        "infer/engine.py", "infer/batching.py", "elastic/checkpoint.py",
        "elastic/signals.py", "elastic/watchdog.py",
    }
    assert _SANCTIONED_BY_FILE["monitor/export.py"] == {"drain", "flush", "_fetch"}
    assert _SANCTIONED_BY_FILE["monitor/trace.py"] == {"export"}
    assert _SANCTIONED_BY_FILE["monitor/flight.py"] == {"dump"}


def test_perf_attribution_files_are_scanned():
    """The perf-attribution files (the chip peaks, the program ledger, the
    host ledger's feeders in compile.py and spans.py, flight recorder) promise
    host-side arithmetic over already-drained data — the scanner must reach
    them all, and only the flight recorder's ``dump`` (its one crash-dump write
    path) is sanctioned; the ledgers and their feeders get NO sanctions and NO
    waivers."""
    monitor_files = sorted(
        p.relative_to(_PKG_ROOT).as_posix()
        for p in (_PKG_ROOT / "monitor").rglob("*.py")
    )
    assert "monitor/roofline.py" in monitor_files
    assert "monitor/program.py" in monitor_files
    assert "monitor/overlap.py" not in monitor_files     # went with PR 35
    assert "monitor/spans.py" in monitor_files
    assert "monitor/flight.py" in monitor_files
    assert "monitor/roofline.py" not in _SANCTIONED_BY_FILE
    assert "monitor/program.py" not in _SANCTIONED_BY_FILE
    assert "monitor/compile.py" not in _SANCTIONED_BY_FILE
    assert "monitor/spans.py" not in _SANCTIONED_BY_FILE
    assert _SANCTIONED_BY_FILE["monitor/flight.py"] == {"dump"}
    assert not [k for k in _WAIVED if k[0] in (
        "monitor/roofline.py", "monitor/program.py", "monitor/compile.py",
        "monitor/spans.py", "monitor/flight.py",
    )]


def test_bucketing_is_scanned():
    """parallel/bucketing.py promises static bucket geometry with no host
    readbacks (its docstring cites this scan) — pin that the scanner actually
    reaches it with no waivers or file-scoped sanctions."""
    parallel_files = sorted(
        p.relative_to(_PKG_ROOT).as_posix()
        for p in (_PKG_ROOT / "parallel").rglob("*.py")
    )
    assert "parallel/bucketing.py" in parallel_files
    assert "parallel" not in _SKIP_DIRS
    assert not any(path.startswith("parallel/") for path in _SANCTIONED_BY_FILE)
    assert not any(
        path.startswith("parallel/") for path, _ in _WAIVED
    )
    # and no monitor file carries a (file, func) waiver — the sanction list
    # above is the entire exception surface for the subsystem
    assert not [k for k in _WAIVED if k[0].startswith("monitor/")]


def test_overlap_engine_is_scanned():
    """parallel/overlap.py promises traced flags and static bucket geometry
    with no host syncs (its docstring cites this scan) — pin that the scanner
    reaches it with zero sanctions and zero waivers, so a future ``float()``
    on a found_inf flag (the classic apex-port host-sync bug) fails loudly."""
    parallel_files = sorted(
        p.relative_to(_PKG_ROOT).as_posix()
        for p in (_PKG_ROOT / "parallel").rglob("*.py")
    )
    assert "parallel/overlap.py" in parallel_files
    assert "parallel" not in _SKIP_DIRS
    assert not any(path.startswith("parallel/") for path in _SANCTIONED_BY_FILE)
    assert not any(path.startswith("parallel/") for path, _ in _WAIVED)


def test_infer_package_is_scanned():
    """infer/ promises that everything below the engine's host surface is
    sync-free: the traced step functions and the paged-cache ops never read a
    device value, and the ONLY sanctioned boundary is where serving must read
    tokens back — the engine's prefill/decode/decode_logits and the batcher's
    scheduler drive points. Pin that the scanner reaches every infer file,
    that the sanction set is exactly that boundary, and that nothing in
    infer/ carries a waiver — a future ``.item()`` inside a step function or
    the page allocator fails loudly."""
    infer_files = sorted(
        p.relative_to(_PKG_ROOT).as_posix()
        for p in (_PKG_ROOT / "infer").rglob("*.py")
    )
    assert "infer/engine.py" in infer_files
    assert "infer/kvcache.py" in infer_files
    assert "infer/batching.py" in infer_files
    assert "infer" not in _SKIP_DIRS
    assert _SANCTIONED_BY_FILE["infer/engine.py"] == {
        "prefill", "decode", "decode_logits",
    }
    assert _SANCTIONED_BY_FILE["infer/batching.py"] == {
        "step", "static_batched_generate",
    }
    # the cache/page layer gets NO sanctions and NO waivers
    assert "infer/kvcache.py" not in _SANCTIONED_BY_FILE
    assert not any(path.startswith("infer/") for path, _ in _WAIVED)


def test_remat_and_memory_ledger_are_scanned():
    """remat/ (policies + donation) and the memory ledger promise host-side
    metadata work ONLY (shapes, treedefs, compiler stats — never a traced
    value): pin that the scanner reaches all of them with zero sanctions and
    zero waivers."""
    remat_files = sorted(
        p.relative_to(_PKG_ROOT).as_posix()
        for p in (_PKG_ROOT / "remat").rglob("*.py")
    )
    assert "remat/policies.py" in remat_files
    assert "remat/donation.py" in remat_files
    assert "remat" not in _SKIP_DIRS
    assert not any(path.startswith("remat/") for path in _SANCTIONED_BY_FILE)
    assert not any(path.startswith("remat/") for path, _ in _WAIVED)
    # the ledger lives in monitor/ and must be clean — the monitor sanction
    # set (export/trace) must NOT have grown to admit it
    assert "monitor/memory.py" not in _SANCTIONED_BY_FILE
    assert not [k for k in _WAIVED if k[0] == "monitor/memory.py"]
    assert (_PKG_ROOT / "monitor" / "memory.py").exists()


def test_zero3_engine_is_scanned():
    """optimizers/zero3.py promises that the traced path — prefetched bucket
    gather, custom_vjp reduce-scatter, sharded fused step — never reads a
    device value back (its docstring cites this scan); the sharded-checkpoint
    host I/O lives in module-level helpers that run between steps on numpy
    arrays, not on traced values. Pin that the scanner reaches the file with
    zero file-scoped sanctions and zero waivers, so a future ``.item()`` on
    the found_inf flag or an ``int()`` on a manifest lookup of a traced
    value fails loudly."""
    opt_files = sorted(
        p.relative_to(_PKG_ROOT).as_posix()
        for p in (_PKG_ROOT / "optimizers").rglob("*.py")
    )
    assert "optimizers/zero3.py" in opt_files
    assert "optimizers" not in _SKIP_DIRS
    assert not any(
        path.startswith("optimizers/") for path in _SANCTIONED_BY_FILE
    )
    assert not any(path.startswith("optimizers/") for path, _ in _WAIVED)


def test_multislice_surface_is_scanned():
    """The two-level hierarchical engine promises static tier geometry with
    no readbacks: ``_sized_axes``/``static_axis_size`` resolve slice/intra
    sizes at trace time, and the per-tier ledger books while XLA builds the
    program. Pin that its whole surface — the mesh helpers, the two-level
    bucketing engines, and the tier-aware ledger — sits inside the
    scanner's reach with ZERO file-scoped sanctions and ZERO waivers, so a
    future ``int()`` on a traced axis index in the scatter leg fails
    loudly."""
    for rel in (
        "parallel/parallel_state.py",
        "parallel/bucketing.py",
        "parallel/distributed.py",
        "monitor/comms.py",
    ):
        assert (_PKG_ROOT / rel).is_file(), rel
        assert pathlib.Path(rel).parts[0] not in _SKIP_DIRS
        assert rel not in _SANCTIONED_BY_FILE
        assert not any(path == rel for path, _ in _WAIVED)


def test_elastic_is_scanned():
    """elastic/ promises that checkpointing's host side is confined to the
    manager's snapshot/serialize entry points: ``submit`` (initiates the
    non-blocking D2H copy), ``wait`` (drains the queue), and
    ``_write_generation`` (joins the copy on the writer thread). The trainer's
    loop drains its step row between steps like the examples do (bind the
    fetched value to a name first — ``float(<subscript>)`` stays flagged) and
    gets NO sanction, so a future readback inside its step path fails
    loudly."""
    elastic_files = sorted(
        p.relative_to(_PKG_ROOT).as_posix()
        for p in (_PKG_ROOT / "elastic").rglob("*.py")
    )
    assert "elastic/checkpoint.py" in elastic_files
    assert "elastic/trainer.py" in elastic_files
    assert "elastic/signals.py" in elastic_files
    assert "elastic/watchdog.py" in elastic_files
    assert "elastic" not in _SKIP_DIRS
    assert _SANCTIONED_BY_FILE["elastic/checkpoint.py"] == {
        "submit", "wait", "_write_generation",
    }
    # the preemption bridge and the watchdog are host-side BY DESIGN, but
    # only at their documented entry points: the async-signal-safe handler,
    # the heartbeat, and the monitor scan — pinned so a readback anywhere
    # else in those files (the tick/check polls especially, which run once
    # per step) fails the scan
    assert _SANCTIONED_BY_FILE["elastic/signals.py"] == {"_handler"}
    assert _SANCTIONED_BY_FILE["elastic/watchdog.py"] == {
        "_monitor_loop", "beat",
    }
    assert "elastic/trainer.py" not in _SANCTIONED_BY_FILE
    assert not any(path.startswith("elastic/") for path, _ in _WAIVED)


def test_quantized_tier_is_scanned():
    """The O6 tier is hot-path-only by construction: ops/quantized.py keeps
    every amax/scale decision device-side (its docstring's tracer-hygiene
    contract), and the collective-matmul ring in tensor_parallel/collective.py
    runs inside shard_map where any readback would deadlock a rank. Pin that
    both files sit inside the scanner's reach with ZERO file-scoped sanctions
    and ZERO waivers — a future ``.item()`` on an amax observation or a hop
    count must fail this suite, not ship."""
    for rel in (
        "ops/quantized.py",
        "transformer/tensor_parallel/collective.py",
    ):
        assert (_PKG_ROOT / rel).is_file(), rel
        assert pathlib.Path(rel).parts[0] not in _SKIP_DIRS
    assert not any(
        path.startswith(("ops/quantized", "transformer/tensor_parallel/"))
        for path in _SANCTIONED_BY_FILE
    )
    assert not any(
        path.startswith(("ops/quantized", "transformer/tensor_parallel/"))
        for path, _ in _WAIVED
    )


def test_telemetry_surface_is_scanned():
    """The telemetry trio (streaming histogram, goodput ledger, serving
    request telemetry) promises pure host-side bookkeeping over values the
    batcher/trainer ALREADY read back at their sanctioned boundaries — the
    histogram's ``bucketize`` stays a pure jnp function whose counts come
    home through the MetricsLogger drain, and the serving hooks take clock
    readings as arguments instead of reading anything. Pin that all three
    files sit inside the scanner's reach with ZERO file-scoped sanctions
    and ZERO waivers — a future ``.item()`` on a bucketize result or a
    ``float()`` on a drained subscript must fail this suite, not ship."""
    for rel in (
        "monitor/histo.py",
        "monitor/goodput.py",
        "infer/telemetry.py",
    ):
        assert (_PKG_ROOT / rel).is_file(), rel
        assert pathlib.Path(rel).parts[0] not in _SKIP_DIRS
        assert rel not in _SANCTIONED_BY_FILE
        assert not any(path == rel for path, _ in _WAIVED)


def test_moe_surface_is_scanned():
    """The MoE subsystem promises routing with NO host syncs: capacity is a
    static Python int from static shapes, every keep/drop decision is a
    traced comparison, and the drop fraction surfaces as a Metrics key
    instead of a readback. Pin that the whole package sits inside the
    scanner's reach with ZERO file-scoped sanctions and ZERO waivers."""
    moe_files = sorted(
        p.relative_to(_PKG_ROOT).as_posix()
        for p in (_PKG_ROOT / "moe").rglob("*.py")
    )
    assert "moe/router.py" in moe_files
    assert "moe/experts.py" in moe_files
    assert "moe/dispatch.py" in moe_files
    for rel in moe_files:
        assert pathlib.Path(rel).parts[0] not in _SKIP_DIRS
        assert rel not in _SANCTIONED_BY_FILE
        assert not any(path == rel for path, _ in _WAIVED)
