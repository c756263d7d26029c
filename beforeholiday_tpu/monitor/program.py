"""The program ledger — every instruction of a compiled step, and whose it is.

A ``monitor.spans.span`` names the device ops it encloses: the scope path is
the ``op_name`` of the instructions traced under it, and the profiler prints it
beside each op (``tf_op``). The compiler also makes instructions of its own
(layout copies, a ``concatenate`` turned ``dynamic-update-slice``, zero fills,
``slice-start`` / ``slice-done`` prefetches, nameless fusions): they carry no
``op_name``, no span can reach them, and an HLO name moves with every
recompile. But the program that ran IS the compile: the optimized module
(``Compiled.as_text()``) lists every instruction with its operands, a nameless
one sits between named ones, and the process that compiled it can join the
text to the device trace by the instruction's name. This module reads that
text: :func:`parse_instructions` (the one parser; ``tools/offline_step.py``
imports it), and :func:`program_ops`, one record an instruction of the
computations the device runs op by op.

**The owner rule.** A named instruction is its own (``scope`` = its
``op_name``). For a nameless one:

* ``consumer``: walk its users breadth-first through nameless instructions to
  the nearest named ones; of several at the same distance, the one the
  schedule runs first (a compiled module prints its schedule: the op ran
  because that user needed it).
* ``producer``: the same over its operands; of several, the one the schedule
  runs last.
* ``tuple``, ``get-tuple-element``, ``bitcast`` and an async ``-start`` /
  ``-done`` pair pass through at no cost; an element taken from a tuple
  follows that element alone, through a ``while`` too.
* A program's parameters name nothing (their ``op_name`` is the argument's
  path). A walk that leaves a called computation through its parameter or its
  root goes on at the calling instruction's operand or users; where that finds
  nothing, the calling instruction's own scope. A walk that ends only at the
  program's parameters and results gives ``""``.
* ``hops``: how far the owner's name came (the ``consumer``'s walk, where it
  has none the ``producer``'s): 1 is a direct neighbour, 0 a calling
  instruction's scope or nothing.

The same text gives the same records.

**What it costs.** ``remat.donate_step`` notes, the first time an entry is
called, the entry's name, its jitted function and the arguments' abstract
values (:func:`note_entry`; no device array is kept). Nothing is lowered,
compiled or parsed until :func:`program_ops` is asked; then
``jitted.lower(avals).compile().as_text()`` — JAX's own caches hand back the
executable the step runs, so nothing is traced or compiled anew where the
abstract values are the call's — parsed once and kept. The program's other
ledgers (the host ledger, comms, tiles, dispatch counters) are left as they
were found. Host-only: no device value is read.
"""

from __future__ import annotations

import contextlib
import re
import threading
import weakref
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

__all__ = [
    "note_entry",
    "parse_instructions",
    "program_ops",
    "reset_program_ledger",
    "shape_bytes",
]


# ------------------------------------------------------------------ the text
class Instruction(NamedTuple):
    """One line of an HLO text: ``rest`` is what follows the operands'
    closing parenthesis (attributes, ``metadata={op_name=...}``, the backend's
    configuration), ``args`` the text between the parentheses."""

    computation: str
    name: str
    shape: str
    opcode: str
    operands: List[str]
    rest: str
    root: bool
    args: str


_OPERAND = re.compile(r"%[\w.\-]+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_ARRAY = re.compile(r"\b([a-z]+[0-9a-z]*)\[([\d,]*)\]")
_BITS = re.compile(r"\d+")
_INDEX = re.compile(r"\bindex=(\d+)")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_ENTRY = re.compile(r"^ENTRY\s+(%[\w.\-]+)", re.M)
# attributes that name computations the device runs instruction by instruction
_CALLED = re.compile(
    r"\b(body|condition|true_computation|false_computation|to_apply|calls)=(%[\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")


def _closing(text: str, start: int) -> int:
    """Index of the parenthesis that closes the one at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if not depth:
                return i
    raise ValueError(f"unbalanced parentheses: {text[:80]}")


def parse_instructions(hlo: str) -> List[Instruction]:
    """Every instruction of an HLO text, in the text's order (a compiled
    module prints its schedule)."""
    out, computation = [], None
    for line in hlo.splitlines():
        line = line.strip()
        root = line.startswith("ROOT ")
        if root:
            line = line[5:]
        if line.endswith("{") and " -> " in line:  # "%fused_computation.3 (p: ...) -> ... {"
            computation = line.removeprefix("ENTRY ").split(" ", 1)[0]
        if not line.startswith("%") or " = " not in line:
            continue
        name, rest = line.split(" = ", 1)
        end = _closing(rest, 0) + 1 if rest.startswith("(") else rest.index(" ")
        shape, call = rest[:end], rest[end:].lstrip()
        if "(" not in call:
            continue
        opcode, args = call.split("(", 1)
        close = _closing("(" + args, 0) - 1
        out.append(Instruction(computation, name, shape, opcode,
                               _OPERAND.findall(args[:close]), args[close:], root,
                               args[:close]))
    return out


def shape_bytes(shape: str) -> int:
    """Bytes of an HLO shape, a tuple's members summed (``token[]`` and an
    opaque member are nothing; sub-byte types round up by array)."""
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        if dtype == "token":
            continue
        bits = _BITS.search(dtype)
        bits = int(bits.group()) if bits else 8        # pred
        elements = 1
        for d in dims.split(","):
            if d:
                elements *= int(d)
        total += (elements * bits + 7) // 8
    return total


# ----------------------------------------------------------------- the graph
_PASS = frozenset({"tuple", "get-tuple-element", "bitcast"})


def _free(opcode: str) -> bool:
    return opcode in _PASS or opcode.endswith(("-start", "-done"))


class _Graph:
    """The instructions of the computations the device runs op by op, with
    users, callers and the text's order."""

    def __init__(self, instructions: List[Instruction], entry: Optional[str]):
        first: Dict[str, List[Instruction]] = {}
        for i in instructions:
            first.setdefault(i.computation, []).append(i)
        # computation -> [(calling instruction's name, role, position among branches)]
        self.callers: Dict[str, List[Tuple[str, str, int]]] = {}
        device, todo = [], [entry] if entry else []
        while todo:
            comp = todo.pop()
            if comp in device or comp not in first:
                continue
            device.append(comp)
            for i in first[comp]:
                for called, role, n in self._called(i):
                    self.callers.setdefault(called, []).append((i.name, role, n))
                    todo.append(called)
        keep = set(device)
        self.ops = [i for i in instructions if i.computation in keep]
        self.by_name = {i.name: i for i in self.ops}
        self.order = {i.name: n for n, i in enumerate(self.ops)}
        self.scope = {}
        self.users: Dict[str, List[Tuple[str, int]]] = {}
        for i in self.ops:
            m = _OP_NAME.search(i.rest)
            self.scope[i.name] = m.group(1) if m else ""
            for pos, o in enumerate(i.operands):
                self.users.setdefault(o, []).append((i.name, pos))

    @staticmethod
    def _called(i: Instruction) -> Iterable[Tuple[str, str, int]]:
        if i.opcode == "fusion":
            return
        for role, name in _CALLED.findall(i.rest):
            if role == "to_apply" and i.opcode != "call":   # a reducer
                continue
            if role.endswith("_computation"):       # a two-way conditional's branches
                yield name, "branch", int(role.startswith("false"))
            else:
                yield name, role, 0
        for m in _BRANCHES.finditer(i.rest):
            for n, name in enumerate(_OPERAND.findall(m.group(1))):
                yield name, "branch", n

    def named(self, name: str) -> bool:
        return bool(self.scope[name]) and self.by_name[name].opcode != "parameter"

    @staticmethod
    def element(i: Instruction) -> Optional[int]:
        """The ``index=`` of a ``get-tuple-element``."""
        m = _INDEX.search(i.rest)
        return int(m.group(1)) if m else None

    # one step of a walk: [(name, tuple element in flight or None)], and
    # whether the step left a called computation
    def toward_operands(self, name: str, k: Optional[int]):
        i = self.by_name[name]
        if i.opcode == "get-tuple-element":
            return [(i.operands[0], self.element(i))], False
        if i.opcode == "tuple" and k is not None and k < len(i.operands):
            return [(i.operands[k], None)], False
        if i.opcode == "parameter":
            out = []
            for caller, role, n in self.callers.get(i.computation, ()):
                c = self.by_name[caller]
                if role in ("body", "condition"):
                    pos = 0
                elif role == "branch":
                    pos = n + 1
                else:
                    pos = int(i.args) if i.args.isdigit() else 0
                if pos < len(c.operands):
                    out.append((c.operands[pos], k))
            return out, bool(out)
        carry = k if i.opcode == "while" else None    # its result's element k is its operand's
        return [(o, carry) for o in i.operands], False

    def toward_users(self, name: str, k: Optional[int]):
        out, left = [], False
        for user, pos in self.users.get(name, ()):
            u = self.by_name[user]
            if u.opcode == "tuple":
                out.append((user, pos))
            elif u.opcode == "get-tuple-element":
                if k is None or self.element(u) == k:
                    out.append((user, None))
            else:
                out.append((user, k if u.opcode == "while" else None))
        i = self.by_name[name]
        if i.root:
            for caller, role, _ in self.callers.get(i.computation, ()):
                if role != "condition":       # a condition's result feeds the loop alone
                    out += self.toward_users(caller, k)[0]
                    left = True
        return out, left

    def nearest(self, start: str, users: bool) -> Tuple[str, int]:
        """``(scope, hops)`` of the nearest named instruction from ``start``
        over its users (else its operands)."""
        step = self.toward_users if users else self.toward_operands
        seen, frontier, hops, left = {(start, None)}, [(start, None)], 0, False
        while frontier:
            hops += 1
            found, following, expand = [], [], frontier
            while expand:                 # what is free to cross stays on this level
                reached, out = step(*expand.pop())
                left |= out
                for at in reached:
                    if at in seen or at[0] not in self.by_name:
                        continue
                    seen.add(at)
                    opcode = self.by_name[at[0]].opcode
                    if self.named(at[0]):
                        found.append(at[0])
                    elif _free(opcode) or opcode == "parameter":
                        expand.append(at)
                    else:
                        following.append(at)
            if found:
                pick = min if users else max
                return self.scope[pick(found, key=self.order.__getitem__)], hops
            frontier = following
        return (self.calling_scope(self.by_name[start].computation) if left else ""), 0

    def calling_scope(self, computation: str) -> str:
        """The scope of the nearest instruction that calls ``computation``."""
        seen, todo = set(), [computation]
        while todo:
            comp = todo.pop(0)
            if comp in seen:
                continue
            seen.add(comp)
            for caller, _, _ in self.callers.get(comp, ()):
                if self.scope[caller]:
                    return self.scope[caller]
                todo.append(self.by_name[caller].computation)
        return ""


def _records(text: str, entry: str) -> List[Dict[str, Any]]:
    m = _MODULE.match(text)
    module = m.group(1) if m else ""
    m = _ENTRY.search(text)
    graph = _Graph(parse_instructions(text), m.group(1) if m else None)
    shape = {i.name: shape_bytes(i.shape) for i in graph.ops}
    out = []
    for i in graph.ops:
        row = {"entry": entry, "module": module, "computation": i.computation,
               "name": i.name, "opcode": i.opcode, "scope": graph.scope[i.name],
               "bytes_out": shape[i.name],
               "bytes_in": sum(shape.get(o, 0) for o in i.operands)}
        if not row["scope"]:
            consumer, hops = graph.nearest(i.name, users=True)
            producer, producer_hops = graph.nearest(i.name, users=False)
            row.update(consumer=consumer, producer=producer,
                       hops=hops if consumer else producer_hops)
        out.append(row)
    return out


# ---------------------------------------------------------------- the ledger
_LOCK = threading.Lock()
# [{"entry", "jitted": weakref, "args", "kwargs", "records": None | [dict]}]
_NOTES: List[Dict[str, Any]] = []


def _abstract(x: Any) -> Any:
    """An array's shape, dtype and (where it was placed) sharding; anything
    else as it is."""
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, weak_type=x.weak_type,
                                    sharding=x.sharding if x.committed else None)
    if isinstance(x, np.ndarray):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def note_entry(entry: str, jitted: Any, args: tuple, kwargs: dict) -> bool:
    """Remember what ``entry`` was first called with, so that
    :func:`program_ops` can ask for its compiled text later. Abstract values
    only: a donated array is gone after the call. Under an outer trace there
    is nothing to note (``False``): the outer program owns the instructions."""
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return False
    args, kwargs = jax.tree_util.tree_map(_abstract, (args, kwargs))
    with _LOCK:
        _NOTES[:] = [n for n in _NOTES if n["jitted"]() is not None]
        _NOTES.append({"entry": entry, "jitted": weakref.ref(jitted), "args": args,
                       "kwargs": kwargs, "records": None})
    return True


@contextlib.contextmanager
def _put_back(lock, *tables):
    """The ``tables`` (dicts of dicts, guarded by ``lock``) after the block as
    they were before it."""
    with lock:
        kept = [{k: dict(v) for k, v in t.items()} for t in tables]
    try:
        yield
    finally:
        with lock:
            for table, was in zip(tables, kept):
                table.clear()
                table.update(was)


@contextlib.contextmanager
def _other_ledgers_held():
    """What the program's other ledgers held before the block, after it: the
    host ledger drops this thread's events meanwhile (a collection's pause
    excepted: it happened), comms, tiles and dispatch counters are put back."""
    from beforeholiday_tpu.guard import dispatch
    from beforeholiday_tpu.monitor import comms, compile as compile_ledger
    from beforeholiday_tpu.monitor.trace import held

    pending = len(compile_ledger._PENDING.events)
    try:
        with _put_back(dispatch._VERDICTS_LOCK, dispatch._COUNTERS, dispatch._TILES), \
                _put_back(comms._LOCK, comms._RECORDS), held():
            yield
    finally:
        del compile_ledger._PENDING.events[pending:]


def _compiled_text(note: Dict[str, Any]) -> Optional[str]:
    jitted = note["jitted"]()
    if jitted is None:
        return None
    with _other_ledgers_held():
        return jitted.lower(*note["args"], **note["kwargs"]).compile().as_text()


def program_ops(entry: Optional[str] = None, *, program: Any = None
                ) -> List[Dict[str, Any]]:
    """One record an instruction of the computations the device runs op by op
    (the entry, ``while`` bodies and conditions, ``conditional`` branches,
    ``call``ed computations; not fusion bodies, not reducers), in the
    schedule's order: ``entry`` (the ``donate_step`` entry it was compiled
    for), ``module``, ``computation``, ``name`` (as the device trace prints
    it: ``%copy.436``), ``opcode``, ``scope`` (its own ``op_name``, ``""``
    where it has none), ``bytes_out``, ``bytes_in`` and, for a nameless one,
    ``consumer``, ``producer`` and ``hops`` by the module's owner rule.

    Of every entry ``donate_step`` has seen, or of ``entry`` alone. With
    ``program`` (a ``jax.stages.Compiled`` or its text) the records are of
    that program, labelled ``entry``, and the ledger is not consulted: what
    ``tools/offline_step.py`` prints for a described chip."""
    if program is not None:
        text = program if isinstance(program, str) else program.as_text()
        return _records(text, entry or "")
    out = []
    with _LOCK:
        for note in _NOTES:
            if entry is not None and note["entry"] != entry:
                continue
            if note["records"] is None:
                text = _compiled_text(note)
                if text is None:            # the step function is gone
                    continue
                note["records"] = _records(text, note["entry"])
            out += [dict(r) for r in note["records"]]
    return out


def reset_program_ledger() -> None:
    """Forget every noted entry and its records. An entry already called is
    not noted again: ``donate_step`` notes a wrapper's first call only."""
    with _LOCK:
        del _NOTES[:]
