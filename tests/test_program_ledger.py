"""The program ledger (``monitor/program.py``): the one parser of a compiled
module's text, the bytes of its shapes, the owner rule case by case on HLO
snippets, and on a small jitted step under ``donate_step`` (CPU backend) that
the ledger is no work until asked and leaves the other ledgers alone."""

import collections
import re

import jax
import jax.numpy as jnp
import pytest

from beforeholiday_tpu import monitor
from beforeholiday_tpu.guard import dispatch
from beforeholiday_tpu.monitor import comms, program
from beforeholiday_tpu.remat import donate_step

# ------------------------------------------------------------------- the parser
_LINES = {
    "plain": (
        '  %copy.436 = f32[1,8192,16384]{2,1,0:T(8,128)} copy(%fusion.169), metadata={op_name="a/b"}',
        ("%copy.436", "f32[1,8192,16384]{2,1,0:T(8,128)}", "copy", ["%fusion.169"], False)),
    "root": (
        "  ROOT %tuple.4 = (bf16[65536]{0:T(1024)(128)(2,1)}, pred[]{:T(512)}) tuple(%dus.1, %check.3)",
        ("%tuple.4", "(bf16[65536]{0:T(1024)(128)(2,1)}, pred[]{:T(512)})", "tuple",
         ["%dus.1", "%check.3"], True)),
    "async_start": (
        "  %copy-start.1 = (bf16[402432,128]{1,0:T(8,128)(2,1)}, bf16[402432,128]{1,0:T(8,128)(2,1)S(1)}, "
        "u32[]{:S(2)}) copy-start(%dynamic_slice.182)",
        ("%copy-start.1", "(bf16[402432,128]{1,0:T(8,128)(2,1)}, bf16[402432,128]{1,0:T(8,128)(2,1)S(1)}, "
         "u32[]{:S(2)})", "copy-start", ["%dynamic_slice.182"], False)),
    "async_done": (
        "  %slice-done.7 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} slice-done(%slice-start.7)",
        ("%slice-done.7", "bf16[8,128]{1,0:T(8,128)(2,1)S(1)}", "slice-done", ["%slice-start.7"],
         False)),
    "fusion_calls": (
        '  %fusion.143 = s32[1,8,4,128]{3,2,1,0:T(4,128)S(1)} fusion(%copy-done.32), kind=kLoop, '
        'calls=%fused_computation.186, backend_config={"window_config":{"estimated_cycles":"1929"}}',
        ("%fusion.143", "s32[1,8,4,128]{3,2,1,0:T(4,128)S(1)}", "fusion", ["%copy-done.32"], False)),
    "while": (
        '  %while.2 = (s32[]{:T(128)}, bf16[4,1024,1024]{2,1,0:T(8,128)(2,1)}, /*index=2*/f32[24,1024]{1,0}) '
        'while(%tuple.9), condition=%wide.region_1.3, body=%wide.region_0.2.sunk, '
        'metadata={op_name="jit(step)/amp_forward/jvp(gpt_blocks)/while"}',
        ("%while.2", "(s32[]{:T(128)}, bf16[4,1024,1024]{2,1,0:T(8,128)(2,1)}, /*index=2*/f32[24,1024]{1,0})",
         "while", ["%tuple.9"], False)),
    "custom_call_with_body": (
        '  %flash_attention.3 = (bf16[64,1024,64]{2,1,0}, f32[64,1024]{1,0}) custom-call(%q.1, %k.1, %v.1), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[64,1024,64]{2,1,0}}, '
        'metadata={op_name="jit(step)/amp_forward/flash_attention/pallas_call"}, '
        'backend_config={"custom_call_config": {"body": "TUxJUu+/vQ(=)", "kernel_name(x)": "fa"}}',
        ("%flash_attention.3", "(bf16[64,1024,64]{2,1,0}, f32[64,1024]{1,0})", "custom-call",
         ["%q.1", "%k.1", "%v.1"], False)),
    "no_operands": (
        '  %custom-call.14 = bf16[24,4,1024,4096]{3,2,1,0:T(8,128)(2,1)} custom-call(), '
        'custom_call_target="AllocateBuffer"',
        ("%custom-call.14", "bf16[24,4,1024,4096]{3,2,1,0:T(8,128)(2,1)}", "custom-call", [], False)),
    "parameter": (
        '  %state_0__0_.1 = bf16[354779136]{0:T(1024)(128)(2,1)} parameter(3), sharding={replicated}, '
        'metadata={op_name="state[0][0]"}',
        ("%state_0__0_.1", "bf16[354779136]{0:T(1024)(128)(2,1)}", "parameter", [], False)),
    "conditional": (
        "  %conditional.5 = f32[8]{0} conditional(%pred.1, %a.1, %b.1), "
        "branch_computations={%branch_0.1, %branch_1.2}",
        ("%conditional.5", "f32[8]{0}", "conditional", ["%pred.1", "%a.1", "%b.1"], False)),
    "constant": (
        "  %constant.137 = f32[1,1]{1,0:T(1,128)} constant({ {1e-05} })",
        ("%constant.137", "f32[1,1]{1,0:T(1,128)}", "constant", [], False)),
}


@pytest.mark.parametrize("kind", sorted(_LINES))
def test_parser_reads_every_shape_of_line(kind):
    line, (name, shape, opcode, operands, root) = _LINES[kind]
    text = "ENTRY %main.1 (p: f32[8]) -> f32[8] {\n" + line + "\n}\n"
    (got,) = program.parse_instructions(text)
    assert (got.computation, got.name, got.shape, got.opcode, got.operands, got.root) == \
        ("%main.1", name, shape, opcode, operands, root)
    assert got.rest.startswith(")")
    if kind == "parameter":
        assert got.args == "3"
    if kind in ("plain", "while", "custom_call_with_body"):
        assert "op_name=" in got.rest


def test_parser_names_each_instruction_s_computation():
    got = program.parse_instructions(_WHILE)
    assert collections.Counter(i.computation for i in got) == {
        "%fused.1": 2, "%sum.1": 3, "%body.1": 9, "%cond.1": 4, "%main.9": 11}


@pytest.mark.parametrize("shape, expected", [
    ("f32[4,8]{1,0}", 128),
    ("bf16[3]{0}", 6),
    ("pred[7]{0}", 7),
    ("u32[]{:S(2)}", 4),
    ("token[]", 0),
    ("s4[3]{0}", 2),
    ("f8e4m3fn[16]{0}", 16),
    ("bf16[8,128]{1,0:T(8,128)(2,1)S(1)}", 2048),
    ("(bf16[8,128]{1,0:T(8,128)(2,1)}, bf16[8,128]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)})", 4100),
    ("(s32[]{:T(128)}, /*index=1*/(f32[2]{0}, f64[2]{0}))", 28),
])
def test_bytes_of_a_shape(shape, expected):
    assert program.shape_bytes(shape) == expected


# ---------------------------------------------------------------- the owner rule
def _owners(text):
    return {r["name"]: r for r in program.program_ops("e", program=text)}


def _named(name, scope, operands, opcode="add", shape="f32[8]{0}"):
    return f'  {name} = {shape} {opcode}({operands}), metadata={{op_name="{scope}"}}'


_FLAT = "\n".join([
    "HloModule jit_step, is_scheduled=true",
    "",
    "ENTRY %main.1 (p0: f32[8], p1: f32[8]) -> f32[8] {",
    '  %p0 = f32[8]{0} parameter(0), metadata={op_name="state[0]"}',
    '  %p1 = f32[8]{0} parameter(1), metadata={op_name="batch[0]"}',
    _named("%a", "s/amp_forward/a", "%p0, %p1"),
    _named("%b", "s/amp_forward/b", "%p0, %p1"),
    "  %x = f32[8]{0} copy(%a)",                      # users: %far_copy, %d (direct), %e (direct)
    "  %far_copy = f32[8]{0} copy(%x)",
    _named("%c", "s/amp_backward/c", "%far_copy, %b"),
    "  %y = f32[8]{0} add(%a, %b)",                   # two named operands: the later wins
    _named("%d", "s/amp_backward/d", "%x, %y"),
    _named("%e", "s/amp_backward/e", "%x, %y"),
    "  %bc = f32[2,4]{1,0} bitcast(%e)",
    "  %cs = (f32[2,4]{1,0}, f32[2,4]{1,0}, u32[]{:S(2)}) copy-start(%bc)",
    "  %cd = f32[2,4]{1,0} copy-done(%cs)",
    "  %z = f32[2,4]{1,0} copy(%cd)",                 # producer through a free chain: 1 hop
    "  %t = (f32[8]{0}, f32[2,4]{1,0}) tuple(%far_copy, %z)",
    "  %g0 = f32[8]{0} get-tuple-element(%t), index=0",
    "  %g1 = f32[2,4]{1,0} get-tuple-element(%t), index=1",
    _named("%f", "s/fused_adam_step_flat/f", "%g0"),
    _named("%g", "s/amp_unscale/g", "%g1", shape="f32[2,4]{1,0}"),
    "  %lone = f32[8]{0} copy(%p0)",                  # parameter in, result out
    "  ROOT %out = (f32[8]{0}, f32[2,4]{1,0}, f32[8]{0}) tuple(%f, %g, %lone)",
    "}",
])


@pytest.mark.parametrize("name, producer, consumer, hops", [
    # nearest wins, and of the two direct users the schedule's first
    ("%x", "s/amp_forward/a", "s/amp_backward/d", 1),
    # its other user's name is nearer than %f's behind the tuple
    ("%far_copy", "s/amp_forward/a", "s/amp_backward/c", 1),
    # of two operands at the same distance, the schedule's last
    ("%y", "s/amp_forward/b", "s/amp_backward/d", 1),
    # bitcast and the async pair cost nothing, either way
    ("%z", "s/amp_backward/e", "s/amp_unscale/g", 1),
    ("%cs", "s/amp_backward/e", "s/amp_unscale/g", 2),
    ("%bc", "s/amp_backward/e", "s/amp_unscale/g", 2),
    # a tuple's element follows that element alone
    ("%g0", "s/amp_forward/a", "s/fused_adam_step_flat/f", 1),
    ("%g1", "s/amp_backward/e", "s/amp_unscale/g", 1),
    # the program's parameters and results name nothing
    ("%lone", "", "", 0),
])
def test_owner_rule_in_one_computation(name, producer, consumer, hops):
    row = _owners(_FLAT)[name]
    assert (row["scope"], row["producer"], row["consumer"], row["hops"]) == \
        ("", producer, consumer, hops)


def test_a_named_instruction_is_its_own_and_parameters_name_nothing():
    rows = _owners(_FLAT)
    assert rows["%c"]["scope"] == "s/amp_backward/c" and "consumer" not in rows["%c"]
    assert rows["%p0"]["scope"] == "state[0]"          # kept on the record, never an owner
    assert all(r["module"] == "jit_step" and r["entry"] == "e" for r in rows.values())
    assert rows["%x"]["bytes_in"] == rows["%x"]["bytes_out"] == 32
    assert rows["%cs"]["bytes_out"] == 68 and rows["%t"]["bytes_in"] == 64


_WHILE = "\n".join([
    "HloModule jit_step, is_scheduled=true",
    "",
    "%fused.1 (q: f32[8]) -> f32[8] {",
    "  %q = f32[8]{0} parameter(0)",
    "  ROOT %neg.1 = f32[8]{0} negate(%q)",
    "}",
    "",
    "%sum.1 (l: f32[], r: f32[]) -> f32[] {",
    "  %l = f32[]{:T(128)} parameter(0)",
    "  %r = f32[]{:T(128)} parameter(1)",
    "  ROOT %add.9 = f32[]{:T(128)} add(%l, %r)",
    "}",
    "",
    "%body.1 (arg: (s32[], f32[8], f32[8])) -> (s32[], f32[8], f32[8]) {",
    "  %arg = (s32[]{:T(128)}, f32[8]{0}, f32[8]{0}) parameter(0)",
    "  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0",
    "  %h = f32[8]{0} get-tuple-element(%arg), index=1",
    "  %w = f32[8]{0} get-tuple-element(%arg), index=2",
    "  %in_copy = f32[8]{0} copy(%w)",                 # leaves through the parameter
    _named("%mul", "s/amp_forward/while/body/mul", "%h, %in_copy"),
    "  %out_copy = f32[8]{0} copy(%mul)",              # leaves through the root
    "  %i_copy = s32[]{:T(128)} copy(%i)",             # in: a constant; out: the loop alone
    "  ROOT %next = (s32[]{:T(128)}, f32[8]{0}, f32[8]{0}) tuple(%i_copy, %out_copy, %w)",
    "}",
    "",
    "%cond.1 (arg.1: (s32[], f32[8], f32[8])) -> pred[] {",
    "  %arg.1 = (s32[]{:T(128)}, f32[8]{0}, f32[8]{0}) parameter(0)",
    "  %i.1 = s32[]{:T(128)} get-tuple-element(%arg.1), index=0",
    "  %n = s32[]{:T(128)} constant(3)",
    "  ROOT %lt = pred[]{:T(512)} compare(%i.1, %n), direction=LT",
    "}",
    "",
    "ENTRY %main.9 (p0: f32[8], p1: f32[8]) -> f32[8] {",
    "  %p0 = f32[8]{0} parameter(0)",
    "  %p1 = f32[8]{0} parameter(1)",
    "  %zero = s32[]{:T(128)} constant(0)",
    _named("%h0", "s/amp_forward/h0", "%p0", opcode="negate"),
    _named("%w0", "s/amp_forward/w0", "%p1", opcode="negate"),
    "  %init = (s32[]{:T(128)}, f32[8]{0}, f32[8]{0}) tuple(%zero, %h0, %w0)",
    '  %loop = (s32[]{:T(128)}, f32[8]{0}, f32[8]{0}) while(%init), condition=%cond.1, body=%body.1, '
    'metadata={op_name="s/amp_forward/while"}',
    "  %res = f32[8]{0} get-tuple-element(%loop), index=1",
    "  %fus = f32[8]{0} fusion(%res), kind=kLoop, calls=%fused.1",
    '  %total = f32[]{:T(128)} reduce(%fus, %zero), dimensions={0}, to_apply=%sum.1, '
    'metadata={op_name="s/amp_backward/reduce_sum"}',
    "  ROOT %done = f32[8]{0} copy(%fus)",
    "}",
])


@pytest.mark.parametrize("name, producer, consumer, hops", [
    # through the body's parameter: element 2 of the loop's operand, not %h0
    ("%in_copy", "s/amp_forward/w0", "s/amp_forward/while/body/mul", 1),
    # through the body's root: what reads element 1 of the loop's result (a
    # nameless fusion first, so two hops)
    ("%out_copy", "s/amp_forward/while/body/mul", "s/amp_backward/reduce_sum", 2),
    # left the body both ways and found no name: the calling instruction's
    ("%i_copy", "s/amp_forward/while", "s/amp_forward/while", 0),
    # a condition's result feeds the loop alone
    ("%lt", "s/amp_forward/while", "", 0),
    # in the entry: fed by the loop, feeding the reduction
    ("%fus", "s/amp_forward/while", "s/amp_backward/reduce_sum", 1),
    ("%done", "s/amp_forward/while", "", 2),
])
def test_owner_rule_across_a_while(name, producer, consumer, hops):
    row = _owners(_WHILE)[name]
    assert (row["producer"], row["consumer"], row["hops"]) == (producer, consumer, hops)


def test_fusion_bodies_and_reducers_are_not_on_the_ledger():
    rows = program.program_ops(program=_WHILE)
    assert {r["computation"] for r in rows} == {"%main.9", "%body.1", "%cond.1"}
    assert [r["name"] for r in rows if r["computation"] == "%main.9"][-1] == "%done"
    assert len({r["name"] for r in rows}) == len(rows)


_BRANCHES = "\n".join([
    "HloModule jit_step",
    "",
    "%on.1 (a: f32[8]) -> f32[8] {",
    "  %a = f32[8]{0} parameter(0)",
    "  ROOT %a_copy = f32[8]{0} copy(%a)",
    "}",
    "",
    "%off.1 (b: f32[8]) -> f32[8] {",
    "  %b = f32[8]{0} parameter(0)",
    "  ROOT %b_copy = f32[8]{0} copy(%b)",
    "}",
    "",
    "%callee.1 (c: f32[8], d: f32[8]) -> f32[8] {",
    "  %c = f32[8]{0} parameter(0)",
    "  %d = f32[8]{0} parameter(1)",
    "  ROOT %d_copy = f32[8]{0} copy(%d)",
    "}",
    "",
    "ENTRY %main.2 (p: pred[], x: f32[8]) -> f32[8] {",
    "  %p = pred[]{:T(512)} parameter(0)",
    "  %x = f32[8]{0} parameter(1)",
    _named("%left", "s/amp_forward/left", "%x", opcode="negate"),
    _named("%right", "s/amp_forward/right", "%x", opcode="negate"),
    "  %pick = f32[8]{0} conditional(%p, %left, %right), branch_computations={%on.1, %off.1}",
    "  %via = f32[8]{0} call(%left, %right), to_apply=%callee.1",
    _named("%end", "s/amp_backward/end", "%pick, %via"),
    "  ROOT %r = f32[8]{0} copy(%end)",
    "}",
])


@pytest.mark.parametrize("name, producer, consumer", [
    ("%a_copy", "s/amp_forward/left", "s/amp_backward/end"),     # branch 0 reads operand 1
    ("%b_copy", "s/amp_forward/right", "s/amp_backward/end"),    # branch 1 reads operand 2
    ("%d_copy", "s/amp_forward/right", "s/amp_backward/end"),    # a call's parameter n is operand n
    ("%pick", "s/amp_forward/right", "s/amp_backward/end"),
])
def test_owner_rule_across_branches_and_calls(name, producer, consumer):
    row = _owners(_BRANCHES)[name]
    assert (row["producer"], row["consumer"]) == (producer, consumer)


def test_the_same_text_gives_the_same_records():
    assert program.program_ops("e", program=_WHILE) == program.program_ops("e", program=_WHILE)


# ------------------------------------------- a jitted step under ``donate_step``
def _step(state, batch):
    def loss(w):
        with monitor.span("amp_forward"):
            comms.record("psum", "data", w, site="test.site")      # booked per TRACE
            dispatch.count_tiles("flash", "fwd", (8,), total=4, live=3, masked=1)
            dispatch.count_forced("flash", "pallas", w)
            h = jax.lax.fori_loop(0, 3, lambda i, h: jnp.tanh(h @ w), batch)
            return (h * h).sum()

    with monitor.span("amp_backward"):
        value, grad = jax.value_and_grad(loss)(state)
    with monitor.span("fused_adam_step_flat"):
        return state - 0.1 * grad, value


def _other_ledgers():
    kinds = collections.Counter(
        r["kind"] for r in monitor.host_records() if r["kind"].startswith(("compile.", "cache.")))
    return (kinds, monitor.comms_records(), monitor.tile_records(), monitor.dispatch_counters(),
            monitor.compile_summary())


@pytest.fixture
def stepped():
    monitor.reset_program_ledger()
    step = donate_step(_step)
    state, batch = jnp.ones((16, 16)), jnp.ones((16, 16))
    for _ in range(2):
        state, _ = step(state, batch)
    yield step, state, batch
    monitor.reset_program_ledger()


@pytest.mark.parametrize("retrace", [False, True], ids=["jit_cache_warm", "jit_cache_cleared"])
def test_ledger_is_no_work_until_asked_and_leaves_the_other_ledgers(stepped, monkeypatch, retrace):
    step, state, batch = stepped
    asked = []
    text_of = program._compiled_text
    monkeypatch.setattr(program, "_compiled_text", lambda note: asked.append(note) or text_of(note))
    state, _ = step(state, batch)
    assert asked == []                               # nothing lowered, compiled or parsed yet
    if retrace:
        jax.clear_caches()                           # the ledger's lowering traces the step anew
    before = _other_ledgers()
    rows = monitor.program_ops()
    assert len(asked) == 1 and rows
    assert _other_ledgers() == before
    assert monitor.program_ops() == rows and len(asked) == 1    # parsed once and kept
    assert {r["entry"] for r in rows} == {"_step"}
    assert monitor.program_ops("_step") == rows and monitor.program_ops("another") == []


def test_ledger_names_every_instruction_of_the_entry_computation_once(stepped):
    step, state, batch = stepped
    rows = monitor.program_ops("_step")
    text = step.jitted.lower(state, batch).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    names = re.findall(r"^\s+(?:ROOT )?(%[\w.\-]+) = ", entry[:entry.index("\n}")], re.M)
    computation = re.match(r"\nENTRY (%[\w.\-]+)", entry).group(1)
    assert names and sorted(r["name"] for r in rows if r["computation"] == computation) == sorted(names)
    assert rows[0]["module"] == "jit__step"
    named = [r for r in rows if r["scope"]]
    assert any("amp_forward" in r["scope"] for r in named)
    assert all({"consumer", "producer", "hops"} <= set(r) for r in rows if not r["scope"])


def test_only_a_wrapper_s_first_call_is_noted_and_no_array_is_kept(stepped):
    step, state, batch = stepped
    (note,) = [n for n in program._NOTES if n["entry"] == "_step"]
    leaves = jax.tree_util.tree_leaves((note["args"], note["kwargs"]))
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)
    step(state, batch)
    assert len([n for n in program._NOTES if n["entry"] == "_step"]) == 1


def test_a_step_called_under_an_outer_trace_is_not_noted():
    monitor.reset_program_ledger()
    inner = donate_step(lambda x: x + 1)
    assert float(jax.jit(lambda x: inner(x) * 2)(jnp.ones(()))) == 4.0
    assert program._NOTES == [] and monitor.program_ops() == []
    inner(jnp.ones(()))                              # its own program now: noted
    assert len(program._NOTES) == 1
    monitor.reset_program_ledger()


def test_an_entry_whose_step_function_is_gone_is_dropped():
    monitor.reset_program_ledger()
    step = donate_step(lambda x: x * 2)
    step(jnp.ones((4,)))
    del step
    import gc
    gc.collect()
    assert monitor.program_ops() == []
    monitor.reset_program_ledger()


def test_offline_step_prints_the_nameless_records_with_bytes_and_no_times(capsys):
    """``tools/offline_step.py --nameless``: one line a nameless instruction
    (no parameter, constant, tuple or bitcast), largest first, with its
    neighbours; the tool's parser is the package's."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "tools"))
    import offline_step

    assert offline_step.parse_instructions is program.parse_instructions
    offline_step.print_nameless("cell", _FLAT)
    head, *lines = capsys.readouterr().out.splitlines()
    assert head == "cell: 7 instructions without an op_name (1 with no named neighbour)"
    assert [ln.split()[0] for ln in lines[:2]] == ["%cs", "%cd"]     # 68 bytes out, then 68 in
    assert "s/amp_backward/e -> s/amp_unscale/g" in lines[0] and "MB" in lines[0]
    assert lines[-1].split()[0] == "%lone" and lines[-1].endswith("- -> -")
    assert not any(" ms" in ln for ln in lines)
