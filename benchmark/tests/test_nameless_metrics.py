"""The six per-layer metrics that read the program ledger (PR 52): device self
time of the trace's operations whose framework name (``tf_op``) is empty, by
the owner the ledger (``monitor.program_ops()``) gives each.

First a trace and a ledger built by hand: what each metric file reads and what
it leaves. Then the chip's own names — one recording a family kind, made by
``tools/dump_tf_ops.py`` (its ``ops`` and ``nameless`` tables, PR 52's final
program: a stacked family, an unrolled one, a GPT cell): the four owner metrics
partition the empty-name time exactly, the two mechanism metrics read only what
their patterns name, a named operation is read by none, and an operation the
ledger lacks reads under ``nameless_orphan_ms``."""

import glob
import json
import os
import re
import sys

import pytest

from benchmark import run
from benchmark.reductions import nameless_time
from benchmark.trace_reduce import Op

OWNERS = ("nameless_update_ms", "nameless_backward_ms", "nameless_forward_ms",
          "nameless_orphan_ms")
MECHANISMS = ("nameless_pack_ms", "nameless_stack_ms")
FIXTURES = sorted(glob.glob(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                         "fixtures", "nameless", "*.json")))
STEP = 16
MS = 10 ** 9                      # ps in a ms


class FakeTrace:
    """What a reduction reads of ``trace_reduce.Trace``."""

    def __init__(self, *chips):
        self.chips = [{"ops": list(ops)} for ops in chips]

    def per_chip(self, fn):
        return [fn(c) for c in range(len(self.chips))]


def op(name, ms, tf_op="", **stats):
    return Op(name, 0, 1, int(ms * MS * STEP), True, dict(stats, tf_op=tf_op))


def record(name, producer="", consumer="", scope="", module="jit_step"):
    row = {"entry": "step", "module": module, "computation": "%main", "name": name,
           "opcode": "copy", "scope": scope, "bytes_in": 8, "bytes_out": 8}
    if not scope:
        row.update(producer=producer, consumer=consumer, hops=1)
    return row


def read(metric, trace, records, monkeypatch):
    from beforeholiday_tpu import monitor

    monkeypatch.setattr(monitor, "program_ops", lambda entry=None: records, raising=False)
    return nameless_time.reduce(run.load("layer_metrics", metric), {"trace": trace, "steps": STEP})


F, B = "jit(step)/amp_forward/jvp(", "jit(step)/amp_backward/transpose(jvp("
PACK = "jit(step)/amp_backward/transpose(amp_forward)/jvp()/concatenate"
HAND = [
    # (name, ms, producer, consumer)
    ("%copy.1", 1.0, F + "m_layers)/attn_mixer/dot_general", B + "m_layers))/attn_mixer/dot_general"),
    ("%slice-start.2", 0.5, F + "m_layers)/moe/mul", B + "m_layers))/moe/mul"),     # a prefetch
    ("%copy.3", 2.0, F + "m_embed)/gather", F + "m_layers)/squeeze"),              # unstacking
    ("%dus.4", 3.0, B + "m_layers))/broadcast_in_dim", PACK),                       # the pack
    ("%dus.5", 4.0, B + "m_layers))/moe/moe_experts/jit(_tgmm)/pallas_call",
     B + "m_layers))/concatenate"),                                                # stacking
    ("%fill.6", 0.25, "", "jit(step)/fused_adam_step_flat/mul"),
    ("%copy.7", 0.125, B + "m_head))/dot_general", "jit(step)/amp_unscale/mul"),
    ("%copy.8", 8.0, PACK, "jit(step)/amp_backward/ddp_reduce_gradients/psum"),     # update outranks
    ("%copy.9", 16.0, F + "m_loss)/log", ""),                 # no consumer: its producer owns it
    ("%copy.10", 32.0, "", ""),                               # no named neighbour
    ("%copy.11", 64.0, "jit(step)/convert_element_type", "jit(step)/mul"),   # named, under no level
]
EXPECTED = {
    "nameless_update_ms": 0.25 + 0.125 + 8.0,
    "nameless_backward_ms": 1.0 + 0.5 + 3.0 + 4.0,
    "nameless_forward_ms": 2.0 + 16.0 + 0.0625,
    "nameless_orphan_ms": 32.0 + 64.0 + 128.0,          # and the op the ledger lacks
    "nameless_pack_ms": 3.0 + 8.0,
    "nameless_stack_ms": 2.0 + 3.0 + 4.0 + 0.0625,
}


def hand_built():
    ops = [op(name, ms) for name, ms, _, _ in HAND]
    ops += [op("%while.15", 0.0625),               # named by the text, not by the trace: its own
            op("%ghost.12", 128.0),                                        # not in the ledger
            op("%fusion.13", 256.0, tf_op=B + "m_layers))/concatenate:"),  # named: nobody's
            op("%fusion.14", 512.0, tf_op=PACK + ":")]
    records = [record(name, producer, consumer) for name, _, producer, consumer in HAND]
    records += [record("%while.15", scope=F + "m_layers)/while"),
                record("%fusion.13", scope=B + "m_layers))/concatenate"),
                record("%fusion.14", scope=PACK)]
    return FakeTrace(ops), records


@pytest.mark.parametrize("metric", OWNERS + MECHANISMS)
def test_metric_reads_the_hand_built_trace(metric, monkeypatch):
    trace, records = hand_built()
    assert read(metric, trace, records, monkeypatch) == pytest.approx(EXPECTED[metric], rel=1e-12)


def test_the_four_owners_partition_the_empty_name_time(monkeypatch):
    trace, records = hand_built()
    empty = sum(o.self_ps for o in trace.chips[0]["ops"] if not o.stats["tf_op"]) / MS / STEP
    assert sum(read(m, trace, records, monkeypatch) for m in OWNERS) == pytest.approx(empty)
    for m in MECHANISMS:
        assert read(m, trace, records, monkeypatch) <= empty


def test_worst_chip_and_of_two_entries_the_one_noted_last(monkeypatch):
    records = [record("%copy.1", consumer=F + "x)/mul", module="jit_first"),
               record("%copy.1", consumer=B + "x))/mul", module="jit_step"),
               record("%copy.2", consumer=F + "x)/mul", module="jit_step")]
    trace = FakeTrace([op("%copy.1", 1.0), op("%copy.2", 8.0)],
                      [op("%copy.1", 2.0, program_id=7), op("%copy.2", 4.0)])
    assert read("nameless_backward_ms", trace, records, monkeypatch) == pytest.approx(2.0)
    assert read("nameless_forward_ms", trace, records, monkeypatch) == pytest.approx(8.0)
    assert read("nameless_orphan_ms", trace, records, monkeypatch) == 0.0


def test_a_program_without_the_ledger_gives_nothing(monkeypatch):
    from beforeholiday_tpu import monitor

    trace, _ = hand_built()
    spec = run.load("layer_metrics", "nameless_orphan_ms")
    monkeypatch.delattr(monitor, "program_ops")
    assert nameless_time.reduce(spec, {"trace": trace, "steps": STEP}) is None
    monkeypatch.setattr(monitor, "program_ops", lambda entry=None: [], raising=False)
    assert nameless_time.reduce(spec, {"trace": trace, "steps": STEP}) is None   # no step noted


def test_every_name_and_pattern_lives_in_the_metric_files():
    source = open(nameless_time.__file__).read()
    for word in ("amp_forward", "amp_backward", "amp_unscale", "fused_adam", "ddp_", "_layers"):
        assert word not in source
    manifest = json.load(open(os.path.join(os.path.dirname(run._HERE), "BENCHMARK.json")))
    cells = [w["name"] for w in manifest["workloads"]]
    for name in OWNERS + MECHANISMS:
        (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert entry["workloads"] == cells and entry["moves"] == "tokens_per_s"
        assert entry["layer"] == "device (XLA:TPU + Mosaic)" and entry["better"] == "lower"


def test_the_tool_s_tables_hold_what_the_metrics_read():
    """``tools/dump_tf_ops.py:tables``: the ``nameless`` rows sum to the empty
    ``tf_op``'s time, carry the ledger's fields, and an op the ledger lacks has none."""
    sys.path.insert(0, os.path.join(os.path.dirname(run._HERE), "tools"))
    import dump_tf_ops

    trace, records = hand_built()
    ops = trace.chips[0]["ops"] + [op("%copy.1", 1.0, hlo_category="data formatting")]
    out = dump_tf_ops.tables(ops, records)
    rows = {r["name"]: r for r in out["nameless"]}
    assert sum(r["self_ps"] for r in rows.values()) == dict(out["ops"])[""]
    assert rows["%copy.1"]["calls"] == 2 and rows["%copy.1"]["self_ps"] == 2 * MS * STEP
    assert rows["%dus.4"]["consumer"] == PACK and rows["%dus.4"]["opcode"] == "copy"
    assert rows["%ghost.12"]["opcode"] is None and rows["%ghost.12"]["producer"] is None
    assert rows["%while.15"]["scope"] == F + "m_layers)/while" and rows["%dus.4"]["scope"] == ""
    assert "%fusion.13" not in rows and out["nameless"][0]["name"] == "%ghost.12"
    assert out["stat_names"] == ["hlo_category", "tf_op"]
    assert dict(out["hlo_names"])["%copy"] == sum(
        o.self_ps for o in ops if o.name.startswith("%copy."))


# ------------------------------------------------------- the chip's own names
def recorded(path):
    """A trace and a ledger from a ``tools/dump_tf_ops.py`` recording: every
    empty-name operation by its HLO name with its ledger record (none where the
    recording has none), every named scope as one operation."""
    data = json.load(open(path))
    ops, records = [], []
    for row in data["nameless"]:
        ops.append(Op(row["name"], 0, 1, row["self_ps"], True, {"tf_op": ""}))
        if row["opcode"] is not None:
            records.append(dict(row, entry="step", module="jit_step"))
    for i, (scope, ps) in enumerate(data["ops"]):
        if scope:
            ops.append(Op(f"%named.{i}", 0, 1, ps, True, {"tf_op": scope}))
            records.append(record(f"%named.{i}", scope=scope.rstrip(":")))
    return data, FakeTrace(ops), records


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_recorded_names(path, monkeypatch):
    data, trace, records = recorded(path)
    steps = data["steps"]

    def ms(metric, t=trace):
        from beforeholiday_tpu import monitor

        monkeypatch.setattr(monitor, "program_ops", lambda entry=None: records, raising=False)
        return nameless_time.reduce(run.load("layer_metrics", metric), {"trace": t, "steps": steps})

    empty_ps = dict(data["ops"])[""]
    assert sum(r["self_ps"] for r in data["nameless"]) == empty_ps
    owners = {m: ms(m) for m in OWNERS}
    assert sum(owners.values()) == pytest.approx(empty_ps * 1e-9 / steps, rel=1e-9)
    assert owners["nameless_backward_ms"] == max(owners.values())
    assert owners["nameless_orphan_ms"] < 0.1 * sum(owners.values())
    for m in MECHANISMS:
        pattern = re.compile(run.load("layer_metrics", m)["either"])
        want = sum(r["self_ps"] for r in data["nameless"] if r["opcode"] is not None
                   and any(pattern.search(x) for x in nameless_time.neighbours(r)))
        assert ms(m) == pytest.approx(want * 1e-9 / steps, rel=1e-9) and ms(m) <= sum(owners.values())
    # the pack is there in every program; the stacks only where a family stacks
    assert ms("nameless_pack_ms") > 0.5
    if "gpt2" in data["cell"]:
        assert ms("nameless_stack_ms") == 0.0
    # a named operation is read by none of the six; one the ledger lacks is an orphan's
    more = FakeTrace(trace.chips[0]["ops"] + [
        Op("%named.big", 0, 1, 7 * MS * steps, True, {"tf_op": PACK + ":"}),
        Op("%ghost.1", 0, 1, 3 * MS * steps, True, {"tf_op": ""})])
    for m in OWNERS + MECHANISMS:
        extra = 3.0 if m == "nameless_orphan_ms" else 0.0
        assert ms(m, more) == pytest.approx(ms(m) + extra, rel=1e-9, abs=1e-9)


def test_a_recording_a_family_kind_is_committed():
    cells = {json.load(open(p))["cell"] for p in FIXTURES}
    assert any("gpt2" in c for c in cells) and len(cells) >= 3
    for p in FIXTURES:      # a ``while`` is named by the text alone: its own, by its scope
        named = [r for r in json.load(open(p))["nameless"] if r["scope"]]
        assert named and all(r["opcode"] == "while" and r["producer"] is None for r in named)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
