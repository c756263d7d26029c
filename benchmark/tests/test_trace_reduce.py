"""The trace reduction on a small trace written by hand, in the layout the chip's
profiler records (plane, line and stat names as in a ``TPU v5 lite`` trace), with
idle share, scope time, kernel time and exposed collective time computed by hand.

Times below are in milliseconds (1 ms = 1e9 ps). Chip 0, two steps traced:

    XLA Ops        while.1 [0,600] encloses fusion.1 [0,200], flash_attention.2
                   [250,450], psum.3 (an all-reduce) [500,600]; then fusion.4 [700,1000]
    Async XLA Ops  all-reduce-start.5 [100,550]
    python (host)  dispatch [590,640], fence [640,1000]

busy = [0,600] + [700,1000] = 900 of a 1000 window: idle 10 %. The optimizer's
scope holds fusion.1 and fusion.4: 500, or 250 a step. Collectives cover
[100,600]; compute leaves cover [0,200], [250,450], [700,1000]; exposed is
[200,250] + [450,600] = 200, or 100 a step. The one idle gap, [600,700], lies
40 under ``dispatch`` and 60 under ``fence``. Chip 1 runs fusion.1 [0,500], then an
all-reduce [600,750] that encloses fusion.4 [650,700], as a synchronous
all-reduce is recorded: 100 of it exposed, 50 a step; busy 650 of a 750 window.
"""

import struct

import pytest

from benchmark import trace_reduce, xplane
from benchmark.reductions import (exposed_collective, idle_share, kernel_time, roofline,
                                  stat_time)

MS = 10 ** 9


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _plane(name, lines, metadata, stat_names=("hlo_category", "tf_op")):
    """``metadata``: {id: (name, {stat: text})}; ``lines``: {name: [(id, start, dur)]}."""
    stat_id = {s: i + 1 for i, s in enumerate(stat_names)}
    out = _field(2, name)
    for lname, events in lines.items():
        body = _field(2, lname) + _field(3, 0)
        for meta, start, dur in events:
            body += _field(4, _field(1, meta) + _field(2, start * MS) + _field(3, dur * MS))
        out += _field(3, body)
    for mid, (mname, stats) in metadata.items():
        m = _field(1, mid) + _field(2, mname)
        for s, text in stats.items():
            m += _field(5, _field(1, stat_id[s]) + _field(5, text))
        out += _field(4, _entry(mid, m))
    for s, i in stat_id.items():
        out += _field(5, _entry(i, _field(1, i) + _field(2, s)))
    return _field(1, out)


ADAM = "jit(step)/jit(main)/fused_adam_step_flat/mul"
OPS = {
    1: ("%while.1 = (s32[], bf16[4,8]) while(%tuple), body=%b", {}),
    2: ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", {"hlo_category": "convolution fusion", "tf_op": ADAM}),
    3: ("%flash_attention.2 = bf16[8] custom-call(bf16[8] %q), custom_call_target=\"tpu_custom_call\"",
        {"hlo_category": "custom-call"}),
    4: ("%psum.3 = bf16[8] all-reduce(bf16[8] %g)", {"hlo_category": "all-reduce"}),
    5: ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %m)", {"hlo_category": "loop fusion", "tf_op": ADAM}),
    6: ("%all-reduce-start.5 = bf16[8] all-reduce-start(bf16[8] %g)", {}),
    7: ("dispatch", {}), 8: ("fence", {}), 9: ("$profiler.py:1 start_trace", {}),
}


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    chip0 = _plane("/device:TPU:0", {
        "XLA Ops": [(1, 0, 600), (2, 0, 200), (3, 250, 200), (4, 500, 100), (5, 700, 300)],
        "Async XLA Ops": [(6, 100, 450)], "Steps": [(1, 0, 1000)]}, OPS)
    chip1 = _plane("/device:TPU:1", {"XLA Ops": [(2, 0, 500), (4, 600, 150), (5, 650, 50)]}, OPS)
    host = _plane("/host:CPU", {"python": [(9, 0, 5), (7, 590, 50), (8, 640, 360)]}, OPS)
    path = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "t" / "x.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(chip0 + chip1 + host + _plane("Task Environment", {}, {}))
    return trace_reduce.load(str(path.parent.parent.parent.parent), chips=2)


def test_the_reader_gives_names_times_and_metadata_stats(trace):
    ops = {op.name: op for op in trace.chips[0]["ops"]}
    assert set(ops) == {"%while.1", "%fusion.1", "%flash_attention.2", "%psum.3", "%fusion.4"}
    assert ops["%fusion.1"].stats == {"hlo_category": "convolution fusion", "tf_op": ADAM}
    assert (ops["%fusion.4"].start, ops["%fusion.4"].end) == (700 * MS, 1000 * MS)
    assert not ops["%while.1"].leaf and ops["%while.1"].self_ps == 100 * MS
    assert all(ops[n].leaf for n in ops if n != "%while.1")


def test_idle_share_and_the_result_lines_device_fields(trace):
    assert idle_share.reduce({}, {"trace": trace}) == pytest.approx(100 / 7.5)   # chip 1; chip 0 is 10 %
    assert trace.busy_s() == pytest.approx((0.9 + 0.65) / 2)
    assert trace.window_s() == pytest.approx((1.0 + 0.75) / 2)


def test_scope_time_kernel_time_and_category_time_per_step(trace):
    ctx = {"trace": trace, "steps": 2}
    scope = {"stat": "tf_op", "pattern": "fused_adam_step_flat"}
    assert stat_time.reduce(scope, ctx) == pytest.approx(275.0)             # chip 1: (500 + 50) / 2
    conv = {"stat": "hlo_category", "pattern": "convolution"}
    assert stat_time.reduce(conv, ctx) == pytest.approx(250.0)              # chip 1's 500 / 2
    assert kernel_time.reduce({"pattern": "^%flash_attention"}, ctx) == pytest.approx(100.0)
    assert kernel_time.reduce({"pattern": "^%no_such_kernel"}, ctx) is None
    assert stat_time.reduce({"stat": "tf_op", "pattern": "fused_sgd"}, ctx) is None


def test_exposed_collective_time_per_step(trace):
    assert exposed_collective.reduce({}, {"trace": trace, "steps": 2}) == pytest.approx(100.0)
    one = trace_reduce.Trace.__new__(trace_reduce.Trace)
    one.chips, one.host = trace.chips[1:], []
    assert exposed_collective.reduce({}, {"trace": one, "steps": 2}) == pytest.approx(50.0)


def test_roofline_share_of_the_kernel(trace):
    class Family:
        @staticmethod
        def attention_flops(cfg):
            return 1e9                                    # per item
    ctx = {"trace": trace, "steps": 2, "family": Family, "cfg": {}, "items_per_step": 8,
           "cell": {"chips": 2}, "peak": {"bf16_flops_per_s": 1e12}}
    spec = {"pattern": "^%flash_attention", "count": "attention_flops"}
    # one chip's share: 4 items * 2 steps * 1e9 = 8e9 operations, 8 ms at the peak, 200 ms taken
    assert roofline.reduce(spec, ctx) == pytest.approx(100.0 * 0.008 / 0.2)
    assert roofline.reduce(dict(spec, count="absent"), ctx) is None


def test_breakdown_names_the_heaviest_operations_and_the_hosts_part_in_the_gaps(trace):
    b = trace.breakdown()
    assert b["device_ops"][0] == [
        "%fusion.4 [loop fusion] jit(main)/fused_adam_step_flat/mul", pytest.approx(0.3)]
    assert dict(map(tuple, b["device_ops"]))["%while.1 [?] "] == pytest.approx(0.1)
    assert b["idle_gaps"] == [["host:fence", pytest.approx(0.1)]]


def test_interval_arithmetic():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert trace_reduce.subtract([(0, 4), (6, 9)], []) == [(0, 4), (6, 9)]


def test_too_few_device_planes_is_an_error(tmp_path):
    (tmp_path / "x.xplane.pb").write_bytes(_plane("/host:CPU", {"python": [(7, 0, 1)]}, OPS))
    with pytest.raises(RuntimeError, match="device plane"):
        trace_reduce.load(str(tmp_path), chips=1)


def test_the_reader_agrees_with_jaxs_own_on_a_recorded_trace(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jax.jit(lambda a: jnp.sin(a) @ a)(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    theirs = jax.profiler.ProfileData.from_file(path)
    for plane, mine in zip(theirs.planes, xplane.read(path)):
        assert plane.name == mine.name
        for line, my_line in zip(plane.lines, mine.lines):
            events = list(line.events)
            assert line.name == my_line.name and len(events) == len(my_line.events)
            for ev, my_ev in list(zip(events, my_line.events))[:50]:
                assert ev.name == my_ev.name
                assert ev.start_ns == pytest.approx(my_ev.start_ps / 1000)
                assert ev.duration_ns == pytest.approx(my_ev.duration_ps / 1000)
