"""The per-layer metrics that read the program's layer scopes (PR 24).

``fixtures/tf_ops/<cell>.json`` holds, from a traced run of each cell on the
chip, every distinct framework name (``tf_op``, the ``monitor.spans`` scope
path XLA kept on each device op) of chip 0 with its self time. On them: the
first-level metrics are a partition (every name is matched by exactly one, so
their sum is the chip's busy time), each metric finds something in the cells
``BENCHMARK.json`` names for it and nothing elsewhere, and on a program that
lacks the scopes (the parent commit) the readers return nothing and do not
raise. Then the collective ledger's reduction on the data-parallel rehearsal,
and a program span on the profiler's own host line."""

import glob
import json
import os
import re

import pytest

from benchmark import run, trace_reduce, xplane
from benchmark.reductions import comms_ledger, stat_time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
FIRST_LEVEL = ("forward_ms", "backward_ms", "unscale_ms", "grad_reduce_ms",
               "optimizer_ms.gpt", "unattributed_ms")
NEW = ("forward_ms", "backward_ms", "unscale_ms", "grad_reduce_ms", "grad_reduce_gb",
       "layer_norm_ms", "head_loss_ms.gpt", "unattributed_ms")
CELLS = ("gpt2-medium.train", "gpt2-medium.train-dp4")


def _fixture(cell):
    with open(os.path.join(HERE, "fixtures", "tf_ops", cell + ".json")) as f:
        return json.load(f)


def _trace(ops):
    """A one-chip :class:`trace_reduce.Trace` of leaf ops ``[(tf_op, self_ps)]``, back to back."""
    t, at, out = trace_reduce.Trace.__new__(trace_reduce.Trace), 0, []
    for i, (tf_op, ps) in enumerate(ops):
        out.append(trace_reduce.Op(f"%op.{i}", at, at + ps, ps, True, {"tf_op": tf_op}))
        at += ps
    t.chips, t.host = [{"ops": out, "async": []}], []
    return t


def _manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


def test_every_listed_cell_has_its_recorded_names():
    found = {os.path.basename(p)[:-5] for p in glob.glob(os.path.join(HERE, "fixtures", "tf_ops", "*.json"))}
    assert found == set(CELLS)
    for cell in CELLS:
        fx = _fixture(cell)
        assert fx["cell"] == cell and fx["device_kind"] == "TPU v5 lite" and fx["steps"] > 0
        assert len(fx["ops"]) > 30


@pytest.mark.parametrize("cell", CELLS)
def test_first_level_metrics_partition_the_step(cell):
    fx = _fixture(cell)
    patterns = {m: re.compile(run.load("layer_metrics", m)["pattern"]) for m in FIRST_LEVEL}
    total = {m: 0 for m in FIRST_LEVEL}
    for tf_op, ps in fx["ops"]:
        hits = [m for m, p in patterns.items() if p.search(tf_op)]
        assert len(hits) == 1, (tf_op, hits)
        total[hits[0]] += ps
    assert sum(total.values()) == sum(ps for _, ps in fx["ops"])
    # self times of properly nested events add up to the busy time
    assert sum(total.values()) == pytest.approx(fx["busy_ps"], rel=1e-6)
    # and the reduction the harness runs gives the same numbers
    ctx = {"trace": _trace(fx["ops"]), "steps": fx["steps"]}
    for m in FIRST_LEVEL:
        got = stat_time.reduce(run.load("layer_metrics", m), ctx)
        assert (got or 0.0) == pytest.approx(total[m] * 1e-9 / fx["steps"])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("metric", [m for m in NEW if m != "grad_reduce_gb"])
def test_metric_reads_the_cells_it_names_and_nothing_elsewhere(metric, cell):
    fx = _fixture(cell)
    spec = run.load("layer_metrics", metric)
    value = stat_time.reduce(spec, {"trace": _trace(fx["ops"]), "steps": fx["steps"]})
    if cell in _manifest()[metric]["workloads"]:
        assert value is not None and value > 0
    else:
        assert value is None


@pytest.mark.parametrize("cell", CELLS)
def test_second_level_metrics_split_forward_and_backward(cell):
    """``layer_norm_ms`` and ``head_loss_ms.gpt`` never count an op twice, and
    count only ops of the forward and backward passes."""
    fx = _fixture(cell)
    second = [re.compile(run.load("layer_metrics", m)["pattern"])
              for m in ("layer_norm_ms", "head_loss_ms.gpt")]
    passes = [re.compile(run.load("layer_metrics", m)["pattern"])
              for m in ("forward_ms", "backward_ms")]
    for tf_op, _ in fx["ops"]:
        hits = sum(bool(p.search(tf_op)) for p in second)
        assert hits <= 1, tf_op
        if hits:
            assert any(p.search(tf_op) for p in passes), tf_op


def test_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent commit's names: only the scopes it had. The new readers
    return ``None`` there, but for ``unattributed_ms``, which by its
    definition reads everything the first-level scopes do not cover."""
    parent = [("jit(one_chip_step)/jit(main)/jvp(jit(loss))/while/body/closed_call/dot_general", 500),
              ("jit(one_chip_step)/jit(main)/reduce_or", 200), ("", 10),
              ("jit(one_chip_step)/jit(main)/master_weights_step/fused_adam_step_flat/mul", 300)]
    ctx = {"trace": _trace(parent), "steps": 1}
    for metric in NEW:
        spec = run.load("layer_metrics", metric)
        if spec["reduction"] != "stat_time":
            continue
        value = stat_time.reduce(spec, ctx)
        if metric == "unattributed_ms":
            assert value == pytest.approx(710e-9)
        else:
            assert value is None, metric


def test_collective_ledger_counts_the_gradient_arenas_of_the_dp_rehearsal():
    import jax

    from beforeholiday_tpu import monitor

    spec = run.load("layer_metrics", "grad_reduce_gb")
    seen = {}
    for name in ("tiny-gpt.train", "tiny-gpt.train-dp4"):
        monitor.reset_comms_ledger()
        cell = run.load("workloads", name)
        cfg = run.load("configs", cell["config"])
        cell_run = run.Cell(cell, cfg, jax.devices()[:cell["chips"]])
        cell_run.start(11)
        cell_run.build()
        cell_run.run_step(0)
        cell_run.run_step(1)            # a second step does not trace again
        seen[name] = comms_ledger.reduce(spec, {})
        params = cell_run.state[0]      # the model's arenas: the gradients have their shapes
        arena_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
        count = cell_run.family.param_count(cfg)
    monitor.reset_comms_ledger()
    assert seen["tiny-gpt.train"] is None                      # one chip: no collective
    # per rank and step: every gradient once, in the arenas' types (bf16 but for
    # the float32 norm parameters), padding included
    assert seen["tiny-gpt.train-dp4"] == pytest.approx(arena_bytes / 1e9)
    assert 2 * count <= arena_bytes < 4 * count


def test_program_spans_land_on_the_profilers_host_line(tmp_path):
    """With a profiler session on, ``monitor.spans.span`` is on the host plane's
    ``python`` line, the line and clock of the harness's own annotations,
    nested inside the caller's ``dispatch``."""
    import jax
    import jax.numpy as jnp

    from beforeholiday_tpu.remat import donate_step

    step = donate_step(lambda s, x: (s + x, jnp.sum(x)), donate_argnums=(0,))
    state, _ = step(jnp.zeros((4,)), jnp.ones((4,)))        # compiled before the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("dispatch"):
            state, out = step(state, jnp.ones((4,)))
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    host, = [p for p in xplane.read(path) if p.name == trace_reduce._HOST_PLANE]
    line, = [ln for ln in host.lines if ln.name == trace_reduce._HOST_LINE]
    spans = {ev.name: (ev.start_ps, ev.start_ps + ev.duration_ps) for ev in line.events
             if ev.name in ("dispatch", "donate_step.prepare", "donate_step.call")}
    assert set(spans) == {"dispatch", "donate_step.prepare", "donate_step.call"}
    outer = spans["dispatch"]
    assert outer[0] <= spans["donate_step.prepare"][0]
    assert spans["donate_step.prepare"][1] <= spans["donate_step.call"][0]
    assert spans["donate_step.call"][1] <= outer[1]
