"""Family ``qwen3_next`` (PR 26): its rehearsal cell, its control, its counts,
and its per-layer metrics on the names the chip printed.

``fixtures/tf_ops_qwen3_next/<cell>.json`` is a traced run of the cell on the
chip (``tools/dump_tf_ops.py``): every distinct framework name of chip 0 with
its self time, and every HLO name stem. It lies beside ``fixtures/tf_ops/``
and not in it, because ``test_layer_metrics.py`` pins that directory to the
two cells it was written for."""

import json
import os
import re

import jax
import pytest

from benchmark import check, run, trace_reduce
from benchmark.families import gpt, qwen3_next as family
from benchmark.reductions import family_counter, kernel_time, roofline, stat_time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "qwen3-next-80b-a3b.train-s8k"
TINY = "tiny-qwen3-next.train"
FIRST_LEVEL = ("forward_ms", "backward_ms", "unscale_ms", "grad_reduce_ms",
               "optimizer_ms.gpt", "unattributed_ms")
NEW_TRACE = ("gated_delta_ms", "moe_ms", "linear_mixer_ms", "attn_mixer_ms",
             "head_loss_ms.qwen3_next")
NEW = NEW_TRACE + ("gated_delta_roofline", "expert_rows_per_step", "expert_load_max_over_mean")
APPENDED = ("forward_ms", "backward_ms", "unscale_ms", "unattributed_ms", "layer_norm_ms",
            "flash_attn_ms", "flash_attn_roofline", "optimizer_ms.gpt")


def _fixture(cell=CELL, directory="tf_ops_qwen3_next"):
    with open(os.path.join(HERE, "fixtures", directory, cell + ".json")) as f:
        return json.load(f)


def _trace(ops, names=()):
    """A one-chip trace of leaf ops ``[(tf_op, self_ps)]`` then ``[(hlo name, self_ps)]``."""
    t, at, out = trace_reduce.Trace.__new__(trace_reduce.Trace), 0, []
    for i, (tf_op, ps) in enumerate(ops):
        out.append(trace_reduce.Op(f"%op.{i}", at, at + ps, ps, True, {"tf_op": tf_op}))
        at += ps
    for i, (name, ps) in enumerate(names):
        out.append(trace_reduce.Op(f"{name}.{i}", at, at + ps, ps, True, {}))
        at += ps
    t.chips, t.host = [{"ops": out, "async": []}], []
    return t


def _manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _per_layer():
    return {m["name"]: m for m in _manifest()["per_layer"]}


# -- the manifest -----------------------------------------------------------------

def test_the_manifest_gained_the_configuration_the_cell_and_the_metrics():
    m = _manifest()
    assert m["configs"][-1]["name"] == "qwen3-next-80b-a3b"
    assert m["configs"][-1]["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert m["workloads"][-1] == {
        "name": CELL, "config": "qwen3-next-80b-a3b", "traffic": "train-s8k", "chips": 1,
        "why": run.load("workloads", CELL)["why"]}
    assert [x["name"] for x in m["per_layer"][-len(NEW):]] == [
        "gated_delta_ms", "gated_delta_roofline", "moe_ms", "linear_mixer_ms",
        "attn_mixer_ms", "head_loss_ms.qwen3_next", "expert_rows_per_step",
        "expert_load_max_over_mean"]
    for name in NEW:
        assert _per_layer()[name]["workloads"] == [CELL], name
    for name in APPENDED:
        assert _per_layer()[name]["workloads"][-1] == CELL, name
        assert _per_layer()[name]["workloads"][:2] == ["gpt2-medium.train", "gpt2-medium.train-dp4"]
    for name in ("collective_exposed_ms", "grad_reduce_ms", "grad_reduce_gb", "head_loss_ms.gpt"):
        assert CELL not in _per_layer()[name]["workloads"], name


def test_the_configuration_holds_the_published_widths():
    cfg = run.load("configs", "qwen3-next-80b-a3b")
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
        "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 32, 18992)
    assert cfg["published"] == {**{k: published[k] for k in cfg["reduced"]},
                                "parameters": cfg["published"]["parameters"]}
    assert cfg["num_experts_published"] == 512 and cfg["vocab_size"] * 8 == 151936
    for key in ("weights", "optimizer", "loss", "seq_len", "moe_rows_bound"):
        assert key in cfg["assumed"], key
    assert "16 chips" in cfg["deployment"] and len(cfg["departures"]) == 3


# -- counts -----------------------------------------------------------------------

def test_parameters_and_required_operations():
    cfg = run.load("configs", "qwen3-next-80b-a3b")
    D, V, F, E = 2048, 18992, 512, 32
    linear = D * (2 * 2048 + 2 * 4096) + D * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * D
    attn = D * 16 * 2 * 256 + 2 * D * 2 * 256 + 2 * 256 + 16 * 256 * D
    every = 2 * D + D * 512 + D + 3 * D * F + E * 3 * D * F
    assert family.param_count(cfg) == 3 * linear + attn + 4 * every + 2 * V * D + D == 625_667_136
    # 16 bytes a parameter (bf16 weight and gradient, fp32 master and two moments): 10.0 GB
    assert round(16 * family.param_count(cfg) / 1e9, 2) == 10.01
    token = 3 * (D * 12288 + D * 64 + 4096 * D) + (D * 8192 + 2 * D * 512 + 4096 * D) \
        + 4 * (D * 512 + D + 3 * D * F + (10 * 32 / 512) * 3 * D * F) + V * D
    recurrence = 3 * 32 * (3 + 6) * 2 * 128 * 128
    attention = 6 * 8192 * 16 * 256
    assert family.gated_delta_flops_per_item(cfg) == recurrence == 28_311_552
    assert family.attention_flops_per_item(cfg) == attention == 201_326_592
    assert family.model_flops_per_item(cfg) == 6 * token + attention + recurrence == 1_380_827_136


# -- the rehearsal cell and its control -------------------------------------------

def _cell(seed):
    cell = run.load("workloads", TINY)
    c = run.Cell(cell, run.load("configs", cell["config"]), jax.devices()[:1])
    c.start(seed)
    return c


@pytest.mark.parametrize("seed", (1, 2, 3))
def test_program_passes_and_fp8_control_fails(seed):
    c = _cell(seed)
    reference = c.reference()
    control = check.compare(c.reference("fp8"), reference, c.cell["limits"])
    assert not all(r["ok"] for r in control), control
    c.build()
    sound = check.compare(c.program_numbers(), reference, c.cell["limits"])
    assert all(r["ok"] for r in sound), sound


def test_a_sound_rehearsal_is_correct_and_reports_no_time(capsys):
    assert run.main(["--workload", TINY, "--seed", "2147483659", "--seconds", "0.3",
                     "--trace", "0"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and set(line["metrics"]) == {"setup_s"}


def test_a_step_that_drops_a_routed_row_is_a_failed_step(monkeypatch, capsys):
    """``moe_rows_bound`` too tight for the traffic: the dropped rows are counted
    and the harness's ``failed_steps`` sees them through ``found_inf``."""
    real = run.load
    monkeypatch.setattr(run, "load", lambda kind, name: dict(real(kind, name), moe_rows_bound=8)
                        if kind == "configs" else real(kind, name))
    assert run.main(["--workload", TINY, "--seed", "7", "--seconds", "0.2", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert re.search(r"window\.failed_steps = [1-9]\d*  limit 0  FAILED", out)
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert family.counters()["dropped_rows"] > 0


def test_a_real_cell_refuses_any_backend_but_tpu(capsys):
    assert run.main(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]) == 1
    captured = capsys.readouterr()
    assert captured.out == "" and "tpu" in captured.err


def test_the_counters_are_read_from_the_newest_state():
    c = _cell(11)
    c.build()
    for i in range(3):
        c.run_step(i)
    rows = run.load("layer_metrics", "expert_rows_per_step")
    load = run.load("layer_metrics", "expert_load_max_over_mean")
    ctx = {"family": family}
    seen = family.counters()
    assert seen["steps"] == 3
    assert family_counter.reduce(rows, ctx) == pytest.approx(seen["expert_rows"] / 3)
    assert family_counter.reduce(load, ctx) == seen["expert_load_max_over_mean"] >= 1.0
    # a family without counters (the parent's, the GPT cells') gives nothing
    assert family_counter.reduce(rows, {"family": gpt}) is None
    assert family_counter.reduce(load, {"family": gpt}) is None


# -- the per-layer metrics on the chip's names ------------------------------------

def test_the_recorded_names():
    fx = _fixture()
    assert fx["cell"] == CELL and fx["device_kind"] == "TPU v5 lite" and fx["steps"] == 16
    assert len(fx["ops"]) > 100 and len(fx["hlo_names"]) > 20
    assert sum(ps for _, ps in fx["ops"]) == sum(ps for _, ps in fx["hlo_names"])


def test_first_level_metrics_partition_the_step():
    fx = _fixture()
    patterns = {m: re.compile(run.load("layer_metrics", m)["pattern"]) for m in FIRST_LEVEL}
    total = {m: 0 for m in FIRST_LEVEL}
    for tf_op, ps in fx["ops"]:
        hits = [m for m, p in patterns.items() if p.search(tf_op)]
        assert len(hits) == 1, (tf_op, hits)
        total[hits[0]] += ps
    assert total["grad_reduce_ms"] == 0                      # one chip: no collective
    assert sum(total.values()) == pytest.approx(fx["busy_ps"], rel=1e-6)
    ctx = {"trace": _trace(fx["ops"]), "steps": fx["steps"]}
    for m in FIRST_LEVEL:
        got = stat_time.reduce(run.load("layer_metrics", m), ctx)
        assert (got or 0.0) == pytest.approx(total[m] * 1e-9 / fx["steps"])


@pytest.mark.parametrize("metric", NEW_TRACE + ("layer_norm_ms",))
def test_scope_metrics_read_this_cell(metric):
    fx = _fixture()
    value = stat_time.reduce(run.load("layer_metrics", metric),
                             {"trace": _trace(fx["ops"]), "steps": fx["steps"]})
    assert value is not None and value > 1.0                 # each is milliseconds a step


@pytest.mark.parametrize("cell", ("gpt2-medium.train", "gpt2-medium.train-dp4"))
@pytest.mark.parametrize("metric", NEW_TRACE)
def test_new_metrics_find_nothing_in_the_gpt_cells(metric, cell):
    fx = _fixture(cell, "tf_ops")
    spec = run.load("layer_metrics", metric)
    assert spec["family"] == "qwen3_next"                    # the harness skips it there
    assert stat_time.reduce(spec, {"trace": _trace(fx["ops"]), "steps": fx["steps"]}) is None


def test_readers_find_nothing_in_a_program_without_the_scopes():
    parent = [("jit(one_chip_step)/amp_forward/jvp(gpt_blocks)/while/body/closed_call/dot_general", 500),
              ("jit(one_chip_step)/amp_unscale/reduce_or", 200), ("", 10),
              ("jit(one_chip_step)/master_weights_step/fused_adam_step_flat/mul", 300)]
    ctx = {"trace": _trace(parent, [("%flash_attention", 100)]), "steps": 1, "family": gpt,
           "cfg": run.load("configs", "gpt2-medium"), "cell": run.load("workloads", "gpt2-medium.train"),
           "items_per_step": 4096, "peak": run.peak_of("TPU v5 lite")}
    for metric in NEW_TRACE:
        assert stat_time.reduce(run.load("layer_metrics", metric), ctx) is None, metric
    assert roofline.reduce(run.load("layer_metrics", "gated_delta_roofline"), ctx) is None


def test_second_level_metrics_nest_as_the_model_does():
    fx = _fixture()
    p = {m: re.compile(run.load("layer_metrics", m)["pattern"])
         for m in NEW_TRACE + ("layer_norm_ms", "forward_ms", "backward_ms")}
    for tf_op, _ in fx["ops"]:
        kinds = [m for m in ("linear_mixer_ms", "attn_mixer_ms", "moe_ms", "head_loss_ms.qwen3_next")
                 if p[m].search(tf_op)]
        assert len(kinds) <= 1, tf_op                        # a layer part is one kind
        # constants the compiler hoists out of both passes keep the model's scope
        # alone (``jit(step)/attn_mixer/cos``); at the first level they are unattributed
        hoisted = re.match(r"jit\(step\)/(?:linear_mixer|attn_mixer|moe)/", tf_op)
        if p["gated_delta_ms"].search(tf_op):
            assert kinds == ["linear_mixer_ms"], tf_op
        if kinds and not tf_op.startswith("ragged-dot"):     # the compiler's own name: no scope
            assert p["forward_ms"].search(tf_op) or p["backward_ms"].search(tf_op) or hoisted, tf_op


def test_kernel_patterns_match_the_kernels_alone():
    fx = _fixture()
    names = dict(fx["hlo_names"])
    for metric, kernels in (("gated_delta_roofline", {"%gated_delta_fwd", "%gated_delta_bwd"}),
                            ("flash_attn_ms", {"%flash_attention"}),
                            ("flash_attn_roofline", {"%flash_attention"})):
        pattern = re.compile(run.load("layer_metrics", metric)["pattern"])
        assert {n for n in names if pattern.search(n)} == kernels, metric


def test_rooflines_on_the_recorded_times_stay_under_their_roof():
    fx = _fixture()
    cfg, cell = run.load("configs", "qwen3-next-80b-a3b"), run.load("workloads", CELL)
    ctx = {"trace": _trace([], fx["hlo_names"]), "steps": fx["steps"], "family": family,
           "cfg": cfg, "cell": cell, "items_per_step": cfg["seq_len"],
           "peak": run.peak_of(fx["device_kind"])}
    gd = roofline.reduce(run.load("layer_metrics", "gated_delta_roofline"), ctx)
    fa = roofline.reduce(run.load("layer_metrics", "flash_attn_roofline"), ctx)
    assert 1.0 < gd < 100.0 and 1.0 < fa < 100.0
    by_hand = 28_311_552 * 8192 / 197e12 / (
        sum(ps for n, ps in fx["hlo_names"] if n.startswith("%gated_delta")) * 1e-12 / fx["steps"])
    assert gd == pytest.approx(100.0 * by_hand)
    assert kernel_time.reduce(run.load("layer_metrics", "flash_attn_ms"), ctx) > 1.0
