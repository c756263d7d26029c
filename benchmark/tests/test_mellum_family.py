"""Family ``mellum`` (PR 31): the manifest's new entries, the configuration
against the catalog's published keys, its counts, its rehearsal cell and its
control, and its per-layer metrics on the names the chip printed.

``fixtures/tf_ops_mellum/<cell>.json`` is a traced run of the cell on the chip
(``tools/dump_tf_ops.py``, PR 31's program): every distinct framework name of
chip 0 with its self time, and every HLO name stem. The cases the issue asked
for in ``test_counts.py`` and ``test_layer_metrics.py`` are here instead: a PR
that adds a configuration may not edit a file the benchmark already has."""

import json
import os
import re

import jax
import pytest

from benchmark import check, run, trace_reduce
from benchmark.families import gpt, mellum as family
from benchmark.reductions import family_counter, kernel_time, roofline, stat_time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "mellum2-12b-a2.5b.train-s8k"
CONFIG = "mellum2-12b-a2.5b"
TINY = "tiny-mellum.train"
FIRST_LEVEL = ("forward_ms", "backward_ms", "unscale_ms", "grad_reduce_ms",
               "optimizer_ms.gpt", "unattributed_ms")
NEW_SCOPES = ("window_mixer_ms", "full_mixer_ms", "moe_ms.mellum", "head_loss_ms.mellum")
NEW_KERNELS = ("flash_window_ms", "flash_window_roofline")
NEW_COUNTERS = ("expert_rows_per_step.mellum", "expert_load_max_over_mean.mellum")
NEW = ("window_mixer_ms", "full_mixer_ms", "flash_window_ms", "flash_window_roofline",
       "moe_ms.mellum", "expert_rows_per_step.mellum", "expert_load_max_over_mean.mellum",
       "head_loss_ms.mellum")
APPENDED = ("flash_attn_ms", "flash_attn_roofline", "optimizer_ms.gpt", "forward_ms",
            "backward_ms", "unscale_ms", "layer_norm_ms", "unattributed_ms")
QWEN_ONLY = ("gated_delta_ms", "gated_delta_roofline", "moe_ms", "linear_mixer_ms",
             "attn_mixer_ms", "head_loss_ms.qwen3_next", "expert_rows_per_step",
             "expert_load_max_over_mean")


def _fixture(cell=CELL, directory="tf_ops_mellum"):
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


def _context(fx, ops=True):
    cfg, cell = run.load("configs", CONFIG), run.load("workloads", CELL)
    return {"trace": _trace(fx["ops"] if ops else [], fx["hlo_names"]), "steps": fx["steps"],
            "family": family, "cfg": cfg, "cell": cell, "items_per_step": cfg["seq_len"],
            "peak": run.peak_of(fx["device_kind"])}


# -- the manifest -----------------------------------------------------------------

def test_the_manifest_gained_the_configuration_the_cell_and_the_metrics():
    m = _manifest()
    assert [c["name"] for c in m["configs"]] == ["gpt2-medium", "qwen3-next-80b-a3b", CONFIG]
    assert m["configs"][-1]["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert [w["name"] for w in m["workloads"]][-1] == CELL and len(m["workloads"]) == 4
    assert m["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "train-s8k", "chips": 1,
        "why": run.load("workloads", CELL)["why"]}
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    assert [x["name"] for x in m["per_layer"][-len(NEW):]] == list(NEW)
    for name in NEW:
        assert _per_layer()[name]["workloads"] == [CELL], name
        assert run.load("layer_metrics", name)["family"] == "mellum"
    for name in APPENDED:
        assert _per_layer()[name]["workloads"] == [
            "gpt2-medium.train", "gpt2-medium.train-dp4", "qwen3-next-80b-a3b.train-s8k", CELL], name
    for name in QWEN_ONLY + ("collective_exposed_ms", "grad_reduce_ms", "grad_reduce_gb",
                             "head_loss_ms.gpt"):
        assert CELL not in _per_layer()[name]["workloads"], name
    assert not [e for e in m["end_to_end"] if "workloads" in e] and len(m["end_to_end"]) == 4


def test_the_cell_is_what_the_issue_named():
    cell = run.load("workloads", CELL)
    assert (cell["chips"], cell["layout"], cell["per_chip_batch"], cell["pool"]) == (1, "single", 1, 8)
    assert run.load("configs", CONFIG)["seq_len"] == 8192
    assert run.load("configs", CONFIG)["remat_policy"] is None
    assert family.GUARDED_OPS == ("flash_attention", "layer_norm")
    for key in ("read_by", "loss_gap", "first_grad_norm_gap", "update_norm_gap", "the control fails"):
        assert key in cell["limits_from"], key


def test_the_configuration_holds_every_published_key():
    """Every key of the catalog's ``config`` for this model, as published, but
    for the three that ``reduced`` lists."""
    cfg = run.load("configs", CONFIG)
    period = ["sliding_attention"] * 3 + ["full_attention"]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 7168, "layer_types": period * 7, "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0, "model_type": "mellum",
        "moe_intermediate_size": 896, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                               "original_max_position_embeddings": 8192, "beta_fast": 32,
                               "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304,
        "use_sliding_window": True}
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, 12288)
    assert cfg["published"] == {**{k: published[k] for k in cfg["reduced"]},
                                "parameters": cfg["published"]["parameters"]}
    assert cfg["num_experts_published"] == 64 and cfg["vocab_size"] * 8 == 98304
    assert cfg["moe_rows_bound"] == 24576 == 1.5 * 8192 * 8 * 16 / 64
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == period      # one whole period
    for key in ("layer", "qk_norm", "rotate_half", "router", "sliding_window", "yarn",
                "initializer_range", "embedding_init_std", "weights", "optimizer", "loss",
                "seq_len", "moe_rows_bound"):
        assert key in cfg["assumed"], key
    for said in ("expert-parallel 4", "8-way", "24 layers", "experts 0-15"):
        assert said in cfg["deployment"], said
    assert len(cfg["departures"]) == 3 and cfg["source"].endswith("config.json")


# -- counts -----------------------------------------------------------------------

def test_parameters_and_required_operations():
    cfg = run.load("configs", CONFIG)
    D, V, F, E, H, Hkv, hd = 2304, 12288, 896, 16, 32, 4, 128
    layer = 2 * D * H * hd + 2 * D * Hkv * hd + D * 64 + 2 * D + 2 * hd + E * 3 * D * F
    assert family.param_count(cfg) == 4 * layer + 2 * V * D + D == 538_531_072
    assert round(16 * family.param_count(cfg) / 1e9, 2) == 8.62       # 16 B a parameter
    token = 4 * (2 * D * H * hd + 2 * D * Hkv * hd + D * 64 + (8 * 16 / 64) * 3 * D * F) + V * D
    window = 3 * 12 * H * hd * (1024 - 1024 * 1023 / (2 * 8192))
    full = 12 * H * hd * (8192 + 1) / 2
    assert family.window_attention_flops_per_item(cfg) == window == 141_566_976.0
    assert family.attention_flops_per_item(cfg) == window + full == 342_918_144.0
    assert family.model_flops_per_item(cfg) == 6 * token + window + full == 1_323_205_632.0


@pytest.mark.parametrize("window,seq_len", ((8192, 8192), (9000, 8192), (131072, 8192), (64, 64)))
def test_a_window_that_holds_the_sequence_counts_as_the_full_layer(window, seq_len):
    cfg = dict(run.load("configs", CONFIG), sliding_window=window, seq_len=seq_len)
    assert family.keys_per_query(cfg, "sliding_attention") \
        == family.keys_per_query(cfg, "full_attention") == (seq_len + 1) / 2
    assert family.window_attention_flops_per_item(cfg) * 4 == family.attention_flops_per_item(cfg) * 3


@pytest.mark.parametrize("window,seq_len,keys", ((1, 8192, 1.0), (2, 4, 1.75), (1024, 8192, 960.0625),
                                                 (3, 8, (1 + 2 + 6 * 3) / 8)))
def test_keys_a_window_leaves_a_query(window, seq_len, keys):
    """Against the sum it abbreviates: query ``i`` keeps ``min(i + 1, W)`` keys."""
    cfg = dict(run.load("configs", CONFIG), sliding_window=window, seq_len=seq_len)
    by_hand = sum(min(i + 1, window) for i in range(seq_len)) / seq_len
    assert family.keys_per_query(cfg, "sliding_attention") == by_hand == keys


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


def test_a_traced_rehearsal_runs_two_passes_over_the_pool(capsys):
    assert run.main(["--workload", TINY, "--seed", "5", "--seconds", "0.3", "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] == 6 and line["metrics"] == {}


def test_a_step_that_drops_a_routed_row_is_a_failed_step(monkeypatch, capsys):
    real = run.load
    monkeypatch.setattr(run, "load", lambda kind, name: dict(real(kind, name), moe_rows_bound=8)
                        if kind == "configs" else real(kind, name))
    assert run.main(["--workload", TINY, "--seed", "7", "--seconds", "0.2", "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert re.search(r"window\.failed_steps = [1-9]\d*  limit 0  FAILED", out)
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False
    assert family.counters()["dropped_rows"] > 0


def test_the_real_cell_refuses_any_backend_but_tpu(capsys):
    assert run.main(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]) == 1
    captured = capsys.readouterr()
    assert captured.out == "" and "tpu" in captured.err


def test_the_counters_are_read_from_the_newest_state():
    c = _cell(11)
    c.build()
    for i in range(3):
        c.run_step(i)
    rows = run.load("layer_metrics", "expert_rows_per_step.mellum")
    load = run.load("layer_metrics", "expert_load_max_over_mean.mellum")
    seen = family.counters()
    assert seen["steps"] == 3
    assert family_counter.reduce(rows, {"family": family}) == pytest.approx(seen["expert_rows"] / 3)
    assert family_counter.reduce(load, {"family": family}) == seen["expert_load_max_over_mean"] >= 1.0
    assert family_counter.reduce(rows, {"family": gpt}) is None   # a family without counters


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


@pytest.mark.parametrize("metric", NEW_SCOPES + ("layer_norm_ms",))
def test_scope_metrics_read_this_cell(metric):
    fx = _fixture()
    value = stat_time.reduce(run.load("layer_metrics", metric),
                             {"trace": _trace(fx["ops"]), "steps": fx["steps"]})
    assert value is not None and value > 1.0                 # each is milliseconds a step


@pytest.mark.parametrize("metric", [m for m in QWEN_ONLY if "expert" not in m])
def test_the_qwen3_next_files_read_nothing_here(metric):
    """They carry ``"family": "qwen3_next"``, so the harness skips them in this
    cell; but for ``moe_ms``, whose scopes both families' MoE opens, their
    readers would also find nothing to read on this program's names."""
    fx = _fixture()
    spec = run.load("layer_metrics", metric)
    assert spec["family"] == "qwen3_next"
    reduction = roofline if spec["reduction"] == "roofline" else stat_time
    value = reduction.reduce(spec, _context(fx))
    assert (value is None) == (metric != "moe_ms"), metric


@pytest.mark.parametrize("directory,cell", (("tf_ops", "gpt2-medium.train"),
                                            ("tf_ops", "gpt2-medium.train-dp4"),
                                            ("tf_ops_qwen3_next", "qwen3-next-80b-a3b.train-s8k")))
@pytest.mark.parametrize("metric", NEW_SCOPES + NEW_KERNELS)
def test_new_metrics_find_nothing_in_the_other_cells(metric, cell, directory):
    """The parent's programs (no window, no ``mellum_*`` scope): the readers
    return nothing and do not raise — but for ``moe_ms.mellum`` on the Qwen
    cell, which the harness never asks (``"family": "mellum"``)."""
    fx = _fixture(cell, directory)
    spec = run.load("layer_metrics", metric)
    assert spec["family"] == "mellum"
    ctx = dict(_context(_fixture()), trace=_trace(fx["ops"], fx.get("hlo_names", ())),
               steps=fx["steps"])
    reduction = {"roofline": roofline, "kernel_time": kernel_time}.get(spec["reduction"], stat_time)
    value = reduction.reduce(spec, ctx)
    assert (value is None) == (not (metric == "moe_ms.mellum" and "qwen3" in cell)), (metric, cell)


def test_second_level_metrics_nest_as_the_model_does():
    fx = _fixture()
    p = {m: re.compile(run.load("layer_metrics", m)["pattern"])
         for m in NEW_SCOPES + ("layer_norm_ms", "forward_ms", "backward_ms")}
    flash = [tf_op for tf_op, _ in fx["ops"] if "flash_attention" in tf_op]
    assert flash and all(("window_mixer" in n) != ("full_mixer" in n) for n in flash)
    for tf_op, _ in fx["ops"]:
        kinds = [m for m in NEW_SCOPES if p[m].search(tf_op)]
        assert len(kinds) <= 1, tf_op                        # a layer part is one kind
        hoisted = re.match(r"jit\(step\)/(?:window_mixer|full_mixer|moe|mellum_embed)/", tf_op)
        if kinds and not tf_op.startswith("ragged-dot"):     # the compiler's own name: no scope
            assert p["forward_ms"].search(tf_op) or p["backward_ms"].search(tf_op) or hoisted, tf_op


def test_kernel_patterns_match_the_kernels_alone():
    names = dict(_fixture()["hlo_names"])
    windowed = {"%flash_attention_window_fwd", "%flash_attention_window_dq",
                "%flash_attention_window_dkv"}
    for metric, kernels in (("flash_window_ms", windowed), ("flash_window_roofline", windowed),
                            ("flash_attn_ms", windowed | {"%flash_attention"}),
                            ("flash_attn_roofline", windowed | {"%flash_attention"})):
        pattern = re.compile(run.load("layer_metrics", metric)["pattern"])
        assert {n for n in names if pattern.search(n)} == kernels, metric


def test_rooflines_on_the_recorded_times_stay_under_their_roof():
    fx = _fixture()
    ctx = _context(fx, ops=False)
    window = roofline.reduce(run.load("layer_metrics", "flash_window_roofline"), ctx)
    every = roofline.reduce(run.load("layer_metrics", "flash_attn_roofline"), ctx)
    assert 1.0 < window < 100.0 and 1.0 < every < 100.0
    ms = kernel_time.reduce(run.load("layer_metrics", "flash_window_ms"), ctx)
    by_hand = 141_566_976.0 * 8192 / 197e12 / (ms * 1e-3)
    assert window == pytest.approx(100.0 * by_hand)
    assert kernel_time.reduce(run.load("layer_metrics", "flash_attn_ms"), ctx) > ms > 1.0
