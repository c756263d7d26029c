"""Family ``nemotron_h`` (PR 33): the manifest's new entries **looked up by name**,
the configuration against the catalog's published keys, its counts, its
rehearsal cell and its control, and its per-layer metrics on the names the chip
printed.

``fixtures/tf_ops_nemotron_h/<cell>.json`` is a traced run of the cell on the
chip (``tools/dump_tf_ops.py``, PR 33's program): every distinct framework name
of chip 0 with its self time, and every HLO name stem."""

import json
import os
import re

import jax
import pytest

from benchmark import check, run, trace_reduce
from benchmark.families import gpt, nemotron_h as family
from benchmark.reductions import family_counter, kernel_time, roofline, stat_time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "nemotron-3-super-120b-a12b.train-s8k"
CONFIG = "nemotron-3-super-120b-a12b"
TINY = "tiny-nemotron-h.train"
SOURCE = "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size", "mamba_num_heads", "n_groups",
           "num_attention_heads", "num_key_value_heads"]
FIRST_LEVEL = ("forward_ms", "backward_ms", "unscale_ms", "grad_reduce_ms",
               "optimizer_ms.gpt", "unattributed_ms")
NEW_SCOPES = ("ssm_mixer_ms", "attn_mixer_ms.nemotron_h", "ssd_ms", "moe_ms.nemotron_h",
              "latent_proj_ms", "head_loss_ms.nemotron_h")
NEW_KERNELS = ("ssd_roofline", "grouped_matmul_ms.nemotron_h")
NEW_COUNTERS = ("expert_rows_per_step.nemotron_h", "expert_load_max_over_mean.nemotron_h")
NEW = ("ssm_mixer_ms", "attn_mixer_ms.nemotron_h", "ssd_ms", "ssd_roofline", "moe_ms.nemotron_h",
       "latent_proj_ms", "grouped_matmul_ms.nemotron_h", "expert_rows_per_step.nemotron_h",
       "expert_load_max_over_mean.nemotron_h", "head_loss_ms.nemotron_h")
APPENDED = ("flash_attn_ms", "flash_attn_roofline", "optimizer_ms.gpt", "forward_ms",
            "backward_ms", "unscale_ms", "layer_norm_ms", "unattributed_ms")
SSD_KERNELS = {"%ssd_fwd", "%ssd_bwd_states", "%ssd_bwd"}


def _fixture(cell=CELL, directory="tf_ops_nemotron_h"):
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


def _named(entries):
    return {e["name"]: e for e in entries}


def _context(fx, ops=True):
    cfg, cell = run.load("configs", CONFIG), run.load("workloads", CELL)
    return {"trace": _trace(fx["ops"] if ops else [], fx["hlo_names"]), "steps": fx["steps"],
            "family": family, "cfg": cfg, "cell": cell, "items_per_step": cfg["seq_len"],
            "peak": run.peak_of(fx["device_kind"])}


# -- the manifest, by name --------------------------------------------------------

def test_the_manifest_holds_the_configuration_the_cell_and_the_metrics():
    m = _manifest()
    config = _named(m["configs"])[CONFIG]
    assert config == {"name": CONFIG, "source": SOURCE, "file": f"benchmark/configs/{CONFIG}.json",
                      "reduced": REDUCED, "why": config["why"]}
    assert 0 < len(config["why"]) <= 200
    assert _named(m["workloads"])[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "train-s8k", "chips": 1,
        "why": run.load("workloads", CELL)["why"]}
    assert [w["name"] for w in m["workloads"] if w["config"] == CONFIG] == [CELL]   # one cell
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    per_layer = _named(m["per_layer"])
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "tokens_per_s"
        assert run.load("layer_metrics", name)["family"] == "nemotron_h"
    for name in APPENDED:
        assert per_layer[name]["workloads"].count(CELL) == 1, name
    for name, entry in per_layer.items():
        if name not in NEW + APPENDED and "workloads" in entry:
            assert CELL not in entry["workloads"], name
    assert not [e for e in m["end_to_end"] if "workloads" in e] and len(m["end_to_end"]) == 4
    assert all("why" not in e for e in m["per_layer"])


def test_the_cell_is_what_the_issue_named():
    cell = run.load("workloads", CELL)
    assert (cell["chips"], cell["layout"], cell["per_chip_batch"], cell["pool"]) == (1, "single", 1, 8)
    cfg = run.load("configs", CONFIG)
    assert cfg["seq_len"] == 8192 and cfg["remat_policy"] is None and cfg["family"] == "nemotron_h"
    assert family.GUARDED_OPS == ("flash_attention", "layer_norm", "ssd", "grouped_matmul")
    for said in ("352 rows", "1/8", "29 %"):
        assert said in cell["why"], said
    for key in ("read_by", "loss_gap", "first_grad_norm_gap", "update_norm_gap", "the control fails"):
        assert key in cell["limits_from"], key


def test_the_configuration_holds_every_published_key():
    """Every key of the catalog's ``config`` for this model, as published, but
    for the seven that ``reduced`` lists. No width is among them: the shared
    expert's stays 5,376 under its key, and a further key says how many of its
    columns are held."""
    cfg = run.load("configs", CONFIG)
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 4096,
        "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                                   "EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 22, "num_hidden_layers": 88, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
        "use_mamba_kernels": True, "vocab_size": 131072}
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == sorted(REDUCED)
    held = {k: cfg[k] for k in REDUCED}
    assert held == {"num_hidden_layers": 11, "n_routed_experts": 8, "vocab_size": 16384,
                    "mamba_num_heads": 16, "n_groups": 1, "num_attention_heads": 4,
                    "num_key_value_heads": 1}
    width = re.compile(r"hidden_size|intermediate|latent|state_size|proj|_dim$|_rank$|expand|per_tok")
    assert not [k for k in cfg["reduced"] if width.search(k)]
    assert {k: cfg["published"][k] for k in REDUCED} == {k: published[k] for k in REDUCED}
    assert cfg["moe_shared_expert_columns_held"] == 672 == 5376 // 8 and 672 % 128
    assert cfg["n_routed_experts_published"] == 512 and cfg["vocab_size"] * 8 == 131072
    assert cfg["moe_rows_bound"] == 8192 and 8192 * 22 * 8 // 512 == 2816      # 2.9 x, as pinned
    assert cfg["optimizer"]["lr"] == 1e-7                 # flat expert_rows over a window: PERF.md section 6
    assert len(published["hybrid_override_pattern"]) == 88
    assert family.reference.pattern(cfg) == "EMEMEMEMEM*" \
        == published["hybrid_override_pattern"][26:37]
    for key in ("position", "norms", "router", "experts", "weights", "keep_fp32", "optimizer",
                "loss", "seq_len", "first_layer", "moe_rows_bound", "parameters",
                "moe_shared_expert_columns_held"):
        assert key in cfg["assumed"], key
    for said in ("rank 0", "tensor-parallel 8", "expert-parallel 64", "64 chips", "8 pipeline stages",
                 "heads 0-15", "experts 0-7", "not a multiple of 128", "repeated 4 x"):
        assert said in cfg["deployment"], said
    assert len(cfg["departures"]) == 4 and cfg["source"] == SOURCE


# -- counts -----------------------------------------------------------------------

def test_parameters_and_required_operations():
    cfg = run.load("configs", CONFIG)
    D, V = 4096, 16384
    mamba = D + D * (2 * 1024 + 2 * 128 + 16) + 1280 * 4 + 1280 + 3 * 16 + 1024 + 1024 * D
    attn = D + D * 512 + 2 * D * 128 + 512 * D
    moe = D + D * 512 + 2 * D * 1024 + 8 * 2 * 1024 * 2688 + 2 * D * 672
    assert family.param_count(cfg) == 5 * mamba + attn + 5 * moe + 2 * V * D + D == 508_187_120
    assert round(16 * family.param_count(cfg) / 1e9, 2) == 8.13       # 16 B a parameter
    token = 5 * (D * 2320 + 1024 * D) + (2 * D * 512 + 2 * D * 128) \
        + 5 * (D * 512 + 2 * D * 1024 + 2 * D * 672 + (22 * 8 / 512) * 2 * 1024 * 2688) + V * D
    assert token == 230_252_544.0
    attention = 6 * 8192 * 4 * 128
    ssd = 3 * 5 * (16 * (4 * 128 * 64 + 64.5 * 2 * 64) + 64.5 * 2 * 128)
    assert family.attention_flops_per_item(cfg) == attention == 25_165_824
    assert family.ssd_flops_per_item(cfg) == ssd == 10_093_440.0
    assert family.model_flops_per_item(cfg) == 6 * token + attention + ssd == 1_416_774_528.0


@pytest.mark.parametrize("change,ratio", (({"chunk_size": 256}, None), ({"n_groups": 2}, None),
                                          ({"mamba_num_heads": 32}, None)))
def test_the_recurrence_count_follows_its_shapes(change, ratio):
    cfg = run.load("configs", CONFIG)
    base, other = family.ssd_flops_per_item(cfg), family.ssd_flops_per_item(dict(cfg, **change))
    assert other > base          # more tokens seen in a chunk, more groups' scores, more heads
    by_hand = dict(cfg, **change)
    H, G, C = by_hand["mamba_num_heads"], by_hand["n_groups"], by_hand["chunk_size"]
    assert other == 15 * (H * (4 * 128 * 64 + (C + 1) / 2 * 128) + G * (C + 1) / 2 * 256)


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
    rows = run.load("layer_metrics", "expert_rows_per_step.nemotron_h")
    load = run.load("layer_metrics", "expert_load_max_over_mean.nemotron_h")
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
    dispatch = {d["op"]: d for d in fx["dispatch"]}
    for op in family.GUARDED_OPS:         # each dispatched its kernels, none the jnp path
        assert dispatch[op]["pallas"] > 0 and dispatch[op]["jnp"] == 0, op
    assert {t["kernel"] for t in fx["tiles"] if t["op"] == "ssd"} == {"fwd", "bwd_states", "bwd"}


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
    assert value is not None and value > 0.5                 # each is milliseconds a step


@pytest.mark.parametrize("directory,cell", (("tf_ops", "gpt2-medium.train"),
                                            ("tf_ops", "gpt2-medium.train-dp4"),
                                            ("tf_ops_qwen3_next", "qwen3-next-80b-a3b.train-s8k"),
                                            ("tf_ops_mellum", "mellum2-12b-a2.5b.train-s8k")))
@pytest.mark.parametrize("metric", NEW_SCOPES + NEW_KERNELS)
def test_new_metrics_find_nothing_in_the_other_cells(metric, cell, directory):
    """The parent's programs (no ``ssd``, no ``nemotron_h_*`` scope, no latent):
    the readers return nothing and do not raise — but for the three whose scopes
    or kernels another family's program opens too (``attn_mixer``, ``/moe/``, the
    grouped kernels), which the harness never asks there (``"family":
    "nemotron_h"``)."""
    fx = _fixture(cell, directory)
    spec = run.load("layer_metrics", metric)
    assert spec["family"] == "nemotron_h"
    ctx = dict(_context(_fixture()), trace=_trace(fx["ops"], fx.get("hlo_names", ())),
               steps=fx["steps"])
    reduction = {"roofline": roofline, "kernel_time": kernel_time}.get(spec["reduction"], stat_time)
    shared = {"attn_mixer_ms.nemotron_h": ("qwen3",), "moe_ms.nemotron_h": ("qwen3", "mellum"),
              "grouped_matmul_ms.nemotron_h": ("qwen3", "mellum")}
    expected = any(word in cell for word in shared.get(metric, ()))
    assert (reduction.reduce(spec, ctx) is not None) == expected, (metric, cell)


def test_second_level_metrics_nest_as_the_model_does():
    fx = _fixture()
    blocks = ("ssm_mixer_ms", "attn_mixer_ms.nemotron_h", "moe_ms.nemotron_h")
    p = {m: re.compile(run.load("layer_metrics", m)["pattern"])
         for m in NEW_SCOPES + ("layer_norm_ms", "forward_ms", "backward_ms", "flash_attn_ms")}
    total = dict.fromkeys(NEW_SCOPES, 0)
    for tf_op, ps in fx["ops"]:
        kinds = [m for m in blocks if p[m].search(tf_op)]
        assert len(kinds) <= 1, tf_op                        # a block is one of the three
        for m in NEW_SCOPES:
            total[m] += ps if p[m].search(tf_op) else 0
        if p["ssd_ms"].search(tf_op):
            assert kinds == ["ssm_mixer_ms"], tf_op          # the recurrence inside its mixer
        if p["latent_proj_ms"].search(tf_op):
            assert kinds == ["moe_ms.nemotron_h"], tf_op
        if "flash_attention" in tf_op:
            assert kinds == ["attn_mixer_ms.nemotron_h"], tf_op
    assert 0 < total["ssd_ms"] < total["ssm_mixer_ms"]
    assert 0 < total["latent_proj_ms"] < total["moe_ms.nemotron_h"]
    assert total["attn_mixer_ms.nemotron_h"] < total["ssm_mixer_ms"]     # one block against five


def test_kernel_patterns_match_the_kernels_alone():
    names = dict(_fixture()["hlo_names"])
    grouped = {"%grouped_matmul_fwd", "%grouped_matmul_dlhs", "%grouped_matmul_drhs"}
    for metric, kernels in (("ssd_roofline", SSD_KERNELS), ("flash_attn_ms", {"%flash_attention"}),
                            ("flash_attn_roofline", {"%flash_attention"}),
                            ("grouped_matmul_ms.nemotron_h", grouped)):
        pattern = re.compile(run.load("layer_metrics", metric)["pattern"])
        assert {n for n in names if pattern.search(n)} == kernels, metric


def test_rooflines_on_the_recorded_times_stay_under_their_roof():
    fx = _fixture()
    ctx = _context(fx, ops=False)
    ssd = roofline.reduce(run.load("layer_metrics", "ssd_roofline"), ctx)
    flash = roofline.reduce(run.load("layer_metrics", "flash_attn_roofline"), ctx)
    assert 0.5 < ssd < 100.0 and 1.0 < flash < 100.0
    ms = sum(ps for n, ps in fx["hlo_names"] if n in SSD_KERNELS) * 1e-9 / fx["steps"]
    assert ssd == pytest.approx(100.0 * 10_093_440.0 * 8192 / 197e12 / (ms * 1e-3))
    assert kernel_time.reduce(run.load("layer_metrics", "grouped_matmul_ms.nemotron_h"), ctx) > 1.0
