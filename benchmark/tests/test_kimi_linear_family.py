"""Family ``kimi_linear`` (PR 49): the manifest's new entries **looked up by
name**, the configuration against the catalog's published keys, its counts, its
rehearsal cell with a broken path, and its per-layer metrics on the scope names
of the compiled step and on the names the chip printed.

``fixtures/tf_ops_kimi_linear/<cell>.json`` is a traced run of the cell on the
chip (``tools/dump_tf_ops.py``, from the unpacked archive of PR 49's final tree):
every distinct framework name of chip 0 with its self time, and every HLO name
stem."""

import json
import os
import re

import jax
import pytest

from benchmark import check, run, trace_reduce
from benchmark.families import gpt, kimi_linear as family
from benchmark.reductions import family_counter, kernel_time, roofline, stat_time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "kimi-linear-48b-a3b.train-s8k"
CONFIG = "kimi-linear-48b-a3b"
TINY = "tiny-kimi-linear.train"
SOURCE = "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_SCOPES = ("kda_mixer_ms", "kda_proj_ms", "kda_ms", "mla_mixer_ms.kimi_linear",
              "dense_ffn_ms.kimi_linear", "moe_ms.kimi_linear", "moe_shared_ms.kimi_linear",
              "moe_sort_ms.kimi_linear", "head_loss_ms.kimi_linear")
NEW_KERNELS = ("kda_roofline", "grouped_matmul_ms.kimi_linear")
NEW_COUNTERS = ("expert_rows_per_step.kimi_linear", "expert_load_max_over_mean.kimi_linear")
NEW = NEW_SCOPES + NEW_KERNELS + NEW_COUNTERS
APPENDED = ("flash_attn_ms", "flash_attn_roofline", "optimizer_ms.gpt", "forward_ms",
            "backward_ms", "unscale_ms", "layer_norm_ms", "unattributed_ms")


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
    assert 0 < len(run.load("workloads", CELL)["why"]) <= 200
    assert [w["name"] for w in m["workloads"] if w["config"] == CONFIG] == [CELL]   # one cell
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    per_layer = _named(m["per_layer"])
    assert len(NEW) == 13
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "tokens_per_s"
        assert run.load("layer_metrics", name)["family"] == "kimi_linear"
        assert set(per_layer[name]) == {"name", "unit", "better", "source", "layer", "moves",
                                        "workloads"}
        spec = run.load("layer_metrics", name)
        assert {k: spec[k] for k in ("unit", "better", "source", "layer", "moves")} == \
            {k: per_layer[name][k] for k in ("unit", "better", "source", "layer", "moves")}
    for name in APPENDED:
        assert per_layer[name]["workloads"].count(CELL) == 1, name    # by name: a later cell follows
    for name, entry in per_layer.items():
        if name not in NEW + APPENDED and "workloads" in entry:
            assert CELL not in entry["workloads"], name
    assert not [e for e in m["end_to_end"] if "workloads" in e] and len(m["end_to_end"]) == 4
    assert all("why" not in e for e in m["per_layer"])
    layers = {per_layer[n]["layer"] for n in NEW}
    assert layers == {"model (models/kimi_linear.py)", "kernels (ops/kda.py)",
                      "mixture of experts (moe/dropless.py)", "kernels (ops/grouped_matmul.py)"}
    roof = per_layer["kda_roofline"]
    assert (roof["unit"], roof["better"], roof["source"]) == ("%", "higher", "device_trace")


def test_the_cell_is_what_the_issue_named():
    cell = run.load("workloads", CELL)
    assert (cell["chips"], cell["layout"], cell["per_chip_batch"], cell["pool"]) == (1, "single", 1, 8)
    cfg = run.load("configs", CONFIG)
    assert cfg["seq_len"] == 8192 and cfg["family"] == "kimi_linear"
    assert family.GUARDED_OPS == ("flash_attention", "layer_norm", "kda_rule", "deltanet_qkv",
                                  "deltanet_gate", "grouped_matmul")
    for said in ("256 rows", "1/32", "5 of 27"):
        assert said in cell["why"], said
    assert set(cell["limits"]) == {"loss_gap", "first_grad_norm_gap", "update_norm_gap"}
    assert all(0 < v < 0.1 for v in cell["limits"].values())


def test_the_configuration_holds_every_published_key():
    """Every key of the catalog's ``config`` for this model, as published, but
    for the three that ``reduced`` lists. No width is among them, and the nested
    group is whole."""
    cfg = run.load("configs", CONFIG)
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == sorted(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == {"num_hidden_layers": 5, "num_experts": 8,
                                            "vocab_size": 20480}
    width = re.compile(r"hidden_size|intermediate|latent|state_size|proj|_dim$|_rank$|expand|per_tok")
    assert not [k for k in cfg["reduced"] if width.search(k)]
    assert {k: cfg["published"][k] for k in REDUCED} == {k: published[k] for k in REDUCED}
    assert cfg["first_layer"] == 0 and cfg["num_experts_published"] == 256
    assert cfg["first_expert"] == 0 and cfg["vocab_size"] * 8 == 163840
    assert cfg["moe_rows_bound"] >= 1.5 * 8192 * 8 * 8 // 256      # at least 1.5 x the expected
    assert cfg["optimizer"]["lr"] in (1e-6, 1e-7) and cfg["initializer_range"] == 0.02
    assert family.reference.held(cfg) == [("kda", "dense"), ("kda", "moe"), ("kda", "moe"),
                                          ("mla", "moe"), ("kda", "moe")]
    for key in ("layer", "kda", "attention", "router", "weights", "embedding", "keep_fp32",
                "optimizer", "loss", "seq_len", "first_layer", "moe_rows_bound", "remat_policy",
                "kda_chunk", "parameters"):
        assert key in cfg["assumed"], key
    for said in ("32 chips", "expert-parallel 32", "rank 0", "experts 0-7", "layers 0-4",
                 "ids 0-20,479", "no exchange", "nothing stands in"):
        assert said in cfg["deployment"], said
    assert len(cfg["departures"]) >= 4 and cfg["source"] == SOURCE


# -- counts -----------------------------------------------------------------------

def test_parameters_and_required_operations():
    """ISSUE 49's counts, by hand."""
    cfg = run.load("configs", CONFIG)
    D, V = 2304, 20480
    kda = 4 * D * 4096 + 2 * (D * 128 + 128 * 4096) + D * 32 + 3 * 4096 * 4 + 32 + 4096 + 128
    mla = D * 6144 + D * 576 + 512 + 512 * 8192 + 4096 * D
    assert (kda, mla) == (39_514_272, 29_114_880)
    expert = 3 * D * 1024
    ffn = {"dense": 3 * D * 9216, "moe": D * 256 + 256 + expert + 8 * expert}
    assert (expert, ffn["dense"]) == (7_077_888, 63_700_992)
    layers = [kda + ffn["dense"]] + 3 * [kda + ffn["moe"]] + [mla + ffn["moe"]]
    assert family.param_count(cfg) == sum(layers) + 5 * 2 * D + 2 * V * D + D == 602_434_432
    assert round(16 * family.param_count(cfg) / 1e9, 2) == 9.64       # 16 B a parameter
    token = 4 * (4 * D * 4096 + 2 * (D * 128 + 128 * 4096) + D * 32) \
        + (D * 6144 + D * 576 + 512 * 8192 + 4096 * D) + 3 * D * 9216 \
        + 4 * (D * 256 + expert + (8 * 8 / 256) * expert) + V * D
    assert token == 335_593_472.0                                      # the issue's 335.6 M
    attention = 6 * 32 * (192 + 128) * (8192 + 1) / 2                  # ONE latent layer
    assert family.attention_flops_per_item(cfg) == attention == 251_688_960.0
    recurrence = 3 * (3 * 2 * 128 * 128) * 32 * 4
    assert family.kda_flops_per_item(cfg) == recurrence == 37_748_736
    assert family.model_flops_per_item(cfg) == 6 * token + attention + recurrence == 2_302_998_528.0
    # a step: 18.9 TFLOP, a floor of 96 ms at the published peak
    assert round(family.model_flops_per_item(cfg) * 8192 / 1e12, 1) == 18.9
    assert round(family.model_flops_per_item(cfg) * 8192 / 197e12 * 1e3) == 96


@pytest.mark.parametrize("change,ratio", (
    ({"num_hidden_layers": 3}, 0.75), ({"first_layer": 3, "num_hidden_layers": 1}, 0.0),
    ({"first_layer": 4, "num_hidden_layers": 4}, 0.75)))
def test_the_recurrences_count_follows_the_held_kda_layers(change, ratio):
    cfg = run.load("configs", CONFIG)
    base = family.kda_flops_per_item(cfg)
    assert family.kda_flops_per_item(dict(cfg, **change)) == ratio * base
    latent = family.attention_flops_per_item(dict(cfg, **change))
    assert (latent > 0) == any(m == "mla" for m, _ in family.reference.held(dict(cfg, **change)))


# -- the rehearsal cell and a broken path ---------------------------------------------

def _cell(seed):
    cell = run.load("workloads", TINY)
    c = run.Cell(cell, run.load("configs", cell["config"]), jax.devices()[:1])
    c.start(seed)
    return c


@pytest.mark.parametrize("seed", (1, 2, 3))
def test_program_passes_the_rehearsal_cells_limits(seed):
    c = _cell(seed)
    reference = c.reference()
    c.build()
    sound = check.compare(c.program_numbers(), reference, c.cell["limits"])
    assert all(r["ok"] for r in sound), sound


@pytest.mark.parametrize("broken", ("scalar_decay", "silu_gate", "scores_at_32", "scale_one",
                                    "no_beta"))
def test_a_broken_path_fails_correct(monkeypatch, broken):
    """Five faults this family could have and the check must see: a decay that is
    one number a head (the channels' mean: the scalar rule), an output gate of
    SiLU as a gated DeltaNet's, latent scores scaled as if queries were 32 wide
    and not 48, a router without its 2.446, and a delta rule that writes with
    ``beta = 1``. (A rotary table on the latent layer moves nothing a limit can
    see on weights of 0.02, where attention is nearly uniform:
    ``tests/test_kimi_linear.py`` holds the mixer to no rotary on weights of 0.1.)"""
    from beforeholiday_tpu.moe import dropless
    from beforeholiday_tpu.ops import attention, deltanet, kda

    jnp = jax.numpy
    if broken == "scalar_decay":
        real = kda.kda_rule
        monkeypatch.setattr(kda, "kda_rule", lambda q, k, v, g, beta, **kw: real(
            q, k, v, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape), beta, **kw))
    elif broken == "silu_gate":
        real = deltanet.deltanet_gate
        monkeypatch.setattr(deltanet, "deltanet_gate",
                            lambda *a, activation="silu", **kw: real(*a, **kw))
    elif broken == "scores_at_32":
        real = attention.flash_attention
        monkeypatch.setattr("beforeholiday_tpu.ops.flash_attention",
                            lambda q, k, v, scale=None, **kw: real(q, k, v, scale=32 ** -0.5, **kw))
    elif broken == "scale_one":
        real = dropless.route_sigmoid
        monkeypatch.setattr(dropless, "route_sigmoid",
                            lambda *a, scale=1.0, **kw: real(*a, scale=1.0, **kw))
    else:
        real = kda.kda_rule
        monkeypatch.setattr(kda, "kda_rule", lambda q, k, v, g, beta, **kw: real(
            q, k, v, g, jnp.ones_like(beta), **kw))
    c = _cell(4)
    reference = c.reference()
    c.build()
    rows = check.compare(c.program_numbers(), reference, c.cell["limits"])
    assert not all(r["ok"] for r in rows), rows


def test_a_sound_rehearsal_is_correct_and_reports_no_time(capsys):
    assert run.main(["--workload", TINY, "--seed", "2147483659", "--seconds", "0.3",
                     "--trace", "0"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and set(line["metrics"]) == {"setup_s"}


def test_a_traced_rehearsal_runs_two_passes_over_the_pool(capsys):
    assert run.main(["--workload", TINY, "--seed", "7", "--seconds", "0.3", "--trace", "1"]) == 0
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
    rows = run.load("layer_metrics", "expert_rows_per_step.kimi_linear")
    load = run.load("layer_metrics", "expert_load_max_over_mean.kimi_linear")
    seen = family.counters()
    assert seen["steps"] == 3
    assert family_counter.reduce(rows, {"family": family}) == pytest.approx(seen["expert_rows"] / 3)
    assert family_counter.reduce(load, {"family": family}) == seen["expert_load_max_over_mean"] >= 1.0
    assert family_counter.reduce(rows, {"family": gpt}) is None   # a family without counters


# -- the per-layer metrics on the compiled step's names ---------------------------------

@pytest.fixture(scope="module")
def names():
    """The distinct ``op_name`` of every op of the compiled rehearsal step: the
    paths the chip's profiler prints as ``tf_op``."""
    c = _cell(7)
    c.build()
    text = c.program.step.jitted.lower(c.state, c.pool[0]).compile().as_text()
    return sorted(set(re.findall(r'op_name="(jit\([^"]+)"', text)))


@pytest.mark.parametrize("metric", NEW_SCOPES)
def test_scope_metrics_read_this_familys_step(names, metric):
    value = stat_time.reduce(run.load("layer_metrics", metric),
                             {"trace": _trace([(n, 1000) for n in names]), "steps": 1})
    assert value is not None and value > 0


def test_second_level_metrics_nest_as_the_model_does(names):
    p = {m: re.compile(run.load("layer_metrics", m)["pattern"]) for m in NEW_SCOPES}
    parts = ("kda_mixer_ms", "mla_mixer_ms.kimi_linear", "dense_ffn_ms.kimi_linear",
             "moe_ms.kimi_linear")
    hit = dict.fromkeys(NEW_SCOPES, 0)
    for n in names:
        kinds = [m for m in parts if p[m].search(n)]
        assert len(kinds) <= 1, n                             # a part is one of the four
        for m in NEW_SCOPES:
            hit[m] += bool(p[m].search(n))
        for inner in ("kda_proj_ms", "kda_ms"):
            if p[inner].search(n):
                assert kinds == ["kda_mixer_ms"], n
        assert not (p["kda_proj_ms"].search(n) and p["kda_ms"].search(n)), n
        for inner in ("moe_sort_ms.kimi_linear", "moe_shared_ms.kimi_linear"):
            if p[inner].search(n):
                assert kinds == ["moe_ms.kimi_linear"], n
        if "flash_attention" in n:
            assert kinds == ["mla_mixer_ms.kimi_linear"], n
        if p["head_loss_ms.kimi_linear"].search(n):
            assert not kinds, n
    assert 0 < hit["kda_proj_ms"] < hit["kda_mixer_ms"] and 0 < hit["kda_ms"] < hit["kda_mixer_ms"]
    # the products under kda_proj are the four projections' and nothing of the rule's
    proj = [n for n in names if p["kda_proj_ms"].search(n)]
    assert any(n.endswith("dot_general") for n in proj)
    assert not [n for n in proj if "kda_gate_proj" in n or "/kda/" in n]


def test_the_roofline_reads_the_four_kernels_by_name_and_counts_the_recurrence():
    """``kda_roofline``: the kernels' own ``name=`` (the chip prints
    ``%kda_prepare_fwd.N`` ...), against ``kda_flops_per_item``; ``kda_proj`` and the
    other families' kernels are not among them."""
    spec = run.load("layer_metrics", "kda_roofline")
    pattern = re.compile(spec["pattern"])
    kernels = ["%kda_prepare_fwd", "%kda_prepare_bwd", "%kda_scan_fwd", "%kda_scan_bwd"]
    others = ["%gated_delta_fwd", "%wy_prepare_fwd", "%deltanet_gate_fwd", "%fusion",
              "%flash_attention", "%grouped_matmul_fwd"]
    assert all(pattern.search(k + ".3") for k in kernels)
    assert not [k for k in others if pattern.search(k + ".1")]
    cfg, cell = run.load("configs", CONFIG), run.load("workloads", CELL)
    steps, ms = 16, 40.0
    hlo = [(k, int(ms / 4 * 1e9 * steps)) for k in kernels] + [("%fusion", 10 ** 12)]
    ctx = {"trace": _trace([], hlo), "steps": steps, "family": family, "cfg": cfg, "cell": cell,
           "items_per_step": cfg["seq_len"], "peak": run.peak_of("TPU v5 lite")}
    got = roofline.reduce(spec, ctx)
    assert got == pytest.approx(100.0 * 37_748_736 * 8192 / 197e12 / (ms * 1e-3))
    assert 0 < got < 100.0
    assert roofline.reduce(spec, dict(ctx, trace=_trace([], [("%fusion", 10 ** 12)]))) is None
    grouped = run.load("layer_metrics", "grouped_matmul_ms.kimi_linear")
    assert kernel_time.reduce(grouped, dict(ctx, trace=_trace(
        [], [("%grouped_matmul_fwd", 16 * 10 ** 9)]))) == pytest.approx(1.0)


@pytest.mark.parametrize("directory,cell", (("tf_ops", "gpt2-medium.train"),
                                            ("tf_ops_qwen3_next", "qwen3-next-80b-a3b.train-s8k"),
                                            ("tf_ops_deepseek_v3", "kanana-2-30b-a3b.train-s8k")))
@pytest.mark.parametrize("metric", ("kda_mixer_ms", "kda_proj_ms", "kda_ms", "kda_roofline",
                                    "head_loss_ms.kimi_linear"))
def test_new_metrics_find_nothing_in_the_other_cells(metric, cell, directory):
    """The parent's programs (no ``kda_mixer``, no ``kimi_linear_*`` scope, no
    ``%kda_*`` kernel): the readers return nothing and do not raise."""
    with open(os.path.join(HERE, "fixtures", directory, cell + ".json")) as f:
        fx = json.load(f)
    spec = run.load("layer_metrics", metric)
    cfg = run.load("configs", CONFIG)
    ctx = {"trace": _trace(fx["ops"], fx.get("hlo_names", ())), "steps": fx["steps"],
           "family": family, "cfg": cfg, "cell": run.load("workloads", CELL),
           "items_per_step": cfg["seq_len"], "peak": run.peak_of(fx["device_kind"])}
    reduction = {"roofline": roofline, "stat_time": stat_time}[spec["reduction"]]
    assert reduction.reduce(spec, ctx) is None


# -- the per-layer metrics on the names the chip printed --------------------------------

def _fixture():
    with open(os.path.join(HERE, "fixtures", "tf_ops_kimi_linear", CELL + ".json")) as f:
        return json.load(f)


def test_the_recorded_names():
    """``fixtures/tf_ops_kimi_linear/<cell>.json``: a traced run of the cell on the
    chip (``tools/dump_tf_ops.py``, PR 49's final tree): every distinct framework
    name of chip 0 with its self time, and every HLO name stem."""
    fx = _fixture()
    assert fx["cell"] == CELL and fx["device_kind"] == "TPU v5 lite" and fx["steps"] == 16
    assert len(fx["ops"]) > 100 and len(fx["hlo_names"]) > 20
    assert sum(ps for _, ps in fx["ops"]) == sum(ps for _, ps in fx["hlo_names"])
    dispatch = {d["op"]: d for d in fx["dispatch"]}
    for op in family.GUARDED_OPS:         # each dispatched its kernels, none the jnp path
        assert dispatch[op]["pallas"] > 0 and dispatch[op]["jnp"] == 0, op
    assert dispatch["kda_rule"]["pallas"] == 4 and dispatch["flash_attention"]["pallas"] == 1
    names = dict(fx["hlo_names"])
    pattern = re.compile(run.load("layer_metrics", "kda_roofline")["pattern"])
    assert {n for n in names if pattern.search(n)} == {
        "%kda_prepare_fwd", "%kda_prepare_bwd", "%kda_scan_fwd", "%kda_scan_bwd"}


@pytest.mark.parametrize("metric", NEW_SCOPES + ("layer_norm_ms",))
def test_scope_metrics_read_the_chips_names(metric):
    fx = _fixture()
    value = stat_time.reduce(run.load("layer_metrics", metric),
                             {"trace": _trace(fx["ops"]), "steps": fx["steps"]})
    assert value is not None and value > 0.5                 # each is milliseconds a step


def test_the_new_mechanism_does_most_of_the_work_and_its_roofline_reads_low():
    fx = _fixture()
    cfg = run.load("configs", CONFIG)
    ctx = {"trace": _trace(fx["ops"], fx["hlo_names"]), "steps": fx["steps"], "family": family,
           "cfg": cfg, "cell": run.load("workloads", CELL), "items_per_step": cfg["seq_len"],
           "peak": run.peak_of(fx["device_kind"])}
    read = lambda m: stat_time.reduce(run.load("layer_metrics", m), ctx)
    step = fx["busy_ps"] * 1e-9 / fx["steps"]
    assert read("kda_mixer_ms") > 0.4 * step                  # ISSUE 49: over 40 % of the step
    assert read("kda_ms") + read("kda_proj_ms") < read("kda_mixer_ms")
    assert read("kda_mixer_ms") > 4 * read("mla_mixer_ms.kimi_linear")
    roof = roofline.reduce(run.load("layer_metrics", "kda_roofline"), ctx)
    assert 0.5 < roof < 10.0
    flash = roofline.reduce(run.load("layer_metrics", "flash_attn_roofline"), ctx)
    assert 10.0 < flash < 100.0
