"""Family ``deepseek_v3`` (PR 42): the manifest's new entries **looked up by
name**, the configuration against the catalog's published keys, its counts, its
rehearsal cell with a broken path, and its per-layer metrics on the names the
chip printed.

``fixtures/tf_ops_deepseek_v3/<cell>.json`` is a traced run of the cell on the
chip (``tools/dump_tf_ops.py``, PR 42's program): every distinct framework name
of chip 0 with its self time, and every HLO name stem."""

import json
import os
import re

import jax
import pytest

from benchmark import check, run, trace_reduce
from benchmark.families import deepseek_v3 as family, gpt
from benchmark.reductions import family_counter, kernel_time, roofline, stat_time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "kanana-2-30b-a3b.train-s8k"
CONFIG = "kanana-2-30b-a3b"
TINY = "tiny-deepseek-v3.train"
SOURCE = "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
FIRST_LEVEL = ("forward_ms", "backward_ms", "unscale_ms", "grad_reduce_ms",
               "optimizer_ms.gpt", "unattributed_ms")
NEW_SCOPES = ("mla_mixer_ms.deepseek_v3", "mla_latent_ms.deepseek_v3", "mla_glue_ms.deepseek_v3",
              "dense_ffn_ms.deepseek_v3", "moe_ms.deepseek_v3", "moe_shared_ms.deepseek_v3",
              "moe_sort_ms.deepseek_v3", "head_loss_ms.deepseek_v3")
NEW_KERNELS = ("grouped_matmul_ms.deepseek_v3",)
NEW_COUNTERS = ("expert_rows_per_step.deepseek_v3", "expert_load_max_over_mean.deepseek_v3")
NEW = NEW_SCOPES + NEW_KERNELS + NEW_COUNTERS
APPENDED = ("flash_attn_ms", "flash_attn_roofline", "optimizer_ms.gpt", "forward_ms",
            "backward_ms", "unscale_ms", "layer_norm_ms", "unattributed_ms")
_REDUCTIONS = {"roofline": roofline, "kernel_time": kernel_time, "stat_time": stat_time}


def _fixture(cell=CELL, directory="tf_ops_deepseek_v3"):
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
    assert 0 < len(run.load("workloads", CELL)["why"]) <= 200
    assert [w["name"] for w in m["workloads"] if w["config"] == CONFIG] == [CELL]   # one cell
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    per_layer = _named(m["per_layer"])
    assert len(NEW) == 11
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "tokens_per_s"
        assert run.load("layer_metrics", name)["family"] == "deepseek_v3"
        assert set(per_layer[name]) == {"name", "unit", "better", "source", "layer", "moves",
                                        "workloads"}
    for name in APPENDED:
        assert per_layer[name]["workloads"].count(CELL) == 1, name
    for name, entry in per_layer.items():
        if name not in NEW + APPENDED and "workloads" in entry:
            assert CELL not in entry["workloads"], name
    assert not [e for e in m["end_to_end"] if "workloads" in e] and len(m["end_to_end"]) == 4
    assert all("why" not in e for e in m["per_layer"])
    layers = {per_layer[n]["layer"] for n in NEW}
    assert layers == {"model (models/deepseek_v3.py)", "mixture of experts (moe/dropless.py)",
                      "kernels (ops/grouped_matmul.py)"}
    # no second roofline: the flash kernels' share in this cell is flash_attn_roofline itself
    assert not [n for n in per_layer if "roofline" in n and "deepseek" in n]


def test_the_cell_is_what_the_issue_named():
    cell = run.load("workloads", CELL)
    assert (cell["chips"], cell["layout"], cell["per_chip_batch"], cell["pool"]) == (1, "single", 1, 8)
    cfg = run.load("configs", CONFIG)
    assert cfg["seq_len"] == 8192 and cfg["remat_policy"] is None and cfg["family"] == "deepseek_v3"
    assert family.GUARDED_OPS == ("flash_attention", "layer_norm", "grouped_matmul")
    for said in ("384 rows", "1/8", "3,072"):
        assert said in cell["why"], said
    for key in ("read_by", "loss_gap", "first_grad_norm_gap", "update_norm_gap", "the control fails"):
        assert key in cell["limits_from"], key
    assert set(cell["limits"]) == {"loss_gap", "first_grad_norm_gap", "update_norm_gap"}
    assert all(0 < v < 0.1 for v in cell["limits"].values())


def test_the_configuration_holds_every_published_key():
    """Every key of the catalog's ``config`` for this model, as published, but
    for the three that ``reduced`` lists. No width is among them."""
    cfg = run.load("configs", CONFIG)
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 1000000, "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
        "v_head_dim": 128, "vocab_size": 128256}
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == sorted(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == {"num_hidden_layers": 5, "n_routed_experts": 16,
                                            "vocab_size": 16032}
    width = re.compile(r"hidden_size|intermediate|latent|state_size|proj|_dim$|_rank$|expand|per_tok")
    assert not [k for k in cfg["reduced"] if width.search(k)]
    assert {k: cfg["published"][k] for k in REDUCED} == {k: published[k] for k in REDUCED}
    assert cfg["first_layer"] == 0 and cfg["n_routed_experts_published"] == 128
    assert cfg["first_expert"] == 0 and cfg["vocab_size"] * 8 == 128256
    assert cfg["moe_rows_bound"] == 9216 == 1.5 * 8192 * 6 * 16 // 128       # 1.5 x the expected
    assert cfg["optimizer"]["lr"] == 1e-6 and cfg["tie_word_embeddings"] is False
    assert cfg["initializer_range"] == 0.02 and cfg["embedding_init_std"] == 1.0
    assert family.reference.held(cfg) == ["dense", "moe", "moe", "moe", "moe"]
    for key in ("layer", "attention", "router", "weights", "embedding", "keep_fp32", "optimizer",
                "loss", "seq_len", "first_layer", "moe_rows_bound", "remat_policy", "parameters"):
        assert key in cfg["assumed"], key
    for said in ("8 chips", "expert-parallel 8", "rank 0", "experts 0-15", "layers 0-4",
                 "ids 0-16,031", "no exchange", "nothing stands in"):
        assert said in cfg["deployment"], said
    assert len(cfg["departures"]) == 4 and cfg["source"] == SOURCE


# -- counts -----------------------------------------------------------------------

def test_parameters_and_required_operations():
    """ISSUE 42's counts, by hand."""
    cfg = run.load("configs", CONFIG)
    D, V = 2048, 16032
    mixer = D * 32 * 192 + D * 576 + 512 + 512 * 32 * 256 + 32 * 128 * D   # q, kva, its norm, kvb, o
    assert mixer == 26_345_984
    dense = mixer + 2 * D + 3 * D * 6144
    moe = mixer + 2 * D + D * 128 + 128 + 3 * D * 1536 + 16 * 3 * D * 768
    assert (dense, moe) == (64_098_816, 111_547_008)
    assert family.param_count(cfg) == dense + 4 * moe + 2 * V * D + D == 575_955_968
    assert round(16 * family.param_count(cfg) / 1e9, 2) == 9.22       # 16 B a parameter
    token = 5 * (mixer - 512) + 3 * D * 6144 \
        + 4 * (D * 128 + 3 * D * 1536 + (6 * 16 / 128) * 3 * D * 768) + V * D
    assert token == 255_262_720.0
    attention = 5 * 6 * 32 * (192 + 128) * (8192 + 1) / 2
    assert family.attention_flops_per_item(cfg) == attention == 1_258_444_800.0
    assert family.model_flops_per_item(cfg) == 6 * token + attention == 2_790_021_120.0
    # one layer's attention: 251.7 MFLOP a token, 2.06 TFLOP a step, 10.5 ms at the peak
    assert attention / 5 == 251_688_960.0
    assert round(attention / 5 * 8192 / 197e12 * 1e3, 1) == 10.5


@pytest.mark.parametrize("change,ratio", (({"seq_len": 16385}, 2.0), ({"num_hidden_layers": 1}, 0.2),
                                          ({"qk_rope_head_dim": 0, "v_head_dim": 192}, 1.0),
                                          ({"num_attention_heads": 16}, 0.5)))
def test_the_attention_count_follows_its_shapes(change, ratio):
    """Both widths count: a key costs ``qk_head_dim + v_head_dim`` a head."""
    cfg = run.load("configs", CONFIG)
    base = family.attention_flops_per_item(cfg)
    assert family.attention_flops_per_item(dict(cfg, **change)) == ratio * base


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


@pytest.mark.parametrize("broken", ("no_latent_norm", "k_rot_per_head", "gated_shared", "scale_one",
                                    "scores_at_128"))
def test_a_broken_path_fails_correct(monkeypatch, broken):
    """Five faults this family could have and the check must see: a latent that
    skips its own norm, a rotary key that is not the one all heads share (each
    head's scaled by its index), a shared expert gated as Qwen's, a router
    without its 2.448, and scores scaled as if queries were 32 wide and not 48.
    (The rotary embedding's layout moves nothing a limit can see on weights of
    0.02, where attention is nearly uniform: ``tests/test_deepseek_v3.py`` holds
    it to the published order on weights of 0.1.)"""
    from beforeholiday_tpu.models import deepseek_v3 as model
    from beforeholiday_tpu.moe import dropless
    from beforeholiday_tpu.ops import attention

    if broken == "no_latent_norm":
        real = model.rms_norm
        monkeypatch.setattr(model, "rms_norm", lambda x, w, eps: (
            x if w.shape[0] == 64 and x.shape[-1] == 64 else real(x, w, eps)))
    elif broken == "k_rot_per_head":
        real = attention.flash_attention

        def shifted(q, k, v, **kw):
            dr = 16
            k = k.at[..., -dr:].set(k[..., -dr:] * (1 + jax.numpy.arange(k.shape[1])[:, None, None]))
            return real(q, k, v, **kw)

        monkeypatch.setattr("beforeholiday_tpu.ops.flash_attention", shifted)
    elif broken == "gated_shared":
        real = dropless.dropless_moe
        monkeypatch.setattr(dropless, "dropless_moe", lambda x, p, **kw: real(
            x, dict(p, shared_score=jax.numpy.zeros((x.shape[1], 1), x.dtype)), **kw))
    elif broken == "scale_one":
        real = dropless.route_sigmoid
        monkeypatch.setattr(dropless, "route_sigmoid",
                            lambda *a, scale=1.0, **kw: real(*a, scale=1.0, **kw))
    else:
        real = attention.flash_attention
        monkeypatch.setattr("beforeholiday_tpu.ops.flash_attention",
                            lambda q, k, v, scale=None, **kw: real(q, k, v, scale=32 ** -0.5, **kw))
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
    rows = run.load("layer_metrics", "expert_rows_per_step.deepseek_v3")
    load = run.load("layer_metrics", "expert_load_max_over_mean.deepseek_v3")
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
    assert dispatch["flash_attention"]["pallas"] == 5
    tiles = {t["kernel"]: t for t in fx["tiles"]
             if t["op"] == "flash_attention" and t["key"] == repr((8192, 8192, (192, 128), True, False))}
    # both widths in the key; blocks of 1,024 in 4 strips: 32 x 32 tiles, 528 live, 32 masked
    assert set(tiles) == {"fwd", "dq", "dkv"}
    assert all((t["total"], t["live"], t["masked"]) == (1024, 528, 32) for t in tiles.values())


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
                                            ("tf_ops_mellum", "mellum2-12b-a2.5b.train-s8k"),
                                            ("tf_ops_nemotron_h",
                                             "nemotron-3-super-120b-a12b.train-s8k"),
                                            ("tf_ops_lfm2_moe", "lfm2-8b-a1b.train-s8k")))
@pytest.mark.parametrize("metric", NEW_SCOPES + NEW_KERNELS)
def test_new_metrics_find_nothing_in_the_other_cells(metric, cell, directory):
    """The parent's programs (no ``mla_mixer``, no ``deepseek_v3_*`` scope): the
    readers return nothing and do not raise — but for those whose scopes or
    kernels another family's program opens too (``dense_ffn``, ``/moe/``,
    ``moe_shared``, the grouped kernels), which the harness never asks there
    (``"family": "deepseek_v3"``)."""
    fx = _fixture(cell, directory)
    spec = run.load("layer_metrics", metric)
    assert spec["family"] == "deepseek_v3"
    ctx = dict(_context(_fixture()), trace=_trace(fx["ops"], fx.get("hlo_names", ())),
               steps=fx["steps"])
    moe_cells = ("qwen3", "mellum", "nemotron", "lfm2")
    shared = {"dense_ffn_ms.deepseek_v3": ("lfm2",), "moe_ms.deepseek_v3": moe_cells,
              "moe_shared_ms.deepseek_v3": ("qwen3", "nemotron"),
              "moe_sort_ms.deepseek_v3": moe_cells, "grouped_matmul_ms.deepseek_v3": moe_cells}
    expected = any(word in cell for word in shared.get(metric, ()))
    got = _REDUCTIONS[spec["reduction"]].reduce(spec, ctx)
    if metric == "moe_sort_ms.deepseek_v3" and expected and got is None:
        pytest.skip("a names fixture older than PR 34's loops: no row mover under the spans")
    assert (got is not None) == expected, (metric, cell)


def test_second_level_metrics_nest_as_the_model_does():
    fx = _fixture()
    parts = ("mla_mixer_ms.deepseek_v3", "dense_ffn_ms.deepseek_v3", "moe_ms.deepseek_v3")
    p = {m: re.compile(run.load("layer_metrics", m)["pattern"])
         for m in NEW_SCOPES + ("layer_norm_ms", "forward_ms", "backward_ms")}
    total = dict.fromkeys(NEW_SCOPES, 0)
    for tf_op, ps in fx["ops"]:
        kinds = [m for m in parts if p[m].search(tf_op)]
        assert len(kinds) <= 1, tf_op                        # a part is one of the three
        for m in NEW_SCOPES:
            total[m] += ps if p[m].search(tf_op) else 0
        for inner in ("mla_latent_ms.deepseek_v3", "mla_glue_ms.deepseek_v3"):
            if p[inner].search(tf_op):
                assert kinds == ["mla_mixer_ms.deepseek_v3"], tf_op
        for inner in ("moe_sort_ms.deepseek_v3", "moe_shared_ms.deepseek_v3"):
            if p[inner].search(tf_op):
                assert kinds == ["moe_ms.deepseek_v3"], tf_op
        if "flash_attention" in tf_op:
            assert kinds == ["mla_mixer_ms.deepseek_v3"], tf_op
            assert not p["mla_glue_ms.deepseek_v3"].search(tf_op), tf_op
            assert not p["mla_latent_ms.deepseek_v3"].search(tf_op), tf_op
        if p["mla_glue_ms.deepseek_v3"].search(tf_op):
            assert "dot_general" not in tf_op, tf_op
        if p["head_loss_ms.deepseek_v3"].search(tf_op):
            assert not kinds, tf_op
    assert 0 < total["mla_latent_ms.deepseek_v3"] < total["mla_mixer_ms.deepseek_v3"]
    assert 0 < total["mla_glue_ms.deepseek_v3"] < total["mla_mixer_ms.deepseek_v3"]
    assert 0 < total["moe_sort_ms.deepseek_v3"] < total["moe_ms.deepseek_v3"]
    assert 0 < total["moe_shared_ms.deepseek_v3"] < total["moe_ms.deepseek_v3"]
    # the new mechanism does most of the work: the five mixers are over half the step
    assert total["mla_mixer_ms.deepseek_v3"] > 0.5 * fx["busy_ps"]
    assert total["mla_mixer_ms.deepseek_v3"] > 4 * total["moe_ms.deepseek_v3"]


def test_kernel_patterns_match_the_kernels_alone():
    names = dict(_fixture()["hlo_names"])
    grouped = {"%grouped_matmul_fwd", "%grouped_matmul_dlhs", "%grouped_matmul_drhs"}
    for metric, kernels in (("flash_attn_ms", {"%flash_attention"}),
                            ("flash_attn_roofline", {"%flash_attention"}),
                            ("grouped_matmul_ms.deepseek_v3", grouped)):
        pattern = re.compile(run.load("layer_metrics", metric)["pattern"])
        assert {n for n in names if pattern.search(n)} == kernels, metric


def test_the_flash_roofline_counts_both_widths_and_stays_under_its_roof():
    """``flash_attn_roofline`` in this cell reads the two-width kernels (their
    ``name=`` prefix is the op's) against ``6 * heads * (192 + 128)`` a kept key."""
    fx = _fixture()
    ctx = _context(fx, ops=False)
    flash = roofline.reduce(run.load("layer_metrics", "flash_attn_roofline"), ctx)
    ms = dict(fx["hlo_names"])["%flash_attention"] * 1e-9 / fx["steps"]
    assert flash == pytest.approx(100.0 * 1_258_444_800 * 8192 / 197e12 / (ms * 1e-3))
    assert 10.0 < flash < 100.0
    assert kernel_time.reduce(run.load("layer_metrics", "grouped_matmul_ms.deepseek_v3"), ctx) > 1.0
