"""Family ``keye_vl2`` (PR 46): the manifest's new entries **looked up by name**,
the configuration against the catalog's published keys, its counts, its rehearsal
cell with a broken path, and its per-layer metrics' patterns on the names the
program gives its scopes and kernels and on the names the chip printed.

``fixtures/tf_ops_keye_vl2/<cell>.json`` is a traced run of the cell on the chip
(``tools/dump_tf_ops.py``, PR 46's final tree, seed 2147600307): every distinct
framework name of chip 0 with its self time, and every HLO name stem."""

import json
import os
import re

import jax
import pytest

from benchmark import check, run, trace_reduce
from benchmark.families import gpt, keye_vl2 as family
from benchmark.reductions import family_counter, kernel_time, roofline, stat_time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "keye-vl-2.0-30b-a3b.train-s8k"
CONFIG = "keye-vl-2.0-30b-a3b"
TINY = "tiny-keye-vl2.train"
SOURCE = "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW_SCOPES = ("sparse_mixer_ms", "indexer_proj_ms", "indexer_select_ms", "moe_ms.keye_vl2",
              "moe_sort_ms.keye_vl2", "head_loss_ms.keye_vl2")
NEW_KERNELS = ("flash_sparse_ms", "flash_sparse_roofline", "index_select_ms",
               "index_select_roofline", "grouped_matmul_ms.keye_vl2")
NEW_COUNTERS = ("selected_pairs_per_step", "expert_rows_per_step.keye_vl2",
                "expert_load_max_over_mean.keye_vl2")
NEW = NEW_SCOPES + NEW_KERNELS + NEW_COUNTERS
APPENDED = ("flash_attn_ms", "flash_attn_roofline", "optimizer_ms.gpt", "forward_ms",
            "backward_ms", "unscale_ms", "layer_norm_ms", "unattributed_ms")
_REDUCTIONS = {"roofline": roofline, "kernel_time": kernel_time, "stat_time": stat_time}


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


def _fixture():
    with open(os.path.join(HERE, "fixtures", "tf_ops_keye_vl2", CELL + ".json")) as f:
        return json.load(f)


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
    assert len(NEW) == 14
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL], name
        assert per_layer[name]["moves"] == "tokens_per_s"
        assert run.load("layer_metrics", name)["family"] == "keye_vl2"
        assert set(per_layer[name]) == {"name", "unit", "better", "source", "layer", "moves",
                                        "workloads"}
        for key in ("unit", "better", "source", "layer"):
            assert per_layer[name][key] == run.load("layer_metrics", name)[key], (name, key)
    for name in APPENDED:
        assert per_layer[name]["workloads"].count(CELL) == 1, name
        assert per_layer[name]["workloads"][-1] == CELL, name       # appended, at the end
    for name, entry in per_layer.items():
        if name not in NEW + APPENDED and "workloads" in entry:
            assert CELL not in entry["workloads"], name
    assert not [e for e in m["end_to_end"] if "workloads" in e] and len(m["end_to_end"]) == 4
    assert all("why" not in e for e in m["per_layer"])
    # the new entries are the last of their lists
    assert m["configs"][-1]["name"] == CONFIG and m["workloads"][-1]["name"] == CELL
    assert [e["name"] for e in m["per_layer"][-len(NEW):]] == list(
        ("sparse_mixer_ms", "indexer_proj_ms", "indexer_select_ms", "flash_sparse_ms",
         "flash_sparse_roofline", "index_select_ms", "index_select_roofline",
         "selected_pairs_per_step", "moe_ms.keye_vl2", "moe_sort_ms.keye_vl2",
         "grouped_matmul_ms.keye_vl2", "expert_rows_per_step.keye_vl2",
         "expert_load_max_over_mean.keye_vl2", "head_loss_ms.keye_vl2"))
    layers = {per_layer[n]["layer"] for n in NEW}
    assert {"model (models/keye_vl2.py)", "mixture of experts (moe/dropless.py)",
            "kernels (ops/grouped_matmul.py)", "kernels (ops/attention.py)"} < layers
    indexer = layers - {"model (models/keye_vl2.py)", "mixture of experts (moe/dropless.py)",
                        "kernels (ops/grouped_matmul.py)", "kernels (ops/attention.py)"}
    (indexer,) = indexer                  # one layer text for the indexer's three metrics
    assert indexer.startswith("kernels (ops/indexer.py") and "vector units" in indexer
    rooflines = [n for n in NEW if "roofline" in n]
    assert rooflines == ["flash_sparse_roofline", "index_select_roofline"]
    for n in rooflines:
        assert per_layer[n]["unit"] == "%" and run.load("layer_metrics", n)["bound"] == "compute"


def test_the_cell_is_what_the_issue_named():
    cell = run.load("workloads", CELL)
    assert (cell["chips"], cell["layout"], cell["per_chip_batch"], cell["pool"]) == (1, "single", 1, 8)
    cfg = run.load("configs", CONFIG)
    assert cfg["seq_len"] == 8192 and cfg["remat_policy"] is None and cfg["family"] == "keye_vl2"
    assert family.GUARDED_OPS == ("flash_attention", "layer_norm", "grouped_matmul", "index_select")
    for said in ("512 rows", "1/8", "top-2,048", "text"):
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
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == sorted(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == {"num_hidden_layers": 4, "num_experts": 16,
                                            "vocab_size": 18992}
    width = re.compile(r"hidden_size|intermediate|latent|state_size|proj|_dim$|_rank$|expand|per_tok")
    assert not [k for k in cfg["reduced"] if width.search(k)]
    assert {k: cfg["published"][k] for k in REDUCED} == {k: published[k] for k in REDUCED}
    assert cfg["first_layer"] == 0 and cfg["num_experts_published"] == 128
    assert cfg["first_expert"] == 0 and cfg["vocab_size"] * 8 == 151936
    assert cfg["moe_rows_bound"] == 12288 == 1.5 * 8192 * 8 * 16 // 128      # 1.5 x the expected
    assert cfg["optimizer"]["lr"] == 1e-6 and cfg["tie_word_embeddings"] is False
    assert cfg["initializer_range"] == 0.02 and cfg["embedding_init_std"] == 1.0
    for key in ("layer", "qk_norm", "rotary", "indexer", "selection", "chunk_sizes", "attention",
                "router", "initializer_range", "weights", "optimizer", "loss", "seq_len",
                "num_experts_published", "first_expert", "first_layer", "moe_rows_bound",
                "remat_policy", "embedding_init_std"):
        assert key in cfg["assumed"], key
    for said in ("8 chips", "expert-parallel 8", "rank 0", "experts 0-15", "layers 0-3",
                 "ids 0-18,991", "no exchange", "nothing stands in"):
        assert said in cfg["deployment"], said
    assert len(cfg["departures"]) == 4 and cfg["source"] == SOURCE
    for n, said in enumerate(("vision tower", "balancing loss", "indexer loss", "depth 48 -> 4")):
        assert said in cfg["departures"][n], said
    mcfg = family.model_config(cfg)
    assert mcfg.mrope_section == (16, 24, 24) and mcfg.sa_config.topk == 2048
    assert (mcfg.sa_config.indexer_num_heads, mcfg.sa_config.indexer_head_dim) == (16, 64)


# -- counts -----------------------------------------------------------------------

def test_parameters_and_required_operations():
    """ISSUE 46's counts, by hand."""
    cfg = run.load("configs", CONFIG)
    D, V = 2048, 18992
    attention = 2 * D * 4096 + 2 * D * 512
    indexer = D * 1024 + D * 64 + D * 16
    assert (attention, indexer) == (18_874_368, 2_260_992)
    outside = attention + indexer + D * 128 + 2 * D + 2 * 128 + 2 * 64
    assert outside == 21_401_984                                    # ISSUE 46: 21.4 M
    layer = outside + 16 * 3 * D * 768
    assert layer == 96_899_456                                      # ISSUE 46: 96.9 M
    assert family.param_count(cfg) == 4 * layer + 2 * V * D + D == 465_391_104
    token = 4 * (attention + D * 128 + (8 * 16 / 128) * 3 * D * 768) + V * D
    assert token == 134_316_032.0
    sparse = 4 * 6 * 32 * 256 * 14_681_088 / 8192
    index = 4 * 2 * 16 * 64 * 8193 / 2
    assert family.sparse_attention_flops_per_item(cfg) == sparse == 352_346_112.0
    assert family.index_flops_per_item(cfg) == index == 33_558_528.0
    assert family.model_flops_per_item(cfg) == 6 * token + 2 * 4 * indexer + sparse + index \
        == 1_209_888_768.0
    # a step: 9.9 TFLOP, 50.3 ms at the bf16 peak; a layer's selected pairs 3.66 ms
    assert round(family.model_flops_per_item(cfg) * 8192 / 197e12 * 1e3, 1) == 50.3
    assert round(sparse / 4 * 8192 / 197e12 * 1e3, 2) == 3.66


@pytest.mark.parametrize("change,ratio", (
    ({"num_hidden_layers": 1}, 0.25), ({"num_attention_heads": 16}, 0.5),
    ({"sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "topk": 8192}},
     (8193 / 2) / (14_681_088 / 8192))))
def test_the_attention_count_follows_the_selection(change, ratio):
    """The SELECTED pairs count, whatever the kernels walk: with ``topk`` the
    sequence it is the causal count."""
    cfg = run.load("configs", CONFIG)
    base = family.sparse_attention_flops_per_item(cfg)
    assert family.sparse_attention_flops_per_item(dict(cfg, **change)) == pytest.approx(
        ratio * base, rel=1e-12)


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


@pytest.mark.parametrize("broken", ("no_selection", "smallest_scores", "no_qk_norm",
                                    "scores_at_128"))
def test_a_broken_path_fails_correct(monkeypatch, broken):
    """Four faults this family could have and the check must see: an attention
    that takes every causal key, a selection of the SMALLEST index scores, main
    heads without their QK norm, and scores scaled as if the heads were 128 wide
    and not 32."""
    from beforeholiday_tpu.models import layers
    from beforeholiday_tpu.ops import attention, indexer

    if broken == "no_selection":
        real = attention.flash_attention
        monkeypatch.setattr("beforeholiday_tpu.ops.flash_attention",
                            lambda q, k, v, selected=None, **kw: real(q, k, v, **kw))
    elif broken == "smallest_scores":
        real = indexer.index_select
        monkeypatch.setattr(indexer, "index_select",
                            lambda q, k, w, **kw: real(q, k, -w, **kw))
    elif broken == "no_qk_norm":
        real = layers.rms_norm
        monkeypatch.setattr(layers, "rms_norm", lambda x, w, eps: (
            x if x.ndim == 4 else real(x, w, eps)))
    else:
        real = attention.flash_attention
        monkeypatch.setattr("beforeholiday_tpu.ops.flash_attention",
                            lambda q, k, v, scale=None, **kw: real(q, k, v, scale=128 ** -0.5, **kw))
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
    rows = run.load("layer_metrics", "expert_rows_per_step.keye_vl2")
    load = run.load("layer_metrics", "expert_load_max_over_mean.keye_vl2")
    pairs = run.load("layer_metrics", "selected_pairs_per_step")
    seen = family.counters()
    assert seen["steps"] == 3
    assert family_counter.reduce(rows, {"family": family}) == pytest.approx(seen["expert_rows"] / 3)
    assert family_counter.reduce(load, {"family": family}) == seen["expert_load_max_over_mean"] >= 1.0
    # the newest step's count as it stands: 2 layers x 2 sequences x (16 * 17 / 2 + 32 * 16)
    assert family_counter.reduce(pairs, {"family": family}) == seen["selected_pairs"] == 2 * 2 * 648
    assert family_counter.reduce(pairs, {"family": gpt}) is None   # a family without counters


# -- the per-layer metrics on the program's names -------------------------------------

def test_each_new_metric_reads_its_own_names_and_no_others():
    """The patterns on the scope paths and kernel names the program gives (the
    kernels' ``name=`` is what the chip prints: ``%flash_attention_sparse_fwd.N``,
    ``%index_select.N``, ``%grouped_matmul_fwd.N``)."""
    fwd = "jit(step)/amp_forward/jvp(keye_vl2_layers)/sparse_mixer/"
    ops = [(fwd + "indexer_proj/dot_general", 100), (fwd + "indexer_proj/layer_norm/x", 10),
           (fwd + "indexer_select/index_select/pallas_call", 400),
           (fwd + "flash_attention/flash_attention_sparse_fwd/pallas_call", 700),
           (fwd + "dot_general", 50),
           ("jit(step)/amp_forward/jvp(keye_vl2_layers)/moe/moe_dispatch/gather", 30),
           ("jit(step)/amp_forward/jvp(keye_vl2_layers)/moe/moe_experts/grouped_matmul_fwd", 60),
           ("jit(step)/amp_forward/jvp(keye_vl2_head)/dot_general", 80),
           ("jit(step)/amp_forward/jvp(keye_vl2_head)/layer_norm/x", 5),
           ("jit(step)/amp_forward/jvp(keye_vl2_loss)/reduce", 20)]
    names = [("%flash_attention_sparse_fwd", 700), ("%flash_attention_sparse_dqkv_blocks", 1500),
             ("%index_select", 400), ("%grouped_matmul_fwd", 60), ("%fusion", 9)]
    cfg, cell = run.load("configs", CONFIG), run.load("workloads", CELL)
    ctx = {"trace": _trace(ops, names), "steps": 1, "family": family, "cfg": cfg, "cell": cell,
           "items_per_step": 8192, "peak": run.peak_of("TPU v5 lite")}
    read = {}
    for name in NEW_SCOPES + NEW_KERNELS:
        spec = run.load("layer_metrics", name)
        read[name] = _REDUCTIONS[spec["reduction"]].reduce(spec, ctx)
    ms = lambda ps: ps * 1e-9
    assert read["sparse_mixer_ms"] == pytest.approx(ms(100 + 10 + 400 + 700 + 50))
    assert read["indexer_proj_ms"] == pytest.approx(ms(110))
    assert read["indexer_select_ms"] == pytest.approx(ms(400))
    assert read["moe_ms.keye_vl2"] == pytest.approx(ms(90))
    assert read["moe_sort_ms.keye_vl2"] == pytest.approx(ms(30))
    assert read["head_loss_ms.keye_vl2"] == pytest.approx(ms(100))      # not the head's norm
    assert read["flash_sparse_ms"] == pytest.approx(ms(2200))
    assert read["index_select_ms"] == pytest.approx(ms(400))
    assert read["grouped_matmul_ms.keye_vl2"] == pytest.approx(ms(60))
    flash = run.load("layer_metrics", "flash_attn_roofline")            # the accepted share
    assert read["flash_sparse_roofline"] == pytest.approx(roofline.reduce(flash, ctx))
    want = 100 * (family.sparse_attention_flops_per_item(cfg) * 8192 / 197e12) / (2200e-12)
    assert read["flash_sparse_roofline"] == pytest.approx(want)
    want = 100 * (family.index_flops_per_item(cfg) * 8192 / 197e12) / (400e-12)
    assert read["index_select_roofline"] == pytest.approx(want)


def test_a_program_without_the_new_kernels_gives_the_new_metrics_nothing():
    """On the parent's program (no sparse kernel, no indexer, no such counter) a
    reader returns ``None`` and does not raise: the line leaves the metric out."""
    cfg, cell = run.load("configs", CONFIG), run.load("workloads", CELL)
    ctx = {"trace": _trace([("jit(step)/amp_forward/jvp(mellum_layers)/full_mixer/dot_general", 5)],
                           [("%flash_attention", 7)]),
           "steps": 1, "family": gpt, "cfg": cfg, "cell": cell, "items_per_step": 8192,
           "peak": run.peak_of("TPU v5 lite")}
    for name in NEW_SCOPES + NEW_KERNELS:
        spec = run.load("layer_metrics", name)
        if name.startswith(("moe", "grouped", "head_loss")):
            continue
        assert _REDUCTIONS[spec["reduction"]].reduce(spec, ctx) is None, name
    for name in NEW_COUNTERS:
        assert family_counter.reduce(run.load("layer_metrics", name), ctx) is None, name


# -- the per-layer metrics on the chip's names ------------------------------------

FIRST_LEVEL = ("forward_ms", "backward_ms", "unscale_ms", "grad_reduce_ms",
               "optimizer_ms.gpt", "unattributed_ms")


def test_the_recorded_names():
    fx = _fixture()
    assert fx["cell"] == CELL and fx["device_kind"] == "TPU v5 lite" and fx["steps"] == 16
    assert len(fx["ops"]) > 100 and len(fx["hlo_names"]) > 20
    assert sum(ps for _, ps in fx["ops"]) == sum(ps for _, ps in fx["hlo_names"])
    dispatch = {d["op"]: d for d in fx["dispatch"]}
    for op in family.GUARDED_OPS:         # each dispatched its kernels, none the jnp path
        assert dispatch[op]["pallas"] > 0 and dispatch[op]["jnp"] == 0, op
    assert dispatch["flash_attention"]["pallas"] == dispatch["index_select"]["pallas"] == 4
    tiles = {t["kernel"]: t for t in fx["tiles"] if t["op"] == "flash_attention"}
    # the selected plan: blocks of 1,024 in 4 strips, every causal tile walked and masked
    assert set(tiles) == {"fwd", "dqkv_blocks"}
    assert all(t["key"] == repr((8192, 8192, 128, True, False, "selected")) for t in tiles.values())
    assert all((t["total"], t["live"], t["masked"]) == (1024, 528, 528) for t in tiles.values())
    assert (tiles["fwd"]["steps"], tiles["fwd"]["copies"]) == (36, 36)
    stems = {n for n, _ in fx["hlo_names"]}
    assert {"%flash_attention_sparse_fwd", "%flash_attention_sparse_dqkv_blocks",
            "%index_select"} <= stems
    assert "%flash_attention" not in stems        # every flash kernel of this cell is a sparse one


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
    assert fx["busy_ps"] > 0.999 * fx["window_ps"]


def test_the_new_metrics_on_the_chips_names():
    """What the traced run of the final tree printed (PERF.md section 5), read
    again from the recorded names."""
    fx = _fixture()
    cfg, cell = run.load("configs", CONFIG), run.load("workloads", CELL)
    ctx = {"trace": _trace(fx["ops"], []), "steps": fx["steps"], "family": family, "cfg": cfg,
           "cell": cell, "items_per_step": 8192, "peak": run.peak_of(fx["device_kind"])}
    by_name = dict(ctx, trace=_trace([], fx["hlo_names"]))
    read = {}
    for name in NEW_SCOPES + NEW_KERNELS:
        spec = run.load("layer_metrics", name)
        read[name] = _REDUCTIONS[spec["reduction"]].reduce(
            spec, ctx if spec["reduction"] == "stat_time" else by_name)
    want = {"sparse_mixer_ms": 101.80, "indexer_proj_ms": 1.78, "indexer_select_ms": 14.39,
            "flash_sparse_ms": 52.51, "flash_sparse_roofline": 27.90, "index_select_ms": 14.03,
            "index_select_roofline": 9.94, "moe_ms.keye_vl2": 22.80, "moe_sort_ms.keye_vl2": 11.20,
            "grouped_matmul_ms.keye_vl2": 8.53, "head_loss_ms.keye_vl2": 10.97}
    assert {k: round(v, 2) for k, v in read.items()} == want
    # the parts lie inside the mixer, and the kernels inside their scopes
    assert read["indexer_proj_ms"] + read["indexer_select_ms"] + read["flash_sparse_ms"] \
        < read["sparse_mixer_ms"]
    assert read["index_select_ms"] <= read["indexer_select_ms"]
    assert read["grouped_matmul_ms.keye_vl2"] + read["moe_sort_ms.keye_vl2"] < read["moe_ms.keye_vl2"]
    assert all(0 < read[n] < 100 for n in ("flash_sparse_roofline", "index_select_roofline"))
    flash = run.load("layer_metrics", "flash_attn_roofline")
    assert roofline.reduce(flash, by_name) == pytest.approx(read["flash_sparse_roofline"])
