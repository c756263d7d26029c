"""``models/keye_vl2.py`` against the benchmark's plain float32 reference
(``benchmark/reference/keye_vl2.py``: index scores and masked softmax by blocks of
queries, the selection by ``lax.top_k``, every held expert on every token), the
three-row rotary table, the indexer's leaves, and the share test of its expert
layer.

Seeded random weights at a small size, float32 at ``highest`` matmul precision.
The program computes the same mathematics in another order (the selection as a
mask, flash attention by blocks, rows sorted by expert), so the tolerances are
those of float32 reassociation through the layers, as ``tests/test_mellum.py``'s:
2e-6 relative on the loss, 1e-3 of each gradient tensor's largest entry. The
sequence (48) is three times ``topk`` (16), so the selection cuts in every layer
for two thirds of the queries."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from beforeholiday_tpu.models import keye_vl2 as model, layers  # noqa: E402
from beforeholiday_tpu.moe import dropless  # noqa: E402
from beforeholiday_tpu.ops.indexer import selected_pairs  # noqa: E402
from benchmark.families import keye_vl2 as family  # noqa: E402
from benchmark.reference import keye_vl2 as reference  # noqa: E402

CFG = {
    "num_hidden_layers": 3, "first_layer": 0, "hidden_size": 64, "vocab_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [4, 6, 6], "rope_type": "default", "type": "default"},
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 16},
    "num_experts": 4, "num_experts_published": 16, "first_expert": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "norm_topk_prob": True, "moe_rows_bound": None,
    "rms_norm_eps": 1e-06, "initializer_range": 0.02, "embedding_init_std": 1.0, "seq_len": 48,
    "compute_dtype": "float32", "remat_policy": None,
}
INDEXER = model.INDEXER_LEAVES


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(cfg, seed=0):
    """Seeded weights with every norm weight (and the LayerNorm's bias) off its
    identity, and matmul weights large enough (0.1) that attention and the index
    scores are far from uniform."""
    flat = family.weights(cfg, jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))

    def perturb(name, v, key):
        if "norm" in name:
            return v + 0.1 * jax.random.normal(key, v.shape)
        return v if name == "embed" else 5.0 * v

    return {k: perturb(k, v, kk) for (k, v), kk in zip(sorted(flat.items()), keys)}


def _batch(cfg, seed=3, rows=2):
    return family.batch(cfg, rows, jax.random.PRNGKey(seed))


def _mcfg(cfg, **overrides):
    mcfg = family.model_config(cfg)
    return mcfg.__class__(**{**mcfg.__dict__, **overrides})


def _program_loss(flat, batch, cfg, **overrides):
    return model.loss_fn(family._to_tree(flat), *batch, _mcfg(cfg, **overrides))[0]


def _with_topk(cfg, topk):
    return dict(cfg, sa_config=dict(cfg["sa_config"], topk=topk))


@pytest.mark.parametrize("overrides", (
    {}, {"first_expert": 0, "num_experts": 16}, {"num_hidden_layers": 1},
    {"remat_policy": "full"}, {"topk": 48}, {"topk": 1},
), ids=("share", "all-experts", "one-layer", "remat", "topk-is-the-sequence", "one-key"))
def test_loss_matches_the_reference(overrides):
    overrides = dict(overrides)
    cfg = _with_topk(CFG, overrides.pop("topk", CFG["sa_config"]["topk"]))
    cfg = dict(cfg, **overrides)
    w, batch = _weights(cfg), _batch(cfg)
    got = float(jax.jit(lambda w: _program_loss(w, batch, cfg))(w))
    want = float(jax.jit(lambda w: reference.loss(w, batch, cfg))(w))
    assert abs(got - want) <= 2e-6 * abs(want), (got, want)


def test_the_selection_changes_the_loss_and_the_indexer_decides_it():
    """What the comparison above would miss if both sides dropped it alike: the
    selection cuts (a ``topk`` of the whole sequence is another loss), and WHICH
    keys are kept is the indexer's doing (another ``w_wi`` is another loss, though
    no gradient says so)."""
    w, batch = _weights(CFG), _batch(CFG)
    base = float(reference.loss(w, batch, CFG))
    assert abs(float(reference.loss(w, batch, _with_topk(CFG, 48))) - base) > 2e-5 * base
    other = dict(w, **{k: -v for k, v in w.items() if k.endswith("/w_wi")})
    assert abs(float(reference.loss(other, batch, CFG)) - base) > 1e-5 * base
    assert abs(float(_program_loss(other, batch, CFG)) - float(reference.loss(other, batch, CFG))) \
        <= 2e-6 * base


_GRADS = {}
_SHAPES = model.param_shapes(model.KeyeVL2Config())
_LEAVES = sorted(k for k in _SHAPES if k != "layers") + sorted(
    f"layers.{i}/{name}" for i in range(CFG["num_hidden_layers"]) for name in _SHAPES["layers"][0])


def _grads():
    if not _GRADS:
        w, batch = _weights(CFG), _batch(CFG)
        _GRADS["got"] = jax.jit(jax.grad(lambda w: _program_loss(w, batch, CFG)))(w)
        _GRADS["want"] = jax.jit(jax.grad(lambda w: reference.loss(w, batch, CFG)))(w)
    return _GRADS


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_matches_the_reference(leaf):
    got, want = _grads()["got"][leaf], _grads()["want"][leaf]
    scale = float(jnp.max(jnp.abs(want)))
    if leaf.split("/")[-1] in INDEXER:          # the indexer is held: exactly zero, both sides
        assert scale == 0.0 and float(jnp.max(jnp.abs(got))) == 0.0, leaf
        return
    assert scale > 0, f"{leaf}: the reference's gradient is all zero"
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-3 * scale, leaf


def test_an_fp8_product_would_fail_the_tolerances():
    w, batch = _weights(CFG), _batch(CFG)
    sound = float(reference.loss(w, batch, CFG))
    control = float(reference.loss(w, batch, CFG, mode="fp8"))
    assert abs(control - sound) > 50 * 2e-6 * abs(sound)


@pytest.mark.parametrize("impl", ("pallas", "jnp"))
def test_the_mixer_through_the_kernels(impl):
    """One mixer at a length the kernels tile (S 256, topk 40: the indexer's
    kernel and the selected-keys flash kernels in the interpreter), GQA by
    repetition, QK-norm and both rotary tables, against the reference's
    materialised selection; the kept pairs are counted."""
    cfg = _with_topk(dict(CFG, seq_len=256), 40)
    w = _weights(cfg, seed=5)
    p = reference._group(w, "layers.1")
    u = jax.random.normal(jax.random.PRNGKey(6), (2, 256, cfg["hidden_size"]))
    mcfg = _mcfg(cfg, attention_impl=impl)
    got, pairs = jax.jit(lambda u, p: model.attention(
        mcfg, u, p, model.rotary_tables(mcfg, 256)))(u, p)
    want, pairs_ref = jax.jit(lambda u, p: reference.attention(
        u, p, cfg, reference.text_positions(256), "float32"))(u, p)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert int(pairs) == int(pairs_ref) == 2 * selected_pairs(256, 40)


# -- the three-row rotary table -------------------------------------------------------

def _positions(S, equal):
    t = jnp.arange(S)
    return jnp.stack([t, t, t] if equal else [t, (t * 7) % 13, (t * 5) % 11])


@pytest.mark.parametrize("equal", (True, False), ids=("text", "unequal-rows"))
def test_the_three_row_table_against_the_reference(equal):
    """``layers.mrope_table`` + ``apply_rotary`` against the reference's ``rope``
    with the three rows unequal (an image token's) and equal (text), where it is
    the plain table's rotation."""
    S, hd, sections, theta = 24, 32, (4, 6, 6), 1e7
    x = jax.random.normal(jax.random.PRNGKey(0), (2, S, 3, hd))
    pos = _positions(S, equal)
    got = layers.apply_rotary(x, *layers.mrope_table(pos, hd, theta, sections))
    np.testing.assert_allclose(got, reference.rope(x, pos, theta, sections), rtol=1e-5, atol=1e-5)
    plain = layers.apply_rotary(x, *layers.rotary_table(S, hd, theta))
    assert bool(jnp.array_equal(got, plain)) == equal


def test_a_frequency_pair_reads_its_own_row():
    """Pairs 0-3 follow the temporal row, 4-9 the height, 10-15 the width."""
    S, hd, sections = 8, 32, (4, 6, 6)
    base = jnp.zeros((3, S), jnp.int32)
    for row, pairs in enumerate((range(0, 4), range(4, 10), range(10, 16))):
        cos, _ = layers.mrope_table(base.at[row].set(jnp.arange(S) + 1), hd, 1e4, sections)
        moved = {i for i in range(16) if not bool(jnp.all(cos[:, i] == 1.0))}
        assert moved == set(pairs), (row, moved)
    with pytest.raises(ValueError):
        layers.mrope_table(base, hd, 1e4, (4, 6, 5))


def test_positions_reach_the_forward_pass():
    """Unequal rows through the whole model: program and reference agree, and
    differ from text's."""
    cfg = dict(CFG, num_hidden_layers=1)
    w, (tokens, _) = _weights(cfg), _batch(cfg)
    pos = _positions(48, equal=False)
    mcfg = family.model_config(cfg)
    got, _ = model.forward(family._to_tree(w), tokens, mcfg, positions=pos)
    want = reference.logits(w, tokens, cfg, positions=pos)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    text, _ = model.forward(family._to_tree(w), tokens, mcfg)
    assert float(jnp.max(jnp.abs(text - got))) > 1e-3


# -- the expert layer's share -------------------------------------------------------

@pytest.mark.parametrize("published,shares", ((128, 8), (16, 4), (16, 2)))
def test_the_shares_add_up_to_the_uncut_reference_layer(published, shares):
    """The cell's deployment (128 experts over 8 chips, ``first_expert`` 0, 16,
    ..): every share runs the whole mixer — indexer, selection, attention, which
    every chip computes alike and which counts ONCE — then routes over all the
    experts and computes its own; with no shared expert the parts add up to the
    whole layer, as the plain reference gives it with every expert held."""
    cfg = dict(CFG, num_experts_published=published, num_experts=published, first_expert=0,
               num_hidden_layers=1, num_experts_per_tok=8)
    w = _weights(cfg, seed=published + shares)
    p = reference._group(w, "layers.0")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, cfg["hidden_size"]))
    whole, _ = reference.layer(x, p, cfg, reference.text_positions(48), "float32")
    held = published // shares
    mixed = None
    total = jnp.zeros_like(x)
    for rank in range(shares):
        mine = dict(p, **{k: p[k][rank * held:(rank + 1) * held]
                          for k in ("w_gate", "w_up", "w_down")})
        mcfg = _mcfg(cfg, num_experts=held, first_expert=rank * held)
        y, counters = model._layer(mcfg, x, mine, model.rotary_tables(mcfg, 48))
        # what every chip computes alike: the stream after the mixer
        u = layers.rms_norm(x, mine["input_norm"], mcfg.rms_norm_eps)
        after = x + model.attention(mcfg, u, mine, model.rotary_tables(mcfg, 48))[0]
        if mixed is None:
            mixed = after
        assert bool(jnp.array_equal(after, mixed))
        total = total + (y - after)                       # this share's experts' part
        assert float(counters["selected_pairs"]) == 2 * selected_pairs(48, 16)
    got = mixed + total
    assert float(jnp.max(jnp.abs(got - whole))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))


# -- plumbing ---------------------------------------------------------------------

def test_the_family_round_trips_the_tree_and_counts():
    flat = family.weights(CFG, jax.random.PRNGKey(0))
    back = family._to_flat(family._to_tree(flat))
    assert set(back) == set(flat) and all(bool(jnp.array_equal(back[k], flat[k])) for k in flat)
    assert family.param_count(CFG) == model.param_count(family.model_config(CFG)) == \
        sum(v.size for v in flat.values())
    assert 0.9 < float(jnp.std(flat["embed"])) < 1.1
    assert 0.015 < float(jnp.std(flat["head"])) < 0.025
    assert float(jnp.std(flat["layers.2/w_qi"])) < 0.025
    assert bool(jnp.all(flat["layers.0/indexer_k_norm"] == 1.0))
    assert bool(jnp.all(flat["layers.0/indexer_k_norm_bias"] == 0.0))
    # the program's own init draws the same shapes
    mine = family._to_flat(model.init(jax.random.PRNGKey(0), family.model_config(CFG)))
    assert {k: v.shape for k, v in mine.items()} == {k: v.shape for k, v in flat.items()}


def test_one_shared_index_key_only():
    with pytest.raises(ValueError):
        model.SparseAttentionConfig(indexer_num_kv_heads=2)


def test_keep_fp32_mask():
    tree = family._to_tree(family.weights(CFG, jax.random.PRNGKey(0)))
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    name = lambda path: str(getattr(path[-1], "key", path[-1]))
    kept = {name(path) for path, _ in flat if model.keep_fp32(path)}
    assert kept == {"norm", "input_norm", "post_norm", "q_norm", "k_norm", "indexer_k_norm",
                    "indexer_k_norm_bias"}


def test_counters_and_scopes_of_the_step():
    """The step as the benchmark's family wires it (amp O5 + FusedAdam under
    donate_step): counters come out as device scalars, the indexer's leaves do
    not move, and every scope the per-layer metrics read is in the program."""
    from benchmark import run

    cell = run.load("workloads", "tiny-keye-vl2.train")
    cfg = run.load("configs", cell["config"])
    c = run.Cell(cell, cfg, jax.devices()[:1])
    c.start(11)
    c.build()
    before = {k: np.asarray(v) for k, v in c.program.masters(c.state).items()}
    c.run_step(0)
    c.run_step(1)
    seen = family.counters()
    assert seen["steps"] == 2 and seen["dropped_rows"] == 0
    tokens = cell["per_chip_batch"] * 48
    assert 0 < seen["expert_rows"] <= 2 * 2 * tokens * 4     # steps, layers, top-k
    assert seen["expert_load_max_over_mean"] >= 1.0
    # the newest step's count, not a sum: layers x sequences x pairs
    assert seen["selected_pairs"] == 2 * cell["per_chip_batch"] * selected_pairs(48, 16)
    after = c.program.masters(c.state)
    for k, v in before.items():
        moved = not np.array_equal(v, np.asarray(after[k]))
        assert moved == (k.split("/")[-1] not in INDEXER), k
    hlo = c.program.step.jitted.lower(c.state, c.pool[0]).compile().as_text()
    for scope in ("amp_forward", "amp_backward", "amp_unscale", "fused_adam_step_flat",
                  "keye_vl2_embed", "keye_vl2_layers", "keye_vl2_head", "keye_vl2_loss",
                  "sparse_mixer", "indexer_proj", "indexer_select", "index_select",
                  "flash_attention", "layer_norm",
                  "moe/moe_route", "moe/moe_dispatch", "moe/moe_experts", "moe/moe_combine"):
        assert scope in hlo, scope
    assert "moe_shared" not in hlo


def test_required_operations_at_the_published_widths():
    from benchmark import run

    cfg = run.load("configs", "keye-vl-2.0-30b-a3b")
    attention, indexer, router = 18_874_368, 2_260_992, 262_144
    norms = 2 * 2048 + 2 * 128 + 2 * 64
    experts = 16 * 4_718_592
    assert family.param_count(cfg) == 4 * (attention + indexer + router + norms + experts) \
        + 2 * 18992 * 2048 + 2048 == 465_391_104             # ISSUE 46: 465 M, 7.4 GB at 16 B
    assert round(16 * family.param_count(cfg) / 1e9, 2) == 7.45
    per = family.matmul_params_per_token(cfg)
    assert per == {"attention": attention, "indexer": indexer, "moe": router + 4_718_592,
                   "head": 18992 * 2048}
    assert family.selected_pairs_per_item(cfg) * 8192 == 14_681_088 == selected_pairs(8192, 2048)
    assert 14_681_088 / (8192 * 8193 // 2) == pytest.approx(0.437, abs=1e-3)
    sparse = 6 * 32 * 256 * 14_681_088 / 8192 * 4
    assert family.sparse_attention_flops_per_item(cfg) == sparse \
        == family.attention_flops_per_item(cfg)
    index = 2 * 16 * 64 * 4096.5 * 4
    assert family.index_flops_per_item(cfg) == index
    total = family.model_flops_per_item(cfg)
    assert total == 6 * (4 * (attention + router + 4_718_592) + 18992 * 2048) \
        + 2 * 4 * indexer + sparse + index
    assert 1.20e9 < total < 1.22e9                          # ISSUE 46: about 1.2 G a token
