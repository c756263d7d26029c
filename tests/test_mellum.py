"""``models/mellum.py`` against the benchmark's plain float32 reference
(``benchmark/reference/mellum.py``: attention by materialised masks, every held
expert on every token), the YaRN table against values worked by hand, and the
share test of its expert layer.

Seeded random weights at a small size, float32 at ``highest`` matmul precision.
The program computes the same mathematics in another order (flash attention by
blocks over the band, rows sorted by expert), so the tolerances are those of
float32 reassociation through four layers, as ``tests/test_qwen3_next.py``'s:
2e-6 relative on the loss, 1e-3 of each gradient tensor's largest entry. The
sequence (48) is three windows (16) long, so the window cuts in every sliding
layer, and the period has both kinds."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from beforeholiday_tpu.models import layers, mellum as model  # noqa: E402
from beforeholiday_tpu.moe import dropless  # noqa: E402
from benchmark.families import mellum as family  # noqa: E402
from benchmark.reference import mellum as reference  # noqa: E402

SLIDING, FULL = "sliding_attention", "full_attention"
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 32, "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2772588722239782}
CFG = {
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 2, "num_hidden_layers": 4,
    "hidden_size": 64, "vocab_size": 96, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "sliding_window": 16,
    "rope_parameters": {FULL: YARN, SLIDING: {"rope_type": "default", "rope_theta": 500000}},
    "num_experts": 4, "num_experts_published": 16, "first_expert": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "norm_topk_prob": True, "moe_rows_bound": None,
    "rms_norm_eps": 1e-06, "initializer_range": 0.02, "embedding_init_std": 1.0, "seq_len": 48,
    "compute_dtype": "float32", "remat_policy": None,
}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(cfg, seed=0):
    """Seeded weights with every norm weight off its identity, and matmul
    weights large enough (0.1) that attention is far from uniform."""
    flat = family.weights(cfg, jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))

    def perturb(name, v, key):
        if "norm" in name:
            return v + 0.1 * jax.random.normal(key, v.shape)
        return v if name == "embed" else 5.0 * v

    return {k: perturb(k, v, kk) for (k, v), kk in zip(sorted(flat.items()), keys)}


def _batch(cfg, seed=3, rows=2):
    return family.batch(cfg, rows, jax.random.PRNGKey(seed))


def _program_loss(flat, batch, cfg, **overrides):
    mcfg = family.model_config(cfg)
    mcfg = mcfg.__class__(**{**mcfg.__dict__, **overrides})
    return model.loss_fn(family._to_tree(flat), *batch, mcfg)[0]


@pytest.mark.parametrize("overrides", (
    {}, {"first_expert": 0, "num_experts": 16}, {"num_hidden_layers": 8},
    {"remat_policy": "full"}, {"sliding_window": 48}, {"sliding_window": 1},
), ids=("share", "all-experts", "two-periods", "remat", "window-is-the-sequence", "own-key"))
def test_loss_matches_the_reference(overrides):
    cfg = dict(CFG, **overrides)
    w, batch = _weights(cfg), _batch(cfg)
    got = float(jax.jit(lambda w: _program_loss(w, batch, cfg))(w))
    want = float(jax.jit(lambda w: reference.loss(w, batch, cfg))(w))
    assert abs(got - want) <= 2e-6 * abs(want), (got, want)


def test_the_window_and_the_second_table_change_the_loss():
    """What the comparison above would miss if both sides dropped it alike."""
    w, batch = _weights(CFG), _batch(CFG)
    base = float(reference.loss(w, batch, CFG))
    no_window = float(reference.loss(w, batch, dict(CFG, sliding_window=48)))
    plain = dict(CFG, rope_parameters={FULL: CFG["rope_parameters"][SLIDING],
                                       SLIDING: CFG["rope_parameters"][SLIDING]})
    assert abs(no_window - base) > 1e-4 * base
    assert abs(float(reference.loss(w, batch, plain)) - base) > 1e-5 * base


_GRADS = {}


_SHAPES = model.param_shapes(model.MellumConfig())
_LEAVES = sorted(_SHAPES["top"]) + sorted(
    f"layers.{i}/{name}" for i in range(CFG["num_hidden_layers"]) for name in _SHAPES["layers"])


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_leaf_matches_the_reference(leaf):
    if not _GRADS:
        w, batch = _weights(CFG), _batch(CFG)
        _GRADS["got"] = jax.jit(jax.grad(lambda w: _program_loss(w, batch, CFG)))(w)
        _GRADS["want"] = jax.jit(jax.grad(lambda w: reference.loss(w, batch, CFG)))(w)
    got, want = _GRADS["got"][leaf], _GRADS["want"][leaf]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, f"{leaf}: the reference's gradient is all zero"
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-3 * scale, leaf


def test_an_fp8_product_would_fail_the_tolerances():
    w, batch = _weights(CFG), _batch(CFG)
    sound = float(reference.loss(w, batch, CFG))
    control = float(reference.loss(w, batch, CFG, mode="fp8"))
    assert abs(control - sound) > 50 * 2e-6 * abs(sound)


@pytest.mark.parametrize("kind", (SLIDING, FULL))
@pytest.mark.parametrize("impl", ("pallas", "jnp"))
def test_attention_layer_through_flash(kind, impl):
    """Both kinds at a length the kernels tile (S 256, window 100: the band
    grid), GQA by repetition, QK-norm and the kind's rotary table, against the
    reference's materialised masks."""
    cfg = dict(CFG, seq_len=256, sliding_window=100)
    w = _weights(cfg, seed=5)
    lp = reference._group(w, "layers.1")
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 256, 64))
    mcfg = family.model_config(cfg)
    mcfg = mcfg.__class__(**{**mcfg.__dict__, "attention_impl": impl})
    got = model.attention(mcfg, x, lp, kind, model.rotary_tables(mcfg, 256)[kind])
    want = reference.attention(x, lp, cfg, kind, "float32")
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(jnp.max(jnp.abs(want)))
    other = reference.attention(x, lp, cfg, FULL if kind == SLIDING else SLIDING, "float32")
    assert float(jnp.max(jnp.abs(other - want))) > 1e-2 * float(jnp.max(jnp.abs(want)))


# -- the rotary tables ------------------------------------------------------------

def test_yarn_frequencies_by_hand():
    """The published full-attention group: head_dim 128, theta 500,000, factor
    16, original length 8192, beta_fast 32, beta_slow 1."""
    c = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(500000))
    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)          # low, high
    yarn = layers.Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    f = np.asarray(layers.rotary_frequencies(128, 5e5, yarn), np.float64)
    e = lambda i: 500000.0 ** (-2 * i / 128)
    assert f.shape == (64,)
    np.testing.assert_allclose(f[0], 1.0, rtol=1e-6)                 # the first: plain
    np.testing.assert_allclose(f[18], e(18), rtol=2e-6)              # the last plain one
    ramp = (26 - 18) / (35 - 18)                                     # a blended one
    np.testing.assert_allclose(f[26], e(26) / 16 * ramp + e(26) * (1 - ramp), rtol=2e-6)
    np.testing.assert_allclose(f[26] / e(26), 1 - ramp * 15 / 16, rtol=2e-6)
    np.testing.assert_allclose(f[35], e(35) / 16, rtol=2e-6)         # the first interpolated
    np.testing.assert_allclose(f[63], e(63) / 16, rtol=2e-6)         # the last
    assert np.all(np.diff(f) < 0)
    # the same numbers from the reference's own lines
    g, a = reference.inverse_frequencies(128, dict(YARN, original_max_position_embeddings=8192))
    np.testing.assert_allclose(f, np.asarray(g, np.float64), rtol=2e-6)
    assert a == 1.2772588722239782
    # whatever the sequence length: the table's rows do not move with it
    short, long_ = (layers.rotary_table(n, 128, 5e5, yarn)[0] for n in (64, 512))
    np.testing.assert_array_equal(short, long_[:64])


def test_the_attention_factor_scales_cos_and_sin():
    yarn = layers.Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    cos, sin = layers.rotary_table(32, 128, 5e5, yarn)
    np.testing.assert_allclose(cos ** 2 + sin ** 2, 1.2772588722239782 ** 2, rtol=1e-5)
    np.testing.assert_allclose(cos[0], 1.2772588722239782, rtol=1e-6)   # position 0
    plain_cos, plain_sin = layers.rotary_table(32, 128, 5e5)
    np.testing.assert_allclose(plain_cos ** 2 + plain_sin ** 2, 1.0, rtol=1e-5)
    # so a full layer's scores carry the factor's square
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 1, 128))
    rot = layers.apply_rotary(q, cos, sin)
    np.testing.assert_allclose(jnp.sum(rot ** 2, -1), 1.2772588722239782 ** 2 * jnp.sum(q ** 2, -1),
                               rtol=1e-4)


@pytest.mark.parametrize("kind", (SLIDING, FULL))
def test_rotary_against_the_reference(kind):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 3, 32))
    mcfg = family.model_config(CFG)
    got = layers.apply_rotary(x, *model.rotary_tables(mcfg, 40)[kind])
    want = reference.rope(x, CFG["rope_parameters"][kind])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- the expert layer's share -------------------------------------------------------

@pytest.mark.parametrize("published,shares", ((64, 4), (16, 4), (16, 2)))
def test_the_shares_add_up_to_the_uncut_reference_layer(published, shares):
    """Expert parallelism over ``shares`` chips (the cell's deployment: 64
    experts, ``first_expert`` 0, 16, 32, 48): each routes over all the experts
    and computes its own; with no shared expert the parts alone add up to the
    whole layer, as the plain reference gives it with every expert held."""
    D, F, K, T = 32, 24, 8, 96
    ks = jax.random.split(jax.random.PRNGKey(published + shares), 5)
    n = lambda k, *shape: jax.random.normal(k, shape) * 0.2
    p = {"router": n(ks[0], D, published), "w_gate": n(ks[1], published, D, F),
         "w_up": n(ks[2], published, D, F), "w_down": n(ks[3], published, F, D)}
    x = jax.random.normal(ks[4], (T, D))
    whole = reference.moe(x, p, {"num_experts_per_tok": K, "norm_topk_prob": True,
                                 "num_experts": published, "first_expert": 0}, "float32")
    held, total, rows = published // shares, jnp.zeros_like(x), 0
    for rank in range(shares):
        mine = dict(p, **{k: p[k][rank * held:(rank + 1) * held]
                          for k in ("w_gate", "w_up", "w_down")})
        part, counters = dropless.dropless_moe(x, mine, top_k=K, first_expert=rank * held)
        total, rows = total + part, rows + float(counters["expert_rows"])
        one = reference.moe(x, mine, {"num_experts_per_tok": K, "norm_topk_prob": True,
                                      "num_experts": held, "first_expert": rank * held}, "float32")
        assert float(jnp.max(jnp.abs(part - one))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))
    assert rows == T * K                               # every assignment lands on one share
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))


# -- plumbing ---------------------------------------------------------------------

def test_the_family_round_trips_the_tree_and_counts():
    cfg = dict(CFG, num_hidden_layers=8)
    flat = family.weights(cfg, jax.random.PRNGKey(0))
    back = family._to_flat(family._to_tree(flat))
    assert set(back) == set(flat) and all(bool(jnp.array_equal(back[k], flat[k])) for k in flat)
    assert family.param_count(cfg) == model.param_count(family.model_config(cfg)) == \
        sum(v.size for v in flat.values())
    # the embedding at unit scale, everything else at initializer_range (or one)
    assert 0.9 < float(jnp.std(flat["embed"])) < 1.1
    assert 0.015 < float(jnp.std(flat["head"])) < 0.025
    assert float(jnp.std(flat["layers.3/w_q"])) < 0.025
    assert bool(jnp.all(flat["layers.0/q_norm"] == 1.0))


def test_layer_types_must_be_whole_periods():
    assert model.MellumConfig().period == (SLIDING, SLIDING, SLIDING, FULL)
    assert model.MellumConfig(num_hidden_layers=8, layer_types=(SLIDING, FULL) * 4).periods == 4
    assert model.MellumConfig(num_hidden_layers=2, layer_types=(SLIDING, SLIDING)).periods == 1
    for bad in ((SLIDING, FULL, SLIDING, SLIDING), (SLIDING,) * 3, (FULL, "linear", FULL, FULL)):
        with pytest.raises(ValueError):
            model.MellumConfig(layer_types=bad).period


@pytest.mark.parametrize("periods", (1, 3))
def test_a_stack_of_one_period_is_not_a_loop(periods):
    """``layers.scan_periods`` is ``lax.scan`` in values and gradients; one
    period is the body called once, with no ``while`` for the compiler to find
    (PR 34: nested in one, the MoE layer's loops cost the Qwen cell 0.96 GiB)."""
    ws = jax.random.normal(jax.random.PRNGKey(periods), (periods, 2, 8, 8))

    def period(x, w):
        for i in range(2):
            x = jnp.tanh(x @ w[i])
        return x, {"seen": jnp.sum(x)}

    def loss(scan, x, ws):
        y, seen = scan(period, x, ws)
        return jnp.sum(y) + jnp.sum(seen["seen"]), seen

    x = jnp.ones((4, 8))
    (got, seen), grads = jax.value_and_grad(
        lambda x, ws: loss(layers.scan_periods, x, ws), (0, 1), has_aux=True)(x, ws)
    (want, seen_w), grads_w = jax.value_and_grad(
        lambda x, ws: loss(jax.lax.scan, x, ws), (0, 1), has_aux=True)(x, ws)
    assert seen["seen"].shape == seen_w["seen"].shape == (periods,)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(grads, grads_w):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    text = jax.jit(jax.grad(lambda x: loss(layers.scan_periods, x, ws)[0])).lower(x).as_text()
    assert ("stablehlo.while" in text) == (periods > 1)


def test_keep_fp32_mask():
    tree = family._to_tree(family.weights(CFG, jax.random.PRNGKey(0)))
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    kept = {"/".join(str(p.key) for p in path) for path, _ in flat if model.keep_fp32(path)}
    assert kept == {"final_norm", "layers/input_norm", "layers/post_norm", "layers/q_norm",
                    "layers/k_norm"}


def test_counters_and_scopes_of_the_step():
    """The step as the benchmark's family wires it (amp O5 + FusedAdam under
    donate_step): counters come out as device scalars, and every scope the
    per-layer metrics read is in the program."""
    from benchmark import run

    cell = run.load("workloads", "tiny-mellum.train")
    c = run.Cell(cell, run.load("configs", cell["config"]), jax.devices()[:1])
    c.start(11)
    c.build()
    c.run_step(0)
    c.run_step(1)
    seen = family.counters()
    assert seen["steps"] == 2 and seen["dropped_rows"] == 0
    tokens = cell["per_chip_batch"] * 48
    assert 0 < seen["expert_rows"] <= 2 * 4 * tokens * 4     # steps, layers, top-k
    assert seen["expert_load_max_over_mean"] >= 1.0
    hlo = c.program.step.jitted.lower(c.state, c.pool[0]).compile().as_text()
    for scope in ("amp_forward", "amp_backward", "amp_unscale", "fused_adam_step_flat",
                  "mellum_embed", "mellum_layers", "mellum_head", "mellum_loss",
                  "window_mixer", "full_mixer", "flash_attention", "layer_norm",
                  "moe/moe_route", "moe/moe_dispatch", "moe/moe_experts", "moe/moe_combine"):
        assert scope in hlo, scope
    assert "moe_shared" not in hlo


def test_required_operations_at_the_published_widths():
    from benchmark import run

    cfg = run.load("configs", "mellum2-12b-a2.5b")
    outside, experts = 2 * 9_437_184 + 2 * 1_179_648 + 147_456 + 4_864, 16 * 6_193_152
    assert family.param_count(cfg) == 4 * (outside + experts) + 2 * 12288 * 2304 + 2304 \
        == 538_531_072                                      # ISSUE 31: 8.62 GB at 16 B
    assert round(16 * family.param_count(cfg) / 1e9, 2) == 8.62
    attn, router, held, head = family.matmul_params_per_token(cfg)
    assert (attn, router, held, head) == (21_233_664, 147_456, 2 * 6_193_152, 12288 * 2304)
    assert family.keys_per_query(cfg, FULL) == 4096.5
    assert family.keys_per_query(cfg, SLIDING) == 1024 - 1024 * 1023 / (2 * 8192)   # 960.06
    window = 3 * 12 * 32 * 128 * family.keys_per_query(cfg, SLIDING)
    full = 12 * 32 * 128 * 4096.5
    assert family.window_attention_flops_per_item(cfg) == window
    assert family.attention_flops_per_item(cfg) == window + full
    total = family.model_flops_per_item(cfg)
    assert total == 6 * (4 * (attn + router + held) + head) + window + full
    assert 1.30e9 < total < 1.34e9                          # ISSUE 31: 1.32 G a token
