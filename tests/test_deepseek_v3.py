"""``models/deepseek_v3.py`` against the benchmark's plain float32 reference
(``benchmark/reference/deepseek_v3.py``: the published order of operations —
interleaved rotary by re-laying then ``rotate_half``, ``k_rot`` expanded over the
heads, attention by materialised masks, every held expert on every token), the
share test of its expert layer, the interleaved rotary embedding and what the
configuration class refuses.

Seeded random weights at a small size, float32 at ``highest`` matmul precision.
The program computes the same mathematics in another order (flash attention by
blocks at two widths, rows sorted by expert), so the tolerances are those of
float32 reassociation through the layers, as ``tests/test_lfm2_moe.py``'s: 2e-6
relative on the loss, 1e-3 of each gradient tensor's largest entry. Two sizes: a
WHOLE small model (a dense first layer, then experts, every expert held, the
whole vocabulary) and a share (``first_layer``, 4 of 16 experts from
``first_expert`` 8, as the benchmark's cell is cut); the whole published DEPTH
(48 layers, 128 experts) is built and run once, forward only."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from beforeholiday_tpu.models import deepseek_v3 as model  # noqa: E402
from beforeholiday_tpu.moe import dropless  # noqa: E402
from benchmark.families import deepseek_v3 as family  # noqa: E402
from benchmark.reference import deepseek_v3 as reference  # noqa: E402

WHOLE = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 4, "first_layer": 0,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": None, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 1000000,
    "rope_interleave": True, "moe_intermediate_size": 32, "n_routed_experts_published": 8,
    "n_routed_experts": 8, "first_expert": 0, "n_shared_experts": 2, "num_experts_per_tok": 2,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "moe_rows_bound": None, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "initializer_range": 0.02, "embedding_init_std": 1.0,
    "seq_len": 48, "compute_dtype": "float32", "remat_policy": None,
}
SHARE = dict(WHOLE, num_hidden_layers=3, first_layer=0, n_routed_experts=4,
             n_routed_experts_published=16, first_expert=8, num_experts_per_tok=4)
_SIZES = {"whole": WHOLE, "share": SHARE}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(cfg, seed=0):
    """Seeded weights with every norm weight off its identity, a selection bias
    that is NOT the configuration's zeros (0.3 against the scores' spread of 0.2:
    it reorders the choice for most tokens), and matmul weights large enough
    (0.1) that attention is far from uniform."""
    flat = family.weights(cfg, jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))

    def perturb(name, v, key):
        if "norm" in name:
            return v + 0.1 * jax.random.normal(key, v.shape)
        if name.endswith("expert_bias"):
            return 0.3 * jax.random.normal(key, v.shape)
        return v if name == "embed" else 5.0 * v

    return {k: perturb(k, v, kk) for (k, v), kk in zip(sorted(flat.items()), keys)}


def _batch(cfg, seed=3, rows=2):
    return family.batch(cfg, rows, jax.random.PRNGKey(seed))


def _mcfg(cfg, **overrides):
    mcfg = family.model_config(cfg)
    return mcfg.__class__(**{**mcfg.__dict__, **overrides})


def _program_loss(flat, batch, cfg, **overrides):
    return model.loss_fn(family._to_tree(flat), *batch, _mcfg(cfg, **overrides))[0]


@pytest.mark.parametrize("base,overrides", (
    (WHOLE, {}), (SHARE, {}), (SHARE, {"first_expert": 0, "n_routed_experts": 16}),
    (SHARE, {"first_layer": 1}), (SHARE, {"first_layer": 45}),
    (SHARE, {"first_k_dense_replace": 2}), (SHARE, {"moe_layer_freq": 2}),
    (SHARE, {"remat_policy": "full"}), (SHARE, {"norm_topk_prob": False}),
    (SHARE, {"tie_word_embeddings": True}), (SHARE, {"n_shared_experts": 1}),
    (SHARE, {"qk_rope_head_dim": 16, "v_head_dim": 8}),
), ids=("whole", "share", "all-experts", "no-dense-layer", "the-last-three", "two-dense-layers",
        "every-other-layer-dense", "remat", "no-renormalisation", "tied", "one-shared-expert",
        "other-head-widths"))
def test_loss_matches_the_reference(base, overrides):
    cfg = dict(base, **overrides)
    w, batch = _weights(cfg), _batch(cfg)
    got = float(jax.jit(lambda w: _program_loss(w, batch, cfg))(w))
    want = float(jax.jit(lambda w: reference.loss(w, batch, cfg))(w))
    assert abs(got - want) <= 2e-6 * abs(want), (got, want)


@pytest.mark.parametrize("size", ("whole", "share"))
def test_logits_match_the_reference(size):
    cfg = _SIZES[size]
    w, (tokens, _) = _weights(cfg), _batch(cfg)
    got, _ = jax.jit(lambda w: model.forward(family._to_tree(w), tokens, _mcfg(cfg)))(w)
    want = jax.jit(lambda w: reference.logits(w, tokens, cfg))(w)
    assert got.shape == want.shape == (2, 48, 96) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(jnp.max(jnp.abs(want)))


def test_each_mechanism_changes_the_loss():
    """What the comparisons above would miss if both sides dropped it alike."""
    w, batch = _weights(SHARE), _batch(SHARE)
    base = float(reference.loss(w, batch, SHARE))
    moved = lambda cfg, w=w: abs(float(reference.loss(w, batch, cfg)) - base) / base
    zero_bias = {k: (0 * v if k.endswith("expert_bias") else v) for k, v in w.items()}
    assert moved(SHARE, zero_bias) > 1e-5                          # the bias chooses
    assert moved(dict(SHARE, norm_topk_prob=False)) > 1e-5
    assert moved(dict(SHARE, routed_scaling_factor=1)) > 1e-5
    assert moved(dict(SHARE, rope_theta=100)) > 1e-6
    assert moved(dict(SHARE, first_expert=0)) > 1e-5
    no_shared = {k: (0 * v if "shared_w_down" in k else v) for k, v in w.items()}
    assert moved(SHARE, no_shared) > 1e-5                          # the shared expert adds
    flat_norm = {k: (jnp.ones_like(v) if "kv_a_layernorm" in k else v) for k, v in w.items()}
    assert moved(SHARE, flat_norm) > 1e-6                          # the latent's own norm


_GRADS = {}


def _leaves(cfg):
    """The program's leaves (``model.param_shapes``) under the reference's flat names."""
    return sorted(family._to_flat(model.param_shapes(family.model_config(cfg))))


def _grads(size):
    if size not in _GRADS:
        cfg = _SIZES[size]
        w, batch = _weights(cfg), _batch(cfg)
        _GRADS[size] = (jax.jit(jax.grad(lambda w: _program_loss(w, batch, cfg)))(w),
                        jax.jit(jax.grad(lambda w: reference.loss(w, batch, cfg)))(w))
    return _GRADS[size]


@pytest.mark.parametrize("size,leaf", [(s, leaf) for s in _SIZES for leaf in _leaves(_SIZES[s])])
def test_every_gradient_leaf_matches_the_reference(size, leaf):
    got, want = (g[leaf] for g in _grads(size))
    scale = float(jnp.max(jnp.abs(want)))
    if leaf.endswith("expert_bias"):
        assert scale == 0 and float(jnp.max(jnp.abs(got))) == 0, leaf    # exactly zero, both
        return
    assert scale > 0, f"{leaf}: the reference's gradient is all zero"
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-3 * scale, leaf


def test_the_leaves_are_the_references_names():
    for cfg in _SIZES.values():
        flat = family.weights(cfg, jax.random.PRNGKey(0))
        assert sorted(flat) == sorted(_leaves(cfg))
    assert len(_leaves(WHOLE)) == 3 + 4 * 7 + 3 + 3 * 8


def test_an_fp8_product_would_fail_the_tolerances():
    w, batch = _weights(SHARE), _batch(SHARE)
    sound = float(reference.loss(w, batch, SHARE))
    control = float(reference.loss(w, batch, SHARE, mode="fp8"))
    assert abs(control - sound) > 50 * 2e-6 * abs(sound)


def test_the_whole_published_depth_builds_and_runs():
    """48 layers (one dense, 47 with experts), all 128 experts held, top-6, two
    shared experts, at small widths: the forward pass, against the reference."""
    cfg = dict(WHOLE, num_hidden_layers=48, n_routed_experts=128, n_routed_experts_published=128,
               num_experts_per_tok=6, hidden_size=32, intermediate_size=48,
               moe_intermediate_size=8, seq_len=16, vocab_size=64)
    mcfg = _mcfg(cfg)
    assert mcfg.held == ("dense",) + ("moe",) * 47
    w, batch = family.weights(cfg, jax.random.PRNGKey(2)), _batch(cfg, rows=1)
    got, counters = jax.jit(lambda w: model.loss_fn(family._to_tree(w), *batch, mcfg))(w)
    want = jax.jit(lambda w: reference.loss(w, batch, cfg))(w)
    assert abs(float(got) - float(want)) <= 5e-6 * abs(float(want))
    assert float(counters["expert_rows"]) == 47 * 16 * 6 and float(counters["dropped_rows"]) == 0


# -- latent attention ---------------------------------------------------------------

def _published_rotary(x, cos, sin):
    """``apply_rotary_pos_emb_interleave`` of the published modelling code on
    ``x (B, H, S, d)``: ``view(d / 2, 2).transpose(4, 3).reshape(d)``, then ``x *
    cos + rotate_half(x) * sin``, ``cos`` / ``sin (S, d)`` = the angles twice."""
    b, h, s, d = x.shape
    x = np.asarray(x).reshape(b, h, s, d // 2, 2).transpose(0, 1, 2, 4, 3).reshape(b, h, s, d)
    rotate_half = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[None, None] + rotate_half * sin[None, None]


@pytest.mark.parametrize("d", (8, 64))
def test_interleaved_rotary_is_the_published_order(d):
    """Dims ``(2i, 2i + 1)`` of the projection turn together by ``pos *
    theta^(-2i / d)``, and the result is laid as the published code lays it:
    evens, then odds. The model re-lays the projection's COLUMNS
    (``evens_then_odds``) and applies ``rotate_half`` to what comes out."""
    S, theta, K = 24, 1e6, 40
    u = jax.random.normal(jax.random.PRNGKey(d), (2, S, K))
    w = jax.random.normal(jax.random.PRNGKey(d + 1), (K, 3, d)) * 0.3
    x = jnp.einsum("bsk,khd->bshd", u, w)                     # the projection as published
    angle = np.arange(S)[:, None] * theta ** (-2.0 * np.arange(d // 2) / d)[None, :]
    cos, sin = np.cos(angle), np.sin(angle)
    table = model._layers.rotary_table(S, d, theta)
    got = model._layers.apply_rotary(
        jnp.einsum("bsk,khd->bshd", u, model.evens_then_odds(w)), *table)
    want = _published_rotary(np.asarray(x).transpose(0, 2, 1, 3),
                             np.concatenate([cos, cos], -1), np.concatenate([sin, sin], -1))
    np.testing.assert_allclose(np.asarray(got).transpose(0, 2, 1, 3), want, atol=2e-5)
    np.testing.assert_allclose(reference.rope_interleaved(x, theta), got, atol=2e-5)
    # pair (2i, 2i + 1) by hand, at one position
    pos, i = 5, 1
    a, b = float(x[0, pos, 0, 2 * i]), float(x[0, pos, 0, 2 * i + 1])
    assert abs(float(got[0, pos, 0, i]) - (a * cos[pos, i] - b * sin[pos, i])) < 1e-5
    assert abs(float(got[0, pos, 0, d // 2 + i]) - (b * cos[pos, i] + a * sin[pos, i])) < 1e-5
    # ... which the rotate_half layout on the projection as it stands would not give
    plain = model._layers.apply_rotary(x, *table)
    assert float(jnp.max(jnp.abs(plain - got))) > 0.1


def test_rope_interleave_false_is_the_rotate_half_layout():
    """The key is honoured: without it the mixer pairs dim ``i`` with ``i + d /
    2`` of the projection as it stands — which is the interleaved mixer on
    weights whose rotary columns were re-laid beforehand."""
    cfg = dict(SHARE, seq_len=64)
    p = reference._group(_weights(cfg, seed=5), "layers.1")
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64))
    table = model._layers.rotary_table(64, 8, 1e6)
    on = model.attention(_mcfg(cfg), x, p, table)
    off = model.attention(_mcfg(cfg, rope_interleave=False), x, p, table)
    size = float(jnp.max(jnp.abs(on)))
    assert float(jnp.max(jnp.abs(on - off))) > 1e-4 * size
    w_q = p["w_q"].reshape(64, 4, 24)               # a head: 16 plain, 8 rotary columns
    relaid = dict(
        p, w_q=jnp.concatenate([w_q[..., :16], model.evens_then_odds(w_q[..., 16:])],
                               -1).reshape(64, 96),
        w_kva=jnp.concatenate([p["w_kva"][:, :32], model.evens_then_odds(p["w_kva"][:, 32:])], -1))
    same = model.attention(_mcfg(cfg, rope_interleave=False), x, relaid, table)
    assert float(jnp.max(jnp.abs(on - same))) <= 1e-6 * size


@pytest.mark.parametrize("impl", ("pallas", "jnp"))
def test_the_mixer_through_flash_at_two_widths(impl):
    """At a length the kernels tile (S 256): 24-wide queries and keys (16 + 8
    rotary, ``k_rot`` one head for all four) on 16-wide values, against the
    reference's materialised mask; forward and the input's cotangent."""
    cfg = dict(SHARE, seq_len=256)
    w = _weights(cfg, seed=5)
    p = reference._group(w, "layers.1")
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 256, 64))
    mcfg = _mcfg(cfg, attention_impl=impl)
    table = model._layers.rotary_table(256, mcfg.qk_rope_head_dim, mcfg.rope_theta)
    got, pull = jax.vjp(lambda x: model.attention(mcfg, x, p, table), x)
    want, pull_ref = jax.vjp(lambda x: reference.attention(x, p, cfg, "float32"), x)
    close = lambda a, b: float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(jnp.max(jnp.abs(b)))
    assert close(got, want) and close(pull(want)[0], pull_ref(want)[0])
    other = reference.attention(x, p, dict(cfg, rope_theta=100), "float32")
    assert float(jnp.max(jnp.abs(other - want))) > 1e-3 * float(jnp.max(jnp.abs(want)))


def test_the_mixer_hands_flash_the_two_widths_unpadded():
    """The kernels see ``q, k`` at ``qk_nope + qk_rope`` and ``v`` at
    ``v_head_dim``, and no projection's result is sliced on the way: every
    ``slice`` is of a weight, or of the rotary columns inside ``apply_rotary``."""
    cfg = dict(SHARE, seq_len=128, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32)
    w = _weights(cfg, seed=5)
    p = reference._group(w, "layers.1")
    mcfg = _mcfg(cfg, attention_impl="pallas")
    table = model._layers.rotary_table(128, 16, mcfg.rope_theta)
    jaxpr = jax.make_jaxpr(lambda x: model.attention(mcfg, x, p, table))(jnp.zeros((1, 128, 64)))
    text = str(jaxpr)
    assert "pallas_call" in text and "f32[4,128,48]" in text and "f32[4,128,32]" in text
    assert not [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pad"]
    sliced = [e.invars[0].aval.shape for e in jaxpr.jaxpr.eqns if e.primitive.name == "slice"]
    assert sliced and all(shape[-1] == 16 for shape in sliced if shape[:2] == (1, 128)), sliced


def _mixer_as_it_stood(cfg, u, p, table, rotary=True):
    """``models.deepseek_v3.attention`` as PR 42 wrote it, before its body moved
    to ``models.layers.latent_attention`` (PR 49); ``rotary`` false leaves the
    shared-key part as projected."""
    layers = model._layers
    B, S, _ = u.shape
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = u.dtype
    relay = model.evens_then_odds if cfg.rope_interleave else (lambda w: w)
    turn = (lambda x: layers.apply_rotary(x, *table)) if rotary else (lambda x: x)
    by_head = lambda w, d: w.astype(dt).reshape(w.shape[0], H, d)
    project = lambda x, w: (x @ w.reshape(w.shape[0], -1)).reshape(B, S, H, -1)
    w_q = by_head(p["w_q"], dn + dr)
    q_nope, q_rot = project(u, w_q[..., :dn]), project(u, relay(w_q[..., dn:]))
    w_kva, w_kvb = p["w_kva"].astype(dt), by_head(p["w_kvb"], dn + dv)
    k_rot = (u @ relay(w_kva[:, r:])).reshape(B, S, 1, dr)
    c = model.rms_norm(u @ w_kva[:, :r], p["kv_a_layernorm"], cfg.rms_norm_eps)
    k_nope, v = project(c, w_kvb[..., :dn]), project(c, w_kvb[..., dn:])
    q = jnp.concatenate([q_nope, turn(q_rot)], axis=-1)
    k_rot = jnp.broadcast_to(turn(k_rot), (B, S, H, dr))
    k = jnp.concatenate([k_nope, k_rot], axis=-1)
    ctx = layers.grouped_query_attention(q, k, v, impl=cfg.attention_impl)
    return ctx.reshape(B, S, H * dv) @ p["w_o"].astype(dt)


@pytest.mark.parametrize("rotary", (True, False), ids=("rotary", "no-rotary"))
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16), ids=("f32", "bf16"))
def test_the_moved_mixer_is_bit_for_bit_the_one_that_stood_here(rotary, dtype):
    """PR 49 moved the mixer's body to ``models.layers.latent_attention`` with the
    rotary table optional: with the table it is the former function bit for bit,
    output and cotangents; without one (a published ``mla_use_nope``) it is that
    function with the rotation taken out, and differs from the rotated one."""
    cfg = dict(SHARE, seq_len=128)
    p = reference._group(_weights(cfg, seed=5), "layers.1")
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 64)).astype(dtype)
    mcfg = _mcfg(cfg, attention_impl="jnp", dtype=dtype)
    table = model._layers.rotary_table(128, mcfg.qk_rope_head_dim, mcfg.rope_theta)
    keys = ("w_q", "w_kva", "kv_a_layernorm", "w_kvb", "w_o")
    mixer = {k: p[k] for k in keys}
    if rotary:
        moved = lambda x, p: model.attention(mcfg, x, p, table)
    else:
        moved = lambda x, p: model._layers.latent_attention(
            x, p, heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, eps=mcfg.rms_norm_eps, relay=model.evens_then_odds, impl="jnp")
    got, pull = jax.vjp(moved, x, mixer)
    want, pull_want = jax.vjp(lambda x, p: _mixer_as_it_stood(mcfg, x, p, table, rotary), x, mixer)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    for a, b in zip(jax.tree.leaves(pull(want)), jax.tree.leaves(pull_want(want))):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    other = _mixer_as_it_stood(mcfg, x, mixer, table, not rotary).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(other - want.astype(jnp.float32)))) \
        > 1e-3 * float(jnp.max(jnp.abs(want.astype(jnp.float32))))


# -- the expert layer ----------------------------------------------------------------

def test_the_models_expert_layer_is_the_references():
    """``sparse_ffn`` hands the layer's own ``expert_bias``, 2.448 and 1e-20 to
    the router and adds the ungated shared expert: the reference's layer, and
    with the shared expert's leaves gated as Qwen's or squared as Nemotron's it
    would not be."""
    cfg = SHARE
    w = _weights(cfg, seed=7)
    p = reference._group(w, "layers.2")
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 48, 64))
    got, counters = model.sparse_ffn(_mcfg(cfg), h, p)
    want = reference.moe(h, p, cfg, "float32")
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale
    unbiased, _ = model.sparse_ffn(_mcfg(cfg), h, dict(p, expert_bias=jnp.zeros((16,))))
    assert float(jnp.max(jnp.abs(unbiased - want))) > 1e-2 * scale
    gated, _ = model.sparse_ffn(_mcfg(cfg), h, dict(p, shared_score=jnp.ones((64, 1))))
    assert float(jnp.max(jnp.abs(gated - want))) > 1e-2 * scale
    assert float(counters["dropped_rows"]) == 0 and 0 < float(counters["expert_rows"]) <= 96 * 4


@pytest.mark.parametrize("published,shares", ((128, 8), (16, 4), (16, 2)))
def test_the_shares_add_up_to_the_uncut_reference_layer(published, shares):
    """Expert parallelism over ``shares`` chips (the cell's deployment: 128
    experts, ``first_expert`` 0, 16, ..., 112): each routes over all the experts
    under the whole bias and computes its own routed part and the WHOLE shared
    expert; the routed parts, with the shared expert counted once, add up to the
    uncut reference's layer. What every chip computes alike is counted once."""
    D, F, K, T = 32, 24, 6, 96
    ks = jax.random.split(jax.random.PRNGKey(published + shares), 9)
    n = lambda k, *shape: jax.random.normal(k, shape) * 0.2
    p = {"router": n(ks[0], D, published), "expert_bias": n(ks[5], published),
         "w_gate": n(ks[1], published, D, F), "w_up": n(ks[2], published, D, F),
         "w_down": n(ks[3], published, F, D), "shared_w_gate": n(ks[6], D, 2 * F),
         "shared_w_up": n(ks[7], D, 2 * F), "shared_w_down": n(ks[8], 2 * F, D)}
    x = jax.random.normal(ks[4], (1, T, D))
    base = {"num_experts_per_tok": K, "norm_topk_prob": True, "routed_scaling_factor": 2.448,
            "n_routed_experts_published": published}
    whole = reference.moe(x, p, dict(base, n_routed_experts=published, first_expert=0), "float32")
    shared = reference.swiglu(x, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"],
                              "float32")
    held, total, rows = published // shares, shared, 0
    for rank in range(shares):
        mine = dict(p, **{k: p[k][rank * held:(rank + 1) * held]
                          for k in ("w_gate", "w_up", "w_down")})
        mcfg = model.DeepseekV3Config(
            hidden_size=D, moe_intermediate_size=F, n_routed_experts_published=published,
            n_routed_experts=held, first_expert=rank * held, num_experts_per_tok=K,
            n_shared_experts=2, routed_scaling_factor=2.448)
        part, counters = model.sparse_ffn(mcfg, x, mine)
        total, rows = total + (part - shared), rows + float(counters["expert_rows"])
        one = reference.moe(x, mine, dict(base, n_routed_experts=held, first_expert=rank * held),
                            "float32")
        assert float(jnp.max(jnp.abs(part - one))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))
    assert rows == T * K                               # every assignment lands on one share
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))
    assert float(jnp.max(jnp.abs(shared))) > 1e-2 * float(jnp.max(jnp.abs(whole)))


# -- plumbing ---------------------------------------------------------------------

def test_the_held_layers_are_decided_on_the_published_index():
    assert model.DeepseekV3Config(num_hidden_layers=48).held == ("dense",) + ("moe",) * 47
    share = model.DeepseekV3Config(num_hidden_layers=5, first_layer=0)
    assert share.held == ("dense", "moe", "moe", "moe", "moe")
    assert reference.held(dict(SHARE, num_hidden_layers=5)) == list(share.held)
    assert model.DeepseekV3Config(num_hidden_layers=2, first_layer=1).held == ("moe", "moe")
    assert model.DeepseekV3Config(num_hidden_layers=4, first_k_dense_replace=3).held == \
        ("dense", "dense", "dense", "moe")
    assert model.DeepseekV3Config(num_hidden_layers=5, moe_layer_freq=2).held == \
        ("dense", "dense", "moe", "dense", "moe")
    layers = model.param_shapes(share)["layers"]       # a layer holds what it needs, no more
    attention = ["input_layernorm", "kv_a_layernorm", "post_attention_layernorm", "w_kva",
                 "w_kvb", "w_o", "w_q"]
    assert sorted(layers[0]) == sorted(attention + ["w_down", "w_gate", "w_up"])
    assert all(sorted(layer) == sorted(attention + [
        "expert_bias", "router", "shared_w_down", "shared_w_gate", "shared_w_up", "w_down",
        "w_gate", "w_up"]) for layer in layers[1:])
    assert "shared_w_up" not in model.param_shapes(
        model.DeepseekV3Config(n_shared_experts=0))["layers"][1]


@pytest.mark.parametrize("bad", (
    {"n_group": 8}, {"topk_group": 4}, {"n_group": 8, "topk_group": 4}, {"q_lora_rank": 1536},
    {"scoring_func": "softmax"}, {"num_key_value_heads": 2},
), ids=lambda b: "-".join(b))
def test_what_is_not_built_raises(bad):
    with pytest.raises(ValueError):
        model.DeepseekV3Config(**bad)


def test_the_family_round_trips_the_tree_and_counts():
    for cfg in _SIZES.values():
        flat = family.weights(cfg, jax.random.PRNGKey(0))
        tree = family._to_tree(flat)
        assert len(tree["layers"]) == cfg["num_hidden_layers"]
        back = family._to_flat(tree)
        assert set(back) == set(flat) and all(back[k] is flat[k] for k in flat)    # a rename
        assert family.param_count(cfg) == model.param_count(family.model_config(cfg)) == \
            sum(v.size for v in flat.values())
        # the reference's own table of tensors and the program's agree, leaf by leaf
        shapes = {k: s for k, (s, _) in
                  family._to_flat(model.param_shapes(family.model_config(cfg))).items()}
        assert shapes == {k: s for k, (s, _) in reference.tensor_shapes(cfg).items()}
        assert {k: v.shape for k, v in flat.items()} == shapes


def test_the_init_is_what_the_configuration_states():
    cfg = dict(SHARE, hidden_size=256, vocab_size=512)
    flat = family.weights(cfg, jax.random.PRNGKey(1))
    assert 0.9 < float(jnp.std(flat["embed"])) < 1.1             # the embedding: N(0, 1)
    assert 0.018 < float(jnp.std(flat["head"])) < 0.022          # the head is its own: 0.02
    assert 0.018 < float(jnp.std(flat["layers.0/w_kva"])) < 0.022
    assert bool(jnp.all(flat["layers.1/expert_bias"] == 0)) and \
        flat["layers.1/expert_bias"].shape == (16,)
    assert bool(jnp.all(flat["layers.1/kv_a_layernorm"] == 1.0))
    every = jnp.concatenate([v.reshape(-1) for v in flat.values()])
    assert bool(jnp.all(every.astype(jnp.bfloat16).astype(jnp.float32) == every))
    tree = model.init(jax.random.PRNGKey(1), _mcfg(cfg))         # the program's own draw
    assert bool(jnp.all(tree["layers"][1]["expert_bias"] == 0))
    assert 0.018 < float(jnp.std(tree["layers"][0]["w_q"])) < 0.022


def test_keep_fp32_mask():
    tree = family._to_tree(family.weights(SHARE, jax.random.PRNGKey(0)))
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    kept = {path[-1].key for path, _ in flat if model.keep_fp32(path)}
    assert kept == {"norm", "input_layernorm", "kv_a_layernorm", "post_attention_layernorm",
                    "expert_bias"}
    assert sum(model.keep_fp32(path) for path, _ in flat) == 1 + 3 * 3 + 2


def test_counters_and_scopes_of_the_step():
    """The step as the benchmark's family wires it (amp O5 + FusedAdam under
    donate_step): counters come out as device scalars, every scope the per-layer
    metrics read is in the program, and Adam leaves the selection bias zeros."""
    from benchmark import run

    cell = run.load("workloads", "tiny-deepseek-v3.train")
    c = run.Cell(cell, run.load("configs", cell["config"]), jax.devices()[:1])
    c.start(11)
    c.build()
    before = {k: np.asarray(v) for k, v in c.program.masters(c.state).items()}
    c.run_step(0)
    c.run_step(1)
    seen = family.counters()
    assert seen["steps"] == 2 and seen["dropped_rows"] == 0
    tokens = cell["per_chip_batch"] * 48
    assert 0 < seen["expert_rows"] <= 2 * 3 * tokens * 4     # steps, expert layers, top-k
    assert seen["expert_load_max_over_mean"] >= 1.0
    after = c.program.masters(c.state)
    for name, was in before.items():
        same = bool(np.array_equal(np.asarray(after[name]), was))
        assert same == name.endswith("expert_bias"), name     # every other leaf has moved
    assert float(np.max(np.abs(np.asarray(after["layers.1/expert_bias"])))) == 0.0
    hlo = c.program.step.jitted.lower(c.state, c.pool[0]).compile().as_text()
    for scope in ("amp_forward", "amp_backward", "amp_unscale", "fused_adam_step_flat",
                  "deepseek_v3_embed", "deepseek_v3_layers", "deepseek_v3_head",
                  "deepseek_v3_loss", "mla_mixer", "mla_mixer/mla_latent", "dense_ffn",
                  "flash_attention", "layer_norm", "moe/moe_route", "moe/moe_dispatch",
                  "moe/moe_experts", "moe/moe_shared", "moe/moe_combine"):
        assert scope in hlo, scope
    assert "moe_latent" not in hlo


def test_required_operations_at_the_published_widths():
    from benchmark import run

    cfg = run.load("configs", "kanana-2-30b-a3b")
    D = 2048
    attention = 2 * D + D * 32 * 192 + D * 576 + 512 + 512 * 32 * 256 + 4096 * D
    assert attention == 26_345_984 + 4_096
    dense = attention + 3 * D * 6144
    moe = attention + D * 128 + 128 + 3 * D * 1536 + 16 * 3 * D * 768
    assert (dense, moe) == (64_098_816, 111_547_008)
    assert family.param_count(cfg) == dense + 4 * moe + 2 * 16032 * D + D == 575_955_968
    assert round(16 * family.param_count(cfg) / 1e9, 2) == 9.22
    per = family.matmul_params_per_token(cfg)
    assert per == {"attention": 26_345_472, "dense": 37_748_736,
                   "moe": 262_144 + 9_437_184 + 3_538_944.0, "head": 32_833_536}
    token = 5 * per["attention"] + per["dense"] + 4 * per["moe"] + per["head"]
    assert token == 255_262_720
    assert family.attention_flops_per_item(cfg) == 5 * 6 * 32 * 320 * 4096.5 == 1_258_444_800.0
    assert family.model_flops_per_item(cfg) == 6 * token + 1_258_444_800.0 == 2_790_021_120.0
    total = family.model_flops_per_item(cfg)
    shares = {k: round(100 * v / total) for k, v in (
        ("mixers", 6 * 5 * per["attention"] + 1_258_444_800), ("scores and values", 1_258_444_800),
        ("dense", 6 * per["dense"]), ("experts", 6 * 4 * per["moe"]), ("head", 6 * per["head"]))}
    assert shares == {"mixers": 73, "scores and values": 45, "dense": 8, "experts": 11, "head": 7}
    # the whole published model: 30.67B
    whole = dict(cfg, num_hidden_layers=48, n_routed_experts=128, vocab_size=128256)
    assert round(family.param_count(whole) / 1e9, 2) == 30.67
