"""``models/lfm2_moe.py`` against the benchmark's plain float32 reference
(``benchmark/reference/lfm2_moe.py``: the convolution by shifted slices,
attention by materialised masks, every held expert on every token), the share
test of its expert layer, the selection bias, ``route_sigmoid(eps=)`` and the
tied embedding.

Seeded random weights at a small size, float32 at ``highest`` matmul precision.
The program computes the same mathematics in another order (flash attention by
blocks, rows sorted by expert), so the tolerances are those of float32
reassociation through the layers, as ``tests/test_mellum.py``'s: 2e-6 relative
on the loss (5e-6 through all 24 layers), 1e-3 of each gradient tensor's
largest entry. Two sizes: the WHOLE published pattern (24 layers: 18
convolutions and 6 attentions on the irregular list, 2 leading dense layers,
every expert held) and a share (published layers 1-5, 4 of 16 experts, as the
benchmark's cell is cut)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from beforeholiday_tpu.models import lfm2_moe as model  # noqa: E402
from beforeholiday_tpu.moe import dropless  # noqa: E402
from benchmark.families import lfm2_moe as family  # noqa: E402
from benchmark.reference import lfm2_moe as reference  # noqa: E402

CONV, ATTN = "conv", "full_attention"
WHOLE = {
    "layer_types": list(model.PUBLISHED_LAYER_TYPES), "num_hidden_layers": 24, "first_layer": 0,
    "num_dense_layers": 2, "hidden_size": 64, "vocab_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "conv_L_cache": 3, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_published": 8, "first_expert": 0,
    "num_experts_per_tok": 2, "use_expert_bias": True, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "moe_rows_bound": None, "rope_theta": 1000000, "norm_eps": 1e-05,
    "tie_word_embeddings": True, "initializer_range": 0.02, "expert_bias_init_std": 0.01,
    "seq_len": 48, "compute_dtype": "float32", "remat_policy": None,
}
SHARE = dict(WHOLE, num_hidden_layers=5, first_layer=1, num_experts=4, num_experts_published=16,
             first_expert=8, num_experts_per_tok=4, routed_scaling_factor=2.5)
_SIZES = {"whole": WHOLE, "share": SHARE}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(cfg, seed=0):
    """Seeded weights with every norm weight off its identity, the selection bias
    large enough (0.3 against the scores' spread of 0.2) to reorder the choice
    for most tokens, and matmul weights large enough (0.1) that attention is far
    from uniform."""
    flat = family.weights(cfg, jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))

    def perturb(name, v, key):
        if "norm" in name:
            return v + 0.1 * jax.random.normal(key, v.shape)
        if name.endswith("expert_bias"):
            return 30.0 * v
        return v if name.endswith("/conv") else 5.0 * v

    return {k: perturb(k, v, kk) for (k, v), kk in zip(sorted(flat.items()), keys)}


def _batch(cfg, seed=3, rows=2):
    return family.batch(cfg, rows, jax.random.PRNGKey(seed))


def _mcfg(cfg, **overrides):
    mcfg = family.model_config(cfg)
    return mcfg.__class__(**{**mcfg.__dict__, **overrides})


def _program_loss(flat, batch, cfg, **overrides):
    return model.loss_fn(family._to_tree(flat), *batch,
                         _mcfg(cfg, **overrides))[0]


@pytest.mark.parametrize("base,overrides,tol", (
    (WHOLE, {}, 5e-6), (SHARE, {}, 2e-6), (SHARE, {"first_expert": 0, "num_experts": 16}, 2e-6),
    (SHARE, {"first_layer": 0}, 2e-6), (SHARE, {"first_layer": 19}, 2e-6),
    (SHARE, {"remat_policy": "full"}, 2e-6), (SHARE, {"use_expert_bias": False}, 2e-6),
    (SHARE, {"norm_topk_prob": False}, 2e-6), (SHARE, {"tie_word_embeddings": False}, 2e-6),
    (SHARE, {"conv_L_cache": 4}, 2e-6),
), ids=("whole-24-layers", "share", "all-experts", "both-dense-layers", "the-last-five", "remat",
        "no-bias", "no-renormalisation", "untied", "four-taps"))
def test_loss_matches_the_reference(base, overrides, tol):
    cfg = dict(base, **overrides)
    w, batch = _weights(cfg), _batch(cfg)
    got = float(jax.jit(lambda w: _program_loss(w, batch, cfg))(w))
    want = float(jax.jit(lambda w: reference.loss(w, batch, cfg))(w))
    assert abs(got - want) <= tol * abs(want), (got, want)


@pytest.mark.parametrize("size", ("whole", "share"))
def test_logits_match_the_reference(size):
    cfg = _SIZES[size]
    w, (tokens, _) = _weights(cfg), _batch(cfg)
    got, _ = jax.jit(lambda w: model.forward(
        family._to_tree(w), tokens, _mcfg(cfg)))(w)
    want = jax.jit(lambda w: reference.logits(w, tokens, cfg))(w)
    assert got.shape == want.shape == (2, 48, 96) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(jnp.max(jnp.abs(want)))


def test_each_mechanism_changes_the_loss():
    """What the comparisons above would miss if both sides dropped it alike."""
    w, batch = _weights(SHARE), _batch(SHARE)
    base = float(reference.loss(w, batch, SHARE))
    moved = lambda cfg, w=w: abs(float(reference.loss(w, batch, cfg)) - base) / base
    assert moved(dict(SHARE, use_expert_bias=False)) > 1e-5       # the bias chooses
    assert moved(dict(SHARE, norm_topk_prob=False)) > 1e-5
    assert moved(dict(SHARE, routed_scaling_factor=1)) > 1e-5
    assert moved(dict(SHARE, rope_theta=10000)) > 1e-6
    assert moved(dict(SHARE, first_expert=0)) > 1e-5
    taps = {k: (v.at[:, 0].set(0.0) if k.endswith("/conv") else v) for k, v in w.items()}
    assert moved(SHARE, taps) > 1e-4                              # the oldest tap counts


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
    assert len(_leaves(WHOLE)) == 2 + 18 * 4 + 6 * 7 + 2 * 4 + 22 * 6


def test_an_fp8_product_would_fail_the_tolerances():
    w, batch = _weights(SHARE), _batch(SHARE)
    sound = float(reference.loss(w, batch, SHARE))
    control = float(reference.loss(w, batch, SHARE, mode="fp8"))
    assert abs(control - sound) > 50 * 2e-6 * abs(sound)


# -- the mixers -------------------------------------------------------------------

@pytest.mark.parametrize("impl", ("pallas", "jnp"))
def test_the_convolution_mixer_through_the_kernels(impl):
    """At a width the kernels take (128 channels, three tiles of 32 rows), against
    the reference's shifted slices; forward and the input's cotangent."""
    cfg = dict(SHARE, hidden_size=128, seq_len=96)
    w = _weights(cfg, seed=5)
    p = reference._group(w, "layers.0")
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 96, 128))
    mcfg = _mcfg(cfg, short_conv_impl=impl)
    got, pull = jax.vjp(lambda x: model.short_conv_mixer(mcfg, x, p), x)
    want, pull_ref = jax.vjp(lambda x: reference.short_conv(x, p, cfg, "float32"), x)
    close = lambda a, b: float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(jnp.max(jnp.abs(b)))
    assert close(got, want) and close(pull(want)[0], pull_ref(want)[0])


@pytest.mark.parametrize("impl", ("pallas", "jnp"))
def test_attention_layer_through_flash(impl):
    """At a length the kernels tile (S 256), GQA by repetition, QK-norm and the
    rotary table at theta 1e6, against the reference's materialised mask."""
    cfg = dict(SHARE, seq_len=256)
    w = _weights(cfg, seed=5)
    p = reference._group(w, "layers.1")                 # published layer 2: attention
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 256, 64))
    mcfg = _mcfg(cfg, attention_impl=impl)
    table = model._layers.rotary_table(256, mcfg.head_dim, mcfg.rope_theta)
    got = model.attention(mcfg, x, p, table)
    want = reference.attention(x, p, cfg, "float32")
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(jnp.max(jnp.abs(want)))
    other = reference.attention(x, p, dict(cfg, rope_theta=100), "float32")
    assert float(jnp.max(jnp.abs(other - want))) > 1e-2 * float(jnp.max(jnp.abs(want)))


# -- the router: the selection bias and eps ------------------------------------------

def _route_sigmoid_before_this_pr(x, w_router, top_k, *, bias=None, scale=1.0, renormalize=True):
    """``moe.dropless.route_sigmoid`` as the parent commit has it, ``+ 1e-20`` in
    the code: the oracle of the default's bit-for-bit test."""
    logits = jnp.dot(x, w_router.astype(x.dtype), preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, idx = jax.lax.top_k(choice, top_k)
    chosen = idx[..., None] == jnp.arange(scores.shape[-1], dtype=idx.dtype)
    weights = jnp.sum(jnp.where(chosen, scores[..., None, :], 0.0), axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * scale, idx.astype(jnp.int32)


def _router_inputs(T=64, D=32, E=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (T, D)).astype(dtype), jax.random.normal(ks[1], (D, E)) * 0.3,
            jax.random.normal(ks[2], (E,)) * 0.3)


@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16))
@pytest.mark.parametrize("kwargs", ({}, {"scale": 5.0}, {"renormalize": False}, {"bias": True}),
                         ids=("plain", "scaled", "raw-scores", "biased"))
def test_route_sigmoids_default_is_bit_for_bit_what_it_was(kwargs, dtype):
    x, w, b = _router_inputs(dtype=dtype)
    kwargs = dict(kwargs, bias=b) if "bias" in kwargs else kwargs
    for jit in (lambda f: f, jax.jit):
        got = jit(lambda x, w: dropless.route_sigmoid(x, w, 4, **kwargs))(x, w)
        was = jit(lambda x, w: _route_sigmoid_before_this_pr(x, w, 4, **kwargs))(x, w)
        for a, b_ in zip(got, was):
            assert a.dtype == b_.dtype and bool(jnp.array_equal(a, b_))
    same = jax.make_jaxpr(lambda x, w: dropless.route_sigmoid(x, w, 4, **kwargs))(x, w)
    old = jax.make_jaxpr(lambda x, w: _route_sigmoid_before_this_pr(x, w, 4, **kwargs))(x, w)
    assert str(same) == str(old)                        # the same traced program


@pytest.mark.parametrize("eps", (1e-6, 0.5))
def test_route_sigmoid_eps_against_the_formula(eps):
    x, w, b = _router_inputs(seed=1)
    weights, idx = dropless.route_sigmoid(x, w, 4, bias=b, scale=2.0, eps=eps)
    s = np.asarray(jax.nn.sigmoid(x @ w), np.float64)
    want_idx = np.argsort(-(s + np.asarray(b, np.float64)), axis=-1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want_idx, -1))
    top = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(weights, 2.0 * top / (top.sum(-1, keepdims=True) + eps), rtol=2e-6)
    if eps == 0.5:      # far from the default's: the keyword is not ignored
        default, _ = dropless.route_sigmoid(x, w, 4, bias=b, scale=2.0)
        assert float(jnp.max(jnp.abs(default - weights))) > 0.05


def test_the_selection_bias_chooses_and_does_not_weigh():
    """A bias large enough to reorder the experts changes WHICH are chosen;
    the weights of those chosen are their scores' (renormalised), as without
    it; and no gradient reaches it."""
    x, w, b = _router_inputs(seed=2)
    plain_w, plain_idx = dropless.route_sigmoid(x, w, 4, eps=1e-6)
    weights, idx = dropless.route_sigmoid(x, w, 4, bias=b, eps=1e-6)
    moved = np.mean(np.sort(np.asarray(idx), -1) != np.sort(np.asarray(plain_idx), -1))
    assert moved > 0.3                                   # most tokens choose otherwise
    s = np.asarray(jax.nn.sigmoid(x @ w))
    top = np.take_along_axis(s, np.asarray(idx), -1)     # the scores, not score + bias
    np.testing.assert_allclose(weights, top / (top.sum(-1, keepdims=True) + 1e-6), rtol=2e-6)
    huge = jnp.zeros((16,)).at[jnp.array([3, 7, 11, 13])].set(100.0)
    _, forced = dropless.route_sigmoid(x, w, 4, bias=huge, eps=1e-6)
    assert np.array_equal(np.sort(np.asarray(forced), -1), np.tile([3, 7, 11, 13], (64, 1)))
    grad = jax.grad(lambda b: jnp.sum(jnp.square(dropless.route_sigmoid(x, w, 4, bias=b)[0])))(b)
    assert float(jnp.max(jnp.abs(grad))) == 0.0


def test_the_models_expert_layer_routes_under_its_bias():
    """``sparse_ffn`` hands the layer's own ``expert_bias`` and 1e-6 to the
    router: with the bias zeroed the layer's output changes, and equals the
    reference's either way."""
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
    assert float(counters["dropped_rows"]) == 0 and 0 < float(counters["expert_rows"]) <= 96 * 4


# -- the expert layer's share -------------------------------------------------------

@pytest.mark.parametrize("published,shares", ((32, 4), (16, 4), (16, 2)))
def test_the_shares_add_up_to_the_uncut_reference_layer(published, shares):
    """Expert parallelism over ``shares`` chips (the cell's deployment: 32
    experts, ``first_expert`` 0, 8, 16, 24): each routes over all the experts
    under the whole bias and computes its own; with no shared expert the parts
    alone add up to the whole layer, as the plain reference gives it with every
    expert held. What every chip computes alike (the router) is counted once."""
    D, F, K, T = 32, 24, 4, 96
    ks = jax.random.split(jax.random.PRNGKey(published + shares), 6)
    n = lambda k, *shape: jax.random.normal(k, shape) * 0.2
    p = {"router": n(ks[0], D, published), "expert_bias": n(ks[5], published),
         "w_gate": n(ks[1], published, D, F), "w_up": n(ks[2], published, D, F),
         "w_down": n(ks[3], published, F, D)}
    x = jax.random.normal(ks[4], (1, T, D))
    base = {"num_experts_per_tok": K, "norm_topk_prob": True, "use_expert_bias": True,
            "routed_scaling_factor": 1, "num_experts_published": published}
    whole = reference.moe(x, p, dict(base, num_experts=published, first_expert=0), "float32")
    held, total, rows = published // shares, jnp.zeros_like(x), 0
    for rank in range(shares):
        mine = dict(p, **{k: p[k][rank * held:(rank + 1) * held]
                          for k in ("w_gate", "w_up", "w_down")})
        mcfg = model.Lfm2MoeConfig(
            hidden_size=D, moe_intermediate_size=F, num_experts_published=published,
            num_experts=held, first_expert=rank * held, num_experts_per_tok=K)
        part, counters = model.sparse_ffn(mcfg, x, mine)
        total, rows = total + part, rows + float(counters["expert_rows"])
        one = reference.moe(x, mine, dict(base, num_experts=held, first_expert=rank * held),
                            "float32")
        assert float(jnp.max(jnp.abs(part - one))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))
    assert rows == T * K                               # every assignment lands on one share
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))


# -- the tied embedding ---------------------------------------------------------------

def test_the_tied_embedding_gets_one_gradient_the_sum_of_both_uses():
    """Untie the same matrix into ``embed`` and ``head``: the tied model's one
    gradient is the sum of the two, and neither alone."""
    w, batch = _weights(SHARE), _batch(SHARE)
    tied = jax.grad(lambda w: _program_loss(w, batch, SHARE))(w)["embed"]
    untied_cfg = dict(SHARE, tie_word_embeddings=False)
    both = jax.grad(lambda w: _program_loss(w, batch, untied_cfg))(dict(w, head=w["embed"]))
    scale = float(jnp.max(jnp.abs(tied)))
    assert float(jnp.max(jnp.abs(tied - (both["embed"] + both["head"])))) <= 1e-5 * scale
    for part in ("embed", "head"):
        assert float(jnp.max(jnp.abs(tied - both[part]))) > 1e-2 * scale
    assert "head" not in model.param_shapes(_mcfg(SHARE))
    assert "head" in model.param_shapes(_mcfg(untied_cfg))


# -- plumbing ---------------------------------------------------------------------

def test_the_held_layers_are_decided_on_the_published_index():
    held = model.Lfm2MoeConfig().held
    assert len(held) == 24 and [m for m, _ in held].count(CONV) == 18
    assert [f for _, f in held] == ["dense"] * 2 + ["moe"] * 22
    assert [i for i, (m, _) in enumerate(held) if m == ATTN] == [2, 6, 10, 14, 18, 21]
    share = model.Lfm2MoeConfig(num_hidden_layers=5, first_layer=1)
    assert share.held == ((CONV, "dense"), (ATTN, "moe"), (CONV, "moe"), (CONV, "moe"), (CONV, "moe"))
    assert reference.held(SHARE) == list(share.held)
    tail = model.Lfm2MoeConfig(num_hidden_layers=2, first_layer=22)
    assert tail.held == ((CONV, "moe"),) * 2
    layers = model.param_shapes(tail)["layers"]        # a layer holds what it needs, no more
    assert len(layers) == 2 and all(sorted(layer) == [
        "conv", "expert_bias", "ffn_norm", "operator_norm", "router", "w_down", "w_gate", "w_in",
        "w_out", "w_up"] for layer in layers)


@pytest.mark.parametrize("bad", (
    {"num_hidden_layers": 25}, {"first_layer": 20, "num_hidden_layers": 5},
    {"layer_types": (CONV, "sliding_attention"), "num_hidden_layers": 2},
    {"num_attention_heads": 3}, {"num_key_value_heads": 3},
))
def test_a_stack_that_is_not_whole_is_refused(bad):
    with pytest.raises(ValueError):
        model.Lfm2MoeConfig(**bad).held


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
        shapes = family._to_flat(model.param_shapes(family.model_config(cfg)))
        assert shapes == reference.tensor_shapes(cfg)
        assert {k: v.shape for k, v in flat.items()} == {k: s for k, (s, _) in shapes.items()}


def test_the_init_is_what_the_configuration_states():
    flat = family.weights(dict(SHARE, hidden_size=256, vocab_size=512), jax.random.PRNGKey(1))
    assert 0.018 < float(jnp.std(flat["embed"])) < 0.022         # the head too: 0.02, not 1
    assert 0.018 < float(jnp.std(flat["layers.0/w_in"])) < 0.022
    conv = flat["layers.0/conv"]
    assert conv.shape == (256, 3) and float(jnp.max(jnp.abs(conv))) <= 0.578125   # bf16(3^-1/2)
    assert 0.30 < float(jnp.std(conv)) < 0.36                    # uniform in +-0.577: 0.333
    bias = flat["layers.2/expert_bias"]
    assert bias.shape == (16,) and 0.003 < float(jnp.std(bias)) < 0.02 and bool(jnp.any(bias != 0))
    assert bool(jnp.all(flat["layers.1/q_norm"] == 1.0))
    every = jnp.concatenate([v.reshape(-1) for v in flat.values()])
    assert bool(jnp.all(every.astype(jnp.bfloat16).astype(jnp.float32) == every))


def test_keep_fp32_mask():
    tree = family._to_tree(family.weights(SHARE, jax.random.PRNGKey(0)))
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    kept = {path[-1].key for path, _ in flat if model.keep_fp32(path)}
    assert kept == {"embedding_norm", "operator_norm", "q_norm", "k_norm", "ffn_norm",
                    "expert_bias"}
    assert sum(model.keep_fp32(path) for path, _ in flat) == 1 + 5 * 2 + 2 + 4


def test_counters_and_scopes_of_the_step():
    """The step as the benchmark's family wires it (amp O5 + FusedAdam under
    donate_step): counters come out as device scalars, every scope the per-layer
    metrics read is in the program, and Adam leaves the selection bias where it
    was drawn."""
    from benchmark import run

    cell = run.load("workloads", "tiny-lfm2-moe.train")
    c = run.Cell(cell, run.load("configs", cell["config"]), jax.devices()[:1])
    c.start(11)
    c.build()
    before = {k: np.asarray(v) for k, v in c.program.masters(c.state).items()}
    c.run_step(0)
    c.run_step(1)
    seen = family.counters()
    assert seen["steps"] == 2 and seen["dropped_rows"] == 0
    tokens = cell["per_chip_batch"] * 48
    assert 0 < seen["expert_rows"] <= 2 * 4 * tokens * 4     # steps, expert layers, top-k
    assert seen["expert_load_max_over_mean"] >= 1.0
    after = c.program.masters(c.state)
    for name, was in before.items():
        same = bool(np.array_equal(np.asarray(after[name]), was))
        assert same == name.endswith("expert_bias"), name     # every other leaf has moved
    assert float(np.max(np.abs(
        np.asarray(c.program.first_gradient(c.state, None)["layers.1/expert_bias"])))) == 0.0
    hlo = c.program.step.jitted.lower(c.state, c.pool[0]).compile().as_text()
    for scope in ("amp_forward", "amp_backward", "amp_unscale", "fused_adam_step_flat",
                  "lfm2_embed", "lfm2_layers", "lfm2_head", "lfm2_loss", "conv_mixer",
                  "short_conv", "attn_mixer", "dense_ffn", "flash_attention", "layer_norm",
                  "moe/moe_route", "moe/moe_dispatch", "moe/moe_experts", "moe/moe_combine"):
        assert scope in hlo, scope
    assert "moe_shared" not in hlo and "moe_latent" not in hlo


def test_required_operations_at_the_published_widths():
    from benchmark import run

    cfg = run.load("configs", "lfm2-8b-a1b")
    D = 2048
    conv = D + D * 3 * D + D * 3 + D * D
    attn = D + 2 * D * D + 2 * D * 512 + 2 * 64
    dense = D + 3 * D * 7168
    moe = D + D * 32 + 32 + 8 * 3 * D * 1792
    assert family.param_count(cfg) == 4 * conv + attn + dense + 4 * moe + 16384 * D + D \
        == 507_820_288                                      # ISSUE 39: 507.9M within 1 %
    assert abs(family.param_count(cfg) / 507.9e6 - 1) < 0.01
    assert round(16 * family.param_count(cfg) / 1e9, 2) == 8.13
    per = family.matmul_params_per_token(cfg)
    assert per == {"conv": 16_777_216, "full_attention": 10_485_760, "dense": 44_040_192,
                   "moe": 65_536 + 11_010_048.0, "head": 33_554_432}
    token = 4 * per["conv"] + per["full_attention"] + per["dense"] + 4 * per["moe"] + per["head"]
    shares = {k: round(100 * v / token) for k, v in (
        ("conv", 4 * per["conv"]), ("experts", 4 * 11_010_048), ("dense", per["dense"]),
        ("head", per["head"]), ("attention", per["full_attention"]))}
    assert shares == {"conv": 34, "experts": 22, "dense": 22, "head": 17, "attention": 5}
    assert family.attention_flops_per_item(cfg) == 12 * 32 * 64 * 4096.5 == 100_675_584.0
    assert family.model_flops_per_item(cfg) == 6 * token + 100_675_584.0 == 1_297_625_088.0
    assert family.short_conv_bytes_per_item(cfg) == 4 * 22 * D      # 11 D bfloat16 a layer
    assert family.short_conv_bytes_per_item(cfg) * 8192 / 4 == 369_098_752    # 369 MB a layer
