"""``models/kimi_linear.py`` against the benchmark's plain float32 reference
(``benchmark/reference/kimi_linear.py``: the KDA recurrence one token at a time,
latent attention by materialised masks and without a rotary embedding, every held
expert on every token), the share test of its expert layer, the layer kinds by
the published 1-based lists and what the configuration class refuses.

Seeded random weights at a small size, float32 at ``highest`` matmul precision.
The program computes the same mathematics in another order (the chunked delta
rule, flash attention by blocks, rows sorted by expert), so the tolerances are
those of float32 reassociation through the layers, as ``tests/test_deepseek_v3.py``'s:
2e-6 relative on the loss, 1e-3 of each gradient tensor's largest entry. Two
sizes: a WHOLE small model (eight layers: a dense first one, two latent ones by
the lists, every expert held) and a share (``first_layer`` 0, five layers, 4 of
16 experts from ``first_expert`` 8, as the benchmark's cell is cut)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from beforeholiday_tpu.models import kimi_linear as model  # noqa: E402
from benchmark.families import kimi_linear as family  # noqa: E402
from benchmark.reference import kimi_linear as reference  # noqa: E402

_LISTS = {"full_attn_layers": [4, 8], "kda_layers": [1, 2, 3, 5, 6, 7], "head_dim": 16,
          "num_heads": 4, "short_conv_kernel_size": 4}
WHOLE = {
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 8, "first_layer": 0,
    "first_k_dense_replace": 1, "intermediate_size": 96, "linear_attn_config": _LISTS,
    "num_attention_heads": 4, "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True, "moe_intermediate_size": 32,
    "num_experts_published": 8, "num_experts": 8, "first_expert": 0, "num_shared_experts": 1,
    "num_experts_per_token": 2, "num_expert_group": 1, "topk_group": 1, "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "moe_router_activation_func": "sigmoid",
    "moe_rows_bound": None, "rms_norm_eps": 1e-05, "initializer_range": 0.02,
    "embedding_init_std": 1.0, "kda_chunk": 16, "seq_len": 48, "compute_dtype": "float32",
    "remat_policy": None,
}
SHARE = dict(WHOLE, num_hidden_layers=5, num_experts=4, num_experts_published=16, first_expert=8,
             num_experts_per_token=4)
_SIZES = {"whole": WHOLE, "share": SHARE}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(cfg, seed=0):
    """Seeded weights with every norm weight off its identity, a selection bias
    that is NOT the configuration's zeros (it reorders the choice for most
    tokens) and matmul weights large enough (0.1) that the gates, the decay and
    attention are far from their values at zero."""
    flat = family.weights(cfg, jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))

    def perturb(name, v, key):
        if "norm" in name:
            return v + 0.1 * jax.random.normal(key, v.shape)
        if name.endswith("expert_bias"):
            return 0.3 * jax.random.normal(key, v.shape)
        return 5.0 * v if v.ndim >= 2 and name != "embed" and "/conv_" not in name else v

    return {k: perturb(k, v, kk) for (k, v), kk in zip(sorted(flat.items()), keys)}


def _batch(cfg, seed=3, rows=2):
    return family.batch(cfg, rows, jax.random.PRNGKey(seed))


def _mcfg(cfg, **overrides):
    mcfg = family.model_config(cfg)
    return mcfg.__class__(**{**mcfg.__dict__, **overrides})


def _program_loss(flat, batch, cfg, **overrides):
    return model.loss_fn(family._to_tree(flat), *batch, _mcfg(cfg, **overrides))[0]


@pytest.mark.parametrize("base,overrides", (
    (WHOLE, {}), (SHARE, {}), (SHARE, {"first_expert": 0, "num_experts": 16}),
    (SHARE, {"first_layer": 2}), (SHARE, {"first_layer": 3}),
    (SHARE, {"first_k_dense_replace": 2}), (SHARE, {"remat_policy": "full"}),
    (SHARE, {"moe_renormalize": False}), (SHARE, {"kda_chunk": 64}),
    (SHARE, {"qk_rope_head_dim": 16, "v_head_dim": 8}),
), ids=("whole", "share", "all-experts", "a-stretch-from-mid-period", "latent-layer-first",
        "two-dense-layers", "remat", "no-renormalisation", "one-chunk-a-sequence",
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
    swap = lambda pick, fn: {k: (fn(v) if pick(k) else v) for k, v in w.items()}
    assert moved(SHARE, swap(lambda k: k.endswith("expert_bias"), lambda v: 0 * v)) > 1e-5
    assert moved(dict(SHARE, moe_renormalize=False)) > 1e-5
    assert moved(dict(SHARE, routed_scaling_factor=1)) > 1e-5
    assert moved(dict(SHARE, first_expert=0)) > 1e-5
    assert moved(SHARE, swap(lambda k: "shared_w_down" in k, lambda v: 0 * v)) > 1e-5
    # the decay is a channel's: one rate a head and one bias for all is another model
    assert moved(SHARE, swap(lambda k: k.endswith("dt_bias"),
                             lambda v: jnp.full_like(v, jnp.mean(v)))) > 1e-6
    assert moved(SHARE, swap(lambda k: k.endswith("a_log"), lambda v: v + 1.0)) > 1e-6
    assert moved(SHARE, swap(lambda k: k.endswith("w_fb"), lambda v: 0 * v)) > 1e-6
    assert moved(SHARE, swap(lambda k: k.endswith("w_gb"), lambda v: 0 * v)) > 1e-6
    assert moved(SHARE, swap(lambda k: k.endswith("w_b"), lambda v: 0 * v)) > 1e-6
    assert moved(SHARE, swap(lambda k: "/conv_k" in k, lambda v: v[:, ::-1])) > 1e-6


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
    # embed / head / norm; 6 KDA mixers of 15 and 2 latent of 5, 2 norms a layer; 3 + 7 x 8
    assert len(_leaves(WHOLE)) == 3 + 6 * 15 + 2 * 5 + 8 * 2 + 3 + 7 * 8


def test_an_fp8_product_would_fail_the_tolerances():
    w, batch = _weights(SHARE), _batch(SHARE)
    sound = float(reference.loss(w, batch, SHARE))
    control = float(reference.loss(w, batch, SHARE, mode="fp8"))
    assert abs(control - sound) > 50 * 2e-6 * abs(sound)


def test_the_whole_published_depth_builds_and_runs():
    """27 layers by the published lists (one dense, 20 KDA and 7 latent mixers),
    all 256 experts held, top-8, at small widths: the loss against the reference."""
    lists = dict(_LISTS, full_attn_layers=[4, 8, 12, 16, 20, 24, 27],
                 kda_layers=[l for l in range(1, 27) if l % 4], head_dim=8, num_heads=2)
    cfg = dict(WHOLE, num_hidden_layers=27, linear_attn_config=lists, num_experts=256,
               num_experts_published=256, num_experts_per_token=8, hidden_size=32,
               intermediate_size=48, moe_intermediate_size=8, seq_len=16, vocab_size=64)
    mcfg = _mcfg(cfg)
    mixers = [m for m, _ in mcfg.held]
    assert mixers.count("kda") == 20 and [i + 1 for i, m in enumerate(mixers) if m == "mla"] \
        == lists["full_attn_layers"]
    assert [f for _, f in mcfg.held] == ["dense"] + ["moe"] * 26
    w, batch = family.weights(cfg, jax.random.PRNGKey(2)), _batch(cfg, rows=1)
    got, counters = jax.jit(lambda w: model.loss_fn(family._to_tree(w), *batch, mcfg))(w)
    want = jax.jit(lambda w: reference.loss(w, batch, cfg))(w)
    assert abs(float(got) - float(want)) <= 5e-6 * abs(float(want))
    assert float(counters["expert_rows"]) == 26 * 16 * 8 and float(counters["dropped_rows"]) == 0


# -- the mixers ---------------------------------------------------------------------

@pytest.mark.parametrize("impl", ("pallas", "jnp"))
def test_the_kda_mixer_through_its_kernels(impl, monkeypatch):
    """One KDA mixer at head dims of 128 (the kernels' shapes; ``pallas`` runs
    them in the interpreter) against the reference's, output and every leaf's
    gradient."""
    from beforeholiday_tpu.ops import deltanet, kda

    force = lambda fn: (lambda *a, **kw: fn(*a, **{**kw, "impl": impl}))
    monkeypatch.setattr(kda, "kda_rule", force(kda.kda_rule))
    monkeypatch.setattr(deltanet, "deltanet_qkv", force(deltanet.deltanet_qkv))
    monkeypatch.setattr(deltanet, "deltanet_gate", force(deltanet.deltanet_gate))
    lists = dict(_LISTS, head_dim=128, num_heads=2)
    cfg = dict(SHARE, linear_attn_config=lists, kda_chunk=64, seq_len=96)
    p = reference._group(_weights(cfg, seed=5), "layers.1")
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 96, 64))
    ct = jax.random.normal(jax.random.PRNGKey(7), (1, 96, 64))
    mixer = {k: v for k, v in p.items() if k in model.param_shapes(_mcfg(cfg))["layers"][1]}
    got, pull = jax.vjp(lambda u, p: model.kda_attention(_mcfg(cfg), u, p), u, mixer)
    want, pull_want = jax.vjp(lambda u, p: reference.kda(u, p, cfg, "float32"), u, mixer)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * float(jnp.max(jnp.abs(want)))
    (du, dp), (du_want, dp_want) = pull(ct), pull_want(ct)
    assert float(jnp.max(jnp.abs(du - du_want))) <= 1e-3 * float(jnp.max(jnp.abs(du_want)))
    for name in ("w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_fa", "w_fb", "a_log",
                 "dt_bias", "w_b", "w_ga", "w_gb", "out_norm", "w_o"):
        scale = float(jnp.max(jnp.abs(dp_want[name])))
        assert scale > 0 and float(jnp.max(jnp.abs(dp[name] - dp_want[name]))) <= 1e-3 * scale, name


def test_the_latent_mixer_has_no_rotary():
    """``mla_use_nope``: shifting every position by the same number of tokens
    leaves a query's output where its keys shift with it, which no rotary table
    indexed from zero would; and the mixer is ``models.layers.latent_attention``
    without a table."""
    from beforeholiday_tpu.models import layers

    cfg = _mcfg(SHARE)
    p = reference._group(_weights(SHARE, seed=4), "layers.3")
    u = jax.random.normal(jax.random.PRNGKey(9), (1, 48, 64))
    got = model.latent_attention(cfg, u, p)
    want = reference.latent_attention(u, p, SHARE, "float32")
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(jnp.max(jnp.abs(want)))
    table = layers.rotary_table(48, 8, 1e4)
    turned = layers.latent_attention(
        u, p, heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        eps=1e-5, table=table)
    assert float(jnp.max(jnp.abs(turned - want))) > 1e-3 * float(jnp.max(jnp.abs(want)))
    # a prefix of zeros' rows in front changes nothing for the rows behind it but their index
    pad = jnp.concatenate([jnp.zeros((1, 16, 64)), u[:, :32]], axis=1)
    shifted = model.latent_attention(cfg, pad, p)
    alone = model.latent_attention(cfg, u[:, :32], p)
    # zero rows project to zero keys (score 0) and zero values: they dilute the softmax, so
    # compare the reference on the same padded input instead of the unpadded rows
    want_shifted = reference.latent_attention(pad, p, SHARE, "float32")
    assert float(jnp.max(jnp.abs(shifted - want_shifted))) <= 2e-5 * float(jnp.max(jnp.abs(want)))
    assert alone.shape == (1, 32, 64)


# -- the expert layer ----------------------------------------------------------------

@pytest.mark.parametrize("published,shares", ((256, 32), (16, 4), (16, 2)))
def test_the_shares_add_up_to_the_uncut_reference_layer(published, shares):
    """Expert parallelism over ``shares`` chips (the cell's deployment: 256
    experts over 32, ``first_expert`` 0, 8, ..., 248): each routes over all the
    experts under the whole bias and computes its own routed part and the WHOLE
    shared expert; the routed parts, with the shared expert counted once, add up
    to the uncut reference's layer. What every chip computes alike (the mixers
    too: they are replicated) is counted once."""
    D, F, K, T = 32, 24, 8, 96
    ks = jax.random.split(jax.random.PRNGKey(published + shares), 9)
    n = lambda k, *shape: jax.random.normal(k, shape) * 0.2
    p = {"router": n(ks[0], D, published), "expert_bias": n(ks[5], published),
         "w_gate": n(ks[1], published, D, F), "w_up": n(ks[2], published, D, F),
         "w_down": n(ks[3], published, F, D), "shared_w_gate": n(ks[6], D, F),
         "shared_w_up": n(ks[7], D, F), "shared_w_down": n(ks[8], F, D)}
    x = jax.random.normal(ks[4], (1, T, D))
    base = {"num_experts_per_token": K, "moe_renormalize": True, "routed_scaling_factor": 2.446,
            "num_experts_published": published}
    whole = reference.moe(x, p, dict(base, num_experts=published, first_expert=0), "float32")
    shared = reference.swiglu(x, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"],
                              "float32")
    held, total, rows = published // shares, shared, 0
    for rank in range(shares):
        mine = dict(p, **{k: p[k][rank * held:(rank + 1) * held]
                          for k in ("w_gate", "w_up", "w_down")})
        mcfg = model.KimiLinearConfig(
            hidden_size=D, moe_intermediate_size=F, num_experts_published=published,
            num_experts=held, first_expert=rank * held, num_experts_per_token=K,
            routed_scaling_factor=2.446)
        part, counters = model.sparse_ffn(mcfg, x, mine)
        total, rows = total + (part - shared), rows + float(counters["expert_rows"])
        one = reference.moe(x, mine, dict(base, num_experts=held, first_expert=rank * held),
                            "float32")
        assert float(jnp.max(jnp.abs(part - one))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))
    assert rows == T * K                               # every assignment lands on one share
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))
    assert float(jnp.max(jnp.abs(shared))) > 1e-2 * float(jnp.max(jnp.abs(whole)))


# -- plumbing ---------------------------------------------------------------------

_PUBLISHED = dict(full_attn_layers=[4, 8, 12, 16, 20, 24, 27],
                  kda_layers=[1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
                  head_dim=128, num_heads=32, short_conv_kernel_size=4)


@pytest.mark.parametrize("first,held,want", (
    (0, 5, "K K K M K"), (0, 27, "K K K M " * 6 + "K K M"), (2, 4, "K M K K"), (3, 2, "M K"),
    (22, 5, "K M K K M"),
), ids=("the-cell", "the-whole-model", "mid-period", "latent-first", "the-last-five"))
def test_the_layer_kinds_follow_the_published_lists(first, held, want):
    """The two 1-based lists decide, on the published index: ``first_layer`` 0
    (the cell), the whole depth (whose LAST layer is latent though 27 is no
    multiple of 4) and stretches that start mid-period."""
    cfg = model.KimiLinearConfig(num_hidden_layers=held, first_layer=first,
                                 linear_attn_config=_PUBLISHED)
    mixers = " ".join("K" if m == "kda" else "M" for m, _ in cfg.held)
    assert mixers == want.strip()
    assert [f for _, f in cfg.held] == ["dense" if first + i < 1 else "moe" for i in range(held)]
    assert reference.held({"linear_attn_config": _PUBLISHED, "first_layer": first,
                           "num_hidden_layers": held, "first_k_dense_replace": 1}) \
        == [tuple(k) for k in cfg.held]
    layers = model.param_shapes(cfg)["layers"]       # a layer holds what it needs, no more
    for (mixer, _), leaves in zip(cfg.held, layers):
        assert ("a_log" in leaves) == (mixer == "kda") and ("w_kva" in leaves) == (mixer == "mla")


@pytest.mark.parametrize("bad", (
    {"num_expert_group": 8}, {"topk_group": 4}, {"q_lora_rank": 1536}, {"mla_use_nope": False},
    {"moe_router_activation_func": "softmax"},
), ids=lambda b: "-".join(b))
def test_what_is_not_built_raises(bad):
    with pytest.raises(ValueError):
        model.KimiLinearConfig(**bad)


def test_a_layer_in_both_lists_or_in_neither_raises():
    for lists in (dict(_LISTS, kda_layers=[1, 2, 3, 4]), dict(_LISTS, kda_layers=[1, 2])):
        with pytest.raises(ValueError, match="both or neither"):
            model.KimiLinearConfig(linear_attn_config=lists).held
        with pytest.raises(ValueError, match="both or neither"):
            reference.held(dict(SHARE, linear_attn_config=lists))


def test_the_family_round_trips_the_tree_and_counts():
    for cfg in _SIZES.values():
        flat = family.weights(cfg, jax.random.PRNGKey(0))
        tree = family._to_tree(flat)
        assert len(tree["layers"]) == cfg["num_hidden_layers"]
        back = family._to_flat(tree)
        assert set(back) == set(flat) and all(back[k] is flat[k] for k in flat)    # a rename
        assert family.param_count(cfg) == model.param_count(family.model_config(cfg)) == \
            sum(v.size for v in flat.values())
        shapes = {k: s for k, (s, _) in
                  family._to_flat(model.param_shapes(family.model_config(cfg))).items()}
        assert shapes == {k: s for k, (s, _) in reference.tensor_shapes(cfg).items()}
        assert {k: v.shape for k, v in flat.items()} == shapes


def test_the_init_is_what_the_configuration_states():
    lists = dict(_LISTS, head_dim=64, num_heads=8)
    cfg = dict(SHARE, hidden_size=256, vocab_size=512, embedding_init_std=0.5,
               linear_attn_config=lists)
    flat = family.weights(cfg, jax.random.PRNGKey(1))
    assert 0.45 < float(jnp.std(flat["embed"])) < 0.55          # embedding_init_std
    assert 0.018 < float(jnp.std(flat["head"])) < 0.022          # the head is its own: 0.02
    assert 0.018 < float(jnp.std(flat["layers.0/w_fb"])) < 0.022
    rate, step = jnp.exp(flat["layers.0/a_log"]), jax.nn.softplus(flat["layers.0/dt_bias"])
    assert flat["layers.0/a_log"].shape == (8,) and flat["layers.0/dt_bias"].shape == (512,)
    assert 1.0 <= float(jnp.min(rate)) and float(jnp.max(rate)) <= 16.1
    assert 0.99e-3 <= float(jnp.min(step)) and float(jnp.max(step)) <= 0.101
    assert float(jnp.max(jnp.abs(flat["layers.0/conv_q"]))) <= 0.5    # +-1/sqrt(4 taps)
    assert bool(jnp.all(flat["layers.1/expert_bias"] == 0)) and \
        flat["layers.1/expert_bias"].shape == (16,)
    assert bool(jnp.all(flat["layers.0/out_norm"] == 1.0))
    every = jnp.concatenate([v.reshape(-1) for v in flat.values()])
    assert bool(jnp.all(every.astype(jnp.bfloat16).astype(jnp.float32) == every))
    tree = model.init(jax.random.PRNGKey(1), _mcfg(cfg))         # the program's own draw
    mine = tree["layers"][0]
    assert bool(jnp.all(tree["layers"][1]["expert_bias"] == 0))
    assert 1.0 <= float(jnp.min(jnp.exp(mine["a_log"]))) <= float(jnp.max(jnp.exp(mine["a_log"]))) <= 16.0
    step = jax.nn.softplus(mine["dt_bias"])
    assert 0.99e-3 <= float(jnp.min(step)) and float(jnp.max(step)) <= 0.101


def test_keep_fp32_mask():
    tree = family._to_tree(family.weights(SHARE, jax.random.PRNGKey(0)))
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    kept = {path[-1].key for path, _ in flat if model.keep_fp32(path)}
    assert kept == {"norm", "input_layernorm", "post_attention_layernorm", "kv_a_layernorm",
                    "out_norm", "a_log", "dt_bias", "expert_bias"}
    # final norm; 2 norms a layer; 4 KDA mixers x 3; one latent norm; 4 biases
    assert sum(model.keep_fp32(path) for path, _ in flat) == 1 + 5 * 2 + 4 * 3 + 1 + 4


def test_counters_and_scopes_of_the_step():
    """The step as the benchmark's family wires it (amp O5 + FusedAdam under
    donate_step): counters come out as device scalars, every scope the per-layer
    metrics read is in the program, and Adam leaves the selection bias zeros."""
    from benchmark import run

    cell = run.load("workloads", "tiny-kimi-linear.train")
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
    hlo = c.program.step.jitted.lower(c.state, c.pool[0]).compile().as_text()
    for scope in ("amp_forward", "amp_backward", "amp_unscale", "fused_adam_step_flat",
                  "kimi_linear_embed", "kimi_linear_layers", "kimi_linear_head",
                  "kimi_linear_loss", "kda_mixer", "kda_mixer/kda_proj", "kda_mixer/deltanet_qkv",
                  "kda_mixer/kda_gate_proj", "kda_mixer/kda/", "kda_mixer/deltanet_gate",
                  "mla_mixer", "mla_mixer/mla_latent", "dense_ffn", "flash_attention",
                  "layer_norm", "moe/moe_route", "moe/moe_dispatch", "moe/moe_experts",
                  "moe/moe_shared", "moe/moe_combine"):
        assert scope in hlo, scope


def test_required_operations_at_the_published_widths():
    from benchmark import run

    cfg = run.load("configs", "kimi-linear-48b-a3b")
    D = 2304
    kda = 2 * D + 4 * D * 4096 + 3 * 4096 * 4 + 2 * (D * 128 + 128 * 4096) + 32 + 4096 \
        + D * 32 + 128
    assert kda == 39_514_272 + 2 * D
    mla = 2 * D + D * 6144 + D * 576 + 512 + 512 * 8192 + 4096 * D
    assert mla == 29_114_880 + 2 * D
    dense, expert = 3 * D * 9216, 3 * D * 1024
    moe = D * 256 + 256 + expert + 8 * expert
    assert family.param_count(cfg) == (kda + dense) + 3 * (kda + moe) + (mla + moe) \
        + 2 * 20480 * D + D == 602_434_432
    assert round(16 * family.param_count(cfg) / 1e9, 2) == 9.64
    per = family.matmul_params_per_token(cfg)
    assert per == {"kda": 4 * D * 4096 + 2 * (D * 128 + 128 * 4096) + D * 32,
                   "mla": D * 6144 + D * 576 + 512 * 8192 + 4096 * D, "dense": dense,
                   "moe": D * 256 + expert + 0.25 * expert, "head": 20480 * D}
    assert family.kda_flops_per_item(cfg) == 3 * (3 * 2 * 128 * 128) * 32 * 4 == 37_748_736
    assert family.attention_flops_per_item(cfg) == 6 * 32 * 320 * 8193 / 2
    token = 4 * per["kda"] + per["mla"] + per["dense"] + 4 * per["moe"] + per["head"]
    assert family.model_flops_per_item(cfg) == 6 * token + family.attention_flops_per_item(cfg) \
        + family.kda_flops_per_item(cfg)
    assert 2.2e9 < family.model_flops_per_item(cfg) < 2.4e9
