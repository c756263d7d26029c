"""``models/nemotron_h.py`` against the benchmark's plain float32 reference
(``benchmark/reference/nemotron_h.py``: the recurrence token by token, attention
by a materialised mask, every held expert on every token), and the tests that
tie a rank's share to the model: for each kind of block, the parts that all the
tensor- and expert-parallel shares give add up to the uncut reference's block.

Seeded random weights at a small size, float32 at ``highest`` matmul precision.
The program computes the same mathematics in another order (the recurrence by
chunks, flash attention by blocks, rows sorted by expert), so the tolerances are
those of float32 reassociation through five blocks, as ``tests/test_mellum.py``'s:
2e-6 relative on the loss, 1e-3 of each gradient tensor's largest entry."""

import dataclasses
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

from beforeholiday_tpu.models import nemotron_h as model  # noqa: E402
from beforeholiday_tpu.moe import dropless  # noqa: E402
from benchmark.families import nemotron_h as family  # noqa: E402
from benchmark.reference import nemotron_h as reference  # noqa: E402

# a share: 4 of 16 experts from the 8th on, 6 choices a token (more than are held)
CFG = {
    "published": {"num_hidden_layers": 17},
    "hybrid_override_pattern": "MEMEM*EMEM*EMEM*E", "first_layer": 6, "num_hidden_layers": 5,
    "hidden_size": 64, "vocab_size": 96,
    "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 16,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "n_routed_experts": 4, "n_routed_experts_published": 16, "first_expert": 8,
    "num_experts_per_tok": 6, "moe_intermediate_size": 32, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 192, "moe_shared_expert_columns_held": 48,
    "routed_scaling_factor": 5, "norm_topk_prob": True, "moe_rows_bound": None,
    "layer_norm_epsilon": 1e-05, "initializer_range": 0.02, "seq_len": 48,
    "compute_dtype": "float32", "remat_policy": None,
}
WHOLE = {"n_routed_experts": 16, "first_expert": 0, "moe_shared_expert_columns_held": 192}
PATTERN = reference.pattern(CFG)                    # EMEM*


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(cfg, seed=0):
    """Seeded weights with every norm weight and ``D`` off its identity, and
    matmul weights large enough (0.1) that routing and attention are far from
    uniform."""
    flat = family.weights(cfg, jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))

    def perturb(name, v, key):
        leaf = name.split("/")[-1]
        if "norm" in leaf or leaf == "d":
            return v + 0.1 * jax.random.normal(key, v.shape)
        return v if leaf in ("embed", "a_log", "dt_bias", "conv", "conv_bias") else 5.0 * v

    return {k: perturb(k, v, kk) for (k, v), kk in zip(sorted(flat.items()), keys)}


def _batch(cfg, seed=3, rows=2):
    return family.batch(cfg, rows, jax.random.PRNGKey(seed))


def _program_loss(flat, batch, cfg, **overrides):
    mcfg = dataclasses.replace(family.model_config(cfg), **overrides)
    return model.loss_fn(family._to_tree(flat, reference.pattern(cfg)), *batch, mcfg)[0]


@pytest.mark.parametrize("overrides", (
    {}, WHOLE, {"first_layer": 1, "num_hidden_layers": 10}, {"remat_policy": "full"},
    {"first_layer": 0, "num_hidden_layers": 17, **WHOLE}, {"chunk_size": 48}, {"chunk_size": 5},
    {"n_groups": 1}, {"num_key_value_heads": 1}, {"first_layer": 6, "num_hidden_layers": 1},
), ids=("share", "whole-widths", "two-periods", "remat", "the-whole-pattern",
        "one-chunk", "ragged-chunks", "one-group", "one-kv-head", "a-moe-block-alone"))
def test_loss_matches_the_reference(overrides):
    cfg = dict(CFG, **overrides)
    w, batch = _weights(cfg), _batch(cfg)
    got = float(jax.jit(lambda w: _program_loss(w, batch, cfg))(w))
    want = float(jax.jit(lambda w: reference.loss(w, batch, cfg))(w))
    assert abs(got - want) <= 2e-6 * abs(want), (got, want)


def test_the_state_space_kernels_give_the_reference_loss():
    """Through ``ops.ssd``'s Pallas kernels (interpreted here) at widths they take."""
    cfg = dict(CFG, mamba_num_heads=2, mamba_head_dim=64, n_groups=1, ssm_state_size=128,
               chunk_size=128, seq_len=256)
    w, batch = _weights(cfg), _batch(cfg, rows=1)
    got = float(jax.jit(lambda w: _program_loss(w, batch, cfg, ssd_impl="pallas"))(w))
    want = float(jax.jit(lambda w: reference.loss(w, batch, cfg))(w))
    assert abs(got - want) <= 2e-6 * abs(want), (got, want)


def test_each_mechanism_changes_the_loss():
    """What the comparison above would miss if both sides dropped it alike."""
    w, batch = _weights(CFG), _batch(CFG)
    base = float(reference.loss(w, batch, CFG))
    for what, changed in (
            ("the routed scale", dict(CFG, routed_scaling_factor=1)),
            ("the renormalisation", dict(CFG, norm_topk_prob=False)),
            ("which experts are held", dict(CFG, first_expert=0))):
        assert abs(float(reference.loss(w, batch, changed)) - base) > 1e-5 * base, what
    for leaf in ("layers.1/d", "layers.1/a_log", "layers.1/conv_bias", "layers.0/fc2_latent",
                 "layers.0/shared_w_down", "layers.1/out_norm"):
        assert abs(float(reference.loss(dict(w, **{leaf: w[leaf] * 1.5}), batch, CFG)) - base) \
            > 1e-6 * base, leaf


_GRADS = {}
_SHAPES = model.param_shapes(family.model_config(CFG))
_KIND = {"M": "mamba", "E": "moe", "*": "attn"}
_LEAVES = sorted(_SHAPES["top"]) + [
    f"layers.{l}/{name}" for l, kind in enumerate(PATTERN) for name in sorted(_SHAPES[_KIND[kind]])]


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
    want = float(reference.loss(w, batch, CFG))
    low = float(reference.loss(w, batch, CFG, mode="fp8"))
    assert abs(low - want) > 2e-6 * abs(want)


# -- the share and the model ------------------------------------------------------

D = CFG["hidden_size"]
X = jax.random.normal(jax.random.PRNGKey(40), (2, 48, D))


def _close(got, want, what, tol=2e-5):
    assert float(jnp.max(jnp.abs(got - want))) <= tol * float(jnp.max(jnp.abs(want))), what


def _block_weights(kind, cfg, seed):
    """One uncut block's weights, by the reference's names."""
    whole = dict(cfg, **WHOLE, first_layer=0, num_hidden_layers=1,
                 hybrid_override_pattern=kind)
    flat = _weights(whole, seed)
    return whole, {k.split("/")[1]: v for k, v in flat.items() if k.startswith("layers.0/")}


@pytest.mark.parametrize("groups,shares", ((4, 4), (2, 2), (1, 1)))
def test_the_mamba_shares_add_up_to_the_uncut_reference_block(groups, shares):
    """Tensor parallelism over the groups: a rank holds its groups' heads, ``B``
    and ``C``, their columns of ``W_in`` and channels of the convolution and the
    gated norm, and their rows of ``W_out``; the out-projections' partial sums
    add up. (``n_groups`` is there to be the tensor-parallel width.)"""
    cfg = dict(CFG, mamba_num_heads=8, n_groups=groups)
    whole, p = _block_weights("M", cfg, 50)
    H, P, G, N = 8, cfg["mamba_head_dim"], groups, cfg["ssm_state_size"]
    d_in = H * P
    want = reference.mamba(X, p, whole, "float32")
    hs, gs = H // shares, G // shares
    total = jnp.zeros_like(want)
    for r in range(shares):
        heads = np.arange(r * hs, (r + 1) * hs)
        chan = (heads[:, None] * P + np.arange(P)).ravel()                 # of d_in
        state = (np.arange(r * gs, (r + 1) * gs)[:, None] * N + np.arange(N)).ravel()
        conv = np.concatenate([chan, d_in + state, d_in + G * N + state])   # x | B | C
        cols = np.concatenate([chan, d_in + conv, 2 * d_in + 2 * G * N + heads])
        mine = {"w_in": p["w_in"][:, cols], "conv": p["conv"][conv],
                "conv_bias": p["conv_bias"][conv], "a_log": p["a_log"][heads],
                "dt_bias": p["dt_bias"][heads], "d": p["d"][heads],
                "out_norm": p["out_norm"][chan], "w_out": p["w_out"][chan]}
        mcfg = dataclasses.replace(family.model_config(whole), mamba_num_heads=hs, n_groups=gs)
        total = total + model.mamba2_mixer(mcfg, X, mine)
    _close(total, want, f"{shares} shares of {groups} groups")


@pytest.mark.parametrize("kv_heads,shares", ((2, 2), (2, 4), (1, 4), (4, 1)))
def test_the_attention_shares_add_up_to_the_uncut_reference_block(kv_heads, shares):
    """Tensor parallelism over the query heads; where the ranks outnumber the KV
    heads, several hold (and repeat) the same one."""
    cfg = dict(CFG, num_attention_heads=4, num_key_value_heads=kv_heads)
    whole, p = _block_weights("*", cfg, 51)
    H, hd = 4, cfg["head_dim"]
    want = reference.attention(X, p, whole, "float32")
    qs = H // shares
    kvs = max(kv_heads // shares, 1)
    total = jnp.zeros_like(want)
    for r in range(shares):
        q = (np.arange(r * qs, (r + 1) * qs)[:, None] * hd + np.arange(hd)).ravel()
        first_kv = r * qs // (H // kv_heads)
        kv = (np.arange(first_kv, first_kv + kvs)[:, None] * hd + np.arange(hd)).ravel()
        mine = {"w_q": p["w_q"][:, q], "w_k": p["w_k"][:, kv], "w_v": p["w_v"][:, kv],
                "w_o": p["w_o"][q]}
        mcfg = dataclasses.replace(family.model_config(whole), num_attention_heads=qs,
                                   num_key_value_heads=kvs)
        total = total + model.attention(mcfg, X, mine)
    _close(total, want, f"{shares} shares of {kv_heads} KV heads")


@pytest.mark.parametrize("ep,tp", ((4, 2), (16, 4), (2, 2), (1, 1)))
def test_the_latent_moe_shares_add_up_to_the_uncut_reference_block(ep, tp):
    """Expert parallelism folded over tensor parallelism, as the deployment's:
    rank ``r`` of ``ep`` holds its slice of the routed experts and columns
    ``r mod tp`` of the shared expert. Router, latent projections and norm are
    replicated; the shared expert's columns of ranks past the first ``tp`` are
    the same columns again (other data-parallel replicas' in the deployment),
    so they are counted once."""
    whole, p = _block_weights("E", CFG, 52)
    E, Fs = whole["n_routed_experts_published"], whole["moe_shared_expert_intermediate_size"]
    want = reference.moe(X, p, whole, "float32")
    held, cols = E // ep, Fs // tp
    total = jnp.zeros_like(want)
    for r in range(ep):
        mine = dict(p, w_up=p["w_up"][r * held:(r + 1) * held],
                    w_down=p["w_down"][r * held:(r + 1) * held],
                    shared_w_up=p["shared_w_up"][:, (r % tp) * cols:(r % tp + 1) * cols],
                    shared_w_down=p["shared_w_down"][(r % tp) * cols:(r % tp + 1) * cols])
        if r >= tp:                                  # its columns are counted already
            mine = {k: v for k, v in mine.items() if not k.startswith("shared_")}
        mcfg = dataclasses.replace(family.model_config(whole), n_routed_experts_held=held,
                                   first_expert=r * held)
        y, counters = model.latent_moe(mcfg, X, mine)
        assert int(counters["dropped_rows"]) == 0
        total = total + y.astype(jnp.float32)
    _close(total, want, f"ep {ep} x tp {tp}")


def test_one_share_matches_the_reference_given_the_same_share():
    cfg = dict(CFG, hybrid_override_pattern="E", first_layer=0, num_hidden_layers=1)
    p = {k.split("/")[1]: v for k, v in _weights(cfg, 53).items() if k.startswith("layers.0/")}
    got, _ = model.latent_moe(family.model_config(cfg), X, p)
    _close(got, reference.moe(X, p, cfg, "float32"), "one share")


# -- the family, the configuration, the counts ------------------------------------

def test_the_seeded_weights_are_the_references_own_draw_at_the_programs_shapes():
    """The benchmark draws both sides' weights in the reference's file, by
    nothing of the program: the shapes must be the program's, the draws the
    configuration's ``assumed.weights``, every value a bfloat16."""
    flat = family.weights(CFG, jax.random.PRNGKey(3))
    mine = family._to_flat(model.init(jax.random.PRNGKey(3), family.model_config(CFG)), PATTERN)
    assert {k: v.shape for k, v in flat.items()} == {k: v.shape for k, v in mine.items()}
    for name, v in flat.items():
        assert v.dtype == jnp.float32, name
        np.testing.assert_array_equal(v, v.astype(jnp.bfloat16).astype(jnp.float32), name)
    leaf = lambda short: np.concatenate(
        [np.ravel(v) for k, v in flat.items() if k.split("/")[-1] == short])
    assert np.std(leaf("w_in")) == pytest.approx(0.02, rel=0.03)
    assert np.std(leaf("w_out")) == pytest.approx(0.02 / np.sqrt(17), rel=0.03)   # the whole depth
    assert np.std(flat["head"]) == pytest.approx(0.02, rel=0.03)
    assert 0.0 <= leaf("a_log").min() and leaf("a_log").max() <= np.log(16.0) + 0.01
    step = np.log1p(np.exp(leaf("dt_bias").astype(np.float64)))           # softplus
    assert 1e-4 * 0.99 <= step.min() and step.max() <= 0.1 * 1.01
    assert np.abs(leaf("conv")).max() <= 0.5 and np.abs(leaf("conv_bias")).max() <= 0.5
    for short in ("norm", "out_norm", "final_norm", "d"):
        assert np.all(leaf(short) == 1.0), short
    other = family.weights(CFG, jax.random.PRNGKey(4))
    assert not np.array_equal(other["layers.0/router"], flat["layers.0/router"])
    assert not np.array_equal(flat["layers.0/router"], flat["layers.2/router"])


def test_the_family_round_trips_the_tree_and_counts():
    flat = family.weights(CFG, jax.random.PRNGKey(0))
    tree = family._to_tree(flat, PATTERN)
    assert set(tree) == {"embed", "head", "final_norm", "mamba", "moe", "attn"}
    assert tree["mamba"]["w_in"].shape[0] == 2 and tree["moe"]["w_up"].shape[:2] == (2, 4)
    assert tree["attn"]["w_q"].shape[0] == 1
    back = family._to_flat(tree, PATTERN)
    assert set(back) == set(flat) == set(_LEAVES)
    for k in flat:
        np.testing.assert_array_equal(back[k], flat[k])
    assert sum(int(v.size) for v in flat.values()) == family.param_count(CFG)
    # layers.1 is the pattern's first M, layers.2 its second E
    np.testing.assert_array_equal(flat["layers.1/a_log"], tree["mamba"]["a_log"][0])
    np.testing.assert_array_equal(flat["layers.2/router"], tree["moe"]["router"][1])


def test_the_published_widths_count_what_the_issue_counted():
    from benchmark import run

    cfg = run.load("configs", "nemotron-3-super-120b-a12b")
    assert reference.pattern(cfg) == "EMEMEMEMEM*"
    mcfg = family.model_config(cfg)
    shapes = model.param_shapes(mcfg)
    count = lambda group: sum(math.prod(s) for s, _ in shapes[group].values())
    assert count("mamba") // 5 == 13_708_592            # 13.71M a Mamba block
    assert count("attn") == 5_246_976                    # 5.25M
    assert count("moe") // 5 == 60_035_072               # 60.04M
    assert count("top") == 2 * 16384 * 4096 + 4096
    assert round(family.param_count(cfg) / 1e6, 1) == 508.2
    assert mcfg.rescale_layers == 88 and mcfg.n_routed_experts == 512


@pytest.mark.parametrize("pattern,period,periods", (
    ("EMEMEMEMEM*", "EMEMEMEMEM*", 1), ("EMEM*EMEM*", "EMEM*", 2), ("MMMM", "M", 4),
    ("MEM*E", "MEM*E", 1), ("E", "E", 1)))
def test_the_period_is_the_shortest_prefix_the_pattern_repeats(pattern, period, periods):
    cfg = model.NemotronHConfig(hybrid_override_pattern=pattern)
    assert (cfg.period, cfg.periods) == (period, periods)


@pytest.mark.parametrize("bad", (
    {"hybrid_override_pattern": "MEX"}, {"hybrid_override_pattern": ""},
    {"mamba_num_heads": 3, "n_groups": 2}, {"num_attention_heads": 3, "num_key_value_heads": 2}))
def test_a_stack_that_is_not_whole_is_refused(bad):
    with pytest.raises(ValueError):
        model.NemotronHConfig(**bad).period


def test_a_pattern_without_a_kind_has_no_parameters_of_it():
    cfg = model.NemotronHConfig(hybrid_override_pattern="MMEM")
    params = model.init(jax.random.PRNGKey(0), cfg)
    assert "attn" not in params and params["mamba"]["w_in"].shape[0] == 3
    tokens = jnp.zeros((1, 16), jnp.int32)
    logits, counters = model.forward(params, tokens, cfg)
    assert logits.shape == (1, 16, cfg.vocab_size) and float(counters["expert_rows"]) == 16 * 4
    _, none = model.forward(model.init(jax.random.PRNGKey(0), dataclasses.replace(
        cfg, hybrid_override_pattern="M*")), tokens, dataclasses.replace(
        cfg, hybrid_override_pattern="M*"))
    assert {k: float(v) for k, v in none.items()} == dict.fromkeys(model.COUNTERS, 0.0)


def test_the_init_is_the_published_modelling_codes():
    cfg = dataclasses.replace(family.model_config(CFG), hidden_size=256, rescale_layers=64)
    p = model.init(jax.random.PRNGKey(7), cfg)
    a = jnp.exp(p["mamba"]["a_log"])
    assert float(a.min()) >= 1.0 and float(a.max()) <= 16.0
    step = jax.nn.softplus(p["mamba"]["dt_bias"])
    assert float(step.min()) >= 0.001 * (1 - 1e-4) and float(step.max()) <= 0.1 * (1 + 1e-4)
    assert bool(jnp.all(p["mamba"]["d"] == 1)) and bool(jnp.all(p["final_norm"] == 1))
    assert abs(float(jnp.std(p["mamba"]["w_in"])) - 0.02) < 2e-3
    assert abs(float(jnp.std(p["mamba"]["w_out"])) - 0.02 / 8) < 3e-4     # over sqrt(64)
    assert abs(float(jnp.std(p["moe"]["fc1_latent"])) - 0.02) < 2e-3
    assert float(jnp.max(jnp.abs(p["mamba"]["conv"]))) <= 0.5


def test_keep_fp32_mask():
    params = model.init(jax.random.PRNGKey(0), family.model_config(CFG))
    kept = {jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(params)
            if model.keep_fp32(path)}
    assert kept == {"['final_norm']", "['mamba']['norm']", "['mamba']['out_norm']",
                    "['mamba']['a_log']", "['mamba']['dt_bias']", "['mamba']['d']",
                    "['moe']['norm']", "['attn']['norm']"}


def test_required_operations_at_the_published_widths():
    from benchmark import run

    cfg = run.load("configs", "nemotron-3-super-120b-a12b")
    per = family.matmul_params_per_token(cfg)
    assert per["M"] == 4096 * (2 * 1024 + 2 * 128 + 16) + 1024 * 4096
    assert per["*"] == 2 * 4096 * 512 + 2 * 4096 * 128
    assert per["E"] == 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 672 \
        + (22 * 8 / 512) * 2 * 1024 * 2688
    assert per["head"] == 16384 * 4096
    matmul = 5 * per["M"] + 5 * per["E"] + per["*"] + per["head"]
    assert round(matmul / 1e6) == 230 and round(100 * per["head"] / matmul) == 29
    attn = 6 * 8192 * 4 * 128
    # per head 2 N P in + 2 N P out; in the chunk (128 + 1) / 2 tokens seen: 2 P each a
    # head, 2 N each a group; forward, and twice that backward; five blocks
    ssd = 3 * 5 * (16 * (4 * 128 * 64 + 64.5 * 2 * 64) + 1 * 64.5 * 2 * 128)
    assert family.attention_flops_per_item(cfg) == attn
    assert family.ssd_flops_per_item(cfg) == ssd
    assert family.model_flops_per_item(cfg) == 6 * matmul + attn + ssd
