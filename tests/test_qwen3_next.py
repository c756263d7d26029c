"""``models/qwen3_next.py`` against the benchmark's plain float32 reference
(``benchmark/reference/qwen3_next.py``: the recurrence token by token, attention
by materialised scores, every expert on every token).

Seeded random weights at a small size, float32 at ``highest`` matmul precision.
The program computes the same mathematics in another order (chunk-wise delta
rule, flash attention by blocks, rows sorted by expert), so the tolerances are
those of float32 reassociation through four layers: 2e-6 relative on the loss
(measured 2e-7), 1e-3 of each gradient tensor's largest entry (measured up to
3e-5 on the matrices and 2e-4 on ``a_log`` and ``dt_bias``, whose gradients are
sums over the tokens of terms that nearly cancel; the test's decay is set mild,
e^-0.03 to e^-0.7 a token, because at the published init's e^-16 those two
gradients sink to 1e-7, where float32 noise is all that is compared). A
product rounded to fp8 moves the loss by 1e-3 and a gradient by 1e-2: it fails
both."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from beforeholiday_tpu.models import qwen3_next as model  # noqa: E402
from benchmark.families import qwen3_next as family  # noqa: E402
from benchmark.reference import qwen3_next as reference  # noqa: E402

CFG = {
    "full_attention_interval": 4, "num_hidden_layers": 4, "hidden_size": 64, "vocab_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "num_experts": 4, "num_experts_published": 16, "first_expert": 8, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "moe_rows_bound": None, "gated_delta_chunk": 16, "rms_norm_eps": 1e-06, "seq_len": 40,
    "compute_dtype": "float32", "remat_policy": None,
}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _weights(cfg, seed=0):
    """Seeded weights with every tensor off its identity, so that no gradient
    is trivially zero (the zero-centred norms start at 0 in a real init)."""
    flat = family.weights(cfg, jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    def perturb(name, v, key):
        if "norm" in name:
            return v + 0.1 * jax.random.normal(key, v.shape)
        if name.endswith("a_log"):      # a decay of e^-0.03..e^-0.7 a token: the state
            return jnp.log(jnp.linspace(0.02, 0.5, v.size)).reshape(v.shape)   # remembers
        return v

    return {k: perturb(k, v, kk) for (k, v), kk in zip(sorted(flat.items()), keys)}


def _batch(cfg, seed=3, rows=2):
    return family.batch(cfg, rows, jax.random.PRNGKey(seed))


def _program_loss(flat, batch, cfg):
    return model.loss_fn(family._to_tree(flat), *batch, family.model_config(cfg))[0]


@pytest.mark.parametrize("overrides", (
    {}, {"first_expert": 0, "num_experts": 16}, {"seq_len": 32, "gated_delta_chunk": 32},
    {"num_hidden_layers": 8}, {"remat_policy": "full"},
), ids=("share", "all-experts", "one-chunk", "two-periods", "remat"))
def test_loss_matches_the_reference(overrides):
    cfg = dict(CFG, **overrides)
    w, batch = _weights(cfg), _batch(cfg)
    got = float(jax.jit(lambda w: _program_loss(w, batch, cfg))(w))
    want = float(jax.jit(lambda w: reference.loss(w, batch, cfg))(w))
    assert abs(got - want) <= 2e-6 * abs(want), (got, want)


def _gradients():
    w, batch = _weights(CFG), _batch(CFG)
    got = jax.jit(jax.grad(lambda w: _program_loss(w, batch, CFG)))(w)
    want = jax.jit(jax.grad(lambda w: reference.loss(w, batch, CFG)))(w)
    return got, want


_GRADS = {}


@pytest.mark.parametrize("leaf", sorted(family.weight_shapes(CFG)))
def test_every_gradient_leaf_matches_the_reference(leaf):
    if not _GRADS:
        _GRADS["got"], _GRADS["want"] = _gradients()
    got, want = _GRADS["got"][leaf], _GRADS["want"][leaf]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, f"{leaf}: the reference's gradient is all zero"
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-3 * scale, leaf


def test_an_fp8_product_would_fail_the_tolerances():
    w, batch = _weights(CFG), _batch(CFG)
    sound = float(reference.loss(w, batch, CFG))
    control = float(reference.loss(w, batch, CFG, mode="fp8"))
    assert abs(control - sound) > 50 * 2e-6 * abs(sound)


def test_the_family_round_trips_the_tree():
    cfg = dict(CFG, num_hidden_layers=8)
    flat = family.weights(cfg, jax.random.PRNGKey(0))
    assert set(flat) == set(family.weight_shapes(cfg))
    assert all(flat[k].shape == shape for k, (shape, _) in family.weight_shapes(cfg).items())
    back = family._to_flat(family._to_tree(flat))
    assert all(bool(jnp.array_equal(back[k], flat[k])) for k in flat)
    assert family.param_count(cfg) == model.param_count(family.model_config(cfg))


# -- the pieces, each against the reference's lines -----------------------------

def test_rope_rotates_the_first_quarter_only():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 32))
    got, want = model.rope_partial(x, 8, 1e7), reference.rope(x, 8, 1e7)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])       # passed through
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)      # position 0
    assert float(jnp.max(jnp.abs(got[:, 1:, :, :8] - x[:, 1:, :, :8]))) > 0.1
    # a rotation: the norm of each pair (i, i + 4) is kept
    pair = lambda t: t[..., :4] ** 2 + t[..., 4:8] ** 2
    np.testing.assert_allclose(pair(got), pair(x), rtol=1e-5)


def test_zero_centred_norm():
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 64)) * 3.0
    w = jax.random.normal(jax.random.PRNGKey(2), (64,)) * 0.1
    np.testing.assert_allclose(model.rms_norm0(x, w, 1e-6), reference.rms0(x, w, 1e-6),
                               rtol=2e-6, atol=2e-6)
    # a zero weight is the identity scale: unit root-mean-square
    unit = model.rms_norm0(x, jnp.zeros(64), 1e-6)
    np.testing.assert_allclose(jnp.sqrt(jnp.mean(unit ** 2, -1)), 1.0, rtol=1e-5)


def test_causal_depthwise_conv():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 6))
    w = jax.random.normal(jax.random.PRNGKey(4), (6, 4))
    got = model._layers.causal_depthwise_conv(x, w)
    np.testing.assert_allclose(got, reference.causal_conv(x, w), rtol=1e-6, atol=1e-6)
    # against lax.conv: torch's Conv1d(groups=C, padding=K-1) cut to the length
    want = jax.lax.conv_general_dilated(
        x.transpose(0, 2, 1), w[:, None, :], (1,), [(3, 0)], feature_group_count=6,
        precision="highest").transpose(0, 2, 1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    later = x.at[:, 7:].set(0.0)          # causal: the past does not see the future
    np.testing.assert_array_equal(model._layers.causal_depthwise_conv(later, w)[:, :7], got[:, :7])


@pytest.mark.parametrize("impl", ("pallas", "jnp"))
def test_gated_attention_shares_each_kv_head(impl):
    """GQA by repetition through flash attention against the reference's
    materialised scores (4 query heads on 2 KV heads), gate and RoPE included."""
    cfg = dict(CFG, seq_len=128)
    w = _weights(cfg, seed=5)
    mp = reference._group(w, "attn.0")
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 64))
    mcfg = family.model_config(cfg)
    mcfg = mcfg.__class__(**{**mcfg.__dict__, "attention_impl": impl})
    got = model.gated_attention(mcfg, x, mp)
    want = reference.gated_attention(x, mp, cfg, "float32")
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(jnp.max(jnp.abs(want)))


def test_gated_delta_net_layer():
    cfg = dict(CFG, seq_len=40)
    w = _weights(cfg, seed=7)
    mp = reference._group(w, "linear.1")
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 40, 64))
    got = model.gated_delta_net(family.model_config(cfg), x, mp)
    want = reference.gated_delta_net(x, mp, cfg, "float32")
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(jnp.max(jnp.abs(want)))


# the kernels of ``ops/deltanet.py`` take head dims of 128 and whole tiles of 16
# rows: the small model at those, three tiles a sequence
_KERNEL_CFG = dict(CFG, linear_key_head_dim=128, linear_value_head_dim=128, seq_len=48)
_KERNEL_GRADS = {}


def _with_the_deltanet_kernels(fn, layers=3):
    """``fn()`` with ``ops.deltanet`` dispatching its kernels (under the
    interpreter here) where the CPU would take the chain; ``layers`` DeltaNet
    layers in what ``fn`` traces, and ``guard.dispatch`` counts each."""
    from beforeholiday_tpu.guard import dispatch
    from beforeholiday_tpu.ops import deltanet

    shared = deltanet._dispatch

    def kernels(op, impl, available, why, *arrays, statics):
        assert impl is None and available, (op, why)
        return "pallas", False          # the chip's answer: unforced, so probed and counted

    deltanet._dispatch = kernels
    dispatch.reset_dispatch_counters()
    try:
        out = fn()
    finally:
        deltanet._dispatch = shared
    counted = {k[0]: v for k, v in dispatch.dispatch_counters().items()}
    for op in ("deltanet_qkv", "deltanet_gate"):
        assert (counted[op]["pallas"], counted[op]["jnp"]) == (layers, 0), (op, counted[op])
    return out


def _kernel_and_chain():
    if not _KERNEL_GRADS:
        w, batch = _weights(_KERNEL_CFG), _batch(_KERNEL_CFG)
        both = lambda: jax.jit(jax.value_and_grad(       # a fresh function each: its own trace
            lambda w: _program_loss(w, batch, _KERNEL_CFG)))(w)
        _KERNEL_GRADS["chain"] = both()
        _KERNEL_GRADS["kernel"] = _with_the_deltanet_kernels(both)
    return _KERNEL_GRADS["kernel"], _KERNEL_GRADS["chain"]


def test_the_loss_is_the_same_with_the_deltanet_kernels_and_with_the_chain():
    (got, _), (want, _) = _kernel_and_chain()
    assert abs(float(got) - float(want)) <= 2e-6 * abs(float(want)), (got, want)


@pytest.mark.parametrize("leaf", sorted(family.weight_shapes(CFG)))
def test_every_gradient_leaf_is_the_same_with_the_deltanet_kernels_and_with_the_chain(leaf):
    (_, got), (_, want) = _kernel_and_chain()
    got, want = got[leaf], want[leaf]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, f"{leaf}: the chain's gradient is all zero"
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-3 * scale, leaf


def test_the_layer_with_the_deltanet_kernels_matches_the_reference():
    cfg = dict(_KERNEL_CFG)
    w = _weights(cfg, seed=7)
    mp = reference._group(w, "linear.1")
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 48, 64))
    got = _with_the_deltanet_kernels(
        lambda: jax.jit(lambda x: model.gated_delta_net(family.model_config(cfg), x, mp))(x),
        layers=1)
    want = reference.gated_delta_net(x, mp, cfg, "float32")
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * float(jnp.max(jnp.abs(want)))


def test_keep_fp32_mask():
    tree = family._to_tree(family.weights(CFG, jax.random.PRNGKey(0)))
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    kept = {"/".join(str(p.key) for p in path) for path, _ in flat if model.keep_fp32(path)}
    assert kept == {"final_norm", "layers/input_norm", "layers/post_norm", "attn/q_norm",
                    "attn/k_norm", "linear/out_norm", "linear/a_log", "linear/dt_bias"}


def test_counters_and_scopes_of_the_step():
    """The step as the benchmark's family wires it: counters come out as device
    scalars, and every scope the per-layer metrics read is in the program."""
    from benchmark import run

    cell = run.load("workloads", "tiny-qwen3-next.train")
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
                  "qwen3n_embed", "qwen3n_layers", "qwen3n_head", "qwen3n_loss",
                  "linear_mixer", "attn_mixer", "gated_delta/gated_delta_scan",
                  "flash_attention", "layer_norm", "moe/moe_route", "moe/moe_dispatch",
                  "moe/moe_experts", "moe/moe_shared", "moe/moe_combine"):
        assert scope in hlo, scope


def test_required_operations_at_the_published_widths():
    from benchmark import run

    cfg = run.load("configs", "qwen3-next-80b-a3b")
    assert family.param_count(cfg) == 625_667_136          # ISSUE 26: 625.7M, 10.01 GB at 16 B
    linear, attn, moe, head = family._matmul_params_per_token(cfg)
    assert (linear, attn) == (33_685_504, 27_262_976)
    assert moe == 2048 * 512 + 2048 + 3 * 2048 * 512 + 0.625 * 3 * 2048 * 512
    assert family.attention_flops_per_item(cfg) == 6 * 8192 * 4096
    assert family.gated_delta_flops_per_item(cfg) == 18 * 128 * 128 * 32 * 3
    total = family.model_flops_per_item(cfg)
    assert total == 6 * (3 * linear + attn + 4 * moe + 18992 * 2048) + 6 * 8192 * 4096 \
        + 18 * 128 * 128 * 32 * 3
    assert 1.2e9 < total < 1.5e9                           # ISSUE 26: 1.38 GFLOP a token


def test_rope_partial_on_the_shared_table_is_bit_for_bit_what_it_was():
    """``rope_partial`` is now a call of ``models.layers``; the parent's own
    lines (a0dd9e5), kept here, give the same bits."""
    def parents(x, rotary_dim, theta):
        half = rotary_dim // 2
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
        angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:rotary_dim].astype(jnp.float32)
        rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return jnp.concatenate([rotated.astype(x.dtype), x[..., rotary_dim:]], axis=-1)

    for dtype, rotary_dim, theta in ((jnp.float32, 64, 1e7), (jnp.bfloat16, 64, 1e7),
                                     (jnp.float32, 8, 1e4)):
        x = jax.random.normal(jax.random.PRNGKey(9), (2, 300, 3, 256), dtype)
        np.testing.assert_array_equal(np.asarray(model.rope_partial(x, rotary_dim, theta)),
                                      np.asarray(parents(x, rotary_dim, theta)))
        np.testing.assert_array_equal(
            np.asarray(jax.jit(model.rope_partial, static_argnums=(1, 2))(x, rotary_dim, theta)),
            np.asarray(jax.jit(parents, static_argnums=(1, 2))(x, rotary_dim, theta)))
