"""``models/layers.py``: what the five decoder families call and none of them
owns — the grouped-query tail and the QK-normed attention body against a plain
float32 ``einsum`` reference, ``keep_fp32`` against the names each family's own
function kept before PR 45, and the shapes tree's ``param_count`` / ``init``
skeleton."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.models import layers

_CONFIG = {"qwen3_next": "Qwen3NextConfig", "mellum": "MellumConfig",
           "nemotron_h": "NemotronHConfig", "lfm2_moe": "Lfm2MoeConfig",
           "deepseek_v3": "DeepseekV3Config"}


def _family(name):
    model = importlib.import_module(f"beforeholiday_tpu.models.{name}")
    return model, getattr(model, _CONFIG[name])()


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ---------------------------------------------------------------------------
# attention: the plain reference (scores materialised, float32 throughout)
# ---------------------------------------------------------------------------

_B, _S, _HD, _D = 2, 24, 16, 32


def _reference_tail(q, k, v, window):
    """Softmax attention at ``hd^-1/2``: query ``i`` sees keys ``j <= i``, and
    with a window the last ``window`` of them; query head ``h`` reads key head
    ``h // (H / Hkv)``."""
    H, Hkv = q.shape[2], k.shape[2]
    k, v = (jnp.stack([t[:, :, h // (H // Hkv)] for h in range(H)], axis=2) for t in (k, v))
    scores = jnp.einsum("bshd,bthd->bhst", q, k) * q.shape[-1] ** -0.5
    i, j = jnp.arange(q.shape[1])[:, None], jnp.arange(q.shape[1])[None, :]
    seen = (j <= i) if window is None else (j <= i) & (j > i - window)
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", weights, v)


def _reference_body(u, p, table, H, Hkv, eps, window):
    def head_norm_then_rotary(x, w):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w
        cos, sin = (t[None, :, None, :] for t in table)
        x1, x2 = x[..., :_HD // 2], x[..., _HD // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    heads = lambda w, n: jnp.einsum("bsd,dk->bsk", u, w).reshape(_B, _S, n, _HD)
    q = head_norm_then_rotary(heads(p["w_q"], H), p["q_norm"])
    k = head_norm_then_rotary(heads(p["w_k"], Hkv), p["k_norm"])
    ctx = _reference_tail(q, k, heads(p["w_v"], Hkv), window)
    return jnp.einsum("bsk,kd->bsd", ctx.reshape(_B, _S, H * _HD), p["w_o"])


def _value_and_pulled(fn, dy, *args):
    """``fn(*args)`` and its cotangents for ``dy``, as one compiled program."""
    def both(dy, *args):
        y, pull = jax.vjp(fn, *args)
        return y, pull(dy)

    return jax.jit(both)(dy, *args)


def _close(got, want, what):
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        w = dict(jax.tree_util.tree_flatten_with_path(want)[0])[path]
        assert g.shape == w.shape and g.dtype == w.dtype, (what, path)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5,
                                   err_msg=f"{what} {jax.tree_util.keystr(path)}")


# the four families' ratios of query to key heads (DeepSeek-V3 and the test
# models 1, the test models 2, LFM2 4, Qwen3-Next and Mellum 8), a window or none
@pytest.mark.parametrize("window", (None, 8))
@pytest.mark.parametrize("group", (1, 2, 4, 8))
def test_the_grouped_query_tail_is_the_einsum_reference_in_value_and_gradients(group, window):
    Hkv, H = 2, 2 * group
    ks = jax.random.split(jax.random.PRNGKey(group), 4)
    q = jax.random.normal(ks[0], (_B, _S, H, _HD))
    k, v = (jax.random.normal(key, (_B, _S, Hkv, _HD)) for key in ks[1:3])
    dy = jax.random.normal(ks[3], (_B, _S, H, _HD))
    got, grads = _value_and_pulled(
        lambda *a: layers.grouped_query_attention(*a, window=window), dy, q, k, v)
    want, grads_ref = _value_and_pulled(lambda *a: _reference_tail(*a, window), dy, q, k, v)
    assert got.shape == (_B, _S, H, _HD)            # the heads back beside their positions
    _close(got, want, "ctx")
    _close(grads, grads_ref, "gradient")


@pytest.mark.parametrize("window", (None, 8))
@pytest.mark.parametrize("group", (1, 2, 4, 8))
def test_the_qk_normed_body_is_the_einsum_reference_in_value_and_gradients(group, window):
    Hkv, H, eps = 2, 2 * group, 1e-5
    ks = jax.random.split(jax.random.PRNGKey(10 + group), 8)
    u = jax.random.normal(ks[0], (_B, _S, _D))
    p = {"w_q": jax.random.normal(ks[1], (_D, H * _HD)) * 0.2,
         "w_k": jax.random.normal(ks[2], (_D, Hkv * _HD)) * 0.2,
         "w_v": jax.random.normal(ks[3], (_D, Hkv * _HD)) * 0.2,
         "q_norm": 1.0 + 0.1 * jax.random.normal(ks[4], (_HD,)),
         "k_norm": 1.0 + 0.1 * jax.random.normal(ks[5], (_HD,)),
         "w_o": jax.random.normal(ks[6], (H * _HD, _D)) * 0.2}
    dy = jax.random.normal(ks[7], (_B, _S, _D))
    angle = jnp.arange(_S)[:, None] * 1e4 ** (-jnp.arange(_HD // 2) * 2.0 / _HD)[None, :]
    table = (jnp.cos(angle), jnp.sin(angle))
    np.testing.assert_allclose(layers.rotary_table(_S, _HD, 1e4)[1], table[1], atol=1e-5)
    got, grads = _value_and_pulled(lambda u, p: layers.qk_norm_attention(
        u, p, table, heads=H, kv_heads=Hkv, head_dim=_HD, eps=eps, window=window), dy, u, p)
    want, grads_ref = _value_and_pulled(
        lambda u, p: _reference_body(u, p, table, H, Hkv, eps, window), dy, u, p)
    _close(got, want, "y")
    _close(grads, grads_ref, "gradient")


# ---------------------------------------------------------------------------
# keep_fp32: the leaf names each family's own function kept at the parent
# ---------------------------------------------------------------------------

_KEPT = {
    "qwen3_next": {"a_log", "dt_bias", "final_norm", "input_norm", "k_norm", "out_norm",
                   "post_norm", "q_norm"},
    "mellum": {"final_norm", "input_norm", "k_norm", "post_norm", "q_norm"},
    "nemotron_h": {"a_log", "d", "dt_bias", "final_norm", "norm", "out_norm"},
    "lfm2_moe": {"embedding_norm", "expert_bias", "ffn_norm", "k_norm", "operator_norm",
                 "q_norm"},
    "deepseek_v3": {"expert_bias", "input_layernorm", "kv_a_layernorm", "norm",
                    "post_attention_layernorm"},
}
_KEPT_LEAVES = {"qwen3_next": 8, "mellum": 5, "nemotron_h": 8, "lfm2_moe": 83, "deepseek_v3": 16}


@pytest.mark.parametrize("name", sorted(_CONFIG))
def test_keep_fp32_keeps_the_leaves_the_familys_own_function_kept(name):
    model, cfg = _family(name)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        model.param_shapes(cfg), is_leaf=layers.is_shape_leaf)
    kept = [path for path, _ in flat if model.keep_fp32(path)]
    assert {path[-1].key for path in kept} == _KEPT[name]
    assert len(kept) == _KEPT_LEAVES[name] and len(kept) < len(flat)
    # a group named for a kept leaf keeps what lies under it: paths, not last names
    assert layers.keep_fp32((jax.tree_util.DictKey("norms"), jax.tree_util.DictKey("w")))
    assert not layers.keep_fp32((jax.tree_util.DictKey("d"),))
    assert layers.keep_fp32((jax.tree_util.DictKey("D"),), also=("d",))


# ---------------------------------------------------------------------------
# the shapes tree: param_count and init's skeleton, one unrolled family and one stacked
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ("lfm2_moe", "mellum"))
def test_param_count_is_the_size_of_what_init_draws(name):
    model, cfg = _family(name)
    params = model.init(jax.random.PRNGKey(0), cfg)
    assert model.param_count(cfg) == sum(x.size for x in jax.tree.leaves(params)) > 0
    assert model.param_count(cfg) == layers.param_count(model.param_shapes(cfg))


@pytest.mark.parametrize("name", ("lfm2_moe", "mellum"))
def test_init_draws_leaf_i_of_the_flattened_shapes_from_fold_in_i(name):
    """Leaf ``i`` of the shapes tree (keys sorted) is drawn from ``fold_in(key, i)``,
    whatever the other leaves are: the draws the benchmark's references were
    taken against."""
    model, cfg = _family(name)
    key = jax.random.PRNGKey(3)
    shapes = model.param_shapes(cfg)
    seen = []
    tree = layers.draw_params(key, shapes, lambda k, shape, kind: seen.append((k, shape, kind))
                              or jnp.zeros(shape))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=layers.is_shape_leaf)
    assert [(s, kind) for _, s, kind in seen] == [leaf for _, leaf in flat]
    for i, (k, _, _) in enumerate(seen):
        np.testing.assert_array_equal(jax.random.key_data(k),
                                      jax.random.key_data(jax.random.fold_in(key, i)))
    assert jax.tree.structure(tree) == jax.tree.structure(
        jax.tree.map(lambda leaf: 0, shapes, is_leaf=layers.is_shape_leaf))
    # and a family's init is that draw under its own rule: a weight at the
    # configuration's scale from the leaf's own key, the top group lifted beside the others
    params = model.init(key, cfg)
    paths = [path for path, _ in flat]
    i = next(n for n, path in enumerate(paths) if path[-1].key == "w_q")
    inside = params
    for part in paths[i]:
        if getattr(part, "key", None) != "top":
            inside = inside[part.key if hasattr(part, "key") else part.idx]
    want = jax.random.normal(jax.random.fold_in(key, i), flat[i][1][0]) * cfg.initializer_range
    np.testing.assert_array_equal(inside, want)
    assert "top" not in params and "embed" in params


# ---------------------------------------------------------------------------
# the loss: one expression for the head's cotangent (PR 53) against autodiff
# ---------------------------------------------------------------------------


def _autodiff_cross_entropy(logits, targets):
    """The form ``layers.cross_entropy`` had before it took a ``custom_vjp``:
    what autodiff differentiates into softmax x g plus a scatter of -g."""
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked.astype(jnp.float32))


_WRAPPED = {"plain": lambda f: f, "jit": jax.jit, "checkpoint": jax.checkpoint}


@pytest.mark.parametrize("wrap", sorted(_WRAPPED))
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16), ids=("float32", "bfloat16"))
@pytest.mark.parametrize("shape", ((1, 8, 128), (2, 16, 384), (1, 5, 130)),
                         ids=lambda s: "x".join(map(str, s)))
def test_cross_entropy_is_the_autodiff_form_in_value_and_cotangent(shape, dtype, wrap):
    """Value and the logits' cotangent under an upstream cotangent of 3, with
    the first id, the last id and a repeated id among the targets."""
    B, S, V = shape
    logits = (4.0 * jax.random.normal(jax.random.PRNGKey(V), shape)).astype(dtype)
    targets = jax.random.randint(jax.random.PRNGKey(S), (B, S), 0, V)
    targets = targets.at[0, :4].set(jnp.asarray([0, V - 1, 7, 7]))
    g = jnp.float32(3.0)

    want, pull = jax.vjp(_autodiff_cross_entropy, logits, targets)
    got, pull_got = jax.vjp(_WRAPPED[wrap](layers.cross_entropy), logits, targets)
    (d_want, _), (d_got, d_targets) = pull(g), pull_got(g)

    assert got.dtype == jnp.float32 and d_got.dtype == dtype and d_got.shape == shape
    assert d_targets.dtype == jax.dtypes.float0
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # float32: two orders of summation apart; bfloat16: autodiff rounds the two
    # terms and then their sum, the expression rounds once
    peak = float(jnp.max(jnp.abs(d_want.astype(jnp.float32))))
    atol = peak * (2e-6 if dtype == jnp.float32 else 2.0 ** -7)
    np.testing.assert_allclose(d_got.astype(jnp.float32), d_want.astype(jnp.float32),
                               rtol=0, atol=atol)
    # every row's cotangent sums to nothing, and only the target's column is negative
    np.testing.assert_allclose(jnp.sum(d_got.astype(jnp.float32), axis=-1), 0.0,
                               atol=V * atol)
    onehot = jax.nn.one_hot(targets, V, dtype=bool)
    assert bool(jnp.all(jnp.where(onehot, d_got <= 0, d_got >= 0)))


def test_cross_entropy_differentiates_through_jax_grad_of_the_head():
    """As the families call it: ``jax.grad`` through ``logits_of`` to the
    activations and the head's rows, the autodiff form's to float32 rounding."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    head = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (96, 32))
    targets = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 96)
    loss = lambda ce: lambda x, head: ce(layers.logits_of(x, head), targets)
    want = jax.grad(loss(_autodiff_cross_entropy), argnums=(0, 1))(x, head)
    got = jax.grad(loss(layers.cross_entropy), argnums=(0, 1))(x, head)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6 * float(jnp.max(jnp.abs(b))))
