"""LFM2-MoE forward and loss in plain float32 ``jax.numpy``, one chip's share.

Written from the published configuration
(huggingface.co/LiquidAI/LFM2-8B-A1B, ``config.json``, ``model_type``
``lfm2_moe``). No kernel, no sorting of tokens, masks materialised, nothing
imported from the program. Bias-free throughout; ``rms(u, w) = w * u /
sqrt(mean(u^2) + eps)``, ``eps = norm_eps``.

* Layer ``l`` (published index): ``h = x + op_l(rms(x, operator_norm_l))``,
  ``y = h + ffn_l(rms(h, ffn_norm_l))``. ``op_l`` is the short convolution where
  ``layer_types[l] == "conv"``, attention where it is ``"full_attention"``;
  ``ffn_l`` is a dense SwiGLU of width ``intermediate_size`` for ``l <
  num_dense_layers`` and the mixture of experts after. After the last layer
  ``rms(., embedding_norm)``, then the head, which is the embedding matrix.
* Short convolution, ``K = conv_L_cache`` taps, no bias: ``[B | C | x~] = u
  W_in`` (``D -> 3 D``, split in that order); ``z = B * x~``; ``c[t] = sum_{j <
  K} w[:, j] * z[t - (K - 1) + j]`` (depthwise, causal, zeros before the start);
  ``y = (C * c) W_out``. No activation function.
* Attention: ``q = u W_q`` on ``num_attention_heads`` heads of ``hidden_size /
  num_attention_heads``, ``k``, ``v`` on ``num_key_value_heads``; ``q, k``
  through ``rms`` over the head (one weight of ``head_dim``); rotary embedding
  on the whole head (``rotate_half``: dim ``i`` pairs with ``i + head_dim /
  2``), ``f_i = rope_theta^(-2i / head_dim)``, no scaling; scores ``q_i . k_j /
  sqrt(head_dim)`` kept where ``j <= i``; softmax; a KV head shared by
  consecutive query heads; ``W_o``. A block of queries at a time against a
  materialised mask.
* Mixture of experts: ``s = sigmoid(u W_r)`` over all ``num_experts_published``
  outputs; the ``num_experts_per_tok`` largest of ``s + b`` (``b``,
  ``expert_bias``: it enters the choice only); ``w = s[idx] / (sum s[idx] +
  1e-6) * routed_scaling_factor`` (``norm_topk_prob``); ``sum_e w_e W_d^e
  (silu(W_g^e x) * W_u^e x)``. No shared expert. Every held expert is run on
  every token and weighted (zero where it was not chosen): a loop over the
  experts.

**The share.** ``cfg["num_experts"]`` experts are held here, ids
``first_expert .. first_expert + num_experts - 1`` of the router's
``num_experts_published`` outputs; the sum over ``e`` runs over the chosen
experts that are among them, the router's normalisation over all the chosen.
The vocabulary is a slice: embedding, head and loss are over ``vocab_size`` ids.
The layers held are ``first_layer .. first_layer + num_hidden_layers - 1`` of
the published ``layer_types``, and ``l < num_dense_layers`` is decided on the
published index.

Departures from the published model: no update rule for the selection bias (it
is data: no gradient reaches it and the optimizer leaves it) and no auxiliary
balancing loss (``config.json`` has a key for neither); the cuts of depth,
experts held and vocabulary that the configuration's file states. Each layer
is recomputed in the backward pass (``jax.checkpoint``): memory, not values.

Weights are a flat ``{name: array}`` dict, one entry per tensor of the model,
none stacked on a layer axis: ``embed`` ``(V, D)`` (the head too),
``embedding_norm``, ``layers.<i>/*`` for the ``i``-th held layer, with the names
of its mixer (``operator_norm``; ``w_in``, ``conv``, ``w_out`` or ``w_q``,
``w_k``, ``w_v``, ``q_norm``, ``k_norm``, ``w_o``) and of its feed-forward part
(``ffn_norm``, ``w_gate``, ``w_up``, ``w_down``; with experts those three
stacked over the held experts, ``router`` and ``expert_bias``).
"""

import math

import jax
import jax.numpy as jnp

from . import precision as prec

STACKED_PREFIX = None              # no tensor is stacked on a layer axis
_QUERY_BLOCK = 512
_ROUTER_EPS = 1e-6


def held(cfg):
    """``[(mixer kind, "dense" | "moe")]`` of the layers held."""
    first = cfg["first_layer"]
    kinds = cfg["layer_types"][first:first + cfg["num_hidden_layers"]]
    return [(kind, "dense" if first + i < cfg["num_dense_layers"] else "moe")
            for i, kind in enumerate(kinds)]


def tensor_shapes(cfg):
    """``{name: (shape, draw)}`` of every tensor of the share, flat; ``draw``
    names a case of :func:`weights`."""
    D, K = cfg["hidden_size"], cfg["conv_L_cache"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, F, Fm = D // H, cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, Eh = cfg["num_experts_published"], cfg["num_experts"]
    part = {
        "conv": {"operator_norm": ((D,), "one"), "w_in": ((D, 3 * D), "std"),
                 "conv": ((D, K), "conv"), "w_out": ((D, D), "std")},
        "full_attention": {"operator_norm": ((D,), "one"), "w_q": ((D, H * hd), "std"),
                           "w_k": ((D, Hkv * hd), "std"), "w_v": ((D, Hkv * hd), "std"),
                           "q_norm": ((hd,), "one"), "k_norm": ((hd,), "one"),
                           "w_o": ((H * hd, D), "std")},
        "dense": {"ffn_norm": ((D,), "one"), "w_gate": ((D, F), "std"),
                  "w_up": ((D, F), "std"), "w_down": ((F, D), "std")},
        "moe": {"ffn_norm": ((D,), "one"), "router": ((D, E), "std"),
                "w_gate": ((Eh, D, Fm), "std"), "w_up": ((Eh, D, Fm), "std"),
                "w_down": ((Eh, Fm, D), "std")},
    }
    if cfg["use_expert_bias"]:
        part["moe"]["expert_bias"] = ((E,), "bias")
    out = {"embed": ((cfg["vocab_size"], D), "std"), "embedding_norm": ((D,), "one")}
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((cfg["vocab_size"], D), "std")
    for i, (mixer, ffn) in enumerate(held(cfg)):
        out.update({f"layers.{i}/{name}": leaf
                    for name, leaf in {**part[mixer], **part[ffn]}.items()})
    return out


def weights(cfg, key):
    """The seeded float32 weights of the share, flat (the configuration's
    ``assumed.weights``): every matmul weight and the tied embedding N(0,
    ``initializer_range``); norm weights one; the convolution uniform in
    +-1/sqrt(taps) (torch ``Conv1d``'s default); the selection bias N(0,
    ``expert_bias_init_std``). Every value is rounded to one a bfloat16 holds, so
    that a bfloat16 copy starts equal. Drawn here, tensor by tensor, by nothing
    of the program. Traceable."""
    def draw(k, shape, how):
        if how == "one":
            return jnp.ones(shape, jnp.float32)
        if how == "conv":
            bound = 1.0 / math.sqrt(cfg["conv_L_cache"])
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        std = cfg["expert_bias_init_std"] if how == "bias" else cfg["initializer_range"]
        return jax.random.normal(k, shape, jnp.float32) * std

    return {name: prec.as_bfloat16_values(draw(jax.random.fold_in(key, t), shape, how))
            for t, (name, (shape, how)) in enumerate(sorted(tensor_shapes(cfg).items()))}


def rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def short_conv(u, p, cfg, mode):
    S, K = u.shape[1], cfg["conv_L_cache"]
    Bm, Cm, x = jnp.split(prec.matmul(u, p["w_in"], mode), 3, axis=-1)
    z = jnp.pad(Bm * x, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(z[:, j:j + S] * p["conv"][:, j] for j in range(K))
    return prec.matmul(Cm * conv, p["w_out"], mode)


def rope(x, theta):
    """``x (B, S, H, hd)``, positions ``0 .. S-1``, the whole head rotated."""
    hd = x.shape[-1]
    f = 1.0 / theta ** (2.0 * jnp.arange(hd // 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * f[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    rotate_half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rotate_half * sin


def attention(u, p, cfg, mode):
    B, S, D = u.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps, theta = D // H, cfg["norm_eps"], cfg["rope_theta"]
    q = prec.matmul(u, p["w_q"], mode).reshape(B, S, H, hd)
    k = prec.matmul(u, p["w_k"], mode).reshape(B, S, Hkv, hd)
    v = prec.matmul(u, p["w_v"], mode).reshape(B, S, Hkv, hd)
    q, k = rope(rms(q, p["q_norm"], eps), theta), rope(rms(k, p["k_norm"], eps), theta)
    k, v = (jnp.repeat(t, H // Hkv, axis=2).transpose(0, 2, 1, 3) for t in (k, v))
    q = q.transpose(0, 2, 1, 3)                                   # (B, H, S, hd)
    block = min(_QUERY_BLOCK, S)
    if S % block:
        raise ValueError(f"seq_len {S} is not a multiple of the query block {block}")

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = prec.matmul(qb, k.transpose(0, 1, 3, 2), mode) / jnp.sqrt(float(hd))
        keep = jnp.arange(S)[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return prec.matmul(probs, v, mode)

    ctx = jax.lax.map(rows, jnp.arange(0, S, block))              # (n, B, H, block, hd)
    ctx = ctx.transpose(1, 0, 3, 2, 4).reshape(B, S, H * hd)
    return prec.matmul(ctx, p["w_o"], mode)


def swiglu(x, w_gate, w_up, w_down, mode):
    h = jax.nn.silu(prec.matmul(x, w_gate, mode)) * prec.matmul(x, w_up, mode)
    return prec.matmul(h, w_down, mode)


def moe(x, p, cfg, mode):
    scores = jax.nn.sigmoid(prec.matmul(x, p["router"], mode))
    choice = scores + p["expert_bias"] if cfg["use_expert_bias"] else scores
    _, idx = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + _ROUTER_EPS)
    top = top * cfg["routed_scaling_factor"]
    # (.., held): the weight of each held expert, zero where it was not chosen
    here = cfg["first_expert"] + jnp.arange(cfg["num_experts"])
    gates = jnp.sum(top[..., None] * (idx[..., None] == here), axis=-2)

    @jax.checkpoint
    def one(gate, w_gate, w_up, w_down):
        return gate[..., None] * swiglu(x, w_gate, w_up, w_down, mode)

    routed, _ = jax.lax.scan(lambda acc, xs: (acc + one(*xs), None), jnp.zeros_like(x), (
        jnp.moveaxis(gates, -1, 0), p["w_gate"], p["w_up"], p["w_down"]))
    return routed


def layer(x, p, mixer, ffn, cfg, mode):
    """One decoder layer. The mixer and the feed-forward part are each
    recomputed in the backward pass on their own."""
    eps = cfg["norm_eps"]
    op = short_conv if mixer == "conv" else attention
    x = x + jax.checkpoint(
        lambda x, p: op(rms(x, p["operator_norm"], eps), p, cfg, mode))(x, p)
    if ffn == "dense":
        part = lambda h, p: swiglu(h, p["w_gate"], p["w_up"], p["w_down"], mode)
    else:
        part = lambda h, p: moe(h, p, cfg, mode)
    return x + jax.checkpoint(lambda x, p: part(rms(x, p["ffn_norm"], eps), p))(x, p)


def _group(w, name):
    """The tensors of one layer: ``{short name: tensor}``."""
    prefix = name + "/"
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def hidden(w, tokens, cfg, mode="float32"):
    """The residual stream after the last layer and the final norm, ``(B, S, D)``."""
    x = w["embed"][tokens]
    for i, (mixer, ffn) in enumerate(held(cfg)):
        x = jax.checkpoint(lambda x, p, mixer=mixer, ffn=ffn: layer(
            x, p, mixer, ffn, cfg, mode))(x, _group(w, f"layers.{i}"))
    return rms(x, w["embedding_norm"], cfg["norm_eps"])


def logits(w, tokens, cfg, mode="float32"):
    """``(B, S, V)`` in one piece: for the tests, at small sizes."""
    head = w["embed" if cfg["tie_word_embeddings"] else "head"]
    return prec.matmul(hidden(w, tokens, cfg, mode), head.T, mode)


def loss(w, batch, cfg, mode="float32"):
    """Mean next-token cross entropy over every position of ``(tokens, targets)``,
    the head and the log-softmax taken a block of positions at a time."""
    tokens, targets = batch
    x = hidden(w, tokens, cfg, mode)
    head = w["embed" if cfg["tie_word_embeddings"] else "head"]
    B, S, D = x.shape
    block = min(_QUERY_BLOCK, S)
    if S % block:
        raise ValueError(f"seq_len {S} is not a multiple of the loss block {block}")

    @jax.checkpoint
    def rows(xs):
        xb, tb = xs
        lg = prec.matmul(xb, head.T, mode)
        picked = jnp.take_along_axis(lg, tb[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked)

    split = lambda t: jnp.moveaxis(t.reshape(B, S // block, block, *t.shape[2:]), 1, 0)
    return jnp.sum(jax.lax.map(rows, (split(x), split(targets)))) / (B * S)
