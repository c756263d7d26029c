"""Keye-VL-2.0's language model, forward and loss in plain float32 ``jax.numpy``,
one chip's share.

Written from the published configuration
(huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, ``config.json``, ``model_type``
``KeyeVL2``): the Qwen3-MoE configuration class's key set with
``rope_scaling.mrope_section`` and, in every layer, ``sa_config``, the lightning
indexer of DeepSeek-V3.2-Exp's sparse attention, in that family's published
order of operations. No kernel, no sorting of tokens, masks materialised,
nothing imported from the program. Bias-free but for the indexer's LayerNorm;
``rms(u, w) = w * u / sqrt(mean(u^2) + eps)``, ``eps = rms_norm_eps``.

* Layer: ``h = x + mixer(rms(x, input_norm))``, ``y = h + moe(rms(h, post_norm))``.
  After the last layer ``rms(., norm)``, then the untied head.
* Main heads: ``q = u W_q`` on ``num_attention_heads`` heads of ``head_dim``,
  ``k``, ``v`` on ``num_key_value_heads``; ``q, k`` through ``rms`` over the head
  (one weight of ``head_dim``); rotary embedding on the whole head
  (``rotate_half``: dim ``i`` pairs with ``i + head_dim / 2``), the angle of
  frequency pair ``i`` being ``pos_r(i) * rope_theta^(-2i / head_dim)`` where
  ``r(i)`` is the run of ``mrope_section`` that ``i`` falls in: the temporal, the
  height or the width position of the token (:func:`rope`). A text token's three
  positions are its index.
* Indexer: ``qI = u W_qI`` on ``indexer_num_heads`` heads of ``indexer_head_dim``;
  ``kI = LayerNorm(u W_kI)`` (weight, bias, ``eps``), one key for all heads;
  rotary embedding on the whole of both, one run, the temporal position;
  ``w = (u W_w) * indexer_num_heads^-1/2 * indexer_head_dim^-1/2``;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``.
* Selection: ``S_t`` = the ``min(t + 1, topk)`` keys ``s <= t`` of largest
  ``I[t, s]`` (``lax.top_k``: the lower index first among equals), the same for
  every main head.
* Attention: scores ``q_t . k_s / sqrt(head_dim)`` kept where ``s`` is in
  ``S_t``; softmax over them; times ``v``; a KV head shared by consecutive query
  heads; ``W_o``. ``I``, the selection and the masked softmax are computed a
  block of 512 queries at a time. The selection passes no gradient.
* MoE: ``p = softmax(x W_r)`` over all the router's outputs; the top
  ``num_experts_per_tok``; ``w_e = p_e / sum_top p`` (``norm_topk_prob``);
  ``sum_e w_e W_d^e (silu(W_g^e x) * W_u^e x)``. No shared expert. Every held
  expert is run on every token and weighted (zero where it was not chosen).

**The share.** ``cfg["num_experts"]`` experts are held here, ids ``first_expert
.. first_expert + num_experts - 1`` of the router's ``num_experts_published``
outputs; the sum over ``e`` runs over the chosen experts that are among them, the
router's normalisation over all the chosen. The vocabulary is a slice: embedding,
head and loss are over ``vocab_size`` ids. Attention, indexer, norms and router
are whole on every rank.

Departures from the published model, as the configuration's file lists them: no
vision tower; no auxiliary balancing loss; no indexer loss — the indexer's leaves
(``w_qi``, ``w_ki``, ``w_wi``, ``indexer_k_norm``, ``indexer_k_norm_bias``) get a
zero gradient, the selection being a set; the cuts of depth, experts held and
vocabulary. Each layer is recomputed in the backward pass (``jax.checkpoint``):
memory, not values.

Weights are a flat ``{name: array}`` dict, one entry per tensor of the model,
none stacked on a layer axis: ``embed``, ``head`` ``(V, D)``, ``norm``,
``layers.<l>/*``.
"""

import jax
import jax.numpy as jnp

from . import precision as prec

STACKED_PREFIX = None              # no tensor is stacked on a layer axis
_QUERY_BLOCK = 512


def tensor_shapes(cfg):
    """``{name: (shape, draw)}`` of every tensor of the share, flat; ``draw``
    names a case of :func:`weights`."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    sa = cfg["sa_config"]
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    E, Eh, F = cfg["num_experts_published"], cfg["num_experts"], cfg["moe_intermediate_size"]
    layer = {
        "input_norm": ((D,), "one"), "w_q": ((D, H * hd), "std"), "w_k": ((D, Hkv * hd), "std"),
        "w_v": ((D, Hkv * hd), "std"), "q_norm": ((hd,), "one"), "k_norm": ((hd,), "one"),
        "w_o": ((H * hd, D), "std"), "w_qi": ((D, Hi * di), "std"), "w_ki": ((D, di), "std"),
        "w_wi": ((D, Hi), "std"), "indexer_k_norm": ((di,), "one"),
        "indexer_k_norm_bias": ((di,), "zero"), "post_norm": ((D,), "one"),
        "router": ((D, E), "std"), "w_gate": ((Eh, D, F), "std"), "w_up": ((Eh, D, F), "std"),
        "w_down": ((Eh, F, D), "std")}
    out = {"embed": ((V, D), "embed"), "norm": ((D,), "one"), "head": ((V, D), "std")}
    for l in range(cfg["num_hidden_layers"]):
        out.update({f"layers.{l}/{name}": leaf for name, leaf in layer.items()})
    return out


def weights(cfg, key):
    """The seeded float32 weights of the share, flat (the configuration's
    ``assumed.weights``): every matmul weight and the head N(0,
    ``initializer_range``); the embedding N(0, ``embedding_init_std``); norm
    weights one, the LayerNorm's bias zero. Every value is rounded to one a
    bfloat16 holds, so that a bfloat16 copy starts equal. Drawn here, tensor by
    tensor, by nothing of the program. Traceable."""
    def draw(k, shape, how):
        if how in ("one", "zero"):
            return jnp.full(shape, 1.0 if how == "one" else 0.0, jnp.float32)
        std = cfg["embedding_init_std"] if how == "embed" else cfg["initializer_range"]
        return jax.random.normal(k, shape, jnp.float32) * std

    return {name: prec.as_bfloat16_values(draw(jax.random.fold_in(key, t), shape, how))
            for t, (name, (shape, how)) in enumerate(sorted(tensor_shapes(cfg).items()))}


def rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def text_positions(seq_len, rows=3):
    """``(rows, S)``: a text token's positions are all its index."""
    return jnp.broadcast_to(jnp.arange(seq_len), (rows, seq_len))


def rope(x, positions, theta, sections):
    """``x (B, S, H, hd)``, the whole head rotated; ``positions (len(sections),
    S)``; frequency pair ``i`` reads the row of the run of ``sections`` it is in."""
    hd = x.shape[-1]
    f = 1.0 / theta ** (2.0 * jnp.arange(hd // 2, dtype=jnp.float32) / hd)
    row = jnp.concatenate([jnp.full((n,), r) for r, n in enumerate(sections)])
    angle = positions.astype(jnp.float32)[row, :].T * f[None, :]          # (S, hd / 2)
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    rotate_half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rotate_half * sin


def index_operands(u, p, cfg, positions, mode):
    """``(qI (B, S, Hi, d), kI (B, S, d), w (B, S, Hi))`` of one layer's indexer."""
    sa = cfg["sa_config"]
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    B, S, _ = u.shape
    theta = float(cfg["rope_theta"])
    q = prec.matmul(u, p["w_qi"], mode).reshape(B, S, Hi, di)
    k = layer_norm(prec.matmul(u, p["w_ki"], mode), p["indexer_k_norm"],
                   p["indexer_k_norm_bias"], cfg["rms_norm_eps"])
    q = rope(q, positions[:1], theta, (di // 2,))
    k = rope(k[:, :, None, :], positions[:1], theta, (di // 2,))[:, :, 0]
    w = prec.matmul(u, p["w_wi"], mode) * (Hi ** -0.5 * di ** -0.5)
    return q, k, w


def selected_keys(qb, k, wb, rows, topk, mode):
    """``(B, block, S)`` bool: the keys the queries ``rows`` of one block keep.
    ``qb (B, block, Hi, d)``, ``k (B, S, d)``, ``wb (B, block, Hi)``."""
    S = k.shape[1]
    products = prec.matmul(qb.transpose(0, 2, 1, 3), k[:, None].transpose(0, 1, 3, 2), mode)
    scores = jnp.sum(jax.nn.relu(products) * wb.transpose(0, 2, 1)[..., None], axis=1)
    causal = jnp.arange(S)[None, :] <= rows[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal[None], scores, -jnp.inf), min(topk, S))
    B, block = scores.shape[:2]
    keep = jnp.zeros((B, block, S), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(block)[None, :, None], idx].set(True)
    return keep & causal[None]            # a row with fewer causal keys than topk keeps them all


def attention(u, p, cfg, positions, mode):
    B, S, _ = u.shape
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    sections = cfg["rope_scaling"]["mrope_section"]
    q = prec.matmul(u, p["w_q"], mode).reshape(B, S, H, hd)
    k = prec.matmul(u, p["w_k"], mode).reshape(B, S, Hkv, hd)
    v = prec.matmul(u, p["w_v"], mode).reshape(B, S, Hkv, hd)
    q = rope(rms(q, p["q_norm"], eps), positions, theta, sections)
    k = rope(rms(k, p["k_norm"], eps), positions, theta, sections)
    k, v = (jnp.repeat(t, H // Hkv, axis=2).transpose(0, 2, 1, 3) for t in (k, v))
    q = q.transpose(0, 2, 1, 3)                                   # (B, H, S, hd)
    # the indexer: forward only, no gradient to the stream or to its own leaves
    qi, ki, wi = jax.lax.stop_gradient(index_operands(u, p, cfg, positions, mode))
    topk = cfg["sa_config"]["topk"]
    block = min(_QUERY_BLOCK, S)
    if S % block:
        raise ValueError(f"seq_len {S} is not a multiple of the query block {block}")

    @jax.checkpoint
    def rows(start):
        at = start + jnp.arange(block)
        cut = lambda t, axis: jax.lax.dynamic_slice_in_dim(t, start, block, axis=axis)
        keep = selected_keys(cut(qi, 1), ki, cut(wi, 1), at, topk, mode)  # (B, block, S)
        scores = prec.matmul(cut(q, 2), k.transpose(0, 1, 3, 2), mode) / jnp.sqrt(float(hd))
        probs = jax.nn.softmax(jnp.where(keep[:, None], scores, -jnp.inf), axis=-1)
        return prec.matmul(probs, v, mode), jnp.sum(keep)

    ctx, pairs = jax.lax.map(rows, jnp.arange(0, S, block))       # (n, B, H, block, hd)
    ctx = ctx.transpose(1, 0, 3, 2, 4).reshape(B, S, H * hd)
    return prec.matmul(ctx, p["w_o"], mode), jnp.sum(pairs)


def swiglu(x, w_gate, w_up, w_down, mode):
    h = jax.nn.silu(prec.matmul(x, w_gate, mode)) * prec.matmul(x, w_up, mode)
    return prec.matmul(h, w_down, mode)


def moe(x, p, cfg, mode):
    probs = jax.nn.softmax(prec.matmul(x, p["router"], mode), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    # (.., held): the weight of each held expert, zero where it was not chosen
    held = cfg["first_expert"] + jnp.arange(cfg["num_experts"])
    gates = jnp.sum(top[..., None] * (idx[..., None] == held), axis=-2)

    @jax.checkpoint
    def one(gate, w_gate, w_up, w_down):
        return gate[..., None] * swiglu(x, w_gate, w_up, w_down, mode)

    routed, _ = jax.lax.scan(lambda acc, xs: (acc + one(*xs), None), jnp.zeros_like(x), (
        jnp.moveaxis(gates, -1, 0), p["w_gate"], p["w_up"], p["w_down"]))
    return routed


def layer(x, p, cfg, positions, mode):
    """One decoder layer: ``(x, the pairs its indexer kept)``. Attention and
    experts are each recomputed in the backward pass on their own."""
    eps = cfg["rms_norm_eps"]
    y, pairs = jax.checkpoint(
        lambda x, p: attention(rms(x, p["input_norm"], eps), p, cfg, positions, mode))(x, p)
    x = x + y
    return x + jax.checkpoint(
        lambda x, p: moe(rms(x, p["post_norm"], eps), p, cfg, mode))(x, p), pairs


def _group(w, name):
    """The tensors of one layer: ``{short name: tensor}``."""
    prefix = name + "/"
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def hidden(w, tokens, cfg, mode="float32", positions=None, with_pairs=False):
    """The residual stream after the last layer and the final norm, ``(B, S, D)``
    (and, ``with_pairs``, the (query, key) pairs the layers' indexers kept)."""
    x = w["embed"][tokens]
    if positions is None:
        positions = text_positions(tokens.shape[1])
    pairs = 0
    for l in range(cfg["num_hidden_layers"]):
        x, n = jax.checkpoint(lambda x, p: layer(x, p, cfg, positions, mode))(
            x, _group(w, f"layers.{l}"))
        pairs = pairs + n
    x = rms(x, w["norm"], cfg["rms_norm_eps"])
    return (x, pairs) if with_pairs else x


def logits(w, tokens, cfg, mode="float32", positions=None):
    """``(B, S, V)`` in one piece: for the tests, at small sizes."""
    return prec.matmul(hidden(w, tokens, cfg, mode, positions), w["head"].T, mode)


def loss(w, batch, cfg, mode="float32"):
    """Mean next-token cross entropy over every position of ``(tokens, targets)``,
    the head and the log-softmax taken a block of positions at a time."""
    tokens, targets = batch
    x = hidden(w, tokens, cfg, mode)
    B, S, D = x.shape
    block = min(_QUERY_BLOCK, S)
    if S % block:
        raise ValueError(f"seq_len {S} is not a multiple of the loss block {block}")

    @jax.checkpoint
    def rows(xs):
        xb, tb = xs
        lg = prec.matmul(xb, w["head"].T, mode)
        picked = jnp.take_along_axis(lg, tb[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked)

    split = lambda t: jnp.moveaxis(t.reshape(B, S // block, block, *t.shape[2:]), 1, 0)
    return jnp.sum(jax.lax.map(rows, (split(x), split(targets)))) / (B * S)
