"""Mellum 2 forward and loss in plain float32 ``jax.numpy``, one chip's share.

Written from the published configuration
(huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, ``config.json``), whose key
set is that of the Qwen3-MoE configuration class with ``layer_types`` and one
``rope_parameters`` group per layer type. No kernel, no sorting of tokens, masks
materialised, nothing imported from the program. Bias-free throughout;
``rms(u, w) = w * u / sqrt(mean(u^2) + eps)``.

* Layer ``l`` of kind ``t = layer_types[l]``: ``h = x + attention_t(rms(x))``,
  ``y = h + moe(rms(h))``. After the last layer ``rms``, then the untied head.
* Attention: ``q = x W_q`` on ``num_attention_heads`` heads of ``head_dim``,
  ``k``, ``v`` on ``num_key_value_heads``; ``q, k`` through ``rms`` over the head
  (one weight of ``head_dim``); rotary embedding on the whole head
  (``rotate_half``: dim ``i`` pairs with ``i + head_dim / 2``) with the kind's
  table, ``cos = a cos(p f)``, ``sin = a sin(p f)``. ``sliding_attention``:
  ``f_i = theta^(-2i / head_dim)``, ``a = 1``. ``full_attention`` (YaRN, computed
  once, whatever the sequence length): ``e_i = theta^(-2i / head_dim)``,
  ``n_i = e_i / factor``, ``c(r) = head_dim ln(original / (2 pi r)) / (2 ln theta)``,
  ``low = max(floor(c(beta_fast)), 0)``, ``high = min(ceil(c(beta_slow)), head_dim - 1)``,
  ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
  ``f_i = n_i ramp_i + e_i (1 - ramp_i)``, ``a = attention_factor``. Scores
  ``q_i . k_j / sqrt(head_dim)``, kept where ``j <= i`` (full) or
  ``i - sliding_window < j <= i`` (sliding: ``sliding_window`` keys, the token's
  own among them); softmax; a KV head shared by consecutive query heads; ``W_o``.
  Computed a block of queries at a time against a materialised mask.
* MoE: ``p = softmax(x W_r)`` over all the router's outputs; the top
  ``num_experts_per_tok``; ``w_e = p_e / sum_top p``;
  ``sum_e w_e W_d^e (silu(W_g^e x) * W_u^e x)``. No shared expert. Every held
  expert is run on every token and weighted (zero where it was not chosen): a
  loop over the experts.

**The share.** ``cfg["num_experts"]`` experts are held here, ids
``first_expert .. first_expert + num_experts - 1`` of the router's
``num_experts_published`` outputs; the sum over ``e`` runs over the chosen
experts that are among them, the router's normalisation over all the chosen.
The vocabulary is a slice: embedding, head and loss are over ``vocab_size`` ids.
``layer_types`` is the published list; the first ``num_hidden_layers`` count.

Departures from the published model: no multi-token-prediction head and no
auxiliary balancing loss (``config.json`` has a key for neither); the cuts of
depth, experts held and vocabulary that the configuration's file states. Each
layer is recomputed in the backward pass (``jax.checkpoint``): memory, not values.

Weights are a flat ``{name: array}`` dict, one entry per tensor of the model,
none stacked on a layer axis: ``embed``, ``head`` ``(V, D)``, ``final_norm``,
``layers.<l>/*``.
"""

import math

import jax
import jax.numpy as jnp

from . import precision as prec

STACKED_PREFIX = None              # no tensor is stacked on a layer axis
_QUERY_BLOCK = 512


def rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def inverse_frequencies(dim, rp):
    """``(f (dim / 2,), a)`` of one ``rope_parameters`` group."""
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    e = 1.0 / rp["rope_theta"] ** (2.0 * i / dim)
    if rp["rope_type"] == "default":
        return e, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r} is not written down here")

    def c(r):
        return (dim * math.log(rp["original_max_position_embeddings"] / (2 * math.pi * r))
                / (2 * math.log(rp["rope_theta"])))

    low = max(math.floor(c(rp["beta_fast"])), 0)
    high = min(math.ceil(c(rp["beta_slow"])), dim - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return e / rp["factor"] * ramp + e * (1.0 - ramp), rp["attention_factor"]


def rope(x, rp):
    """``x (B, S, H, hd)``, positions ``0 .. S-1``, the whole head rotated."""
    hd = x.shape[-1]
    f, a = inverse_frequencies(hd, rp)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * f[None, :]
    cos = a * jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = a * jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    rotate_half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rotate_half * sin


def attention(x, p, cfg, kind, mode):
    B, S, _ = x.shape
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, rp = cfg["rms_norm_eps"], cfg["rope_parameters"][kind]
    q = prec.matmul(x, p["w_q"], mode).reshape(B, S, H, hd)
    k = prec.matmul(x, p["w_k"], mode).reshape(B, S, Hkv, hd)
    v = prec.matmul(x, p["w_v"], mode).reshape(B, S, Hkv, hd)
    q, k = rope(rms(q, p["q_norm"], eps), rp), rope(rms(k, p["k_norm"], eps), rp)
    k, v = (jnp.repeat(t, H // Hkv, axis=2).transpose(0, 2, 1, 3) for t in (k, v))
    q = q.transpose(0, 2, 1, 3)                                   # (B, H, S, hd)
    window = cfg["sliding_window"] if kind == "sliding_attention" else S
    block = min(_QUERY_BLOCK, S)
    if S % block:
        raise ValueError(f"seq_len {S} is not a multiple of the query block {block}")

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = prec.matmul(qb, k.transpose(0, 1, 3, 2), mode) / jnp.sqrt(float(hd))
        i, j = (start + jnp.arange(block))[:, None], jnp.arange(S)[None, :]
        keep = (j <= i) & (j > i - window)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return prec.matmul(probs, v, mode)

    ctx = jax.lax.map(rows, jnp.arange(0, S, block))              # (n, B, H, block, hd)
    ctx = ctx.transpose(1, 0, 3, 2, 4).reshape(B, S, H * hd)
    return prec.matmul(ctx, p["w_o"], mode)


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


def layer(x, p, kind, cfg, mode):
    """One decoder layer. Attention and experts are each recomputed in the
    backward pass on their own, so that only one of them is live at a time."""
    eps = cfg["rms_norm_eps"]
    x = x + jax.checkpoint(
        lambda x, p: attention(rms(x, p["input_norm"], eps), p, cfg, kind, mode))(x, p)
    return x + jax.checkpoint(
        lambda x, p: moe(rms(x, p["post_norm"], eps), p, cfg, mode))(x, p)


def _group(w, name):
    """The tensors of one layer: ``{short name: tensor}``."""
    prefix = name + "/"
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def hidden(w, tokens, cfg, mode="float32"):
    """The residual stream after the last layer and the final norm, ``(B, S, D)``."""
    x = w["embed"][tokens]
    for l, kind in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        x = jax.checkpoint(
            lambda x, p, kind=kind: layer(x, p, kind, cfg, mode))(x, _group(w, f"layers.{l}"))
    return rms(x, w["final_norm"], cfg["rms_norm_eps"])


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
