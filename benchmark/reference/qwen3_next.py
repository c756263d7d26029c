"""Qwen3-Next forward and loss in plain float32 ``jax.numpy``, one chip's share.

Written from the published configuration (huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct,
``config.json``) and the layer equations of its model card and of Gated DeltaNet
(Yang, Kautz, Hatamizadeh 2024). No kernel, no chunk-wise form, no sorting of
tokens, nothing imported from the program. Bias-free throughout;
``rms0(x, w) = x / sqrt(mean(x^2) + eps) * (1 + w)``.

* Layer ``l``: ``h = x + mixer_l(rms0(x))``, ``y = h + moe(rms0(h))``; the mixer
  is gated attention where ``(l + 1) % full_attention_interval == 0`` and a
  gated DeltaNet otherwise. After the last layer ``rms0``, then the untied head.
* Gated DeltaNet: ``[q, k, v, z] = x W_qkvz``, ``[b, a] = x W_ba``; ``q, k, v``
  (concatenated) through a causal depthwise convolution of width 4, then SiLU;
  ``q, k`` L2-normalised per head (eps 1e-6), ``q`` times ``d_k^-1/2``; a key head
  serves ``value_heads / key_heads`` consecutive value heads;
  ``beta = sigmoid(b)``, ``alpha = exp(-exp(A_log) * softplus(a + dt_bias))``.
  Per value head, ``S_0 = 0``: ``S~ = alpha_t S_{t-1}``;
  ``S_t = S~ + k_t (beta_t (v_t - S~^T k_t))^T``; ``o_t = S_t^T q_t``: **the
  recurrence itself, one token at a time** (a ``lax.scan`` over time, in blocks
  that are recomputed in the backward pass so that 8192 states need not be
  kept). Then ``(w_n * o / sqrt(mean(o^2) + eps)) * silu(z)`` per head and ``W_out``.
* Gated attention: ``[q, gate] = x W_q`` (per head: the query, then its gate),
  ``k``, ``v`` on ``num_key_value_heads``; ``q, k`` through ``rms0`` over the
  head; rotary embedding on the first ``partial_rotary_factor * head_dim`` dims
  (``rotate_half``: dim ``i`` pairs with ``i + rotary_dim/2``); causal
  ``softmax(q k^T / sqrt(head_dim)) v``, a KV head shared by consecutive query
  heads; ``(attn * sigmoid(gate)) W_o``. Computed a block of queries at a time.
* MoE: ``p = softmax(x W_r)`` over all the router's outputs; the top
  ``num_experts_per_tok``; ``w_e = p_e / sum_top p``;
  ``sum_e w_e W_d^e (silu(W_g^e x) * W_u^e x)`` plus
  ``sigmoid(x w_s) * swiglu_shared(x)``. Every expert is run on every token
  and weighted (zero where it was not chosen): a loop over the experts.

**The share.** ``cfg["num_experts"]`` experts are held here, ids
``first_expert .. first_expert + num_experts - 1`` of the router's
``num_experts_published`` outputs; the sum over ``e`` runs over the chosen
experts that are among them, the router's normalisation over all the chosen.
The vocabulary is a slice: embedding, head and loss are over ``vocab_size`` ids.

Departures from the published model: no multi-token-prediction module and no
auxiliary balancing loss (``config.json`` has a key for neither); the cuts of
depth, experts held and vocabulary that the configuration's file states. Each
layer is recomputed in the backward pass (``jax.checkpoint``): memory, not values.

Weights are a flat ``{name: array}`` dict, one entry per tensor of the model,
none stacked (the gradient of a slice of a stack is a zero-padded copy of the
stack, and the float32 experts are 0.5 GB a tensor a layer): ``embed``, ``head``
``(V, D)``, ``final_norm``; ``layers.<l>/*`` for what every layer has,
``linear.<i>/*`` for the i-th DeltaNet mixer, ``attn.<i>/*`` for the i-th
attention mixer, in layer order.
"""

import jax
import jax.numpy as jnp

from . import precision as prec

STACKED_PREFIX = None              # no tensor is stacked on a layer axis
_QUERY_BLOCK = 512
_TIME_BLOCK = 64


def rms0(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * (1.0 + w)


def rope(x, rotary_dim, theta):
    """``x (B, S, H, hd)``, positions ``0 .. S-1``."""
    half = rotary_dim // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    rotate_half = jnp.concatenate([-rot[..., half:], rot[..., :half]], -1)
    return jnp.concatenate([rot * cos + rotate_half * sin, rest], -1)


def causal_conv(x, w):
    """Depthwise: ``y[t, c] = sum_j w[c, j] x[t - (K - 1) + j, c]``; ``w (C, K)``."""
    K, S = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + S] * w[:, j] for j in range(K))


def delta_recurrence(q, k, v, alpha, beta):
    """The gated delta rule, token by token. ``q, k (B, S, H, dk)``,
    ``v (B, S, H, dv)``, ``alpha, beta (B, S, H)``; returns ``o (B, S, H, dv)``."""
    B, S, H, dk = q.shape

    def token(state, xs):
        q, k, v, a, b = xs
        decayed = a[..., None, None] * state
        predicted = jnp.einsum("bhkv,bhk->bhv", decayed, k,
                               precision=jax.lax.Precision.HIGHEST)
        state = decayed + k[..., :, None] * (b[..., None] * (v - predicted))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q,
                                 precision=jax.lax.Precision.HIGHEST)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = -S % _TIME_BLOCK            # steps that leave the state alone
    xs = [jnp.pad(jnp.moveaxis(t, 1, 0), ((0, pad),) + ((0, 0),) * (t.ndim - 1),
                  constant_values=1.0 if t is alpha else 0.0)
          for t in (q, k, v, alpha, beta)]
    xs = [t.reshape(-1, _TIME_BLOCK, *t.shape[1:]) for t in xs]
    state = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(block, state, xs)
    return jnp.moveaxis(o.reshape(-1, *o.shape[2:])[:S], 0, 1)


def gated_delta_net(x, p, cfg, mode):
    B, S, _ = x.shape
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    qkvz = prec.matmul(x, p["w_qkvz"], mode)
    ba = prec.matmul(x, p["w_ba"], mode)
    n_conv = 2 * Hk * dk + Hv * dv
    qkv = jax.nn.silu(causal_conv(qkvz[..., :n_conv], p["conv"]))
    z = qkvz[..., n_conv:].reshape(B, S, Hv, dv)
    q = qkv[..., :Hk * dk].reshape(B, S, Hk, dk)
    k = qkv[..., Hk * dk:2 * Hk * dk].reshape(B, S, Hk, dk)
    v = qkv[..., 2 * Hk * dk:].reshape(B, S, Hv, dv)
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)
    q, k = l2(q) * dk ** -0.5, l2(k)
    q, k = (jnp.repeat(t, Hv // Hk, axis=2) for t in (q, k))
    beta = jax.nn.sigmoid(ba[..., :Hv])
    alpha = jnp.exp(-jnp.exp(p["a_log"]) * jax.nn.softplus(ba[..., Hv:] + p["dt_bias"]))
    o = delta_recurrence(q, k, v, alpha, beta)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * p["out_norm"]
    return prec.matmul((o * jax.nn.silu(z)).reshape(B, S, Hv * dv), p["w_out"], mode)


def gated_attention(x, p, cfg, mode):
    B, S, _ = x.shape
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, rd = cfg["rms_norm_eps"], int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    qg = prec.matmul(x, p["w_q"], mode).reshape(B, S, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = prec.matmul(x, p["w_k"], mode).reshape(B, S, Hkv, hd)
    v = prec.matmul(x, p["w_v"], mode).reshape(B, S, Hkv, hd)
    q = rope(rms0(q, p["q_norm"], eps), rd, cfg["rope_theta"])
    k = rope(rms0(k, p["k_norm"], eps), rd, cfg["rope_theta"])
    k, v = (jnp.repeat(t, H // Hkv, axis=2).transpose(0, 2, 1, 3) for t in (k, v))
    q = q.transpose(0, 2, 1, 3)                                   # (B, H, S, hd)
    block = min(_QUERY_BLOCK, S)
    if S % block:
        raise ValueError(f"seq_len {S} is not a multiple of the query block {block}")

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = prec.matmul(qb, k.transpose(0, 1, 3, 2), mode) / jnp.sqrt(float(hd))
        causal = (start + jnp.arange(block))[:, None] >= jnp.arange(S)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return prec.matmul(probs, v, mode)

    ctx = jax.lax.map(rows, jnp.arange(0, S, block))              # (n, B, H, block, hd)
    ctx = ctx.transpose(1, 0, 3, 2, 4).reshape(B, S, H, hd)
    return prec.matmul((ctx * jax.nn.sigmoid(gate)).reshape(B, S, H * hd), p["w_o"], mode)


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
    shared = swiglu(x, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"], mode)
    return routed + jax.nn.sigmoid(prec.matmul(x, p["shared_score"], mode)) * shared


def layer(x, lp, mp, mixer, cfg, mode):
    """One decoder layer. Mixer and experts are each recomputed in the backward
    pass on their own, so that only one of them is live in float32 at a time."""
    eps = cfg["rms_norm_eps"]
    x = x + jax.checkpoint(
        lambda x, lp, mp: mixer(rms0(x, lp["input_norm"], eps), mp, cfg, mode))(x, lp, mp)
    return x + jax.checkpoint(
        lambda x, lp: moe(rms0(x, lp["post_norm"], eps), lp, cfg, mode))(x, lp)


def _group(w, name):
    """The tensors of one layer or mixer: ``{short name: tensor}``."""
    prefix = name + "/"
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def hidden(w, tokens, cfg, mode="float32"):
    """The residual stream after the last layer and the final norm, ``(B, S, D)``."""
    x = w["embed"][tokens]
    n_linear = n_attn = 0
    for l in range(cfg["num_hidden_layers"]):
        lp = _group(w, f"layers.{l}")
        if (l + 1) % cfg["full_attention_interval"] == 0:
            mixer, mp = gated_attention, _group(w, f"attn.{n_attn}")
            n_attn += 1
        else:
            mixer, mp = gated_delta_net, _group(w, f"linear.{n_linear}")
            n_linear += 1
        x = jax.checkpoint(
            lambda x, lp, mp, mixer=mixer: layer(x, lp, mp, mixer, cfg, mode))(x, lp, mp)
    return rms0(x, w["final_norm"], cfg["rms_norm_eps"])


def loss(w, batch, cfg, mode="float32"):
    """Mean next-token cross entropy over every position of ``(tokens, targets)``,
    the head and the log-softmax taken a block of positions at a time (the
    float32 logits of 8192 positions over the slice are 0.6 GB)."""
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
