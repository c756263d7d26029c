"""Kimi-Linear-type forward and loss in plain float32 ``jax.numpy``, one chip's share.

Written from the published configuration
(huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct, ``config.json``,
``model_type`` ``kimi_linear``), the family's published modelling code and the
paper (Kimi Linear, Moonshot AI 2025, arXiv:2510.26692). No kernel, no chunk-wise
form, no sorting of tokens, masks materialised, nothing imported from the
program. Bias-free throughout; ``rms(u, w) = w * u / sqrt(mean(u^2) + eps)``,
``eps = rms_norm_eps``.

* Layer ``l`` (published index, 0-based): ``h = x + mixer_l(rms(x,
  input_layernorm))``, ``y = h + ffn_l(rms(h, post_attention_layernorm))``. The
  mixer is latent attention where ``l + 1`` is in
  ``linear_attn_config.full_attn_layers`` and KDA where it is in ``kda_layers``
  (1-based lists); ``ffn_l`` is a dense SwiGLU of ``intermediate_size`` for ``l <
  first_k_dense_replace`` and the mixture of experts after. After the last layer
  ``rms(., norm)``, then the untied head.
* KDA on ``u``, ``H`` heads of ``d = linear_attn_config.head_dim``:
  ``q, k, v = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))``, each a
  causal depthwise convolution of ``short_conv_kernel_size`` taps without a bias;
  per head ``q <- q / sqrt(sum q^2 + 1e-6) * d^-1/2``, ``k <- k / sqrt(sum k^2 +
  1e-6)``; ``a = (u W_fa) W_fb`` (no activation between), ``alpha = exp(-exp(A_log[head])
  * softplus(a + dt_bias))`` a key channel; ``beta = sigmoid(u W_b)`` a head. Per
  head, ``S_0 = 0 (d, d)``: ``S~ = Diag(alpha_t) S_{t-1}``; ``S_t = S~ + k_t
  (beta_t (v_t - S~^T k_t))^T``; ``o_t = S_t^T q_t`` — **the recurrence itself,
  one token at a time** (a ``lax.scan`` over time, in blocks that are recomputed
  in the backward pass so that 8192 states need not be kept). Then ``z = (u W_ga)
  W_gb``; ``(w_n * o / sqrt(mean(o^2) + eps)) * sigmoid(z)`` per head (``out_norm``:
  one weight of ``d``), and ``W_o``.
* Latent attention (``q_lora_rank`` null, ``mla_use_nope``): ``q = u W_q`` viewed
  ``(H, qk_nope_head_dim + qk_rope_head_dim)``; ``[c | k_s] = u W_kva`` split at
  ``kv_lora_rank`` (``k_s`` is one head); ``rms(c, kv_a_layernorm) W_kvb`` viewed
  ``(H, qk_nope_head_dim + v_head_dim)`` and split ``[k_nope | v]``; ``k = [k_nope |
  k_s expanded over the heads]``; **no rotary embedding anywhere**; scores ``q_i .
  k_j / sqrt(qk_nope_head_dim + qk_rope_head_dim)`` kept where ``j <= i``;
  softmax; times ``v``; ``W_o``. A block of queries at a time against a
  materialised mask.
* Mixture of experts: ``s = sigmoid(u W_r)`` over all ``num_experts_published``
  outputs; the ``num_experts_per_token`` largest of ``s + b`` (``b``,
  ``expert_bias``, the published ``e_score_correction_bias``: it enters the choice
  only; one group, so none is masked); ``w = s[idx] / (sum s[idx] + 1e-20) *
  routed_scaling_factor`` (``moe_renormalize``); ``sum_e w_e W_d^e (silu(W_g^e x) *
  W_u^e x)``; plus the shared expert, one SwiGLU of width ``num_shared_experts *
  moe_intermediate_size`` on every token, not gated. Every held expert is run on
  every token and weighted (zero where it was not chosen): a loop over the experts.

**The share.** ``cfg["num_experts"]`` experts are held here, ids ``first_expert ..
first_expert + num_experts - 1`` of the router's ``num_experts_published`` outputs;
the sum over ``e`` runs over the chosen experts that are among them, the router's
normalisation over all the chosen. The shared expert is whole on every rank. The
vocabulary is a slice: embedding, head and loss are over ``vocab_size`` ids. The
layers held are ``first_layer .. first_layer + num_hidden_layers - 1``, and mixer
and feed-forward kinds are decided on the published index.

Departures from the published model: the selection bias is zeros and nothing
moves it, no balancing loss (``config.json`` has a key for neither); the cuts of
depth, experts held and vocabulary that the configuration's file states. Each
layer is recomputed in the backward pass (``jax.checkpoint``): memory, not values.

Weights are a flat ``{name: array}`` dict, one entry per tensor of the model,
none stacked on a layer axis: ``embed`` ``(V, D)``, ``head`` ``(V, D)``, ``norm``,
``layers.<i>/*`` for the ``i``-th held layer: ``input_layernorm``,
``post_attention_layernorm``, a KDA mixer's ``w_q``, ``w_k``, ``w_v``, ``conv_q``,
``conv_k``, ``conv_v`` ``(H d, taps)``, ``w_fa``, ``w_fb``, ``a_log`` ``(H,)``,
``dt_bias`` ``(H d,)``, ``w_b``, ``w_ga``, ``w_gb``, ``out_norm`` ``(d,)``, ``w_o`` or a
latent mixer's ``w_q``, ``w_kva``, ``kv_a_layernorm``, ``w_kvb``, ``w_o``; and
``w_gate``, ``w_up``, ``w_down`` (with experts those three stacked over the held
experts, beside ``router``, ``expert_bias``, ``shared_w_gate``, ``shared_w_up``,
``shared_w_down``).
"""

import math

import jax
import jax.numpy as jnp

from . import precision as prec

STACKED_PREFIX = None              # no tensor is stacked on a layer axis
_QUERY_BLOCK = 512
_TIME_BLOCK = 64
_ROUTER_EPS = 1e-20
_L2_EPS = 1e-6


def held(cfg):
    """``[(mixer, ffn)]`` of the layers held, by their published index: ``"kda" |
    "mla"`` from the two 1-based lists, ``"dense" | "moe"``."""
    la, first = cfg["linear_attn_config"], cfg["first_layer"]
    out = []
    for l in range(first, first + cfg["num_hidden_layers"]):
        full, kda = l + 1 in la["full_attn_layers"], l + 1 in la["kda_layers"]
        if full == kda:
            raise ValueError(f"published layer {l + 1} is in both or neither list")
        out.append(("mla" if full else "kda",
                    "moe" if l >= cfg["first_k_dense_replace"] else "dense"))
    return out


def tensor_shapes(cfg):
    """``{name: (shape, draw)}`` of every tensor of the share, flat; ``draw``
    names a case of :func:`weights`."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    la = cfg["linear_attn_config"]
    Hl, d, K = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    F, Fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, Eh = cfg["num_experts_published"], cfg["num_experts"]
    Fs = cfg["num_shared_experts"] * Fm
    mixer = {
        "kda": {"w_q": ((D, Hl * d), "std"), "w_k": ((D, Hl * d), "std"),
                "w_v": ((D, Hl * d), "std"), "conv_q": ((Hl * d, K), "conv"),
                "conv_k": ((Hl * d, K), "conv"), "conv_v": ((Hl * d, K), "conv"),
                "w_fa": ((D, d), "std"), "w_fb": ((d, Hl * d), "std"),
                "a_log": ((Hl,), "a_log"), "dt_bias": ((Hl * d,), "dt_bias"),
                "w_b": ((D, Hl), "std"), "w_ga": ((D, d), "std"), "w_gb": ((d, Hl * d), "std"),
                "out_norm": ((d,), "one"), "w_o": ((Hl * d, D), "std")},
        "mla": {"w_q": ((D, H * (dn + dr)), "std"), "w_kva": ((D, r + dr), "std"),
                "kv_a_layernorm": ((r,), "one"), "w_kvb": ((r, H * (dn + dv)), "std"),
                "w_o": ((H * dv, D), "std")},
    }
    ffn = {
        "dense": {"w_gate": ((D, F), "std"), "w_up": ((D, F), "std"),
                  "w_down": ((F, D), "std")},
        "moe": {"router": ((D, E), "std"), "expert_bias": ((E,), "zero"),
                "w_gate": ((Eh, D, Fm), "std"), "w_up": ((Eh, D, Fm), "std"),
                "w_down": ((Eh, Fm, D), "std"),
                "shared_w_gate": ((D, Fs), "std"), "shared_w_up": ((D, Fs), "std"),
                "shared_w_down": ((Fs, D), "std")},
    }
    norms = {"input_layernorm": ((D,), "one"), "post_attention_layernorm": ((D,), "one")}
    out = {"embed": ((V, D), "embed"), "norm": ((D,), "one"), "head": ((V, D), "std")}
    for i, (m, f) in enumerate(held(cfg)):
        out.update({f"layers.{i}/{name}": leaf
                    for name, leaf in {**norms, **mixer[m], **ffn[f]}.items()})
    return out


def weights(cfg, key):
    """The seeded float32 weights of the share, flat (the configuration's
    ``assumed.weights``): every matmul weight and the head N(0,
    ``initializer_range``); the embedding N(0, ``embedding_init_std``); the
    convolutions uniform in +-1/sqrt(taps); ``A_log`` the log of a uniform draw in
    [1, 16] a head; ``dt_bias`` the inverse softplus of a step log-uniform in
    0.001 .. 0.1 a channel; norm weights one; the selection bias zeros. Every
    value is rounded to one a bfloat16 holds, so that a bfloat16 copy starts
    equal. Drawn here, tensor by tensor, by nothing of the program. Traceable."""
    def draw(k, shape, how):
        if how == "one":
            return jnp.ones(shape, jnp.float32)
        if how == "zero":
            return jnp.zeros(shape, jnp.float32)
        if how == "conv":
            bound = 1.0 / math.sqrt(shape[-1])
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        if how == "a_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        if how == "dt_bias":
            step = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                              math.log(1e-3), math.log(1e-1)))
            return jnp.log(jnp.expm1(step))
        std = cfg["embedding_init_std"] if how == "embed" else cfg["initializer_range"]
        return jax.random.normal(k, shape, jnp.float32) * std

    return {name: prec.as_bfloat16_values(draw(jax.random.fold_in(key, t), shape, how))
            for t, (name, (shape, how)) in enumerate(sorted(tensor_shapes(cfg).items()))}


def rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def causal_conv(x, w):
    """Depthwise: ``y[t, c] = sum_j w[c, j] x[t - (K - 1) + j, c]``; ``w (C, K)``."""
    K, S = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + S] * w[:, j] for j in range(K))


def delta_recurrence(q, k, v, alpha, beta):
    """The delta rule under a decay a key channel, token by token. ``q, k, alpha
    (B, S, H, dk)``, ``v (B, S, H, dv)``, ``beta (B, S, H)``; ``o (B, S, H, dv)``."""
    B, S, H, dk = q.shape

    def token(state, xs):
        q, k, v, a, b = xs
        decayed = a[..., :, None] * state                       # Diag(alpha_t) S_{t-1}
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


def kda(u, p, cfg, mode):
    B, S, _ = u.shape
    la = cfg["linear_attn_config"]
    H, d = la["num_heads"], la["head_dim"]
    branch = lambda w, conv: jax.nn.silu(causal_conv(prec.matmul(u, p[w], mode), p[conv])) \
        .reshape(B, S, H, d)
    q, k, v = branch("w_q", "conv_q"), branch("w_k", "conv_k"), branch("w_v", "conv_v")
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True) + _L2_EPS)
    q, k = l2(q) * d ** -0.5, l2(k)
    a = prec.matmul(prec.matmul(u, p["w_fa"], mode), p["w_fb"], mode)
    log_alpha = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        (a + p["dt_bias"]).reshape(B, S, H, d))
    beta = jax.nn.sigmoid(prec.matmul(u, p["w_b"], mode))
    o = delta_recurrence(q, k, v, jnp.exp(log_alpha), beta)
    z = prec.matmul(prec.matmul(u, p["w_ga"], mode), p["w_gb"], mode).reshape(B, S, H, d)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * p["out_norm"]
    return prec.matmul((o * jax.nn.sigmoid(z)).reshape(B, S, H * d), p["w_o"], mode)


def latent_attention(u, p, cfg, mode):
    B, S, _ = u.shape
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q = prec.matmul(u, p["w_q"], mode).reshape(B, S, H, dn + dr).transpose(0, 2, 1, 3)
    cks = prec.matmul(u, p["w_kva"], mode)
    c, k_shared = cks[..., :r], cks[..., r:].reshape(B, S, 1, dr)
    kv = prec.matmul(rms(c, p["kv_a_layernorm"], cfg["rms_norm_eps"]), p["w_kvb"], mode)
    kv = kv.reshape(B, S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_shared, (B, S, H, dr))], -1)
    k, v = k.transpose(0, 2, 1, 3), kv[..., dn:].transpose(0, 2, 1, 3)
    block = min(_QUERY_BLOCK, S)
    if S % block:
        raise ValueError(f"seq_len {S} is not a multiple of the query block {block}")

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
        scores = prec.matmul(qb, k.transpose(0, 1, 3, 2), mode) / jnp.sqrt(float(dn + dr))
        keep = jnp.arange(S)[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return prec.matmul(probs, v, mode)

    ctx = jax.lax.map(rows, jnp.arange(0, S, block))              # (n, B, H, block, dv)
    ctx = ctx.transpose(1, 0, 3, 2, 4).reshape(B, S, H * dv)
    return prec.matmul(ctx, p["w_o"], mode)


def swiglu(x, w_gate, w_up, w_down, mode):
    h = jax.nn.silu(prec.matmul(x, w_gate, mode)) * prec.matmul(x, w_up, mode)
    return prec.matmul(h, w_down, mode)


def moe(x, p, cfg, mode):
    scores = jax.nn.sigmoid(prec.matmul(x, p["router"], mode))
    _, idx = jax.lax.top_k(scores + p["expert_bias"], cfg["num_experts_per_token"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["moe_renormalize"]:
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
    return routed + swiglu(x, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"], mode)


def layer(x, p, kind, cfg, mode):
    """One decoder layer. The mixer and the feed-forward part are each
    recomputed in the backward pass on their own."""
    eps = cfg["rms_norm_eps"]
    mixer = kda if kind[0] == "kda" else latent_attention
    x = x + jax.checkpoint(
        lambda x, p: mixer(rms(x, p["input_layernorm"], eps), p, cfg, mode))(x, p)
    if kind[1] == "dense":
        part = lambda h, p: swiglu(h, p["w_gate"], p["w_up"], p["w_down"], mode)
    else:
        part = lambda h, p: moe(h, p, cfg, mode)
    return x + jax.checkpoint(
        lambda x, p: part(rms(x, p["post_attention_layernorm"], eps), p))(x, p)


def _group(w, name):
    """The tensors of one layer: ``{short name: tensor}``."""
    prefix = name + "/"
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def hidden(w, tokens, cfg, mode="float32"):
    """The residual stream after the last layer and the final norm, ``(B, S, D)``."""
    x = w["embed"][tokens]
    for i, kind in enumerate(held(cfg)):
        x = jax.checkpoint(lambda x, p, kind=kind: layer(x, p, kind, cfg, mode))(
            x, _group(w, f"layers.{i}"))
    return rms(x, w["norm"], cfg["rms_norm_eps"])


def logits(w, tokens, cfg, mode="float32"):
    """``(B, S, V)`` in one piece: for the tests, at small sizes."""
    return prec.matmul(hidden(w, tokens, cfg, mode), w["head"].T, mode)


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
