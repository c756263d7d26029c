"""DeepSeek-V3-type forward and loss in plain float32 ``jax.numpy``, one chip's share.

Written from the published configuration
(huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601, ``config.json``,
``model_type`` ``deepseek_v3``) and the published ``deepseek_v3`` modelling code,
in that code's order of operations. No kernel, no sorting of tokens, masks
materialised, nothing imported from the program. Bias-free throughout; ``rms(u,
w) = w * u / sqrt(mean(u^2) + eps)``, ``eps = rms_norm_eps``.

* Layer ``l`` (published index): ``h = x + attn(rms(x, input_layernorm_l))``,
  ``y = h + ffn_l(rms(h, post_attention_layernorm_l))``. ``ffn_l`` is a dense
  SwiGLU of width ``intermediate_size`` for ``l < first_k_dense_replace`` (or ``l
  % moe_layer_freq != 0``) and the mixture of experts elsewhere. After the last
  layer ``rms(., norm)``, then the head (``head``, or the
  embedding matrix where ``tie_word_embeddings``).
* Attention (``q_lora_rank`` null): ``q = u W_q`` viewed ``(H, qk_nope_head_dim +
  qk_rope_head_dim)`` and split ``[q_nope | q_rot]``; ``[c | k_rot] = u W_kva``
  split at ``kv_lora_rank`` (``k_rot`` is one head); ``rms(c, kv_a_layernorm)
  W_kvb`` viewed ``(H, qk_nope_head_dim + v_head_dim)`` and split ``[k_nope |
  v]``. Rotary embedding on ``q_rot`` and ``k_rot`` (``rope_interleave``): each
  vector is first re-laid, even dims then odd dims (``view(d/2, 2).transpose``),
  then ``x * cos + rotate_half(x) * sin`` with ``cos, sin`` of ``pos * f_i``
  repeated twice, ``f_i = rope_theta^(-2i / qk_rope_head_dim)``: dims ``(2i,
  2i+1)`` of the projection are one pair. ``k_rot`` is expanded over the heads;
  ``q = [q_nope | q_rot]``, ``k = [k_nope | k_rot]``; scores ``q_i . k_j /
  sqrt(qk_head_dim)`` kept where ``j <= i``; softmax; times ``v``; ``W_o``. A
  block of queries at a time against a materialised mask.
* Mixture of experts: ``s = sigmoid(u W_r)`` over all ``n_routed_experts_published``
  outputs; the ``num_experts_per_tok`` largest of ``s + b`` (``b``,
  ``expert_bias``, the published ``e_score_correction_bias``: it enters the
  choice only; ``n_group = topk_group = 1``, so no group is masked); ``w =
  s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor`` (``norm_topk_prob``);
  ``sum_e w_e W_d^e (silu(W_g^e x) * W_u^e x)``; plus the shared expert, one
  SwiGLU of width ``n_shared_experts * moe_intermediate_size`` on every token,
  not gated. Every held expert is run on every token and weighted (zero where it
  was not chosen): a loop over the experts.

**The share.** ``cfg["n_routed_experts"]`` experts are held here, ids
``first_expert .. first_expert + n_routed_experts - 1`` of the router's
``n_routed_experts_published`` outputs; the sum over ``e`` runs over the chosen
experts that are among them, the router's normalisation over all the chosen. The
shared expert is whole on every rank. The vocabulary is a slice: embedding, head
and loss are over ``vocab_size`` ids. The layers held are ``first_layer ..
first_layer + num_hidden_layers - 1``, and dense or experts is decided on the
published index.

Departures from the published model: the selection bias is zeros and nothing
moves it (the published code registers zeros; ``config.json`` has no key for the
rule that moves it), no auxiliary loss and no multi-token-prediction module (no
key for either); the cuts of depth, experts held and vocabulary that the
configuration's file states. Each layer is recomputed in the backward pass
(``jax.checkpoint``): memory, not values.

Weights are a flat ``{name: array}`` dict, one entry per tensor of the model,
none stacked on a layer axis: ``embed`` ``(V, D)``, ``head`` ``(V, D)``, ``norm``,
``layers.<i>/*`` for the ``i``-th held layer: ``input_layernorm``, ``w_q``,
``w_kva``, ``kv_a_layernorm``, ``w_kvb``, ``w_o``, ``post_attention_layernorm``,
and ``w_gate``, ``w_up``, ``w_down`` (with experts those three stacked over the
held experts, beside ``router``, ``expert_bias``, ``shared_w_gate``,
``shared_w_up``, ``shared_w_down``).
"""

import jax
import jax.numpy as jnp

from . import precision as prec

STACKED_PREFIX = None              # no tensor is stacked on a layer axis
_QUERY_BLOCK = 512
_ROUTER_EPS = 1e-20


def held(cfg):
    """``["dense" | "moe"]`` of the layers held, by their published index."""
    first = cfg["first_layer"]
    return ["moe" if l >= cfg["first_k_dense_replace"] and l % cfg["moe_layer_freq"] == 0
            else "dense" for l in range(first, first + cfg["num_hidden_layers"])]


def tensor_shapes(cfg):
    """``{name: (shape, draw)}`` of every tensor of the share, flat; ``draw``
    names a case of :func:`weights`."""
    D, H, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    F, Fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E, Eh = cfg["n_routed_experts_published"], cfg["n_routed_experts"]
    Fs = cfg["n_shared_experts"] * Fm
    attention_part = {
        "input_layernorm": ((D,), "one"), "w_q": ((D, H * (dn + dr)), "std"),
        "w_kva": ((D, r + dr), "std"), "kv_a_layernorm": ((r,), "one"),
        "w_kvb": ((r, H * (dn + dv)), "std"), "w_o": ((H * dv, D), "std"),
        "post_attention_layernorm": ((D,), "one")}
    part = {
        "dense": {"w_gate": ((D, F), "std"), "w_up": ((D, F), "std"),
                  "w_down": ((F, D), "std")},
        "moe": {"router": ((D, E), "std"), "expert_bias": ((E,), "zero"),
                "w_gate": ((Eh, D, Fm), "std"), "w_up": ((Eh, D, Fm), "std"),
                "w_down": ((Eh, Fm, D), "std"),
                "shared_w_gate": ((D, Fs), "std"), "shared_w_up": ((D, Fs), "std"),
                "shared_w_down": ((Fs, D), "std")},
    }
    V = cfg["vocab_size"]
    out = {"embed": ((V, D), "embed"), "norm": ((D,), "one")}
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((V, D), "std")
    for i, ffn in enumerate(held(cfg)):
        out.update({f"layers.{i}/{name}": leaf
                    for name, leaf in {**attention_part, **part[ffn]}.items()})
    return out


def weights(cfg, key):
    """The seeded float32 weights of the share, flat (the configuration's
    ``assumed.weights``): every matmul weight and the head N(0,
    ``initializer_range``); the embedding N(0, ``embedding_init_std``); norm
    weights one; the selection bias zeros. Every value is rounded to one a
    bfloat16 holds, so that a bfloat16 copy starts equal. Drawn here, tensor by
    tensor, by nothing of the program. Traceable."""
    def draw(k, shape, how):
        if how == "one":
            return jnp.ones(shape, jnp.float32)
        if how == "zero":
            return jnp.zeros(shape, jnp.float32)
        std = cfg["embedding_init_std"] if how == "embed" else cfg["initializer_range"]
        return jax.random.normal(k, shape, jnp.float32) * std

    return {name: prec.as_bfloat16_values(draw(jax.random.fold_in(key, t), shape, how))
            for t, (name, (shape, how)) in enumerate(sorted(tensor_shapes(cfg).items()))}


def rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def rope_interleaved(x, theta):
    """``x (B, S, H, d)``, positions ``0 .. S-1``, in the published order: the
    vector re-laid as its even dims then its odd dims, then ``rotate_half``."""
    *lead, d = x.shape
    x = jnp.swapaxes(x.reshape(*lead, d // 2, 2), -1, -2).reshape(*lead, d)
    f = 1.0 / theta ** (2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * f[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None, :]
    rotate_half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotate_half * sin


def attention(u, p, cfg, mode):
    B, S, _ = u.shape
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = prec.matmul(u, p["w_q"], mode).reshape(B, S, H, dn + dr)
    q_nope, q_rot = q[..., :dn], q[..., dn:]
    ckr = prec.matmul(u, p["w_kva"], mode)
    c, k_rot = ckr[..., :r], ckr[..., r:].reshape(B, S, 1, dr)
    kv = prec.matmul(rms(c, p["kv_a_layernorm"], eps), p["w_kvb"], mode)
    kv = kv.reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_rot, k_rot = rope_interleaved(q_rot, theta), rope_interleaved(k_rot, theta)
    q = jnp.concatenate([q_nope, q_rot], -1).transpose(0, 2, 1, 3)        # (B, H, S, dn + dr)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rot, (B, S, H, dr))], -1)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
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
    _, idx = jax.lax.top_k(scores + p["expert_bias"], cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + _ROUTER_EPS)
    top = top * cfg["routed_scaling_factor"]
    # (.., held): the weight of each held expert, zero where it was not chosen
    here = cfg["first_expert"] + jnp.arange(cfg["n_routed_experts"])
    gates = jnp.sum(top[..., None] * (idx[..., None] == here), axis=-2)

    @jax.checkpoint
    def one(gate, w_gate, w_up, w_down):
        return gate[..., None] * swiglu(x, w_gate, w_up, w_down, mode)

    routed, _ = jax.lax.scan(lambda acc, xs: (acc + one(*xs), None), jnp.zeros_like(x), (
        jnp.moveaxis(gates, -1, 0), p["w_gate"], p["w_up"], p["w_down"]))
    return routed + swiglu(x, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"], mode)


def layer(x, p, ffn, cfg, mode):
    """One decoder layer. The mixer and the feed-forward part are each
    recomputed in the backward pass on their own."""
    eps = cfg["rms_norm_eps"]
    x = x + jax.checkpoint(
        lambda x, p: attention(rms(x, p["input_layernorm"], eps), p, cfg, mode))(x, p)
    if ffn == "dense":
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
    for i, ffn in enumerate(held(cfg)):
        x = jax.checkpoint(lambda x, p, ffn=ffn: layer(x, p, ffn, cfg, mode))(
            x, _group(w, f"layers.{i}"))
    return rms(x, w["norm"], cfg["rms_norm_eps"])


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
