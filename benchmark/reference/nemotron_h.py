"""Nemotron-H forward and loss in plain float32 ``jax.numpy``, one rank's share.

Written from the published configuration
(huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, ``config.json``,
``model_type`` ``nemotron_h``). No kernel, no chunking of the recurrence, no
sorting of tokens, masks materialised, nothing imported from the program.
``rms(u, w) = w * u / sqrt(mean(u^2) + eps)``, ``eps = layer_norm_epsilon``.

Block ``l`` of kind ``t = pattern[l]``: ``x <- x + mixer_t(rms(x, norm_l))``.
After the last block ``rms`` (``final_norm``), then the untied head. No block
has a mixer and a feed-forward part.

* ``M``, Mamba-2. ``[z | xBC | dt] = u W_in``, widths ``d_in | d_in + 2 G N | H``
  with ``d_in = H P`` (``H`` ``mamba_num_heads``, ``P`` ``mamba_head_dim``, ``G``
  ``n_groups``, ``N`` ``ssm_state_size``). ``xBC <- silu(conv(xBC) + b)``, the
  convolution causal and depthwise over ``conv_kernel`` taps:
  ``conv(v)[t, c] = sum_j w[c, j] v[t - (K - 1) + j, c]``. Split into ``x (H, P)``,
  ``B (G, N)``, ``C (G, N)``. ``D_t = softplus(dt_t + dt_bias)``,
  ``a_t = exp(D_t A)``, ``A = -exp(A_log)`` a head. Head ``h`` of group
  ``g = h // (H / G)``, state ``S (N, P)`` from zero, token by token
  (``lax.scan``)::

      S_t = a_t S_{t-1} + D_t B_t^g x_t^T        y_t = S_t^T C_t^g + D_h x_t

  ``y <- rms_group(y * silu(z))``: the gate first, the norm over each group's
  ``d_in / G`` channels, weight ``out_norm (d_in,)``. ``out = y W_out``.
* ``*``, attention. ``q, k, v = u W_q, u W_k, u W_v`` on ``num_attention_heads`` /
  ``num_key_value_heads`` heads of ``head_dim``; scores ``q_i . k_j / sqrt(head_dim)``
  kept where ``j <= i``; softmax; a KV head shared by consecutive query heads;
  ``W_o``. No rotary embedding and no other position signal (the published
  modelling code applies none; ``rope_theta`` is vestigial). A block of queries at
  a time against a materialised mask.
* ``E``, LatentMoE. ``s = sigmoid(u W_r)`` over all ``n_routed_experts_published``
  outputs; the ``num_experts_per_tok`` largest of ``s + bias`` (``n_group`` 1,
  ``topk_group`` 1: no group limit; ``bias``, ``e_score_correction_bias``, is a
  constant of zeros: it enters the choice only); ``w = s[idx] / (sum s[idx] +
  1e-20) * routed_scaling_factor`` (``norm_topk_prob``). ``l = u W_fc1``;
  ``r = sum_k w_k relu(l W_up^e)^2 W_down^e``; ``out = r W_fc2 + relu(u W_up^s)^2
  W_down^s``. Every held expert is run on every token and weighted (zero where it
  was not chosen): a loop over the experts.

**The share.** The weights given ARE the share: ``mamba_num_heads`` heads in
``n_groups`` groups, ``num_attention_heads`` / ``num_key_value_heads`` heads,
``moe_shared_expert_columns_held`` columns of the shared expert,
``n_routed_experts`` experts with ids ``first_expert ..`` of the router's
``n_routed_experts_published`` outputs, ``vocab_size`` ids. The router's
normalisation is over all the chosen experts, the routed sum over the chosen
that are held; the out-projections give the share's partial sums. The blocks
held are ``hybrid_override_pattern[first_layer : first_layer + num_hidden_layers]``
of the published string.

Departures from the published model: no multi-token-prediction module
(``num_nextn_predict_layers`` 1, ``mtp_hybrid_override_pattern`` ``*E``: the
config gives neither its input projection nor its loss weight); no update rule
for the selection bias (the config has no key for it) and no auxiliary loss;
the cuts the configuration's file states. Each block is recomputed in the
backward pass, the recurrence a chunk of tokens at a time (``jax.checkpoint``):
memory, not values.

Weights are a flat ``{name: array}`` dict, one entry per tensor of the model,
none stacked on a layer axis: ``embed``, ``head`` ``(V, D)``, ``final_norm``,
``layers.<l>/*`` with the names of the block's kind.
"""

import math

import jax
import jax.numpy as jnp

from . import precision as prec

STACKED_PREFIX = None              # no tensor is stacked on a layer axis
_QUERY_BLOCK = 512
_SCAN_BLOCK = 128                  # tokens of the recurrence recomputed together


def pattern(cfg):
    """The kinds of the blocks held."""
    first = cfg["first_layer"]
    return cfg["hybrid_override_pattern"][first:first + cfg["num_hidden_layers"]]


def tensor_shapes(cfg, kind):
    """``{name: (shape, draw)}`` of one block of ``kind`` (or, for ``"top"``, of
    what is outside the blocks), at the share the configuration states."""
    D = cfg["hidden_size"]
    if kind == "top":
        V = cfg["vocab_size"]
        return {"embed": ((V, D), "std"), "head": ((V, D), "std"), "final_norm": ((D,), "one")}
    if kind == "M":
        H, G, N = cfg["mamba_num_heads"], cfg["n_groups"], cfg["ssm_state_size"]
        d_in = H * cfg["mamba_head_dim"]
        conv = d_in + 2 * G * N
        return {"norm": ((D,), "one"), "w_in": ((D, d_in + conv + H), "std"),
                "conv": ((conv, cfg["conv_kernel"]), "conv"), "conv_bias": ((conv,), "conv"),
                "a_log": ((H,), "a_log"), "dt_bias": ((H,), "dt_bias"), "d": ((H,), "one"),
                "out_norm": ((d_in,), "one"), "w_out": ((d_in, D), "out")}
    if kind == "*":
        H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
        return {"norm": ((D,), "one"), "w_q": ((D, H * hd), "std"), "w_k": ((D, Hkv * hd), "std"),
                "w_v": ((D, Hkv * hd), "std"), "w_o": ((H * hd, D), "std")}
    E, Eh = cfg["n_routed_experts_published"], cfg["n_routed_experts"]
    Dl, F, Fs = (cfg["moe_latent_size"], cfg["moe_intermediate_size"],
                 cfg["moe_shared_expert_columns_held"])
    return {"norm": ((D,), "one"), "router": ((D, E), "std"), "fc1_latent": ((D, Dl), "std"),
            "fc2_latent": ((Dl, D), "std"), "w_up": ((Eh, Dl, F), "std"),
            "w_down": ((Eh, F, Dl), "std"), "shared_w_up": ((D, Fs), "std"),
            "shared_w_down": ((Fs, D), "std")}


def weights(cfg, key):
    """The seeded float32 weights of the share, flat, after the published
    modelling code's init (the configuration's ``assumed.weights``): matmul
    weights, embedding and head N(0, ``initializer_range``), the Mamba
    out-projection that over the square root of the WHOLE model's depth
    (``rescale_prenorm_residual``); ``A_log = log U(1, 16)`` a head; ``dt_bias``
    the inverse softplus of a step log-uniform in ``time_step_min .. max``,
    floored at ``time_step_floor``; ``D`` and the norm weights one; the
    convolution and its bias uniform in +-1/sqrt(taps). Every value is rounded
    to one a bfloat16 holds, so that a bfloat16 copy starts equal. Drawn here,
    tensor by tensor, by nothing of the program. Traceable."""
    std, depth = cfg["initializer_range"], cfg["published"]["num_hidden_layers"]

    def draw(k, shape, how):
        if how == "one":
            return jnp.ones(shape, jnp.float32)
        if how == "conv":
            bound = 1.0 / math.sqrt(cfg["conv_kernel"])
            return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        if how == "a_log":
            return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        if how == "dt_bias":
            lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
            step = jnp.maximum(jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi)),
                               cfg["time_step_floor"])
            return step + jnp.log(-jnp.expm1(-step))
        return jax.random.normal(k, shape, jnp.float32) * (
            std / math.sqrt(depth) if how == "out" else std)

    groups = [("", "top")] + [(f"layers.{l}/", kind) for l, kind in enumerate(pattern(cfg))]
    out = {}
    for g, (prefix, kind) in enumerate(groups):
        for t, (name, (shape, how)) in enumerate(sorted(tensor_shapes(cfg, kind).items())):
            k = jax.random.fold_in(jax.random.fold_in(key, g), t)
            out[prefix + name] = prec.as_bfloat16_values(draw(k, shape, how))
    return out


def rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def relu2(x, w_up, w_down, mode):
    return prec.matmul(jnp.square(jax.nn.relu(prec.matmul(x, w_up, mode))), w_down, mode)


def recurrence(x, step, a, Bm, Cm):
    """``x (B, S, H, P)``, ``step, a (B, S, H)``, ``Bm, Cm (B, S, H, N)`` (a
    group's row repeated over its heads): ``y (B, S, H, P)`` without the skip."""
    B, S, H, P = x.shape

    def token(state, xs):
        x_t, step_t, a_t, b_t, c_t = xs
        state = a_t[..., None, None] * state \
            + (step_t[..., None] * b_t)[..., :, None] * x_t[..., None, :]
        return state, jnp.sum(state * c_t[..., :, None], axis=-2)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    size = min(_SCAN_BLOCK, S)
    if S % size:
        raise ValueError(f"seq_len {S} is not a multiple of the scan block {size}")
    by_block = lambda t: jnp.moveaxis(t, 1, 0).reshape(S // size, size, *t.shape[:1], *t.shape[2:])
    state = jnp.zeros((B, H, Bm.shape[-1], P), jnp.float32)
    _, y = jax.lax.scan(block, state, tuple(by_block(t) for t in (x, step, a, Bm, Cm)))
    return jnp.moveaxis(y.reshape(S, B, H, P), 0, 1)


def mamba(u, p, cfg, mode):
    B, S, _ = u.shape
    H, P, G, N = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
                  cfg["ssm_state_size"])
    d_in, K = H * P, cfg["conv_kernel"]
    zxbcdt = prec.matmul(u, p["w_in"], mode)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * G * N], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j:j + S] * p["conv"][:, j] for j in range(K))
                      + p["conv_bias"])
    x, Bm, Cm = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    x = x.reshape(B, S, H, P)
    per_head = lambda t: jnp.repeat(t.reshape(B, S, G, N), H // G, axis=2)
    step = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(step * -jnp.exp(p["a_log"]))
    y = recurrence(x, step, a, per_head(Bm), per_head(Cm)) + p["d"][:, None] * x
    y = y.reshape(B, S, d_in) * jax.nn.silu(z)
    y = rms(y.reshape(B, S, G, d_in // G), p["out_norm"].reshape(G, d_in // G),
            cfg["layer_norm_epsilon"]).reshape(B, S, d_in)
    return prec.matmul(y, p["w_out"], mode)


def attention(u, p, cfg, mode):
    B, S, _ = u.shape
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = prec.matmul(u, p["w_q"], mode).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = prec.matmul(u, p["w_k"], mode).reshape(B, S, Hkv, hd)
    v = prec.matmul(u, p["w_v"], mode).reshape(B, S, Hkv, hd)
    k, v = (jnp.repeat(t, H // Hkv, axis=2).transpose(0, 2, 1, 3) for t in (k, v))
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


def moe(u, p, cfg, mode):
    scores = jax.nn.sigmoid(prec.matmul(u, p["router"], mode))
    bias = jnp.zeros((cfg["n_routed_experts_published"],), jnp.float32)   # a constant
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    # (.., held): the weight of each held expert, zero where it was not chosen
    held = cfg["first_expert"] + jnp.arange(cfg["n_routed_experts"])
    gates = jnp.sum(top[..., None] * (idx[..., None] == held), axis=-2)
    latent = prec.matmul(u, p["fc1_latent"], mode)

    @jax.checkpoint
    def one(gate, w_up, w_down):
        return gate[..., None] * relu2(latent, w_up, w_down, mode)

    routed, _ = jax.lax.scan(
        lambda acc, xs: (acc + one(*xs), None), jnp.zeros_like(latent),
        (jnp.moveaxis(gates, -1, 0), p["w_up"], p["w_down"]))
    return prec.matmul(routed, p["fc2_latent"], mode) \
        + relu2(u, p["shared_w_up"], p["shared_w_down"], mode)


_MIXER = {"M": mamba, "*": attention, "E": moe}


def block(x, p, kind, cfg, mode):
    """One block, recomputed in the backward pass."""
    return x + jax.checkpoint(lambda x, p: _MIXER[kind](
        rms(x, p["norm"], cfg["layer_norm_epsilon"]), p, cfg, mode))(x, p)


def _group(w, name):
    """The tensors of one block: ``{short name: tensor}``."""
    prefix = name + "/"
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def hidden(w, tokens, cfg, mode="float32"):
    """The residual stream after the last block and the final norm, ``(B, S, D)``."""
    x = w["embed"][tokens]
    for l, kind in enumerate(pattern(cfg)):
        x = block(x, _group(w, f"layers.{l}"), kind, cfg, mode)
    return rms(x, w["final_norm"], cfg["layer_norm_epsilon"])


def loss(w, batch, cfg, mode="float32"):
    """Mean next-token cross entropy over every position of ``(tokens, targets)``,
    the head and the log-softmax taken a block of positions at a time."""
    tokens, targets = batch
    x = hidden(w, tokens, cfg, mode)
    B, S, D = x.shape
    size = min(_QUERY_BLOCK, S)
    if S % size:
        raise ValueError(f"seq_len {S} is not a multiple of the loss block {size}")

    @jax.checkpoint
    def rows(xs):
        xb, tb = xs
        lg = prec.matmul(xb, w["head"].T, mode)
        picked = jnp.take_along_axis(lg, tb[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked)

    split = lambda t: jnp.moveaxis(t.reshape(B, S // size, size, *t.shape[2:]), 1, 0)
    return jnp.sum(jax.lax.map(rows, (split(x), split(targets)))) / (B * S)
