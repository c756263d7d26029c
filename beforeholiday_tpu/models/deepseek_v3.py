"""DeepSeek-V3-type decoders: multi-head latent attention (keys and values made
from a shared low-rank latent, 192-wide queries and keys on 128-wide values, a
rotary part that is one head shared by all), a leading dense SwiGLU layer and
then sigmoid-routed experts beside an always-on shared one, an untied head.

Written from a published configuration of the type
(huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601, ``config.json``,
``model_type`` ``deepseek_v3``) and the published ``deepseek_v3`` modelling code.
Bias-free throughout. With ``rms(x, w) = w * x / sqrt(mean(x^2) + eps)`` (plain
weight, initially one):

* layer ``l``: ``h = x + attn(rms(x, input_layernorm_l))``; ``y = h +
  ffn_l(rms(h, post_attention_layernorm_l))``. ``ffn_l`` is a dense SwiGLU of
  width ``intermediate_size`` for ``l < first_k_dense_replace`` (and wherever
  ``l % moe_layer_freq != 0``) and the mixture of experts elsewhere. After the
  last layer ``rms(., norm)`` and the head (its own matrix unless
  ``tie_word_embeddings``).
* attention (``q_lora_rank`` null): ``q = u W_q`` on ``H`` heads of
  ``qk_nope_head_dim + qk_rope_head_dim``, each ``[q_nope | q_rot]``; ``[c |
  k_rot] = u W_kva`` (``kv_lora_rank + qk_rope_head_dim``; ``k_rot`` is ONE head
  that all ``H`` share); ``[k_nope_h | v_h]`` of each head from ``rms(c,
  kv_a_layernorm) W_kvb``. Rotary embedding on ``q_rot`` and ``k_rot`` only,
  interleaved as published (``rope_interleave``: dims ``(2i, 2i + 1)`` are one
  pair, :func:`evens_then_odds`). ``q_h = [q_nope_h | q_rot_h]``, ``k_h =
  [k_nope_h | k_rot]``: causal softmax of ``q_h . k_h / sqrt(qk_head_dim)`` over
  ``v_h`` (``ops.flash_attention``, which takes the two widths as they are), the
  ``H x v_head_dim`` outputs through ``W_o``. ``k_rot`` is broadcast over the
  heads and joined to ``k_nope`` here, in XLA, and its cotangent summed over the
  heads by autodiff.
* mixture of experts (``moe.dropless``): ``s = sigmoid(u W_r)`` in float32 over
  all the router's outputs; the ``num_experts_per_tok`` largest of ``s + b``
  (``e_score_correction_bias``, here ``expert_bias``); the chosen ``s`` over
  their sum plus 1e-20 (``norm_topk_prob``), times ``routed_scaling_factor``;
  SwiGLU experts of ``moe_intermediate_size``; **plus** one ungated SwiGLU of
  width ``n_shared_experts * moe_intermediate_size`` added to every token.
  ``n_group = topk_group = 1``: the group limit is the identity, and any other
  value raises (no configuration here has one, so nothing could test it).

**The selection bias** ``expert_bias (E,)`` is data: a float32 leaf of the
parameters whose gradient is exactly zero (``route_sigmoid`` takes it under
``stop_gradient`` and it enters the choice only). The published code registers
it as zeros and moves it by a rule outside the gradient; the config has no key
for that rule and none is here, so :func:`init` leaves it zeros.

**The model is told its share**, as ``models.lfm2_moe`` is: which published layer
its first held one is (``first_layer``: dense or experts is decided on the
published index), how many experts live here and which (``n_routed_experts``,
``first_expert``, of the router's ``n_routed_experts_published`` outputs) and how
many ids of the vocabulary. The held experts give their part of the routed sum;
the shared expert is whole on every rank. The whole model is the default.

Parameters are ``{"embed", "norm", "head", "layers": [...]}``: one dict a held
layer, nothing stacked (the layers are unrolled; the first differs from the
rest).

Not here: a query latent (``q_lora_rank`` not null raises), grouped routing, an
update rule for the selection bias, an auxiliary loss, a multi-token-prediction
module (the published ``config.json`` has a key for none of the last three).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from beforeholiday_tpu.models import layers as _layers
from beforeholiday_tpu.models.layers import COUNTERS  # noqa: F401  (the step's counters)
from beforeholiday_tpu.monitor.spans import annotate as _annotate, span as _span
from beforeholiday_tpu.remat import apply as _remat_apply

_F32 = jnp.float32
DENSE, MOE = "dense", "moe"


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 512               # ids held here (a slice of the vocabulary)
    hidden_size: int = 128
    num_hidden_layers: int = 4          # layers held: first_layer .. + held
    first_layer: int = 0                # the published index of the first held layer
    first_k_dense_replace: int = 1      # published layers 0 .. this have a dense SwiGLU
    moe_layer_freq: int = 1
    intermediate_size: int = 256        # the dense SwiGLU's width
    # multi-head latent attention
    num_attention_heads: int = 4
    num_key_value_heads: int = 4        # no grouped keys: every head has its own
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 64
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    rope_theta: float = 1e6
    rope_interleave: bool = True
    # mixture of experts
    moe_intermediate_size: int = 64
    n_routed_experts_published: int = 8  # the router's width
    n_routed_experts: int = 8            # experts first_expert .. + held live here
    first_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 2
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    scoring_func: str = "sigmoid"
    moe_rows_bound: Optional[int] = None   # None: the worst case, never overflows
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02     # every matmul weight and the embedding
    dtype: jnp.dtype = jnp.float32      # activation dtype
    remat_policy: Optional[str] = None  # over one layer; None = no remat
    attention_impl: Optional[str] = None   # forces the flash dispatch in tests

    def __post_init__(self):
        if self.q_lora_rank is not None:
            raise ValueError(f"q_lora_rank {self.q_lora_rank}: a query latent is not built here")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(
                f"n_group {self.n_group} / topk_group {self.topk_group}: only the identity "
                f"group limit (1 / 1) is built here")
        if self.scoring_func != "sigmoid":
            raise ValueError(f"scoring_func {self.scoring_func!r}: only 'sigmoid' is built here")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("latent attention has no grouped keys: num_key_value_heads "
                             "must equal num_attention_heads")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def held(self) -> Tuple[str, ...]:
        """The feed-forward kind of each held layer, decided on the published index."""
        return tuple(
            MOE if l >= self.first_k_dense_replace and l % self.moe_layer_freq == 0 else DENSE
            for l in range(self.first_layer, self.first_layer + self.num_hidden_layers))


def param_shapes(cfg: DeepseekV3Config) -> dict:
    """``(shape, init)`` of every leaf, in the parameters' own tree; init names
    a draw of :func:`init`."""
    D, V, H = cfg.hidden_size, cfg.vocab_size, cfg.num_attention_heads
    r, dn, dr, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, Fm = cfg.intermediate_size, cfg.moe_intermediate_size
    E, Eh, Fs = cfg.n_routed_experts_published, cfg.n_routed_experts, \
        cfg.n_shared_experts * cfg.moe_intermediate_size
    attention_part = {
        "input_layernorm": ((D,), "one"),
        "w_q": ((D, H * (dn + dr)), "std"),
        "w_kva": ((D, r + dr), "std"),
        "kv_a_layernorm": ((r,), "one"),
        "w_kvb": ((r, H * (dn + dv)), "std"),
        "w_o": ((H * dv, D), "std"),
        "post_attention_layernorm": ((D,), "one"),
    }
    part = {
        DENSE: {
            "w_gate": ((D, F), "std"),
            "w_up": ((D, F), "std"),
            "w_down": ((F, D), "std"),
        },
        MOE: {
            "router": ((D, E), "std"),
            "expert_bias": ((E,), "zero"),
            "w_gate": ((Eh, D, Fm), "std"),
            "w_up": ((Eh, D, Fm), "std"),
            "w_down": ((Eh, Fm, D), "std"),
        },
    }
    if Fs:
        part[MOE].update({"shared_w_gate": ((D, Fs), "std"), "shared_w_up": ((D, Fs), "std"),
                          "shared_w_down": ((Fs, D), "std")})
    shapes = {"embed": ((V, D), "std"), "norm": ((D,), "one"),
              "layers": [{**attention_part, **part[ffn]} for ffn in cfg.held]}
    if not cfg.tie_word_embeddings:
        shapes["head"] = ((V, D), "std")
    return shapes


def init(key: jax.Array, cfg: DeepseekV3Config) -> dict:
    """Seeded float32 parameters: matmul weights, the embedding and the head
    N(0, ``initializer_range``), norm weights one, the selection bias zeros (as
    the published code registers it)."""
    def draw(k, shape, kind):
        if kind == "one":
            return jnp.ones(shape, _F32)
        if kind == "zero":
            return jnp.zeros(shape, _F32)
        return jax.random.normal(k, shape, _F32) * cfg.initializer_range

    return _layers.draw_params(key, param_shapes(cfg), draw)


def keep_fp32(path) -> bool:
    """``amp.initialize(keep_fp32_mask=...)``: the norm weights and the
    selection bias."""
    return _layers.keep_fp32(path, also=("expert_bias",))


rms_norm = _layers.rms_norm


def evens_then_odds(w):
    """The rotary columns of a projection re-laid as the published
    ``rope_interleave`` code re-lays the projected vector: the even ones first,
    then the odd ones (last axis). That code pairs dims ``(2i, 2i + 1)`` by
    re-laying each rotary vector so and then applying ``rotate_half``; ``(u W)[..,
    perm] = u W[:, perm]`` column for column, so the permutation is taken on the
    weight, where it moves ``D x 64`` numbers a head and not ``S x 64``, and the
    result is in the published order (``q_rot . k_rot`` does not see it: both
    sides carry it)."""
    return jnp.concatenate([w[..., 0::2], w[..., 1::2]], axis=-1)


@_annotate("mla_mixer")
def attention(cfg: DeepseekV3Config, u, p, table):
    """One latent-attention mixer (``models.layers.latent_attention``); ``table``:
    ``(cos, sin)`` of the sequence at ``qk_rope_head_dim``."""
    return _layers.latent_attention(
        u, p, heads=cfg.num_attention_heads, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, eps=cfg.rms_norm_eps, table=table,
        relay=evens_then_odds if cfg.rope_interleave else None, impl=cfg.attention_impl)


dense_ffn = _annotate("dense_ffn")(_layers.swiglu_ffn)


def sparse_ffn(cfg: DeepseekV3Config, h, p):
    """``(y, counters)`` of one mixture-of-experts part (``moe.dropless``'s spans)."""
    return _layers.sigmoid_moe(
        h, p, top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
        rows_bound=cfg.moe_rows_bound, renormalize=cfg.norm_topk_prob,
        bias=p["expert_bias"], scale=cfg.routed_scaling_factor)


def _layer(cfg: DeepseekV3Config, ffn: str, x, p, table):
    """One decoder layer on its own leaves ``p``: ``(x, the MoE counters or None)``."""
    x = x + attention(cfg, rms_norm(x, p["input_layernorm"], cfg.rms_norm_eps), p, table)
    h = rms_norm(x, p["post_attention_layernorm"], cfg.rms_norm_eps)
    if ffn == DENSE:
        return x + dense_ffn(h, p), None
    y, counters = sparse_ffn(cfg, h, p)
    return x + y, counters


def forward(params: dict, tokens: jax.Array, cfg: DeepseekV3Config):
    """``tokens (B, S) int32 -> (logits (B, S, V) float32, counters)``.
    ``counters``: per step, over the MoE layers (``models.layers.reduce_counters``)."""
    held = cfg.held
    with _span("deepseek_v3_embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        table = _layers.rotary_table(tokens.shape[1], cfg.qk_rope_head_dim, cfg.rope_theta)
    layer = {ffn: _remat_apply(functools.partial(_layer, cfg, ffn), cfg.remat_policy)
             for ffn in sorted(set(held))}
    with _span("deepseek_v3_layers"):
        x, seen = _layers.unrolled_layers(layer, held, params["layers"], x, table)
    counters = _layers.step_counters(seen)
    with _span("deepseek_v3_head"):
        x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
        logits = _layers.logits_of(
            x, params["embed" if cfg.tie_word_embeddings else "head"])
    return logits, counters


cross_entropy = _annotate("deepseek_v3_loss")(_layers.cross_entropy)


def loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
            cfg: DeepseekV3Config, forward_fn=None):
    """``(mean next-token cross entropy over the vocabulary held, counters)``.
    ``forward_fn(params, tokens)`` overrides the plain forward (an amp-wrapped
    apply), as in ``testing/gpt.loss_fn``."""
    return _layers.loss_fn(forward_fn or functools.partial(forward, cfg=cfg), cross_entropy,
                           params, tokens, targets)


def param_count(cfg: DeepseekV3Config) -> int:
    return _layers.param_count(param_shapes(cfg))
