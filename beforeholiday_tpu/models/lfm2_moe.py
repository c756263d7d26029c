"""LFM2-MoE: gated short-convolution mixers among grouped-query attention layers
on a ``layer_types`` list that is not periodic, a feed-forward part that changes
kind along the depth (dense SwiGLU, then a mixture of experts), tied embeddings.

Written from the published configuration
(huggingface.co/LiquidAI/LFM2-8B-A1B, ``config.json``, ``model_type``
``lfm2_moe``). Bias-free throughout. With ``rms(x, w) = w * x / sqrt(mean(x^2)
+ eps)`` (plain weight, initially one):

* layer ``l``: ``h = x + op_l(rms(x, operator_norm_l))``; ``y = h +
  ffn_l(rms(h, ffn_norm_l))``. ``op_l`` is the short convolution where
  ``layer_types[l] == "conv"`` and attention where it is ``"full_attention"``;
  ``ffn_l`` is a dense SwiGLU of width ``intermediate_size`` for ``l <
  num_dense_layers`` and the mixture of experts after. After the last layer
  ``rms(., embedding_norm)`` and the head, which is the embedding matrix
  (``tie_word_embeddings``).
* short convolution (``conv_L_cache`` taps, no bias): ``[B | C | x~] = u W_in``;
  ``z = B * x~``; ``c[t] = sum_j w[:, j] z[t - (K-1) + j]`` (depthwise, causal,
  zeros before the start); ``y = (C * c) W_out``. **No activation function**:
  the two gates are the non-linearity. Between the projections it is
  ``ops.short_conv.gated_short_conv`` (one Pallas pass each way on the TPU).
* attention: ``q = u W_q`` on ``num_attention_heads`` heads of ``hidden_size /
  num_attention_heads``, ``k``, ``v`` on ``num_key_value_heads`` (GQA by
  repetition); ``q``, ``k`` through ``rms`` over the head (one weight of
  ``head_dim`` each, shared by the heads); rotary embedding on the whole head
  (``rotate_half`` layout, ``rope_theta``, no scaling); causal softmax at
  ``head_dim^-1/2`` (``ops.flash_attention``); ``W_o``. This is
  ``models.mellum``'s attention without a window: both call
  ``models.layers.qk_norm_attention``.
* mixture of experts (``moe.dropless``): ``s = sigmoid(u W_r)`` in float32 over
  all the router's outputs; the ``num_experts_per_tok`` largest of ``s + b``
  (``use_expert_bias``); the chosen ``s`` over their sum plus 1e-6
  (``norm_topk_prob``), times ``routed_scaling_factor``; SwiGLU experts, no
  shared one.

**The selection bias** ``expert_bias (E,)`` is data: a float32 leaf of the
parameters **whose gradient is exactly zero** (``route_sigmoid`` takes it under
``stop_gradient`` and it enters the choice only), so Adam leaves it where it
was drawn. The published model moves it by a balancing rule outside the
gradient; the config has no key for that rule and none is here.

**The model is told its share**: which published layer its first held one is
(``first_layer``: ``l < num_dense_layers`` is decided on the published index),
how many experts live here and which (``num_experts``, ``first_expert``, of the
router's ``num_experts_published`` outputs) and how many ids of the vocabulary.
The held experts give their part of the routed sum; what the absent ones would
add is absent, and nothing here stands in for it. The whole model is the
default.

Parameters are ``{"embed", "embedding_norm", "layers": [...]}``: one dict a held
layer with what that layer holds (a convolution or an attention mixer's leaves
with ``operator_norm``; a dense or an expert feed-forward part's with
``ffn_norm``), nothing stacked over layers and nothing padded to a common shape.
The published pattern repeats nothing (``c c a | c c c a`` x 4 ``| c c a | c c``,
two leading dense layers), so the layers are unrolled and no ``lax.scan`` would
consume a stack: a stacked layout would only be unstacked again, a copy of every
weight and of every gradient a step.

Not here: an update rule for the selection bias and an auxiliary balancing
loss (the published ``config.json`` has a key for neither).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from beforeholiday_tpu.models import layers as _layers
from beforeholiday_tpu.models.layers import COUNTERS  # noqa: F401  (the step's counters)
from beforeholiday_tpu.monitor.spans import annotate as _annotate, span as _span
from beforeholiday_tpu.remat import apply as _remat_apply

_F32 = jnp.float32
CONV, ATTENTION = "conv", "full_attention"
DENSE, MOE = "dense", "moe"
_ROUTER_EPS = 1e-6                  # the published renormalisation: s / (sum s + 1e-6)
# the published list: c c a | c c c a x 4 | c c a | c c
PUBLISHED_LAYER_TYPES = ((CONV, CONV, ATTENTION) + (CONV, CONV, CONV, ATTENTION) * 4
                         + (CONV, CONV, ATTENTION) + (CONV, CONV))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 512               # ids held here (a slice of the vocabulary)
    hidden_size: int = 128
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES    # the PUBLISHED list, whole
    num_hidden_layers: int = 24         # layers held: first_layer .. + held
    first_layer: int = 0                # the published index of the first held layer
    num_dense_layers: int = 2           # published layers 0 .. this have a dense SwiGLU
    num_attention_heads: int = 4        # heads of hidden_size / num_attention_heads
    num_key_value_heads: int = 2
    conv_L_cache: int = 3               # taps of the short convolution
    intermediate_size: int = 256        # the dense SwiGLU's width
    # mixture of experts
    moe_intermediate_size: int = 64
    num_experts_published: int = 8      # the router's width
    num_experts: int = 8                # experts first_expert .. + held live here
    first_expert: int = 0
    num_experts_per_tok: int = 2
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    moe_rows_bound: Optional[int] = None   # None: the worst case, never overflows
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02     # every matmul weight and the embedding
    expert_bias_init_std: float = 0.01  # see :func:`init`
    dtype: jnp.dtype = jnp.float32      # activation dtype
    remat_policy: Optional[str] = None  # over one layer; None = no remat
    attention_impl: Optional[str] = None   # forces the flash dispatch in tests
    short_conv_impl: Optional[str] = None  # ... and the short convolution's

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def held(self) -> Tuple[Tuple[str, str], ...]:
        """``(mixer kind, feed-forward kind)`` of each held layer, the second
        decided on the published index."""
        first, n = self.first_layer, self.num_hidden_layers
        kinds = tuple(self.layer_types[first:first + n])
        if len(kinds) != n or set(kinds) - {CONV, ATTENTION}:
            raise ValueError(
                f"layers {first} .. {first + n} of layer_types {tuple(self.layer_types)} are "
                f"not {n} layers of conv / full_attention")
        if self.hidden_size % self.num_attention_heads or \
                self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads do not divide the width, or KV heads the query heads")
        return tuple((kind, DENSE if first + i < self.num_dense_layers else MOE)
                     for i, kind in enumerate(kinds))


def param_shapes(cfg: Lfm2MoeConfig) -> dict:
    """``(shape, init)`` of every leaf, in the parameters' own tree; init names
    a draw of :func:`init`."""
    D, V, K = cfg.hidden_size, cfg.vocab_size, cfg.conv_L_cache
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    F, Fm = cfg.intermediate_size, cfg.moe_intermediate_size
    E, Eh = cfg.num_experts_published, cfg.num_experts
    part = {
        CONV: {
            "operator_norm": ((D,), "one"),
            "w_in": ((D, 3 * D), "std"),
            "conv": ((D, K), "conv"),
            "w_out": ((D, D), "std"),
        },
        ATTENTION: {
            "operator_norm": ((D,), "one"),
            "w_q": ((D, H * hd), "std"),
            "w_k": ((D, Hkv * hd), "std"),
            "w_v": ((D, Hkv * hd), "std"),
            "q_norm": ((hd,), "one"),
            "k_norm": ((hd,), "one"),
            "w_o": ((H * hd, D), "std"),
        },
        DENSE: {
            "ffn_norm": ((D,), "one"),
            "w_gate": ((D, F), "std"),
            "w_up": ((D, F), "std"),
            "w_down": ((F, D), "std"),
        },
        MOE: {
            "ffn_norm": ((D,), "one"),
            "router": ((D, E), "std"),
            "w_gate": ((Eh, D, Fm), "std"),
            "w_up": ((Eh, D, Fm), "std"),
            "w_down": ((Eh, Fm, D), "std"),
        },
    }
    if cfg.use_expert_bias:
        part[MOE]["expert_bias"] = ((E,), "bias")
    shapes = {"embed": ((V, D), "std"), "embedding_norm": ((D,), "one"),
              "layers": [{**part[mixer], **part[ffn]} for mixer, ffn in cfg.held]}
    if not cfg.tie_word_embeddings:
        shapes["head"] = ((V, D), "std")
    return shapes


def init(key: jax.Array, cfg: Lfm2MoeConfig) -> dict:
    """Seeded float32 parameters: matmul weights and the (tied) embedding
    N(0, ``initializer_range``), norm weights one, the convolution uniform in
    +-1/sqrt(taps) (torch's ``Conv1d`` default, as ``models.nemotron_h`` draws
    its own), the selection bias N(0, ``expert_bias_init_std``): small against
    the scores' spread (a sigmoid of N(0, 0.9): 0.2), so that it reorders the
    choice for some tokens in a hundred and the held experts' rows stay near
    their expected number, and **not zero**, so that the choice by ``score +
    bias`` and the weights by ``score`` differ in a step."""
    def draw(k, shape, kind):
        if kind == "one":
            return jnp.ones(shape, _F32)
        if kind == "conv":
            bound = 1.0 / math.sqrt(cfg.conv_L_cache)
            return jax.random.uniform(k, shape, _F32, -bound, bound)
        std = cfg.expert_bias_init_std if kind == "bias" else cfg.initializer_range
        return jax.random.normal(k, shape, _F32) * std

    return _layers.draw_params(key, param_shapes(cfg), draw)


def keep_fp32(path) -> bool:
    """``amp.initialize(keep_fp32_mask=...)``: the norm weights and the
    selection bias."""
    return _layers.keep_fp32(path, also=("expert_bias",))


rms_norm = _layers.rms_norm


@_annotate("conv_mixer")
def short_conv_mixer(cfg: Lfm2MoeConfig, u, p):
    from beforeholiday_tpu.ops.short_conv import gated_short_conv

    dt = u.dtype
    y = gated_short_conv(u @ p["w_in"].astype(dt), p["conv"], impl=cfg.short_conv_impl)
    return y @ p["w_out"].astype(dt)


@_annotate("attn_mixer")
def attention(cfg: Lfm2MoeConfig, u, p, table):
    """One attention mixer; ``table``: ``(cos, sin)`` of the sequence."""
    return _layers.qk_norm_attention(
        u, p, table, heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, eps=cfg.norm_eps, impl=cfg.attention_impl)


dense_ffn = _annotate("dense_ffn")(_layers.swiglu_ffn)


def sparse_ffn(cfg: Lfm2MoeConfig, h, p):
    """``(y, counters)`` of one mixture-of-experts part (``moe.dropless``'s spans)."""
    return _layers.sigmoid_moe(
        h, p, top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
        rows_bound=cfg.moe_rows_bound, renormalize=cfg.norm_topk_prob,
        bias=p.get("expert_bias"), scale=cfg.routed_scaling_factor, eps=_ROUTER_EPS)


def _layer(cfg: Lfm2MoeConfig, mixer: str, ffn: str, x, p, table):
    """One decoder layer on its own leaves ``p``: ``(x, the MoE counters or None)``."""
    u = rms_norm(x, p["operator_norm"], cfg.norm_eps)
    x = x + (short_conv_mixer(cfg, u, p) if mixer == CONV else attention(cfg, u, p, table))
    h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    if ffn == DENSE:
        return x + dense_ffn(h, p), None
    y, counters = sparse_ffn(cfg, h, p)
    return x + y, counters


def forward(params: dict, tokens: jax.Array, cfg: Lfm2MoeConfig):
    """``tokens (B, S) int32 -> (logits (B, S, V) float32, counters)``.
    ``counters``: per step, over the MoE layers (``models.layers.reduce_counters``)."""
    held = cfg.held
    with _span("lfm2_embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        table = (_layers.rotary_table(tokens.shape[1], cfg.head_dim, cfg.rope_theta)
                 if any(mixer == ATTENTION for mixer, _ in held) else None)
    layer = {kinds: _remat_apply(functools.partial(_layer, cfg, *kinds), cfg.remat_policy)
             for kinds in sorted(set(held))}
    with _span("lfm2_layers"):
        x, seen = _layers.unrolled_layers(layer, held, params["layers"], x, table)
    counters = _layers.step_counters(seen)
    with _span("lfm2_head"):
        x = rms_norm(x, params["embedding_norm"], cfg.norm_eps)
        logits = _layers.logits_of(
            x, params["embed" if cfg.tie_word_embeddings else "head"])
    return logits, counters


cross_entropy = _annotate("lfm2_loss")(_layers.cross_entropy)


def loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
            cfg: Lfm2MoeConfig, forward_fn=None):
    """``(mean next-token cross entropy over the vocabulary held, counters)``.
    ``forward_fn(params, tokens)`` overrides the plain forward (an amp-wrapped
    apply), as in ``testing/gpt.loss_fn``."""
    return _layers.loss_fn(forward_fn or functools.partial(forward, cfg=cfg), cross_entropy,
                           params, tokens, targets)


def param_count(cfg: Lfm2MoeConfig) -> int:
    return _layers.param_count(param_shapes(cfg))
