"""Kimi-Linear-type decoders: Kimi Delta Attention (a gated delta rule whose
decay is a vector over the key's channels) three layers in four, latent
attention WITHOUT a rotary embedding the fourth, a leading dense SwiGLU layer
and then sigmoid-routed experts beside an always-on shared one, an untied head.

Written from a published configuration of the type
(huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct, ``config.json``,
``model_type`` ``kimi_linear``), the family's published modelling code and the
paper (Kimi Linear, Moonshot AI 2025; see PAPERS.md). Bias-free throughout. With
``rms(x, w) = w * x / sqrt(mean(x^2) + eps)`` (plain weight, initially one):

* layer ``l`` (published, 0-based): ``h = x + mixer_l(rms(x, input_layernorm))``;
  ``y = h + ffn_l(rms(h, post_attention_layernorm))``. The mixer is latent
  attention where ``l + 1`` is in ``linear_attn_config.full_attn_layers`` and KDA
  where it is in ``kda_layers`` (both lists 1-based, as published); ``ffn_l`` is a
  dense SwiGLU of ``intermediate_size`` for ``l < first_k_dense_replace`` and the
  mixture of experts after. After the last layer ``rms(., norm)`` and the head.
* KDA on ``u``, ``H`` heads of ``d = linear_attn_config.head_dim``: ``q, k, v =
  silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))`` (a causal depthwise
  convolution of ``short_conv_kernel_size`` taps each); per head ``q <- q / |q| *
  d^-1/2``, ``k <- k / |k|``; the decay ``log a_t = -exp(A_log[head]) * softplus((u
  W_fa) W_fb + dt_bias)`` a key channel, float32; ``beta_t = sigmoid(u W_b)`` a
  head; the delta rule under that decay (``ops.kda``); ``y = (rms_d(o, out_norm) *
  sigmoid((u W_ga) W_gb)) W_o``, the norm over each head's ``d`` with one weight
  of ``d``. Everything between the projections and the rule is
  ``ops.deltanet``'s two passes, as in ``models.qwen3_next`` (each key head
  serving one value head, and the output gate's activation the sigmoid).
* latent attention: ``models.layers.latent_attention`` with no table
  (``mla_use_nope``: the ``qk_rope_head_dim`` columns are an unrotated key all
  heads share, and the matching queries).
* mixture of experts: ``models.layers.sigmoid_moe`` as ``models.deepseek_v3``
  calls it (sigmoid over all the router's outputs in float32, the top
  ``num_experts_per_token`` of score + ``expert_bias``, the chosen scores over
  their sum plus 1e-20 where ``moe_renormalize``, times ``routed_scaling_factor``)
  beside one ungated SwiGLU of ``num_shared_experts * moe_intermediate_size``.
  ``num_expert_group = topk_group = 1``: any other value raises.

**The selection bias** ``expert_bias`` is data: a float32 leaf whose gradient
is exactly zero. **The model is told its share**: ``first_layer`` (the mixer and
the feed-forward kind are decided on the published index), ``num_experts`` held
of ``num_experts_published`` from ``first_expert``, the ids of the vocabulary.

Parameters are ``{"embed", "norm", "head", "layers": [...]}``: one dict a held
layer, nothing stacked (the layers are unrolled: the first differs from the
rest, and the mixers from each other).

Not here: the exchange between expert-parallel ranks, an update rule for the
selection bias, a balancing loss, packed documents (a state reset inside a
sequence), the single-token and state-cache forms of the recurrence.
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
KDA, MLA = "kda", "mla"
DENSE, MOE = "dense", "moe"


@dataclasses.dataclass(frozen=True)
class LinearAttnConfig:
    """The published ``linear_attn_config`` group; both lists are 1-based."""
    kda_layers: Tuple[int, ...] = (1, 2, 3)
    full_attn_layers: Tuple[int, ...] = (4,)
    num_heads: int = 4
    head_dim: int = 32
    short_conv_kernel_size: int = 4

    def __post_init__(self):
        for name in ("kda_layers", "full_attn_layers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 512               # ids held here (a slice of the vocabulary)
    hidden_size: int = 128
    num_hidden_layers: int = 4          # layers held: first_layer .. + held
    first_layer: int = 0                # the published index of the first held layer
    first_k_dense_replace: int = 1      # published layers 0 .. this have a dense SwiGLU
    intermediate_size: int = 256        # the dense SwiGLU's width
    linear_attn_config: LinearAttnConfig = LinearAttnConfig()     # or its dict, as published
    # latent attention (no rotary: mla_use_nope)
    num_attention_heads: int = 4
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 64
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    mla_use_nope: bool = True
    # mixture of experts
    moe_intermediate_size: int = 64
    num_experts_published: int = 8      # the router's width
    num_experts: int = 8                # experts first_expert .. + held live here
    first_expert: int = 0
    num_shared_experts: int = 1
    num_experts_per_token: int = 2
    num_expert_group: int = 1
    topk_group: int = 1
    moe_renormalize: bool = True
    routed_scaling_factor: float = 1.0
    moe_router_activation_func: str = "sigmoid"
    moe_rows_bound: Optional[int] = None   # None: the worst case, never overflows
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02     # every matmul weight and the embedding
    dtype: jnp.dtype = jnp.float32      # activation dtype
    remat_policy: Optional[str] = None  # over one layer; None = no remat
    kda_chunk: int = 64
    attention_impl: Optional[str] = None   # forces the flash dispatch in tests

    def __post_init__(self):
        if isinstance(self.linear_attn_config, dict):
            object.__setattr__(self, "linear_attn_config",
                               LinearAttnConfig(**self.linear_attn_config))
        if self.q_lora_rank is not None:
            raise ValueError(f"q_lora_rank {self.q_lora_rank}: a query latent is not built here")
        if not self.mla_use_nope:
            raise ValueError("mla_use_nope false: this family's latent attention has no rotary")
        if self.num_expert_group != 1 or self.topk_group != 1:
            raise ValueError(
                f"num_expert_group {self.num_expert_group} / topk_group {self.topk_group}: only "
                f"the identity group limit (1 / 1) is built here")
        if self.moe_router_activation_func != "sigmoid":
            raise ValueError(f"moe_router_activation_func {self.moe_router_activation_func!r}: "
                             f"only 'sigmoid' is built here")

    @property
    def held(self) -> Tuple[Tuple[str, str], ...]:
        """``(mixer, feed-forward)`` kind of each held layer, decided on the
        published index: the mixer by the two 1-based lists."""
        la = self.linear_attn_config
        kinds = []
        for l in range(self.first_layer, self.first_layer + self.num_hidden_layers):
            if (l + 1 in la.kda_layers) == (l + 1 in la.full_attn_layers):
                raise ValueError(f"published layer {l + 1} is in both or neither of kda_layers "
                                 f"and full_attn_layers")
            kinds.append((KDA if l + 1 in la.kda_layers else MLA,
                          MOE if l >= self.first_k_dense_replace else DENSE))
        return tuple(kinds)


def param_shapes(cfg: KimiLinearConfig) -> dict:
    """``(shape, init)`` of every leaf, in the parameters' own tree; init names
    a draw of :func:`init`."""
    D, V = cfg.hidden_size, cfg.vocab_size
    la = cfg.linear_attn_config
    Hl, d, K = la.num_heads, la.head_dim, la.short_conv_kernel_size
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, Fm = cfg.intermediate_size, cfg.moe_intermediate_size
    E, Eh, Fs = cfg.num_experts_published, cfg.num_experts, \
        cfg.num_shared_experts * cfg.moe_intermediate_size
    mixer = {
        KDA: {
            "w_q": ((D, Hl * d), "std"), "w_k": ((D, Hl * d), "std"), "w_v": ((D, Hl * d), "std"),
            "conv_q": ((Hl * d, K), "conv"), "conv_k": ((Hl * d, K), "conv"),
            "conv_v": ((Hl * d, K), "conv"),
            "w_fa": ((D, d), "std"), "w_fb": ((d, Hl * d), "std"),     # the decay's low-rank pair
            "a_log": ((Hl,), "a_log"), "dt_bias": ((Hl * d,), "dt_bias"),
            "w_b": ((D, Hl), "std"),
            "w_ga": ((D, d), "std"), "w_gb": ((d, Hl * d), "std"),     # the output gate's
            "out_norm": ((d,), "one"),
            "w_o": ((Hl * d, D), "std"),
        },
        MLA: {
            "w_q": ((D, H * (dn + dr)), "std"),
            "w_kva": ((D, r + dr), "std"),
            "kv_a_layernorm": ((r,), "one"),
            "w_kvb": ((r, H * (dn + dv)), "std"),
            "w_o": ((H * dv, D), "std"),
        },
    }
    ffn = {
        DENSE: {"w_gate": ((D, F), "std"), "w_up": ((D, F), "std"), "w_down": ((F, D), "std")},
        MOE: {
            "router": ((D, E), "std"),
            "expert_bias": ((E,), "zero"),
            "w_gate": ((Eh, D, Fm), "std"),
            "w_up": ((Eh, D, Fm), "std"),
            "w_down": ((Eh, Fm, D), "std"),
            "shared_w_gate": ((D, Fs), "std"), "shared_w_up": ((D, Fs), "std"),
            "shared_w_down": ((Fs, D), "std"),
        },
    }
    norms = {"input_layernorm": ((D,), "one"), "post_attention_layernorm": ((D,), "one")}
    return {"embed": ((V, D), "std"), "norm": ((D,), "one"), "head": ((V, D), "std"),
            "layers": [{**norms, **mixer[m], **ffn[f]} for m, f in cfg.held]}


def init(key: jax.Array, cfg: KimiLinearConfig) -> dict:
    """Seeded float32 parameters: matmul weights, the embedding and the head
    N(0, ``initializer_range``); the convolutions uniform in +-1/sqrt(taps)
    (torch's Conv1d default); ``A_log = log U(1, 16)`` a head and ``dt_bias`` the
    inverse softplus of a step log-uniform in 0.001 .. 0.1 a channel (the
    published modelling code); norm weights one; the selection bias zeros."""
    def draw(k, shape, kind):
        if kind == "std":
            return jax.random.normal(k, shape, _F32) * cfg.initializer_range
        if kind == "conv":
            bound = 1.0 / math.sqrt(shape[-1])
            return jax.random.uniform(k, shape, _F32, -bound, bound)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(k, shape, _F32, 1.0, 16.0))
        if kind == "dt_bias":
            step = jnp.exp(jax.random.uniform(k, shape, _F32, math.log(1e-3), math.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))        # softplus^-1(step)
        return jnp.full(shape, 0.0 if kind == "zero" else 1.0, _F32)

    return _layers.draw_params(key, param_shapes(cfg), draw)


def keep_fp32(path) -> bool:
    """``amp.initialize(keep_fp32_mask=...)``: the norm weights, the decay's two
    parameters (``A_log`` enters through two exponentials) and the selection bias."""
    return _layers.keep_fp32(path, also=("a_log", "dt_bias", "expert_bias"))


rms_norm = _layers.rms_norm


@_annotate("kda_mixer")
def kda_attention(cfg: KimiLinearConfig, u, p):
    """One KDA mixer. The three projections' columns are laid head by head
    (``ops.deltanet.by_key_head`` on the WEIGHTS, 28 M numbers, not on the ``(S,
    12288)`` activation), the order ``deltanet_qkv`` reads; the decay is made
    heads first, as the rule's kernels read it."""
    from beforeholiday_tpu.ops.deltanet import by_key_head, deltanet_gate, deltanet_qkv
    from beforeholiday_tpu.ops.kda import kda_rule

    la = cfg.linear_attn_config
    B, S, _ = u.shape
    H, d = la.num_heads, la.head_dim
    heads = dict(key_heads=H, value_heads=H, d_k=d, d_v=d)
    dt = u.dtype
    beside = lambda names, axis: by_key_head(
        jnp.concatenate([p[n] for n in names], axis=axis), axis=axis, **heads)
    with _span("kda_proj"):
        cols = u @ beside(("w_q", "w_k", "w_v"), 1).astype(dt)
    q, k, v = deltanet_qkv(cols, beside(("conv_q", "conv_k", "conv_v"), 0), **heads)
    with _span("kda_gate_proj"):
        a = (u @ p["w_fa"].astype(dt)) @ p["w_fb"].astype(dt)
        z = (u @ p["w_ga"].astype(dt)) @ p["w_gb"].astype(dt)
        beta = jax.nn.sigmoid(jnp.dot(u, p["w_b"].astype(dt), preferred_element_type=_F32))
        rate = -jnp.exp(p["a_log"].astype(_F32))[:, None, None]                  # (H, 1, 1)
        g = rate * jax.nn.softplus(
            jnp.moveaxis((a.astype(_F32) + p["dt_bias"].astype(_F32)).reshape(B, S, H, d), 2, 1))
    o = kda_rule(q, k, v, g, jnp.moveaxis(beta, 2, 1), chunk=cfg.kda_chunk, heads_first=True)
    o = deltanet_gate(o, z, p["out_norm"].astype(_F32), eps=cfg.rms_norm_eps,
                      activation="sigmoid")
    with _span("kda_proj"):
        return o @ p["w_o"].astype(dt)


@_annotate("mla_mixer")
def latent_attention(cfg: KimiLinearConfig, u, p):
    return _layers.latent_attention(
        u, p, heads=cfg.num_attention_heads, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim, qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, eps=cfg.rms_norm_eps, impl=cfg.attention_impl)


dense_ffn = _annotate("dense_ffn")(_layers.swiglu_ffn)


def sparse_ffn(cfg: KimiLinearConfig, h, p):
    """``(y, counters)`` of one mixture-of-experts part (``moe.dropless``'s spans)."""
    return _layers.sigmoid_moe(
        h, p, top_k=cfg.num_experts_per_token, first_expert=cfg.first_expert,
        rows_bound=cfg.moe_rows_bound, renormalize=cfg.moe_renormalize,
        bias=p["expert_bias"], scale=cfg.routed_scaling_factor)


_MIXER = {KDA: kda_attention, MLA: latent_attention}


def _layer(cfg: KimiLinearConfig, kind, x, p):
    """One decoder layer on its own leaves ``p``: ``(x, the MoE counters or None)``."""
    mixer, ffn = kind
    x = x + _MIXER[mixer](cfg, rms_norm(x, p["input_layernorm"], cfg.rms_norm_eps), p)
    h = rms_norm(x, p["post_attention_layernorm"], cfg.rms_norm_eps)
    if ffn == DENSE:
        return x + dense_ffn(h, p), None
    y, counters = sparse_ffn(cfg, h, p)
    return x + y, counters


def forward(params: dict, tokens: jax.Array, cfg: KimiLinearConfig):
    """``tokens (B, S) int32 -> (logits (B, S, V) float32, counters)``.
    ``counters``: per step, over the MoE layers (``models.layers.reduce_counters``)."""
    held = cfg.held
    with _span("kimi_linear_embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    layer = {kind: _remat_apply(functools.partial(_layer, cfg, kind), cfg.remat_policy)
             for kind in sorted(set(held))}
    with _span("kimi_linear_layers"):
        x, seen = _layers.unrolled_layers(layer, held, params["layers"], x)
    counters = _layers.step_counters(seen)
    with _span("kimi_linear_head"):
        x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
        logits = _layers.logits_of(x, params["head"])
    return logits, counters


cross_entropy = _annotate("kimi_linear_loss")(_layers.cross_entropy)


def loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
            cfg: KimiLinearConfig, forward_fn=None):
    """``(mean next-token cross entropy over the vocabulary held, counters)``.
    ``forward_fn(params, tokens)`` overrides the plain forward (an amp-wrapped
    apply), as in ``testing/gpt.loss_fn``."""
    return _layers.loss_fn(forward_fn or functools.partial(forward, cfg=cfg), cross_entropy,
                           params, tokens, targets)


def param_count(cfg: KimiLinearConfig) -> int:
    return _layers.param_count(param_shapes(cfg))
