"""Mellum 2: sliding-window attention layers among full-attention layers, each
kind with its own rotary table, every MLP a mixture of experts with no shared
expert.

Written from the published configuration
(huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, ``config.json``), whose
key set is that of the Qwen3-MoE configuration class with ``layer_types`` and a
``rope_parameters`` group per layer type. Bias-free throughout. With
``rms(x, w) = w * x / sqrt(mean(x^2) + eps)`` (plain weight, initially one):

* layer ``l`` of kind ``layer_types[l]``: ``h = x + attention_l(rms(x))``;
  ``y = h + moe(rms(h))``; after the last layer ``rms`` and an untied head.
* attention: ``q = x W_q`` on ``num_attention_heads`` heads, ``k``, ``v`` on
  ``num_key_value_heads`` (GQA); ``q, k`` through ``rms`` over the head (one
  weight of ``head_dim``, shared by the heads); rotary embedding on the whole
  head (``rotate_half`` layout) from the kind's table — sliding layers plain,
  full layers under YaRN, whose ``attention_factor`` multiplies ``cos`` and
  ``sin`` (``models.layers.rotary_table``); softmax attention, causal, and in a
  sliding layer over the last ``sliding_window`` keys only, the query's own
  among them (``ops.flash_attention(window=)``: the kernels' grid is the band);
  ``W_o``.
* MoE: ``moe.dropless`` (softmax over all experts, top-k renormalised, the
  experts this chip holds; no shared expert).

Both kinds of layer have the same parameters, stacked under ``layers``
``(L, ...)`` (the held experts ``(L, E_held, ...)``); the layer stack is a
``lax.scan`` over periods of ``layer_types`` whose body unrolls one period.
GQA goes through ``ops.flash_attention`` by repeating each KV head over its
query heads (``models.layers.grouped_query_attention``).

Not here: a multi-token-prediction head and an auxiliary balancing loss (the
published ``config.json`` has a key for neither).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from beforeholiday_tpu.models import layers as _layers
from beforeholiday_tpu.models.layers import COUNTERS, Yarn  # noqa: F401
from beforeholiday_tpu.monitor.spans import annotate as _annotate, span as _span
from beforeholiday_tpu.remat import apply as _remat_apply

_F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 512               # ids held here (a slice of the vocabulary)
    hidden_size: int = 128
    num_hidden_layers: int = 4
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 32
    sliding_window: int = 64            # keys a sliding layer's query sees
    rope_theta_sliding: float = 5e5
    rope_theta_full: float = 5e5
    rope_yarn_full: Optional[Yarn] = Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    # mixture of experts
    num_experts: int = 16               # the router's width
    num_experts_held: int = 16          # experts first_expert .. + held live here
    first_expert: int = 0
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 64
    norm_topk_prob: bool = True
    moe_rows_bound: Optional[int] = None   # None: the worst case, never overflows
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02     # every matmul weight and the head
    embedding_init_std: float = 1.0     # see :func:`init`
    dtype: jnp.dtype = jnp.float32      # activation dtype
    remat_policy: Optional[str] = None  # over one layer; None = no remat
    attention_impl: Optional[str] = None   # forces the flash dispatch in tests

    @property
    def period(self) -> Tuple[str, ...]:
        """The kinds of one period: ``layer_types`` up to its first full layer
        (the whole of it where there is none), which the rest must repeat."""
        kinds = tuple(self.layer_types[:self.num_hidden_layers])
        n = kinds.index(FULL) + 1 if FULL in kinds else len(kinds)
        if len(kinds) != self.num_hidden_layers or len(kinds) % n or \
                kinds != kinds[:n] * (len(kinds) // n) or set(kinds) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types {kinds} is not {self.num_hidden_layers} layers in whole "
                f"periods of sliding_attention / full_attention")
        return kinds[:n]

    @property
    def periods(self) -> int:
        return self.num_hidden_layers // len(self.period)


def param_shapes(cfg: MellumConfig) -> dict:
    """``{group: {name: (shape, init)}}``; init is ``std``, ``embed`` or ``one``
    (:func:`init`)."""
    D, L = cfg.hidden_size, cfg.num_hidden_layers
    E, Eh, F = cfg.num_experts, cfg.num_experts_held, cfg.moe_intermediate_size
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    return {
        "top": {
            "embed": ((cfg.vocab_size, D), "embed"),
            "head": ((cfg.vocab_size, D), "std"),
            "final_norm": ((D,), "one"),
        },
        "layers": {
            "input_norm": ((L, D), "one"),
            "post_norm": ((L, D), "one"),
            "w_q": ((L, D, H * hd), "std"),
            "w_k": ((L, D, Hkv * hd), "std"),
            "w_v": ((L, D, Hkv * hd), "std"),
            "q_norm": ((L, hd), "one"),
            "k_norm": ((L, hd), "one"),
            "w_o": ((L, H * hd, D), "std"),
            "router": ((L, D, E), "std"),
            "w_gate": ((L, Eh, D, F), "std"),
            "w_up": ((L, Eh, D, F), "std"),
            "w_down": ((L, Eh, F, D), "std"),
        },
    }


def init(key: jax.Array, cfg: MellumConfig) -> dict:
    """Seeded float32 parameters: matmul weights and the head
    N(0, ``initializer_range``), norm weights one, the embedding
    N(0, ``embedding_init_std``).

    The embedding is drawn at unit scale because of what a randomly initialised
    stack of these layers does with a small one: with near-uniform attention
    (``q . k / sqrt(d)`` of normalised random heads is N(0, 1)) a mixer's output
    is close to the mean of its window's values, the same for every token the
    window holds, and at 0.02 it outweighs the embedding from the first layer
    on (rms 0.12 against 0.02, then 0.6 and 1.1: CPU, float32, published
    widths). Every token's hidden state is then nearly one vector, every token
    picks the same ``top_k`` experts, and a chip's share of the experts
    receives anything from 0.6 to 1.4 times its expected rows, by seed and
    layer. A trained model's residual stream is token-specific and its routing
    balanced; unit-scale embeddings give a random one the same property (rows
    within 0.99 to 1.07 of expected, fullest expert 1.1 to 2.6 times the mean)."""
    def draw(k, shape, kind):
        if kind == "one":
            return jnp.ones(shape, _F32)
        std = cfg.embedding_init_std if kind == "embed" else cfg.initializer_range
        return jax.random.normal(k, shape, _F32) * std

    tree = _layers.draw_params(key, param_shapes(cfg), draw)
    top = tree.pop("top")              # its leaves sit beside the groups
    return {**tree, **top}


def keep_fp32(path) -> bool:
    """``amp.initialize(keep_fp32_mask=...)``: the norm weights."""
    return _layers.keep_fp32(path)


rms_norm = _layers.rms_norm


def rotary_tables(cfg: MellumConfig, seq_len: int) -> dict:
    """``{kind: (cos, sin)}``, once a forward pass."""
    return {
        SLIDING: _layers.rotary_table(seq_len, cfg.head_dim, cfg.rope_theta_sliding),
        FULL: _layers.rotary_table(seq_len, cfg.head_dim, cfg.rope_theta_full,
                                   cfg.rope_yarn_full),
    }


def attention(cfg: MellumConfig, x, p, kind: str, table):
    """One attention mixer of ``kind``; ``table``: the kind's ``(cos, sin)``."""
    with _span("window_mixer" if kind == SLIDING else "full_mixer"):
        return _layers.qk_norm_attention(
            x, p, table, heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim, eps=cfg.rms_norm_eps,
            window=cfg.sliding_window if kind == SLIDING else None, impl=cfg.attention_impl)


def _layer(cfg: MellumConfig, x, lp, kind, table):
    """One decoder layer: ``(x, counters)``."""
    x = x + attention(cfg, rms_norm(x, lp["input_norm"], cfg.rms_norm_eps), lp, kind, table)
    h = rms_norm(x, lp["post_norm"], cfg.rms_norm_eps)
    y, counters = _layers.softmax_moe(
        h, lp, top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
        rows_bound=cfg.moe_rows_bound, renormalize=cfg.norm_topk_prob)
    return x + y, counters


def forward(params: dict, tokens: jax.Array, cfg: MellumConfig):
    """``tokens (B, S) int32 -> (logits (B, S, V) float32, counters)``.
    ``counters``: per step, over the layers (``models.layers.reduce_counters``)."""
    kinds = cfg.period
    with _span("mellum_embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        tables = rotary_tables(cfg, tokens.shape[1])
    layer = {kind: _remat_apply(
        lambda x, lp, table, kind=kind: _layer(cfg, x, lp, kind, table), cfg.remat_policy)
        for kind in set(kinds)}

    def period(x, stacked):
        seen = []
        for kind, lp in zip(kinds, _layers.unstack(stacked, len(kinds))):
            x, c = layer[kind](x, lp, tables[kind])
            seen.append(c)
        return x, jax.tree.map(lambda *v: jnp.stack(v), *seen)

    with _span("mellum_layers"):
        x, seen = _layers.scan_periods(
            period, x, _layers.by_period(params["layers"], cfg.periods, len(kinds)))
    counters = _layers.reduce_counters(seen)
    with _span("mellum_head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        logits = _layers.logits_of(x, params["head"])
    return logits, counters


cross_entropy = _annotate("mellum_loss")(_layers.cross_entropy)


def loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
            cfg: MellumConfig, forward_fn=None):
    """``(mean next-token cross entropy over the vocabulary held, counters)``.
    ``forward_fn(params, tokens)`` overrides the plain forward (an amp-wrapped
    apply), as in ``testing/gpt.loss_fn``."""
    return _layers.loss_fn(forward_fn or functools.partial(forward, cfg=cfg), cross_entropy,
                           params, tokens, targets)


def param_count(cfg: MellumConfig) -> int:
    return _layers.param_count(param_shapes(cfg))
