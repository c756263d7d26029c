"""Qwen3-Next: gated-DeltaNet linear attention among gated softmax attention,
every MLP a mixture of experts with a shared expert.

Written from the published configuration and layer equations
(huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, ``config.json``; Gated
DeltaNet: Yang et al. 2024). Bias-free throughout. With
``rms0(x, w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` (the zero-centred RMSNorm):

* layer ``l``: ``h = x + mixer_l(rms0(x))``; ``y = h + moe(rms0(h))``. The
  mixer is gated softmax attention where ``(l + 1) % period == 0`` and a gated
  DeltaNet otherwise; after the last layer ``rms0`` and an untied head.
* gated DeltaNet: ``[q, k, v, z] = x W_qkvz``, ``[b, a] = x W_ba``; ``q, k, v``
  through a causal depthwise convolution of width 4 and SiLU; ``q, k``
  L2-normalised per head, ``q`` scaled by ``d_k^-1/2``, each key head serving
  ``value_heads / key_heads`` value heads; ``beta = sigmoid(b)``,
  ``log alpha = -exp(A_log) * softplus(a + dt_bias)``; the gated delta rule
  (``ops.gated_delta``); ``(w_n * o / rms(o)) * silu(z)`` per head; ``W_out``.
* gated attention: ``[q, gate] = x W_q`` per head, ``k``, ``v`` on fewer heads
  (GQA); ``q, k`` through ``rms0`` over the head; rotary embedding on the first
  ``rotary_dim`` dims of each head (``rotate_half`` layout); causal softmax
  attention; ``(attn * sigmoid(gate)) W_o``.
* MoE: ``moe.dropless`` (softmax over all experts, top-k renormalised, the
  experts this chip holds, a sigmoid-gated shared expert).

Parameters are stacked so that the arena sees a few large leaves: what every
layer has under ``layers`` ``(L, ...)`` (the held experts ``(L, E_held, ...)``),
the DeltaNet mixers under ``linear`` ``(L - L/period, ...)``, the attention
mixers under ``attn`` ``(L/period, ...)``. The layer stack is a ``lax.scan``
over periods whose body unrolls one period.

GQA goes through ``ops.flash_attention``, which takes equal head counts, by
repeating each KV head over its query heads (the backward pass sums them).

Not here: the multi-token-prediction module and an auxiliary balancing loss
(the published ``config.json`` has a key for neither).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from beforeholiday_tpu.models import layers as _layers
from beforeholiday_tpu.models.layers import COUNTERS  # noqa: F401  (the step's counters)
from beforeholiday_tpu.monitor.spans import annotate as _annotate, span as _span
from beforeholiday_tpu.remat import apply as _remat_apply

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 512               # ids held here (a slice of the vocabulary)
    hidden_size: int = 128
    num_hidden_layers: int = 4
    full_attention_interval: int = 4    # the period: its last layer is attention
    # gated attention
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 64
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # gated DeltaNet
    linear_num_key_heads: int = 2
    linear_num_value_heads: int = 4
    linear_key_head_dim: int = 32
    linear_value_head_dim: int = 32
    linear_conv_kernel_dim: int = 4
    # mixture of experts
    num_experts: int = 16               # the router's width
    num_experts_held: int = 16          # experts first_expert .. + held live here
    first_expert: int = 0
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 64
    shared_expert_intermediate_size: int = 64
    norm_topk_prob: bool = True
    moe_rows_bound: Optional[int] = None   # None: the worst case, never overflows
    rms_norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32      # activation dtype
    remat_policy: Optional[str] = None  # over one layer; None = no remat
    gated_delta_chunk: int = 128
    attention_impl: Optional[str] = None   # forces the flash dispatch in tests

    @property
    def periods(self) -> int:
        if self.num_hidden_layers % self.full_attention_interval:
            raise ValueError(
                f"num_hidden_layers {self.num_hidden_layers} is not whole periods "
                f"of {self.full_attention_interval}")
        return self.num_hidden_layers // self.full_attention_interval

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


def param_shapes(cfg: Qwen3NextConfig) -> dict:
    """``{group: {name: (shape, init)}}``; init is ``std`` (N(0, 0.02)), ``zero``,
    ``one``, ``conv`` or ``a_log`` (see :func:`init`)."""
    D, L, P = cfg.hidden_size, cfg.num_hidden_layers, cfg.periods
    Ll = L - P
    E, Eh = cfg.num_experts, cfg.num_experts_held
    F, Fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    conv_channels = 2 * Hk * dk + Hv * dv
    return {
        "top": {
            "embed": ((cfg.vocab_size, D), "std"),
            "head": ((cfg.vocab_size, D), "std"),
            "final_norm": ((D,), "zero"),
        },
        "layers": {
            "input_norm": ((L, D), "zero"),
            "post_norm": ((L, D), "zero"),
            "router": ((L, D, E), "std"),
            "w_gate": ((L, Eh, D, F), "std"),
            "w_up": ((L, Eh, D, F), "std"),
            "w_down": ((L, Eh, F, D), "std"),
            "shared_w_gate": ((L, D, Fs), "std"),
            "shared_w_up": ((L, D, Fs), "std"),
            "shared_w_down": ((L, Fs, D), "std"),
            "shared_score": ((L, D, 1), "std"),
        },
        "linear": {
            "w_qkvz": ((Ll, D, conv_channels + Hv * dv), "std"),
            "w_ba": ((Ll, D, 2 * Hv), "std"),
            "conv": ((Ll, conv_channels, cfg.linear_conv_kernel_dim), "conv"),
            "a_log": ((Ll, Hv), "a_log"),
            "dt_bias": ((Ll, Hv), "one"),
            "out_norm": ((Ll, dv), "one"),
            "w_out": ((Ll, Hv * dv, D), "std"),
        },
        "attn": {
            "w_q": ((P, D, H * 2 * hd), "std"),
            "w_k": ((P, D, Hkv * hd), "std"),
            "w_v": ((P, D, Hkv * hd), "std"),
            "q_norm": ((P, hd), "zero"),
            "k_norm": ((P, hd), "zero"),
            "w_o": ((P, H * hd, D), "std"),
        },
    }


def init(key: jax.Array, cfg: Qwen3NextConfig) -> dict:
    """Seeded float32 parameters: matmul weights N(0, 0.02); the convolution
    uniform in +-1/sqrt(width) (torch's Conv1d default); ``A_log = log U(0, 16)``
    and ``dt_bias = 1`` (the published modelling code); norm weights at their
    identity."""
    def draw(k, shape, kind):
        if kind == "std":
            return jax.random.normal(k, shape, _F32) * 0.02
        if kind == "conv":
            bound = 1.0 / math.sqrt(shape[-1])
            return jax.random.uniform(k, shape, _F32, -bound, bound)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(k, shape, _F32, 1e-3, 16.0))
        return jnp.full(shape, 0.0 if kind == "zero" else 1.0, _F32)

    tree = _layers.draw_params(key, param_shapes(cfg), draw)
    top = tree.pop("top")              # its leaves sit beside the groups
    return {**tree, **top}


def keep_fp32(path) -> bool:
    """``amp.initialize(keep_fp32_mask=...)``: the norm weights, and the two
    per-head scalars of the decay (``A_log`` enters through two exponentials)."""
    return _layers.keep_fp32(path, also=("a_log", "dt_bias"))


# ---------------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------------


def rms_norm0(x, w, eps):
    """Zero-centred RMSNorm: the weight is stored as its offset from one."""
    from beforeholiday_tpu.ops import fused_rms_norm

    return fused_rms_norm(x, 1.0 + w.astype(_F32), eps=eps)


def rope_partial(x, rotary_dim: int, theta: float):
    """Rotary position embedding on the first ``rotary_dim`` dims of each head
    (``rotate_half`` layout: dim ``i`` pairs with ``i + rotary_dim / 2``), the
    rest passed through. ``x``: ``(B, S, H, hd)``; positions ``0 .. S-1``."""
    return _layers.apply_rotary(x, *_layers.rotary_table(x.shape[1], rotary_dim, theta))


@_annotate("linear_mixer")
def gated_delta_net(cfg: Qwen3NextConfig, x, p):
    """Everything between the two projections and the delta rule is
    ``ops.deltanet``'s two passes (Pallas kernels on the TPU at head dims of 128,
    the ``jnp`` chain elsewhere). The convolved columns and ``z`` are two
    products over column ranges of ``w_qkvz`` — a weight slice is 50 MB where an
    activation slice, and the pad that is its transpose, were 201 — the former
    by key head, the order ``deltanet_qkv`` reads."""
    from beforeholiday_tpu.ops.deltanet import by_key_head, deltanet_gate, deltanet_qkv
    from beforeholiday_tpu.ops.gated_delta import gated_delta_rule

    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    heads = dict(key_heads=Hk, value_heads=Hv, d_k=dk, d_v=dv)
    dt = x.dtype
    w = p["w_qkvz"].astype(dt)
    n_conv = 2 * Hk * dk + Hv * dv
    cols = x @ by_key_head(w[:, :n_conv], axis=1, **heads)
    z = x @ w[:, n_conv:]
    ba = jnp.dot(x, p["w_ba"].astype(dt), preferred_element_type=_F32)
    q, k, v = deltanet_qkv(cols, by_key_head(p["conv"], axis=0, **heads), **heads)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p["a_log"].astype(_F32)) * jax.nn.softplus(
        ba[..., Hv:] + p["dt_bias"].astype(_F32))
    o = gated_delta_rule(q, k, v, jnp.moveaxis(g, 2, 1), jnp.moveaxis(beta, 2, 1),
                         chunk=cfg.gated_delta_chunk, heads_first=True)
    o = deltanet_gate(o, z, p["out_norm"].astype(_F32), eps=cfg.rms_norm_eps)
    return o @ p["w_out"].astype(dt)


@_annotate("attn_mixer")
def gated_attention(cfg: Qwen3NextConfig, x, p):
    B, S, _ = x.shape
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    dt = x.dtype
    qg = (x @ p["w_q"].astype(dt)).reshape(B, S, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (x @ p["w_k"].astype(dt)).reshape(B, S, Hkv, hd)
    v = (x @ p["w_v"].astype(dt)).reshape(B, S, Hkv, hd)
    q = rms_norm0(q, p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm0(k, p["k_norm"], cfg.rms_norm_eps)
    q = rope_partial(q, cfg.rotary_dim, cfg.rope_theta)
    k = rope_partial(k, cfg.rotary_dim, cfg.rope_theta)
    ctx = _layers.grouped_query_attention(q, k, v, impl=cfg.attention_impl)
    ctx = ctx * jax.nn.sigmoid(gate.astype(_F32)).astype(dt)       # a gate a head's dim
    return ctx.reshape(B, S, H * hd) @ p["w_o"].astype(dt)


def _layer(cfg: Qwen3NextConfig, x, lp, mixer, mp):
    """One decoder layer: ``(x, counters)``."""
    from beforeholiday_tpu.moe.dropless import dropless_moe

    B, S, D = x.shape
    x = x + mixer(cfg, rms_norm0(x, lp["input_norm"], cfg.rms_norm_eps), mp)
    h = rms_norm0(x, lp["post_norm"], cfg.rms_norm_eps)
    y, counters = dropless_moe(
        h.reshape(B * S, D), lp, top_k=cfg.num_experts_per_tok,
        first_expert=cfg.first_expert, rows_bound=cfg.moe_rows_bound,
        renormalize=cfg.norm_topk_prob)
    return x + y.reshape(B, S, D), counters


def forward(params: dict, tokens: jax.Array, cfg: Qwen3NextConfig):
    """``tokens (B, S) int32 -> (logits (B, S, V) float32, counters)``.
    ``counters``: per step, over the layers: ``expert_rows`` (sum),
    ``expert_load_max_over_mean`` (max), ``dropped_rows`` (sum)."""
    P, per = cfg.periods, cfg.full_attention_interval
    with _span("qwen3n_embed"):
        x = params["embed"][tokens].astype(cfg.dtype)

    by_period = lambda tree, n: _layers.by_period(tree, P, n)
    unstack = _layers.unstack
    linear = _remat_apply(
        lambda x, lp, mp: _layer(cfg, x, lp, gated_delta_net, mp), cfg.remat_policy)
    attn = _remat_apply(
        lambda x, lp, mp: _layer(cfg, x, lp, gated_attention, mp), cfg.remat_policy)

    def period(x, xs):
        layers, lin, att = xs
        layers, lin = unstack(layers, per), unstack(lin, per - 1)
        seen = []
        for i in range(per - 1):
            x, c = linear(x, layers[i], lin[i])
            seen.append(c)
        x, c = attn(x, layers[per - 1], att)
        seen.append(c)
        return x, jax.tree.map(lambda *v: jnp.stack(v), *seen)

    with _span("qwen3n_layers"):
        x, seen = _layers.scan_periods(period, x, (
            by_period(params["layers"], per), by_period(params["linear"], per - 1),
            params["attn"]))
    counters = _layers.reduce_counters(seen)
    with _span("qwen3n_head"):
        x = rms_norm0(x, params["final_norm"], cfg.rms_norm_eps)
        logits = _layers.logits_of(x, params["head"])
    return logits, counters


cross_entropy = _annotate("qwen3n_loss")(_layers.cross_entropy)


def loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
            cfg: Qwen3NextConfig, forward_fn=None):
    """``(mean next-token cross entropy over the vocabulary held, counters)``.
    ``forward_fn(params, tokens)`` overrides the plain forward (an amp-wrapped
    apply), as in ``testing/gpt.loss_fn``."""
    return _layers.loss_fn(forward_fn or functools.partial(forward, cfg=cfg), cross_entropy,
                           params, tokens, targets)


def param_count(cfg: Qwen3NextConfig) -> int:
    return _layers.param_count(param_shapes(cfg))
