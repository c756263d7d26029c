"""Nemotron-H: a stack whose every block is ONE mixer — a Mamba-2 state-space
mixer, a LatentMoE feed-forward part or a grouped-query attention — chosen by a
pattern string.

Written from the published configuration
(huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, ``config.json``,
``model_type`` ``nemotron_h``). Bias-free but for the convolution. With
``rms(x, w) = w * x / sqrt(mean(x^2) + eps)`` (plain weight, initially one):

* block ``l`` of kind ``hybrid_override_pattern[l]``: ``x + mixer_l(rms(x))``;
  after the last block ``rms`` and an untied head. No block has a mixer *and* a
  feed-forward part.
* ``M``, Mamba-2: ``[z | xBC | dt] = u W_in`` (widths ``d_in | d_in + 2 G N |
  H``, ``d_in = H P``); ``xBC = silu(causal depthwise conv_4(xBC) + b)``, split
  into ``x (H, P)``, ``B (G, N)``, ``C (G, N)``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the state-space recurrence with the skip ``D x``
  (``ops.ssd``: chunked, Pallas kernels on the TPU); ``y = rms_group(y *
  silu(z))`` (gate first, the norm over each group's ``d_in / G`` channels);
  ``y W_out``.
* ``*``, attention: ``q, k, v = u W_q, u W_k, u W_v`` (GQA by repeating each KV
  head over its query heads), causal softmax at scale ``head_dim^-1/2``
  (``ops.flash_attention``), ``W_o``. **No rotary embedding and no other
  position signal**: the published modelling code applies none.
* ``E``, LatentMoE (``moe.dropless``): a sigmoid router over all the experts
  (a constant bias for the choice only, the chosen scores renormalised and
  times ``routed_scaling_factor``); the routed experts two matrices and
  ``relu^2``, working in a latent between ``fc1_latent`` and ``fc2_latent``; an
  ungated ``relu^2`` shared expert on the block's full-width input.

**The model is told its share**, as tensor and expert parallelism ask: how many
Mamba heads and groups, query and KV heads, columns of the shared expert,
routed experts (and which: ``first_expert``) and ids of the vocabulary live
here. A share's out-projections give partial sums, its experts their part of
the routed sum; what the absent ranks would add is absent, and nothing here
stands in for it. The whole model is the default. (Which heads, groups and
columns a share holds changes no computation, only which slice of the
published tensors its parameters are: ``tests/test_nemotron_h.py`` slices them.)

Parameters are stacked by kind — ``mamba`` ``(L_M, ...)``, ``moe`` ``(L_E,
...)``, ``attn`` ``(L_*, ...)`` — and the stack is a ``lax.scan`` over the
periods of the pattern whose body unrolls one period, as in ``models.mellum``
(a pattern that repeats nothing is one period; a scan over the five ``EM``
pairs inside ``EMEMEMEMEM*`` keeps its residuals stacked and took 3.2 GiB more
at 1 x 8192 tokens by the chip compiler's count: PERF.md, PR 33).

Not here: the multi-token-prediction module (``num_nextn_predict_layers``; the
config gives neither its input projection nor its loss weight), an update rule
for the router's selection bias and an auxiliary loss (the config has a key
for neither).
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
MAMBA, MOE, ATTENTION = "M", "E", "*"
_GROUP = {MAMBA: "mamba", MOE: "moe", ATTENTION: "attn"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 512               # ids held here (a slice of the vocabulary)
    hidden_size: int = 128
    hybrid_override_pattern: str = "MEM*E"     # the kinds of the blocks held
    # Mamba-2: the heads and groups HELD (whole groups: heads / groups a group)
    mamba_num_heads: int = 4
    mamba_head_dim: int = 16
    n_groups: int = 2
    ssm_state_size: int = 16
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention: the query and KV heads HELD
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 32
    # LatentMoE
    n_routed_experts: int = 16          # the router's width
    n_routed_experts_held: int = 16     # experts first_expert .. + held live here
    first_expert: int = 0
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 48
    moe_latent_size: int = 64
    moe_shared_expert_intermediate_size: int = 96   # the columns HELD
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    moe_rows_bound: Optional[int] = None   # None: the worst case, never overflows
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    rescale_layers: Optional[int] = None   # the WHOLE model's depth; None: the pattern's
    dtype: jnp.dtype = jnp.float32      # activation dtype
    remat_policy: Optional[str] = None  # over one block; None = no remat
    attention_impl: Optional[str] = None   # forces the flash dispatch in tests
    ssd_impl: Optional[str] = None         # ... and the state-space kernels'

    @property
    def period(self) -> str:
        """The shortest prefix the pattern is whole repeats of (the published
        pattern's stretches between attention blocks are uneven: it is one period)."""
        pattern = self.hybrid_override_pattern
        if not pattern or set(pattern) - set(_GROUP):
            raise ValueError(f"hybrid_override_pattern {pattern!r} is not of M, E and *")
        if self.mamba_num_heads % self.n_groups or \
                self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads held are not whole groups / whole KV heads' shares")
        return next(pattern[:n] for n in range(1, len(pattern) + 1)
                    if pattern[:n] * (len(pattern) // n) == pattern)

    @property
    def periods(self) -> int:
        return len(self.hybrid_override_pattern) // len(self.period)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim


def param_shapes(cfg: NemotronHConfig) -> dict:
    """``{group: {name: (shape, init)}}``; init names a draw of :func:`init`."""
    D, V = cfg.hidden_size, cfg.vocab_size
    n = {kind: cfg.hybrid_override_pattern.count(kind) for kind in _GROUP}
    Hm, G, N, d_in = cfg.mamba_num_heads, cfg.n_groups, cfg.ssm_state_size, cfg.d_inner
    conv = d_in + 2 * G * N
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    E, Eh = cfg.n_routed_experts, cfg.n_routed_experts_held
    F, Dl, Fs = (cfg.moe_intermediate_size, cfg.moe_latent_size,
                 cfg.moe_shared_expert_intermediate_size)
    Lm, Le, La = n[MAMBA], n[MOE], n[ATTENTION]
    shapes = {
        "top": {
            "embed": ((V, D), "std"),
            "head": ((V, D), "std"),
            "final_norm": ((D,), "one"),
        },
        "mamba": {
            "norm": ((Lm, D), "one"),
            "w_in": ((Lm, D, d_in + conv + Hm), "std"),
            "conv": ((Lm, conv, cfg.conv_kernel), "conv"),
            "conv_bias": ((Lm, conv), "conv"),
            "a_log": ((Lm, Hm), "a_log"),
            "dt_bias": ((Lm, Hm), "dt_bias"),
            "d": ((Lm, Hm), "one"),
            "out_norm": ((Lm, d_in), "one"),
            "w_out": ((Lm, d_in, D), "out"),
        },
        "moe": {
            "norm": ((Le, D), "one"),
            "router": ((Le, D, E), "std"),
            "fc1_latent": ((Le, D, Dl), "std"),
            "fc2_latent": ((Le, Dl, D), "std"),
            "w_up": ((Le, Eh, Dl, F), "std"),
            "w_down": ((Le, Eh, F, Dl), "std"),
            "shared_w_up": ((Le, D, Fs), "std"),
            "shared_w_down": ((Le, Fs, D), "std"),
        },
        "attn": {
            "norm": ((La, D), "one"),
            "w_q": ((La, D, H * hd), "std"),
            "w_k": ((La, D, Hkv * hd), "std"),
            "w_v": ((La, D, Hkv * hd), "std"),
            "w_o": ((La, H * hd, D), "std"),
        },
    }
    held = {"top": 1, "mamba": Lm, "moe": Le, "attn": La}
    return {g: s for g, s in shapes.items() if held[g]}


def init(key: jax.Array, cfg: NemotronHConfig) -> dict:
    """Seeded float32 parameters after the published modelling code: matmul
    weights, embedding and head N(0, ``initializer_range``), the Mamba
    out-projection that over the square root of the whole model's depth
    (``rescale_prenorm_residual``); ``A_log = log U(1, 16)`` a head
    (``mamba_ssm``'s ``A_init_range``, the uniform draw); ``dt_bias`` the
    inverse softplus of a step log-uniform in ``time_step_min .. max``, floored at
    ``time_step_floor``; ``D`` and the norm weights one; the convolution and its
    bias uniform in +-1/sqrt(kernel) (torch's Conv1d default)."""
    depth = cfg.rescale_layers or len(cfg.hybrid_override_pattern)

    def draw(k, shape, kind):
        if kind == "one":
            return jnp.ones(shape, _F32)
        if kind == "conv":
            bound = 1.0 / math.sqrt(cfg.conv_kernel)
            return jax.random.uniform(k, shape, _F32, -bound, bound)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(k, shape, _F32, 1.0, 16.0))
        if kind == "dt_bias":
            lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
            step = jnp.maximum(jnp.exp(jax.random.uniform(k, shape, _F32, lo, hi)),
                               cfg.time_step_floor)
            return step + jnp.log(-jnp.expm1(-step))
        std = cfg.initializer_range / (math.sqrt(depth) if kind == "out" else 1.0)
        return jax.random.normal(k, shape, _F32) * std

    tree = _layers.draw_params(key, param_shapes(cfg), draw)
    top = tree.pop("top")              # its leaves sit beside the groups
    return {**tree, **top}


def keep_fp32(path) -> bool:
    """``amp.initialize(keep_fp32_mask=...)``: the norm weights and the three
    per-head scalars of the recurrence (``A_log`` enters through two
    exponentials)."""
    return _layers.keep_fp32(path, also=("a_log", "dt_bias", "d"))


rms_norm = _layers.rms_norm


@_annotate("ssm_mixer")
def mamba2_mixer(cfg: NemotronHConfig, u, p):
    from beforeholiday_tpu.ops.ssd import ssd

    B, S, _ = u.shape
    H, P, G, N, d_in = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                        cfg.ssm_state_size, cfg.d_inner)
    dt_ = u.dtype
    zxbcdt = u @ p["w_in"].astype(dt_)
    z, xbc, step = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * G * N], axis=-1)
    xbc = jax.nn.silu(_layers.causal_depthwise_conv(xbc, p["conv"]).astype(_F32)
                      + p["conv_bias"].astype(_F32)).astype(dt_)
    x, Bm, Cm = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    step = jax.nn.softplus(step.astype(_F32) + p["dt_bias"].astype(_F32))
    y = ssd(x.reshape(B, S, H, P), step, -jnp.exp(p["a_log"].astype(_F32)),
            Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N), p["d"].astype(_F32),
            chunk=cfg.chunk_size, impl=cfg.ssd_impl)
    y = (y.reshape(B, S, d_in).astype(_F32) * jax.nn.silu(z.astype(_F32))).astype(dt_)
    if G == 1:
        y = rms_norm(y, p["out_norm"], cfg.layer_norm_epsilon)
    else:                              # the norm is a group's own, the weight a channel's
        y = rms_norm(y.reshape(B, S, G, d_in // G), jnp.ones((d_in // G,), _F32),
                     cfg.layer_norm_epsilon).reshape(B, S, d_in)
        y = (y.astype(_F32) * p["out_norm"].astype(_F32)).astype(dt_)
    return y @ p["w_out"].astype(dt_)


@_annotate("attn_mixer")
def attention(cfg: NemotronHConfig, u, p):
    B, S, _ = u.shape
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    dt_ = u.dtype
    q = (u @ p["w_q"].astype(dt_)).reshape(B, S, H, hd)
    k = (u @ p["w_k"].astype(dt_)).reshape(B, S, Hkv, hd)
    v = (u @ p["w_v"].astype(dt_)).reshape(B, S, Hkv, hd)
    ctx = _layers.grouped_query_attention(q, k, v, impl=cfg.attention_impl)
    return ctx.reshape(B, S, H * hd) @ p["w_o"].astype(dt_)


def latent_moe(cfg: NemotronHConfig, u, p):
    """``(y, counters)`` of one LatentMoE part (``moe.dropless``'s spans)."""
    # the selection bias is a constant of zeros here (module docstring): none is passed
    return _layers.sigmoid_moe(
        u, p, top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
        rows_bound=cfg.moe_rows_bound, renormalize=cfg.norm_topk_prob,
        scale=cfg.routed_scaling_factor)


def _block(cfg: NemotronHConfig, x, p, kind: str):
    """One block: ``(x + mixer(rms(x)), the MoE counters or None)``."""
    u = rms_norm(x, p["norm"], cfg.layer_norm_epsilon)
    if kind == MOE:
        y, counters = latent_moe(cfg, u, p)
        return x + y, counters
    return x + (mamba2_mixer if kind == MAMBA else attention)(cfg, u, p), None


def forward(params: dict, tokens: jax.Array, cfg: NemotronHConfig):
    """``tokens (B, S) int32 -> (logits (B, S, V) float32, counters)``.
    ``counters``: per step, over the MoE blocks (``models.layers.reduce_counters``)."""
    kinds, periods = cfg.period, cfg.periods
    with _span("nemotron_h_embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    # in sorted order: a set of strings iterates in another order in every
    # process, and the order the blocks' parameters are unstacked in is part of
    # the traced program, so of the compile cache's key
    held = sorted(set(kinds))
    block = {kind: _remat_apply(
        lambda x, p, kind=kind: _block(cfg, x, p, kind), cfg.remat_policy)
        for kind in held}
    per = {_GROUP[kind]: kinds.count(kind) for kind in held}

    def period(x, stacked):
        parts = {g: iter(_layers.unstack(stacked[g], n)) for g, n in per.items()}
        seen = []
        for kind in kinds:
            x, c = block[kind](x, next(parts[_GROUP[kind]]))
            if c is not None:
                seen.append(c)
        return x, jax.tree.map(lambda *v: jnp.stack(v), *seen) if seen else None

    with _span("nemotron_h_layers"):
        x, seen = _layers.scan_periods(period, x, {
            g: _layers.by_period(params[g], periods, n) for g, n in per.items()})
    counters = _layers.reduce_counters(seen) if seen is not None else _layers.step_counters([])
    with _span("nemotron_h_head"):
        x = rms_norm(x, params["final_norm"], cfg.layer_norm_epsilon)
        logits = _layers.logits_of(x, params["head"])
    return logits, counters


cross_entropy = _annotate("nemotron_h_loss")(_layers.cross_entropy)


def loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
            cfg: NemotronHConfig, forward_fn=None):
    """``(mean next-token cross entropy over the vocabulary held, counters)``.
    ``forward_fn(params, tokens)`` overrides the plain forward (an amp-wrapped
    apply), as in ``testing/gpt.loss_fn``."""
    return _layers.loss_fn(forward_fn or functools.partial(forward, cfg=cfg), cross_entropy,
                           params, tokens, targets)


def param_count(cfg: NemotronHConfig) -> int:
    return _layers.param_count(param_shapes(cfg))
