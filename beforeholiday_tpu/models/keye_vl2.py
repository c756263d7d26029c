"""Keye-VL-2.0's language model: grouped-query attention over keys that a learned
indexer selects for each query, every MLP a mixture of experts with no shared
expert.

Written from the published configuration
(huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, ``config.json``, ``model_type``
``KeyeVL2``), whose key set is that of the Qwen3-MoE configuration class (QK-norm,
``norm_topk_prob``, ``moe_intermediate_size``) with a three-row rotary table
(``rope_scaling.mrope_section``) and, in every layer, ``sa_config``: the
lightning indexer of DeepSeek-V3.2-Exp's sparse attention (``indexer_num_heads``
heads of ``indexer_head_dim`` on ONE shared key, ``topk`` keys a query). What the
config leaves open is the convention of that family and is listed in the
benchmark's configuration file under ``assumed``. Bias-free but for the
indexer's LayerNorm. With ``rms(x, w) = w * x / sqrt(mean(x^2) + eps)``:

* layer: ``h = x + mixer(rms(x))``; ``y = h + moe(rms(h))``; after the last layer
  ``rms`` and an untied head.
* main heads (``models.layers.qk_norm_attention``): ``q = u W_q`` on
  ``num_attention_heads`` heads, ``k``, ``v`` on ``num_key_value_heads``; ``q, k``
  through ``rms`` over the head; rotary embedding on the whole head
  (``rotate_half`` layout) from the three-row table
  (``models.layers.mrope_table``: frequency pair ``i`` takes the temporal, the
  height or the width position by ``mrope_section``; a text token's three
  positions are its index, so on text the table is the plain one).
* indexer (:func:`index_operands`): ``qI = u W_qI`` on ``indexer_num_heads`` heads
  of ``indexer_head_dim``; ``kI = LayerNorm(u W_kI)``, one key for all of them;
  rotary embedding on the whole of both (the temporal position); ``w = (u W_w)
  * indexer_num_heads^-1/2 * indexer_head_dim^-1/2`` in float32.
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` and the ``min(t + 1, topk)``
  keys ``s <= t`` of largest ``I[t, s]`` (ties to the lower ``s``), the same for
  every main head: ``ops.index_select``, which returns them as an int8 mask.
* attention: softmax over the selected keys only, at ``head_dim^-1/2``
  (``ops.flash_attention(selected=)``); ``W_o``.
* MoE: ``moe.dropless`` (softmax over all experts, top-k renormalised, the
  experts this chip holds; no shared expert).

**The indexer is held**: the selection passes no gradient (its result is a set),
and the published ``config.json`` names no coefficient or stage for the KL term
DeepSeek-V3.2-Exp trains its indexer with, so ``w_qi``, ``w_ki``, ``w_wi`` and the
key norm are leaves whose gradient is exactly zero (the indexer reads its inputs
under ``stop_gradient``), as a sigmoid router's selection bias is elsewhere.

**The model is told its share**: how many experts live here and which
(``num_experts``, ``first_expert``, of the router's ``num_experts_published``
outputs), how many ids of the vocabulary, and which published layer its first
held one is (``first_layer``: every published layer is of one kind, so it names
the share and changes nothing). The whole model is the default.

Parameters are ``{"embed", "norm", "head", "layers": [...]}``: one dict a held
layer, nothing stacked (the layers are unrolled).

Not here: the vision tower and the positions of image tokens (a caller may pass
``positions (3, S)``; the benchmark's batches are text), the indexer's training
signal, an auxiliary balancing loss.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from beforeholiday_tpu.models import layers as _layers
from beforeholiday_tpu.monitor.spans import annotate as _annotate, span as _span
from beforeholiday_tpu.remat import apply as _remat_apply

_F32 = jnp.float32
# the step's counters: ``moe.dropless``'s, and the (query, key) pairs the indexers kept
COUNTERS = _layers.COUNTERS + ("selected_pairs",)
INDEXER_LEAVES = ("w_qi", "w_ki", "w_wi", "indexer_k_norm", "indexer_k_norm_bias")


@dataclasses.dataclass(frozen=True)
class SparseAttentionConfig:
    """The published ``sa_config``. ``q_chunk_size`` / ``kv_chunk_size`` are the
    tile sizes of the published blockwise evaluation of the index scores and
    take no part in the equations."""

    indexer_head_dim: int = 32
    indexer_num_heads: int = 4
    indexer_num_kv_heads: int = 1
    topk: int = 64
    q_chunk_size: int = 512
    kv_chunk_size: int = 512

    def __post_init__(self):
        if self.indexer_num_kv_heads != 1:
            raise ValueError(f"indexer_num_kv_heads {self.indexer_num_kv_heads}: only one shared "
                             "index key is built here")


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    vocab_size: int = 512               # ids held here (a slice of the vocabulary)
    hidden_size: int = 128
    num_hidden_layers: int = 4          # layers held: first_layer .. + held
    first_layer: int = 0                # the published index of the first held layer
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (4, 6, 6)     # runs of the head_dim / 2 frequency pairs
    sa_config: SparseAttentionConfig = SparseAttentionConfig()
    # mixture of experts
    num_experts_published: int = 16     # the router's width
    num_experts: int = 16               # experts first_expert .. + held live here
    first_expert: int = 0
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 64
    norm_topk_prob: bool = True
    moe_rows_bound: Optional[int] = None   # None: the worst case, never overflows
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02     # every matmul weight and the head
    embedding_init_std: float = 1.0     # see ``models.mellum.init``
    dtype: jnp.dtype = jnp.float32      # activation dtype
    remat_policy: Optional[str] = None  # over one layer; None = no remat
    attention_impl: Optional[str] = None   # forces the flash and indexer dispatch in tests


def param_shapes(cfg: KeyeVL2Config) -> dict:
    """``(shape, init)`` of every leaf, in the parameters' own tree; init names
    a draw of :func:`init`."""
    D, V = cfg.hidden_size, cfg.vocab_size
    H, Hkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Hi, di = cfg.sa_config.indexer_num_heads, cfg.sa_config.indexer_head_dim
    E, Eh, F = cfg.num_experts_published, cfg.num_experts, cfg.moe_intermediate_size
    layer = {
        "input_norm": ((D,), "one"),
        "w_q": ((D, H * hd), "std"),
        "w_k": ((D, Hkv * hd), "std"),
        "w_v": ((D, Hkv * hd), "std"),
        "q_norm": ((hd,), "one"),
        "k_norm": ((hd,), "one"),
        "w_o": ((H * hd, D), "std"),
        "w_qi": ((D, Hi * di), "std"),
        "w_ki": ((D, di), "std"),
        "w_wi": ((D, Hi), "std"),
        "indexer_k_norm": ((di,), "one"),
        "indexer_k_norm_bias": ((di,), "zero"),
        "post_norm": ((D,), "one"),
        "router": ((D, E), "std"),
        "w_gate": ((Eh, D, F), "std"),
        "w_up": ((Eh, D, F), "std"),
        "w_down": ((Eh, F, D), "std"),
    }
    return {"embed": ((V, D), "embed"), "norm": ((D,), "one"), "head": ((V, D), "std"),
            "layers": [dict(layer) for _ in range(cfg.num_hidden_layers)]}


def init(key: jax.Array, cfg: KeyeVL2Config) -> dict:
    """Seeded float32 parameters: matmul weights and the head N(0,
    ``initializer_range``), the embedding N(0, ``embedding_init_std``) (for the
    reason ``models.mellum.init`` gives), norm weights one, the LayerNorm's bias
    zero."""
    def draw(k, shape, kind):
        if kind in ("one", "zero"):
            return jnp.full(shape, 1.0 if kind == "one" else 0.0, _F32)
        std = cfg.embedding_init_std if kind == "embed" else cfg.initializer_range
        return jax.random.normal(k, shape, _F32) * std

    return _layers.draw_params(key, param_shapes(cfg), draw)


def keep_fp32(path) -> bool:
    """``amp.initialize(keep_fp32_mask=...)``: the norm weights (and the
    LayerNorm's bias, whose name holds "norm")."""
    return _layers.keep_fp32(path)


rms_norm = _layers.rms_norm


def rotary_tables(cfg: KeyeVL2Config, seq_len: int, positions=None):
    """``(main, indexer)``, each ``(cos, sin)``, once a forward pass: the main
    heads' three-row table and the indexer's plain one of the temporal row.
    ``positions (3, S)``; None: text, every row ``0 .. S-1``."""
    di = cfg.sa_config.indexer_head_dim
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(seq_len), (len(cfg.mrope_section), seq_len))
    return (_layers.mrope_table(positions, cfg.head_dim, cfg.rope_theta, cfg.mrope_section),
            _layers.mrope_table(positions[:1], di, cfg.rope_theta, (di // 2,)))


@_annotate("indexer_proj")
def index_operands(cfg: KeyeVL2Config, u, p, table):
    """``(qI (B, S, Hi, d), kI (B, S, d), w (B, S, Hi) float32)`` of one layer's
    indexer on the normed stream ``u``; ``table``: the indexer's ``(cos, sin)``."""
    from beforeholiday_tpu.ops import fused_layer_norm

    sa = cfg.sa_config
    B, S, _ = u.shape
    Hi, di, dt = sa.indexer_num_heads, sa.indexer_head_dim, u.dtype
    q = (u @ p["w_qi"].astype(dt)).reshape(B, S, Hi, di)
    k = fused_layer_norm(u @ p["w_ki"].astype(dt), p["indexer_k_norm"].astype(_F32),
                         p["indexer_k_norm_bias"].astype(_F32), eps=cfg.rms_norm_eps)
    q = _layers.apply_rotary(q, *table)
    k = _layers.apply_rotary(k[:, :, None, :], *table)[:, :, 0]
    w = jax.lax.dot_general(u, p["w_wi"].astype(dt), (((2,), (0,)), ((), ())),
                            preferred_element_type=_F32) * (Hi ** -0.5 * di ** -0.5)
    return q, k, w


def select_keys(cfg: KeyeVL2Config, u, p, table):
    """The keys each query keeps, int8 ``(B, S, S)``: the indexer, forward only
    (nothing here passes a gradient, to ``u`` or to its own leaves)."""
    from beforeholiday_tpu.ops.indexer import index_select

    u = jax.lax.stop_gradient(u)
    p = {name: jax.lax.stop_gradient(p[name]) for name in INDEXER_LEAVES}
    q, k, w = index_operands(cfg, u, p, table)
    with _span("indexer_select"):
        return index_select(q, k, w, topk=cfg.sa_config.topk, impl=cfg.attention_impl)


@_annotate("sparse_mixer")
def attention(cfg: KeyeVL2Config, u, p, tables):
    """One mixer: ``(the attention's output, the pairs its indexer kept)``."""
    main, indexer = tables
    selected = select_keys(cfg, u, p, indexer)
    y = _layers.qk_norm_attention(
        u, p, main, heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, eps=cfg.rms_norm_eps, impl=cfg.attention_impl,
        selected=selected)
    return y, jnp.sum(selected, dtype=jnp.int32)


def _layer(cfg: KeyeVL2Config, x, p, tables):
    """One decoder layer on its own leaves ``p``: ``(x, counters)``."""
    y, pairs = attention(cfg, rms_norm(x, p["input_norm"], cfg.rms_norm_eps), p, tables)
    x = x + y
    h = rms_norm(x, p["post_norm"], cfg.rms_norm_eps)
    y, counters = _layers.softmax_moe(
        h, p, top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
        rows_bound=cfg.moe_rows_bound, renormalize=cfg.norm_topk_prob)
    return x + y, {**counters, "selected_pairs": pairs}


def forward(params: dict, tokens: jax.Array, cfg: KeyeVL2Config, positions=None):
    """``tokens (B, S) int32 -> (logits (B, S, V) float32, counters)``.
    ``counters``: per step, over the layers (``models.layers.reduce_counters``,
    and ``selected_pairs``, the sum of the layers' counts, float32).
    ``positions (3, S)``: the temporal, height and width position of every token
    (the same for every sequence of the batch); None: text."""
    with _span("keye_vl2_embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
        tables = rotary_tables(cfg, tokens.shape[1], positions)
    layer = {"layer": _remat_apply(functools.partial(_layer, cfg), cfg.remat_policy)}
    with _span("keye_vl2_layers"):
        x, seen = _layers.unrolled_layers(
            layer, ("layer",) * cfg.num_hidden_layers, params["layers"], x, tables)
    pairs = sum(c["selected_pairs"] for c in seen)
    counters = {**_layers.step_counters(seen), "selected_pairs": pairs.astype(_F32)}
    with _span("keye_vl2_head"):
        x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
        logits = _layers.logits_of(x, params["head"])
    return logits, counters


cross_entropy = _annotate("keye_vl2_loss")(_layers.cross_entropy)


def loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
            cfg: KeyeVL2Config, forward_fn=None):
    """``(mean next-token cross entropy over the vocabulary held, counters)``.
    ``forward_fn(params, tokens)`` overrides the plain forward (an amp-wrapped
    apply), as in ``testing/gpt.loss_fn``."""
    return _layers.loss_fn(forward_fn or functools.partial(forward, cfg=cfg), cross_entropy,
                           params, tokens, targets)


def param_count(cfg: KeyeVL2Config) -> int:
    return _layers.param_count(param_shapes(cfg))
