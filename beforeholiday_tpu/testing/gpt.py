"""Standalone GPT language model — the flagship in-repo model.

TPU-native counterpart of the reference's in-repo test GPT (ref:
apex/transformer/testing/standalone_gpt.py:111 and the underlying
standalone_transformer_lm.py:1574). Where the reference composes
ColumnParallelLinear/RowParallelLinear torch modules, this model is a pure
function over a parameter pytree:

* layers are **stacked** along a leading axis and iterated with ``lax.scan`` so
  XLA compiles one layer body regardless of depth;
* tensor parallelism is expressed as ``PartitionSpec``s over the ``tensor`` mesh
  axis (Megatron layout: QKV/MLP-in column-sharded, proj/MLP-out row-sharded,
  embedding vocab-sharded) — GSPMD inserts the same f/g collectives the
  reference implements by hand (apex/transformer/tensor_parallel/layers.py:429,613);
* activations carry ``sharding_constraint``s: batch over ``data``, and the
  residual stream over ``tensor`` along sequence when sequence_parallel is on
  (ref: mappings.py:205-260).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from jax.sharding import PartitionSpec as P

from beforeholiday_tpu.monitor.spans import annotate as _annotate, span as _span
from beforeholiday_tpu.parallel.parallel_state import DATA_AXIS, TENSOR_AXIS
from beforeholiday_tpu.remat import apply as _remat_apply
from beforeholiday_tpu.remat.policies import TAG_BLOCK as _TAG_BLOCK
from beforeholiday_tpu.testing._model_utils import (
    vocab_head_matmul as _vocab_head_matmul,
    constrain as _constrain,
    layernorm as _layernorm,
    residual_spec as _residual_spec,
)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 512
    seq_len: int = 128
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: Optional[int] = None  # default 4*d_model
    dtype: jnp.dtype = jnp.float32  # activation/compute dtype (params stay fp32)
    sequence_parallel: bool = False
    # attention path: flash (Pallas, ref: contrib fmha/fast_multihead_attn) vs
    # the materialized-scores softmax kernel; attention_impl forces the
    # pallas/jnp dispatch for tests (None = resolve_impl policy)
    use_flash_attention: bool = True
    attention_impl: Optional[str] = None
    # training regularization (ref: standalone GPT's hidden/attention dropout;
    # apex/transformer/testing/standalone_transformer_lm.py) — active only
    # when forward() receives a dropout_key
    dropout_rate: float = 0.0          # embedding + post-attn + post-MLP
    attention_dropout: float = 0.0     # softmax-probs dropout (jnp attn path)
    # activation rematerialization over the scanned block: a registered
    # beforeholiday_tpu.remat policy name ("none"/"full"/"dots_saveable"/
    # "save_boundaries"); None = no remat
    remat_policy: Optional[str] = None
    # Mixture-of-Experts (beforeholiday_tpu.moe): every ``moe_every``-th
    # block's MLP is replaced by a routed expert layer (0 = dense model,
    # bitwise-identical to the pre-MoE code path). The dense-MLP params of
    # a MoE layer still exist in the stacked tree (one tree shape for any
    # moe_every) but are unused. n_layers must divide by moe_every.
    moe_every: int = 0
    moe_experts: int = 4
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2
    moe_z_weight: float = 1e-3
    # static mesh-axis names threaded to moe_layer: set when forward runs
    # inside shard_map with an expert/tensor axis bound (see
    # testing/moe_model.py); None = all experts local (jit/GSPMD path)
    moe_expert_axis: Optional[str] = None
    moe_tensor_axis: Optional[str] = None
    moe_hierarchical: bool = False

    @property
    def ff(self) -> int:
        return self.d_ff if self.d_ff is not None else 4 * self.d_model

    @property
    def moe_groups(self) -> int:
        if self.moe_every == 0:
            return 0
        assert self.n_layers % self.moe_every == 0, (
            f"n_layers ({self.n_layers}) must divide by moe_every "
            f"({self.moe_every})"
        )
        return self.n_layers // self.moe_every

    def moe_cfg(self):
        from beforeholiday_tpu.moe import MoEConfig

        return MoEConfig(
            n_experts=self.moe_experts,
            top_k=self.moe_top_k,
            capacity_factor=self.moe_capacity_factor,
            aux_weight=self.moe_aux_weight,
            z_weight=self.moe_z_weight,
        )

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


def init(key: jax.Array, cfg: GPTConfig) -> dict:
    """Initialize the parameter pytree (fp32 master params)."""
    keys = jax.random.split(key, 8)
    D, F, L, V, S = cfg.d_model, cfg.ff, cfg.n_layers, cfg.vocab_size, cfg.seq_len

    def norm(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(jnp.float32)

    init_std = 0.02
    # output-projection init scaled by depth, as Megatron does
    out_std = init_std / np.sqrt(2.0 * L)
    params = {
        "tok_embed": norm(keys[0], (V, D), init_std),
        "pos_embed": norm(keys[1], (S, D), init_std),
        "blocks": {
            "ln1_scale": jnp.ones((L, D)),
            "ln1_bias": jnp.zeros((L, D)),
            "wqkv": norm(keys[2], (L, D, 3 * D), init_std),
            "bqkv": jnp.zeros((L, 3 * D)),
            "wo": norm(keys[3], (L, D, D), out_std),
            "bo": jnp.zeros((L, D)),
            "ln2_scale": jnp.ones((L, D)),
            "ln2_bias": jnp.zeros((L, D)),
            "wi": norm(keys[4], (L, D, F), init_std),
            "bi": jnp.zeros((L, F)),
            "wo2": norm(keys[5], (L, F, D), out_std),
            "bo2": jnp.zeros((L, D)),
        },
        "lnf_scale": jnp.ones((D,)),
        "lnf_bias": jnp.zeros((D,)),
    }
    if cfg.moe_every:
        from beforeholiday_tpu.moe import init_experts

        G = cfg.moe_groups
        params["moe"] = {
            "w_router": norm(keys[6], (G, D, cfg.moe_experts), init_std),
            "experts": jax.vmap(
                lambda k: init_experts(
                    k, cfg.moe_experts, D, F,
                    init_std=init_std, out_std=out_std,
                )
            )(jax.random.split(keys[7], G)),
        }
    return params


def param_specs(cfg: GPTConfig) -> dict:
    """PartitionSpecs for Megatron-style tensor parallelism over the mesh.

    Column-parallel (QKV, MLP-in) shard the output dim; row-parallel (attn proj,
    MLP-out) shard the input dim; embedding is vocab-parallel
    (ref: apex/transformer/tensor_parallel/layers.py:167,429,613).
    """
    t = TENSOR_AXIS
    specs = {
        "tok_embed": P(t, None),
        "pos_embed": P(None, None),
        "blocks": {
            "ln1_scale": P(None, None),
            "ln1_bias": P(None, None),
            "wqkv": P(None, None, t),
            "bqkv": P(None, t),
            "wo": P(None, t, None),
            "bo": P(None, None),
            "ln2_scale": P(None, None),
            "ln2_bias": P(None, None),
            "wi": P(None, None, t),
            "bi": P(None, t),
            "wo2": P(None, t, None),
            "bo2": P(None, None),
        },
        "lnf_scale": P(None),
        "lnf_bias": P(None),
    }
    if cfg.moe_every:
        from beforeholiday_tpu.moe import expert_param_specs

        # group dim leads each leaf; experts replicated under jit/GSPMD (the
        # expert-PARALLEL placement is shard_map's business — moe_model.py),
        # d_ff tensor-sharded exactly like the dense MLP
        e_specs = expert_param_specs(tensor_axis=t)
        specs["moe"] = {
            "w_router": P(None, None, None),
            "experts": {k: P(None, *s) for k, s in e_specs.items()},
        }
    return specs


def _drop(cfg: GPTConfig, dkey, t, site, rate):
    """cfg.dropout-family dropout at a numbered fold_in site; dkey None =
    deterministic identity (eval/bench)."""
    if dkey is None or rate == 0.0:
        return t
    from beforeholiday_tpu.transformer.tensor_parallel.random import dropout

    return dropout(jax.random.fold_in(dkey, site), t, rate)


def _attn_sublayer(cfg: GPTConfig, x, lp, dkey=None):
    """ln1 + attention + residual — the block half every layer shares,
    whether its MLP half is dense or MoE. x: (B, S, D)."""
    from beforeholiday_tpu.ops import fused_dense, scaled_upper_triang_masked_softmax
    from beforeholiday_tpu.transformer.tensor_parallel.random import dropout

    B, S, D = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    training = dkey is not None

    h = _layernorm(x, lp["ln1_scale"], lp["ln1_bias"])
    qkv = fused_dense(h, lp["wqkv"].astype(h.dtype), lp["bqkv"].astype(h.dtype))
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    attn_rate = cfg.attention_dropout if training else 0.0
    attn_key = jax.random.fold_in(dkey, 0) if (training and attn_rate > 0) else None
    if cfg.use_flash_attention:
        # Pallas flash attention — no (B*H, S, S) score tensor in HBM
        from beforeholiday_tpu.ops import flash_attention

        ctx = flash_attention(
            q, k, v, causal=True, scale=1.0 / np.sqrt(hd),
            dropout_rate=attn_rate, dropout_key=attn_key,
            impl=cfg.attention_impl,
        )
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, D)
    else:
        scores = (q @ k.transpose(0, 1, 3, 2)).reshape(B * H, S, S)
        probs = scaled_upper_triang_masked_softmax(
            scores, 1.0 / np.sqrt(hd)
        ).astype(x.dtype).reshape(B, H, S, S)
        if attn_rate > 0.0:
            probs = dropout(attn_key, probs, attn_rate)
        ctx = (probs @ v).transpose(0, 2, 1, 3).reshape(B, S, D)
    attn_out = fused_dense(ctx, lp["wo"].astype(x.dtype), lp["bo"].astype(x.dtype))
    x = x + _drop(cfg, dkey, attn_out, 1, cfg.dropout_rate)
    return _constrain(x, _residual_spec(cfg))


def _block(cfg: GPTConfig, x, lp, dkey=None):
    """One dense transformer block over the fused-ops layer. x: (B, S, D).
    ``dkey``: per-layer PRNG key; None = deterministic (eval/bench)."""
    from beforeholiday_tpu.ops import fused_dense

    x = _attn_sublayer(cfg, x, lp, dkey=dkey)
    h = _layernorm(x, lp["ln2_scale"], lp["ln2_bias"])
    h = jax.nn.gelu(fused_dense(h, lp["wi"].astype(h.dtype), lp["bi"].astype(h.dtype)))
    mlp_out = fused_dense(h, lp["wo2"].astype(x.dtype), lp["bo2"].astype(x.dtype))
    x = x + _drop(cfg, dkey, mlp_out, 2, cfg.dropout_rate)
    # remat boundary tag: the residual stream between blocks is the cheapest
    # possible save point — one (B, S, D) tensor per layer
    return _checkpoint_name(_constrain(x, _residual_spec(cfg)), _TAG_BLOCK)


def _moe_block(cfg: GPTConfig, x, lp, mp, dkey=None):
    """A transformer block whose MLP is the routed expert layer. Same
    attention half and dropout sites as ``_block``; the dense wi/bi/wo2/bo2
    slots of ``lp`` are ignored. Returns ``(x, aux)`` with the layer's
    router aux scalars."""
    from beforeholiday_tpu.moe import moe_layer

    x = _attn_sublayer(cfg, x, lp, dkey=dkey)
    h = _layernorm(x, lp["ln2_scale"], lp["ln2_bias"])
    B, S, D = h.shape
    # one routing group per rank: every local token competes for the same
    # expert capacity (GShard's group = the local batch)
    y, aux = moe_layer(
        h.reshape(B * S, D),
        mp["w_router"],
        mp["experts"],
        cfg.moe_cfg(),
        expert_axis=cfg.moe_expert_axis,
        tensor_axis=cfg.moe_tensor_axis,
        hierarchical=cfg.moe_hierarchical,
    )
    x = x + _drop(cfg, dkey, y.reshape(B, S, D), 2, cfg.dropout_rate)
    return (
        _checkpoint_name(_constrain(x, _residual_spec(cfg)), _TAG_BLOCK),
        aux,
    )


_MOE_AUX_KEYS = ("moe_aux_loss", "moe_z_loss", "moe_drop_fraction")


def _zero_moe_aux() -> dict:
    return {k: jnp.zeros((), jnp.float32) for k in _MOE_AUX_KEYS}


def forward(params: dict, tokens: jax.Array, cfg: GPTConfig,
            dropout_key: Optional[jax.Array] = None,
            return_aux: bool = False):
    """tokens (B, S) int32 → logits (B, S, V). ``dropout_key`` switches the
    cfg.dropout_rate/attention_dropout sites on (None = eval: identity).

    ``return_aux=True`` also returns the MoE aux dict (router load-balance /
    z loss / drop fraction, MEANS over the model's MoE layers, keys matching
    ``TrainMonitor``'s spec; all-zero for a dense model) — feed it to
    ``TrainMonitor.update(..., moe=...)`` and the weighted loss terms in
    :func:`loss_and_aux`."""
    from beforeholiday_tpu.transformer.tensor_parallel.random import dropout

    B, S = tokens.shape
    # one scope per model sub-layer (embed / blocks / head): the device trace
    # splits forward and backward time by them (jvp(..) / transpose(jvp(..)))
    with _span("gpt_embed"):
        x = params["tok_embed"][tokens] + params["pos_embed"][:S]
        x = x.astype(cfg.dtype)
        if dropout_key is not None and cfg.dropout_rate > 0.0:
            x = dropout(
                jax.random.fold_in(dropout_key, 0x7FFFFFFF), x, cfg.dropout_rate
            )
        x = _constrain(x, _residual_spec(cfg))

    with _span("gpt_blocks"):
        x, aux = _forward_blocks(params, x, cfg, dropout_key)
    with _span("gpt_head"):
        x = _layernorm(x, params["lnf_scale"], params["lnf_bias"])
        logits = _vocab_head_matmul(x, params["tok_embed"])
        logits = _constrain(logits, P(DATA_AXIS, None, TENSOR_AXIS))
    if return_aux:
        return logits, aux
    return logits


def _forward_blocks(params: dict, x, cfg: GPTConfig, dropout_key):
    """The layer stack over the residual stream: ``(x, moe aux)``."""
    # cfg.remat_policy wraps the scanned block body: with scan-over-layers the
    # saved-residual stack is L x (per-block residuals), so the block is
    # exactly the granularity Chen/Megatron checkpointing wants
    if cfg.moe_every:
        return _forward_moe_stack(params, x, cfg, dropout_key)
    if dropout_key is not None:
        layer_keys = jax.random.split(dropout_key, cfg.n_layers)
        blk = _remat_apply(
            lambda carry, lp, lk: _block(cfg, carry, lp, dkey=lk),
            cfg.remat_policy,
        )

        def body(carry, xs):
            lp, lk = xs
            return blk(carry, lp, lk), None

        x, _ = jax.lax.scan(body, x, (params["blocks"], layer_keys))
    else:
        blk = _remat_apply(
            lambda carry, lp: _block(cfg, carry, lp), cfg.remat_policy
        )

        def body(carry, lp):
            return blk(carry, lp), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
    return x, _zero_moe_aux()


def _forward_moe_stack(params: dict, x, cfg: GPTConfig, dropout_key):
    """Scan the layer stack in groups of ``moe_every``: each group is
    ``moe_every - 1`` dense blocks followed by one MoE block, so one compiled
    group body covers any depth (the stacked-layers idiom, one level up).
    Returns ``(x, aux)`` with aux MEANS over the ``moe_groups`` MoE layers."""
    G, every = cfg.moe_groups, cfg.moe_every
    blocks_g = jax.tree.map(
        lambda a: a.reshape(G, every, *a.shape[1:]), params["blocks"]
    )
    if dropout_key is not None:
        group_keys = jax.random.split(dropout_key, cfg.n_layers).reshape(
            G, every, -1
        )
    else:
        group_keys = None

    def group(carry_x, gp, mp, gk):
        for i in range(every - 1):
            lp = jax.tree.map(lambda a: a[i], gp)
            carry_x = _block(
                cfg, carry_x, lp, dkey=None if gk is None else gk[i]
            )
        lp = jax.tree.map(lambda a: a[every - 1], gp)
        return _moe_block(
            cfg, carry_x, lp, mp, dkey=None if gk is None else gk[every - 1]
        )

    grp = _remat_apply(group, cfg.remat_policy)

    def body(carry, xs):
        x, aux = carry
        if group_keys is None:
            gp, mp = xs
            x, aux_g = grp(x, gp, mp, None)
        else:
            gp, mp, gk = xs
            x, aux_g = grp(x, gp, mp, gk)
        return (x, {k: aux[k] + aux_g[k] for k in _MOE_AUX_KEYS}), None

    xs = (blocks_g, params["moe"])
    if group_keys is not None:
        xs = xs + (group_keys,)
    (x, aux), _ = jax.lax.scan(body, (x, _zero_moe_aux()), xs)
    return x, {k: aux[k] / G for k in _MOE_AUX_KEYS}


@_annotate("gpt_loss")
def _cross_entropy(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - tgt)


def loss_and_aux(params: dict, tokens: jax.Array, targets: jax.Array,
                 cfg: GPTConfig, dropout_key: Optional[jax.Array] = None):
    """``(loss, aux)``: next-token cross entropy plus the weighted MoE router
    losses (Switch eq. 4 aux at ``cfg.moe_aux_weight``, ST-MoE z-loss at
    ``cfg.moe_z_weight``), and the raw aux dict for ``TrainMonitor.update``.
    For a dense model the aux dict is zeros and loss == plain CE."""
    logits, aux = forward(params, tokens, cfg, dropout_key, return_aux=True)
    loss = _cross_entropy(logits, targets)
    if cfg.moe_every:
        loss = (
            loss
            + cfg.moe_aux_weight * aux["moe_aux_loss"]
            + cfg.moe_z_weight * aux["moe_z_loss"]
        )
    return loss, aux


def loss_fn(params: dict, tokens: jax.Array, targets: jax.Array, cfg: GPTConfig,
            forward_fn=None):
    """Mean next-token cross entropy. ``forward_fn(params, tokens)`` overrides
    the plain forward (e.g. an amp-wrapped apply) while keeping ONE loss
    definition for trainers/benches. With ``cfg.moe_every`` set (and no
    ``forward_fn`` override) the weighted router losses ride along — the
    scalar every trainer already differentiates trains the router too."""
    if forward_fn is None:
        if cfg.moe_every:
            return loss_and_aux(params, tokens, targets, cfg)[0]
        logits = forward(params, tokens, cfg)
    else:
        logits = forward_fn(params, tokens)
    return _cross_entropy(logits, targets)


def synthetic_batch(key: jax.Array, cfg: GPTConfig, batch: int):
    tokens = jax.random.randint(key, (batch, cfg.seq_len), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=-1)
    return tokens, targets
