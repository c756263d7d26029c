"""GPT-2 (Radford et al. 2019) forward and loss in plain float32 ``jax.numpy``.

Written from the published description: learned token and position embeddings,
pre-LayerNorm blocks (LayerNorm eps 1e-5 with scale and bias; causal multi-head
attention scaled by 1/sqrt(head size); a 4x MLP with the tanh GELU), a final
LayerNorm and the token embedding reused as the output head. No kernel, no
cache, nothing imported from the program.

Weights are a flat ``{name: array}`` dict; per-layer tensors are stacked on a
leading layer axis under ``blocks/``. ``wqkv`` is GPT-2's ``c_attn``: columns
``[0:D]`` are the queries, ``[D:2D]`` keys, ``[2D:3D]`` values, heads contiguous.

Departure: each block is recomputed in the backward pass (``jax.checkpoint``)
so that float32 activations of the full depth fit beside the optimizer state;
this changes memory, not values.
"""

import jax
import jax.numpy as jnp

from . import precision as prec

STACKED_PREFIX = "blocks/"


def layer_norm(x, scale, bias, eps=1e-5):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(x, lp, n_heads, mode):
    B, S, D = x.shape
    hd = D // n_heads
    h = layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
    qkv = prec.matmul(h, lp["wqkv"], mode) + lp["bqkv"]
    q, k, v = (t.reshape(B, S, n_heads, hd).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = prec.matmul(q, k.transpose(0, 1, 3, 2), mode) / jnp.sqrt(float(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = prec.matmul(probs, v, mode).transpose(0, 2, 1, 3).reshape(B, S, D)
    x = x + prec.matmul(ctx, lp["wo"], mode) + lp["bo"]
    h = layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
    h = gelu_tanh(prec.matmul(h, lp["wi"], mode) + lp["bi"])
    return x + prec.matmul(h, lp["wo2"], mode) + lp["bo2"]


def logits(w, tokens, n_heads, mode="float32"):
    S = tokens.shape[1]
    x = w["tok_embed"][tokens] + w["pos_embed"][:S]
    layers = {k[len(STACKED_PREFIX):]: v for k, v in w.items()
              if k.startswith(STACKED_PREFIX)}
    body = jax.checkpoint(lambda x, lp: block(x, lp, n_heads, mode))
    x, _ = jax.lax.scan(lambda x, lp: (body(x, lp), None), x, layers)
    x = layer_norm(x, w["lnf_scale"], w["lnf_bias"])
    return prec.matmul(x, w["tok_embed"].T, mode)


def loss(w, batch, cfg, mode="float32"):
    """Mean next-token cross entropy over every position of ``(tokens, targets)``."""
    tokens, targets = batch
    lg = logits(w, tokens, cfg["n_heads"], mode)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - picked)
