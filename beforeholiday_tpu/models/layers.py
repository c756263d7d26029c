"""What the decoder models share: the shapes tree a family's parameters are
drawn and counted from, the RMS norm, rotary tables, grouped-query attention
(the tail every family with grouped keys ends in, and the QK-normed body two of
them are), the latent-attention mixer (with a rotary shared key or a plain one),
the causal depthwise convolution of the recurrent mixers, the SwiGLU
and sigmoid-routed feed-forward parts, the layer stack's plumbing (scanned
periods, unrolled layers), the head, the loss and the reduction of the MoE
counters over layers.

A function here takes published hyper-parameters and arrays, never a family's
name: a family opens its own span (``monitor.spans``) and calls the body inside
it, so every op keeps the scope its layer metric reads."""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

_F32 = jnp.float32


# ---------------------------------------------------------------------------------
# the shapes tree: ``param_shapes(cfg)`` of a family, leaves ``(shape, init kind)``
# ---------------------------------------------------------------------------------


def is_shape_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def param_count(shapes) -> int:
    return sum(math.prod(shape) for shape, _ in jax.tree.leaves(shapes, is_leaf=is_shape_leaf))


def draw_params(key: jax.Array, shapes, draw: Callable):
    """The tree of ``shapes`` with leaf ``i`` (in the tree's flattened order:
    keys sorted) drawn as ``draw(fold_in(key, i), shape, kind)``."""
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=is_shape_leaf)
    return jax.tree.unflatten(
        treedef, [draw(jax.random.fold_in(key, i), *leaf) for i, leaf in enumerate(leaves)])


def keep_fp32(path, also: Sequence[str] = ()) -> bool:
    """``amp.initialize(keep_fp32_mask=...)`` of a decoder family: the leaves
    with "norm" in a name of their path, and those named in ``also`` (the
    family's per-head scalars, its selection bias)."""
    names = [str(getattr(p, "key", getattr(p, "name", p))).lower() for p in path]
    return any("norm" in n or n in also for n in names)


def rms_norm(x, w, eps):
    """``w * x / sqrt(mean(x^2) + eps)`` over the last axis (``ops.fused_rms_norm``),
    the weight in float32."""
    from beforeholiday_tpu.ops import fused_rms_norm

    return fused_rms_norm(x, w.astype(_F32), eps=eps)


class Yarn(NamedTuple):
    """YaRN's scaling of a rotary table (Peng et al. 2023), as a published
    ``rope_parameters`` group gives it."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def rotary_frequencies(rotary_dim: int, theta: float, yarn: Optional[Yarn] = None):
    """``rotary_dim / 2`` inverse frequencies, float32. Plain:
    ``theta^(-2i / rotary_dim)``. YaRN (fixed, whatever the sequence length):
    dim ``i`` keeps the plain frequency ``e_i`` below ``low``, takes the
    interpolated ``e_i / factor`` above ``high`` and a linear blend between,
    where ``low`` / ``high`` are the dims that turn ``beta_fast`` / ``beta_slow``
    times within the original length:
    ``c(r) = rotary_dim * ln(original / (2 pi r)) / (2 ln theta)``."""
    half = rotary_dim // 2
    plain = theta ** (-jnp.arange(half, dtype=_F32) * 2.0 / rotary_dim)
    if yarn is None:
        return plain

    def turns_at(r):
        return (rotary_dim * math.log(yarn.original_max_position_embeddings
                                      / (2 * math.pi * r)) / (2 * math.log(theta)))

    low = max(math.floor(turns_at(yarn.beta_fast)), 0)
    high = min(math.ceil(turns_at(yarn.beta_slow)), rotary_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=_F32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / yarn.factor * ramp + plain * (1.0 - ramp)


def rotary_table(seq_len: int, rotary_dim: int, theta: float,
                 yarn: Optional[Yarn] = None):
    """``(cos, sin)`` of positions ``0 .. seq_len-1``, each ``(S, rotary_dim / 2)``
    float32, times YaRN's ``attention_factor`` where there is one (so that the
    scores of rotated ``q`` and ``k`` carry its square)."""
    inv_freq = rotary_frequencies(rotary_dim, theta, yarn)
    angle = jnp.arange(seq_len, dtype=_F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if yarn is not None and yarn.attention_factor != 1.0:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    return cos, sin


def mrope_table(positions, rotary_dim: int, theta: float, sections: Sequence[int]):
    """``(cos, sin)``, each ``(S, rotary_dim / 2)`` float32, of a table whose
    frequency pairs take their positions from several rows (a published
    ``mrope_section``): ``positions (len(sections), S)``, pair ``i`` of the
    ``rotary_dim / 2`` reads row ``r`` where ``i`` falls in the ``r``-th run of
    ``sections`` (``[16, 24, 24]``: pairs 0-15 the temporal row, 16-39 the
    height, 40-63 the width). Equal rows give :func:`rotary_table`'s table of
    those positions: on text, whose three positions are the token's index, the
    sections change nothing."""
    half = rotary_dim // 2
    if sum(sections) != half or len(sections) != positions.shape[0]:
        raise ValueError(f"sections {tuple(sections)} are not {positions.shape[0]} runs of the "
                         f"{half} frequency pairs")
    row = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections), total_repeat_length=half)
    angle = positions.astype(_F32).T[:, row] * rotary_frequencies(rotary_dim, theta)[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def apply_rotary(x, cos, sin):
    """Rotary position embedding on the first ``2 * cos.shape[-1]`` dims of
    each head (``rotate_half`` layout: dim ``i`` pairs with ``i + half``), the
    rest passed through. ``x``: ``(B, S, H, hd)``; the products in float32."""
    half = cos.shape[-1]
    rotary_dim = 2 * half
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = x[..., :half].astype(_F32), x[..., half:rotary_dim].astype(_F32)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if rotary_dim == x.shape[-1]:
        return rotated.astype(x.dtype)
    return jnp.concatenate([rotated.astype(x.dtype), x[..., rotary_dim:]], axis=-1)


def grouped_query_attention(q, k, v, *, window: Optional[int] = None,
                            impl: Optional[str] = None, selected=None):
    """Causal softmax attention of ``q (B, S, H, hd)`` over ``k (B, S, Hkv, hd)``,
    ``v (B, S, Hkv, dv)`` at ``hd^-1/2``, each key head serving ``H / Hkv`` query
    heads BY REPETITION (``ops.flash_attention`` takes equal head counts);
    ``window``: the keys a query sees, None for all before it; ``selected``: the
    keys each query keeps, ``(B, S, S)`` int8 (``ops.index_select``), the same for
    every head. ``(B, S, H, dv)``: the heads back beside their positions, for the
    caller to gate or flatten."""
    from beforeholiday_tpu.ops import flash_attention

    H, Hkv, hd = q.shape[2], k.shape[2], q.shape[3]
    if H != Hkv:
        k, v = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))
    heads_first = lambda t: t.transpose(0, 2, 1, 3)
    ctx = flash_attention(heads_first(q), heads_first(k), heads_first(v), causal=True,
                          scale=hd ** -0.5, window=window, impl=impl, selected=selected)
    return heads_first(ctx)


def qk_norm_attention(u, p, table, *, heads: int, kv_heads: int, head_dim: int, eps: float,
                      window: Optional[int] = None, impl: Optional[str] = None,
                      selected=None):
    """One bias-free attention mixer on ``u (B, S, D)``: ``w_q`` / ``w_k`` /
    ``w_v``, ``q`` and ``k`` through an RMS norm over the head (``q_norm`` /
    ``k_norm``: one weight of ``head_dim``, shared by the heads) and the rotary
    embedding of ``table = (cos, sin)`` on the whole head,
    :func:`grouped_query_attention`, ``w_o``."""
    B, S, _ = u.shape
    dt = u.dtype
    q = (u @ p["w_q"].astype(dt)).reshape(B, S, heads, head_dim)
    k = (u @ p["w_k"].astype(dt)).reshape(B, S, kv_heads, head_dim)
    v = (u @ p["w_v"].astype(dt)).reshape(B, S, kv_heads, head_dim)
    q = apply_rotary(rms_norm(q, p["q_norm"], eps), *table)
    k = apply_rotary(rms_norm(k, p["k_norm"], eps), *table)
    ctx = grouped_query_attention(q, k, v, window=window, impl=impl, selected=selected)
    return ctx.reshape(B, S, heads * head_dim) @ p["w_o"].astype(dt)


def latent_attention(u, p, *, heads: int, kv_lora_rank: int, qk_nope_head_dim: int,
                     qk_rope_head_dim: int, v_head_dim: int, eps: float, table=None,
                     relay: Optional[Callable] = None, impl: Optional[str] = None):
    """One multi-head latent-attention mixer without a query latent on ``u (B, S,
    D)``: ``q = u w_q`` on ``heads`` of ``[plain | shared-key part]``; ``[c |
    k_shared] = u w_kva`` (``k_shared`` is ONE head all share); ``[k_plain | v]``
    of each head from ``rms(c, kv_a_layernorm) w_kvb``; causal softmax of ``[q]
    . [k_plain | k_shared]`` at the queries' whole width over ``v``; ``w_o``.
    ``table = (cos, sin)`` at ``qk_rope_head_dim`` turns the shared-key part of
    queries and key by the rotary embedding; None leaves both as projected (a
    published ``mla_use_nope``). ``relay`` re-lays that part's weight columns
    first (a published ``rope_interleave``). The shared key is broadcast over the
    heads and joined to ``k_plain`` here, in XLA, and its cotangent summed over the
    heads by autodiff.

    The three projections are taken apart BY COLUMNS OF THE WEIGHTS (``w_q`` into
    every head's plain and shared-key columns, ``w_kva`` into the latent's and the
    shared key's, ``w_kvb`` into every head's keys and values), so that no
    activation is sliced: the transpose of a slice of ``(S, 6144)`` is a
    zero-padded copy of it and a sum (9.6 ms a step of `add_any` in the first
    chip run of the Kanana cell), the transpose of a slice of a weight is 4 x
    smaller and off the token axis."""
    from beforeholiday_tpu.monitor.spans import span

    B, S, _ = u.shape
    H, r = heads, kv_lora_rank
    dn, dr, dv = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
    dt = u.dtype
    relay = relay or (lambda w: w)
    turn = (lambda x: x) if table is None else (lambda x: apply_rotary(x, *table))
    by_head = lambda w, d: w.astype(dt).reshape(w.shape[0], H, d)
    # x (B, S, K) through some columns of every head, w (K, H, d): (B, S, H, d)
    project = lambda x, w: (x @ w.reshape(w.shape[0], -1)).reshape(B, S, H, -1)
    w_q = by_head(p["w_q"], dn + dr)
    q_nope, q_rot = project(u, w_q[..., :dn]), project(u, relay(w_q[..., dn:]))
    with span("mla_latent"):
        w_kva, w_kvb = p["w_kva"].astype(dt), by_head(p["w_kvb"], dn + dv)
        k_rot = (u @ relay(w_kva[:, r:])).reshape(B, S, 1, dr)          # one head for all
        c = rms_norm(u @ w_kva[:, :r], p["kv_a_layernorm"], eps)
        k_nope, v = project(c, w_kvb[..., :dn]), project(c, w_kvb[..., dn:])
    q = jnp.concatenate([q_nope, turn(q_rot)], axis=-1)
    k_rot = jnp.broadcast_to(turn(k_rot), (B, S, H, dr))
    k = jnp.concatenate([k_nope, k_rot], axis=-1)
    # a group of one; the scale is the queries' whole width: (dn + dr)^-1/2
    ctx = grouped_query_attention(q, k, v, impl=impl)
    return ctx.reshape(B, S, H * dv) @ p["w_o"].astype(dt)


def causal_depthwise_conv(x, w):
    """``y[t, c] = sum_j w[c, j] * x[t - (K-1) + j, c]``, zeros before the
    start. ``x``: ``(B, S, C)``, ``w``: ``(C, K)``."""
    K, S = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    w = w.astype(_F32)
    y = sum(xp[:, j:j + S].astype(_F32) * w[:, j] for j in range(K))
    return y.astype(x.dtype)


def swiglu_ffn(h, p):
    """The dense SwiGLU of ``w_gate`` / ``w_up`` / ``w_down`` (``moe.dropless.swiglu``)."""
    from beforeholiday_tpu.moe.dropless import swiglu

    return swiglu(h, p["w_gate"], p["w_up"], p["w_down"])


def _dropless(h, p, **kw):
    """``moe.dropless.dropless_moe`` (its spans) on ``h (B, S, D)``: ``(y (B, S, D), counters)``."""
    from beforeholiday_tpu.moe.dropless import dropless_moe

    B, S, D = h.shape
    y, counters = dropless_moe(h.reshape(B * S, D), p, **kw)
    return y.reshape(B, S, D), counters


def softmax_moe(h, p, *, top_k: int, first_expert: int, rows_bound: Optional[int],
                renormalize: bool):
    """``(y, counters)`` of one mixture-of-experts part on ``h (B, S, D)`` under
    ``moe.dropless``'s own router (softmax over all the router's outputs, the
    ``top_k`` largest, divided by their sum where ``renormalize``), no shared
    expert: the held experts' part of the routed sum."""
    return _dropless(h, p, top_k=top_k, first_expert=first_expert, rows_bound=rows_bound,
                     renormalize=renormalize)


def sigmoid_moe(h, p, *, top_k: int, first_expert: int, rows_bound: Optional[int],
                renormalize: bool, **route):
    """``(y, counters)`` of one mixture-of-experts part on ``h (B, S, D)``:
    ``moe.dropless.dropless_moe`` (its spans) under ``route_sigmoid(**route)``
    (``bias``, ``scale``, ``eps``: the published router's)."""
    from beforeholiday_tpu.moe.dropless import route_sigmoid

    return _dropless(h, p, top_k=top_k, first_expert=first_expert, rows_bound=rows_bound,
                     renormalize=renormalize, route=functools.partial(route_sigmoid, **route))


def logits_of(x, head):
    """``x (B, S, D)`` against the rows of ``head (V, D)``: float32 logits."""
    return jax.lax.dot_general(
        x, head.astype(x.dtype), (((2,), (1,)), ((), ())), preferred_element_type=_F32)


@jax.custom_vjp
def cross_entropy(logits, targets):
    """Mean next-token cross entropy, the log-sum-exp in float32.

    A ``custom_vjp`` because of what autodiff makes of ``logsumexp`` +
    ``take_along_axis``: the head's cotangent as the SUM of two ``(B, S, V)``
    float32 arrays from different ops in different layouts (softmax x g, and
    -g scattered into zeros), which the TPU compiler writes, re-tiles, adds
    and converts one pass at a time: four nameless ops of 403-671 MB each
    between ``*_loss`` and ``*_head``'s backward products, 4.0-6.3 ms a step
    in each of the seven 8k cells (PERF.md §5, PR 52's chip runs). The
    backward here is that cotangent as ONE elementwise expression of the
    saved logits, their log-sum-exp and the targets, ``(softmax - onehot) *
    g / N`` in float32, which the compiler fuses into the two products or
    writes once. Residuals: the logits (autodiff saved them too), ``logz``
    and the targets. No forward-mode rule. ``testing/gpt.py:_cross_entropy``
    is deliberately not this function yet: the ledger shows no logits-sized
    nameless op in the GPT cells."""
    return _cross_entropy_fwd(logits, targets)[0]


def _target_columns(x, targets):
    """Where ``x (..., V)`` holds its row's target: a ``(..., V)`` mask."""
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) == targets[..., None]


def _cross_entropy_fwd(logits, targets):
    x = logits.astype(_F32)
    logz = jax.nn.logsumexp(x, axis=-1)
    # the target's logit as a masked row sum (exact: one term a row is not 0),
    # so that it rides the log-sum-exp's reduce pass and no gather is made
    picked = jnp.sum(jnp.where(_target_columns(x, targets), x, 0.0), axis=-1)
    return jnp.mean(logz - picked), (logits, logz, targets)


def _cross_entropy_bwd(saved, g):
    logits, logz, targets = saved
    x = logits.astype(_F32)
    softmax = jnp.exp(x - logz[..., None])
    d = (softmax - _target_columns(x, targets).astype(_F32)) * (g / logz.size)
    return d.astype(logits.dtype), None


cross_entropy.defvjp(_cross_entropy_fwd, _cross_entropy_bwd)


def by_period(tree, periods: int, n: int):
    """Leaves stacked ``(periods * n, ...)`` as ``(periods, n, ...)``: the
    ``xs`` of a scan over periods."""
    return jax.tree.map(lambda a: a.reshape(periods, n, *a.shape[1:]), tree)


def scan_periods(period, x, stacked):
    """``lax.scan(period, x, stacked)`` over leaves stacked ``(periods, ...)``;
    a stack of ONE period is the body called once. A loop of one trip is still
    a loop to the compiler until late: its residuals cross the boundary stacked
    ``(1, ...)`` and are re-laid out on the other side, and what is nested in
    it is compiled as a loop's body. With the MoE layer's own loops
    (``moe.dropless.gather_rows``) inside, the one-period Qwen3-Next step held
    0.96 GiB more temporaries by the chip compiler's count than without the
    scan (PERF.md, PR 34)."""
    if jax.tree.leaves(stacked)[0].shape[0] != 1:
        return jax.lax.scan(period, x, stacked)
    x, seen = period(x, jax.tree.map(lambda a: a[0], stacked))
    return x, jax.tree.map(lambda a: a[None], seen)


def unstack(tree, n: int):
    """The ``n`` members of leaves stacked on their leading axis. ``lax.split``:
    its gradient is one concatenation; that of ``a[i]`` is a zero-padded copy
    of the whole stack for every ``i``."""
    parts = jax.tree.map(lambda a: jax.lax.split(a, [1] * n, axis=0), tree)
    return [jax.tree.map(lambda p: p[i][0], parts,
                         is_leaf=lambda p: isinstance(p, (list, tuple)))
            for i in range(n)]


def unrolled_layers(layer: dict, kinds, leaves, x, *shared):
    """``x`` through layers that repeat nothing: ``layer[kind](x, p, *shared)``
    for each ``kind`` of ``kinds`` on its own leaves ``p`` (one dict a layer,
    nothing stacked). ``(x, [the counters of the layers that count])``."""
    seen = []
    for kind, p in zip(kinds, leaves, strict=True):
        x, c = layer[kind](x, p, *shared)
        if c is not None:
            seen.append(c)
    return x, seen


def loss_fn(forward: Callable, cross_entropy: Callable, params, tokens, targets):
    """``(cross_entropy(logits, targets), counters)`` of ``forward(params, tokens)
    -> (logits, counters)``: a family passes its plain forward or the caller's
    amp-wrapped apply, and its own span-named cross entropy."""
    logits, counters = forward(params, tokens)
    return cross_entropy(logits, targets), counters


# what ``moe.dropless`` counts in a layer, and a model returns for a step
COUNTERS = ("expert_rows", "expert_load_max_over_mean", "dropped_rows")


def reduce_counters(seen):
    """A step's MoE counters from its layers' (stacked on any leading axes):
    ``expert_rows`` (sum), ``expert_load_max_over_mean`` (max), ``dropped_rows``
    (sum)."""
    return {
        "expert_rows": jnp.sum(seen["expert_rows"]),
        "expert_load_max_over_mean": jnp.max(seen["expert_load_max_over_mean"]),
        "dropped_rows": jnp.sum(seen["dropped_rows"]),
    }


def step_counters(seen: list):
    """:func:`reduce_counters` of a list of one dict a counting layer; a step
    without such a layer counts zeros."""
    if not seen:
        return {k: jnp.zeros((), _F32) for k in COUNTERS}
    return reduce_counters(jax.tree.map(lambda *v: jnp.stack(v), *seen))
