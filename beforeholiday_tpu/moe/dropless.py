"""Dropless top-k routing over the experts one chip holds.

``moe/router.py`` is the GShard recipe: top-1/2, a static per-expert capacity,
``(T, E, C)`` one-hot dispatch tensors, overflowing tokens dropped. Models that
route each token to ten of several hundred narrow experts (``top_k = 10`` of
512 at width 512) cannot go through it: the one-hots alone would be
``T x E x C`` floats, and they may not drop a token. This module is the other
recipe (MegaBlocks, Gale et al. 2022; see PAPERS.md): sort the ``(token,
choice)`` assignments by expert, run each expert's MLP as one group of a
grouped matmul over the sorted rows (``ops/grouped_matmul.py``: Pallas kernels
that hold an expert's weight panel in VMEM while its row tiles go by, and
``jax.lax.ragged_dot`` off their shapes and off the TPU; either way rows
outside every group cost nothing and are left unspecified, forward and
backward, so both sides of the experts are masked), and scatter the weighted
results back onto the tokens.

**The layer is told which experts it holds** (``first_expert`` and the leading
axis of the stacked expert weights), as expert parallelism asks: the router
keeps all its outputs and its normalisation over all ``top_k`` choices, and
this chip computes the part of the sum that its own experts give. What the
absent experts would add arrives from their chips in a deployment (the
all-to-all of ``moe/dispatch.py``); on one chip it is simply absent. Nothing
here stands in for it.

**Static shapes.** The sorted-rows buffer has ``rows_bound`` rows. ``None``
sizes it for the worst case (every token sending ``min(top_k, held)`` choices
here), which can never overflow. A tighter bound saves memory in proportion;
assignments beyond it are *counted* (``dropped_rows``) so that the caller can
fail the step: they are never silently lost.

**Two expert forms, two routers.** The form is a property of the parameters:
with a ``w_gate`` an expert is SwiGLU's three matrices, ``(silu(x W_g) * x W_u)
W_d``; without one it is two, ``relu(x W_u)^2 W_d``. The shared expert likewise
(gated by ``shared_score`` and SwiGLU, or ungated and ``relu^2``).
:func:`route_topk` is a softmax over all the router's outputs;
:func:`route_sigmoid` scores each expert on its own, chooses by score plus a
constant bias and scales the renormalised weights. Where the parameters hold a
``fc1_latent`` / ``fc2_latent`` pair the routed experts work in that narrower
latent (span ``moe_latent``); router and shared expert see the full width.

Counters come back as device scalars (no host sync): ``expert_rows`` (rows
routed to held experts), ``expert_load_max_over_mean`` (the fullest held
expert's rows over the mean), ``dropped_rows``.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from beforeholiday_tpu.monitor.spans import span as _span
from beforeholiday_tpu.ops.grouped_matmul import grouped_matmul as _grouped_matmul

__all__ = [
    "dropless_experts",
    "dropless_moe",
    "relu2_mlp",
    "route_sigmoid",
    "route_topk",
    "shared_expert",
    "swiglu",
]

_F32 = jnp.float32


def route_topk(
    x: jax.Array, w_router: jax.Array, top_k: int, *, renormalize: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """``(weights (T, k) float32, expert ids (T, k) int32)``: a softmax over
    ALL the router's outputs in float32, the ``top_k`` largest, renormalised
    to sum to one (``norm_topk_prob``). The products are taken in ``x``'s
    dtype and accumulated in float32: of two bfloat16 values they are exact."""
    logits = jnp.dot(x, w_router.astype(x.dtype), preferred_element_type=_F32)
    weights, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, idx.astype(jnp.int32)


def route_sigmoid(
    x: jax.Array, w_router: jax.Array, top_k: int, *, bias: Optional[jax.Array] = None,
    scale: float = 1.0, renormalize: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """``(weights (T, k) float32, expert ids (T, k) int32)``: each of the
    router's outputs through a sigmoid in float32; the ``top_k`` largest of
    ``score + bias`` (``bias (E,)``: it enters the choice only, so it has no
    gradient); the chosen *scores* divided by their sum (plus 1e-20) where
    ``renormalize``, times ``scale``."""
    logits = jnp.dot(x, w_router.astype(x.dtype), preferred_element_type=_F32)
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + jax.lax.stop_gradient(bias.astype(_F32))
    _, idx = jax.lax.top_k(choice, top_k)
    # the chosen scores by comparison, not by a gather: its transpose is a
    # reduction XLA fuses, where a scatter of (T, k) into (T, E) compiles for 11 s
    chosen = idx[..., None] == jnp.arange(scores.shape[-1], dtype=idx.dtype)
    weights = jnp.sum(jnp.where(chosen, scores[..., None, :], 0.0), axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * scale, idx.astype(jnp.int32)


def swiglu(x, w_gate, w_up, w_down):
    """``(silu(x w_gate) * (x w_up)) w_down``, bias-free, in ``x``'s dtype."""
    dt = x.dtype
    h = jax.nn.silu(x @ w_gate.astype(dt)) * (x @ w_up.astype(dt))
    return h @ w_down.astype(dt)


def relu2_mlp(x, w_up, w_down):
    """``relu(x w_up)^2 w_down``: the two-matrix expert, bias-free, in ``x``'s dtype."""
    dt = x.dtype
    return jnp.square(jax.nn.relu(x @ w_up.astype(dt))) @ w_down.astype(dt)


def shared_expert(x, w_gate, w_up, w_down, w_score):
    """The always-on expert, gated per token: ``sigmoid(x w_score) * swiglu(x)``.
    ``w_score``: ``(D, 1)``."""
    score = jnp.dot(x, w_score.astype(x.dtype), preferred_element_type=_F32)
    return (jax.nn.sigmoid(score) * swiglu(x, w_gate, w_up, w_down)).astype(x.dtype)


def dropless_experts(
    x: jax.Array,
    weights: jax.Array,
    idx: jax.Array,
    experts: Dict[str, jax.Array],
    *,
    first_expert: int = 0,
    rows_bound: Optional[int] = None,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The held experts' part of ``sum_e w_e * mlp_e(x)``.

    ``x``: ``(T, D)``; ``weights`` / ``idx``: ``(T, k)`` from a router;
    ``experts``: ``w_up`` ``(E_held, D, F)`` and ``w_down`` ``(E_held, F, D)``
    for expert ids ``first_expert .. first_expert + E_held``, and for SwiGLU
    experts ``w_gate`` like ``w_up`` (without it an expert is ``relu^2``).
    ``impl`` is :func:`~beforeholiday_tpu.ops.grouped_matmul.grouped_matmul`'s.
    Returns ``(y (T, D) float32, counters)``."""
    T, D = x.shape
    k = idx.shape[1]
    held = experts["w_up"].shape[0]
    worst = T * min(k, held)
    R = worst if rows_bound is None else min(int(rows_bound), worst)

    with _span("moe_dispatch"):
        local = idx - first_expert
        key = jnp.where((local >= 0) & (local < held), local, held)
        if k > held:
            # a token reaches at most ``held`` of the held experts: its held
            # choices first, so that the sort below is of T * held keys, not T * k
            key, choice = jax.lax.top_k(-key, held)
            key, at = -key, (jnp.arange(T)[:, None] * k + choice).reshape(-1)
        key = key.reshape(-1)
        # assignments in expert order, the ones for absent experts last
        order = jnp.argsort(key, stable=True)[:R]
        if k > held:
            order = at[order]                   # ... as indices of the (T, k) choices
        token = order // k
        counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                         dtype=jnp.int32)
        ends = jnp.minimum(jnp.cumsum(counts), R)
        group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
        rows = jnp.sum(counts)
        valid = jnp.arange(R) < ends[-1]
        w_sorted = jnp.where(valid, weights.reshape(-1)[order], 0.0)
        # the rows of no group hold whatever the grouped kernel leaves there,
        # in its outputs AND in the cotangent it hands back for ``xs``: this
        # select keeps that out of dx (its transpose is the same select)
        xs = jnp.where(valid[:, None], x[token], 0)
    with _span("moe_experts"):
        dt = x.dtype
        grouped = lambda a, w, out: _grouped_matmul(
            a, w.astype(dt), group_sizes, preferred_element_type=out, impl=impl)
        if "w_gate" in experts:
            h = jax.nn.silu(grouped(xs, experts["w_gate"], _F32)) \
                * grouped(xs, experts["w_up"], _F32)
        else:
            h = jnp.square(jax.nn.relu(grouped(xs, experts["w_up"], _F32)))
        y = grouped(h.astype(dt), experts["w_down"], dt)      # as a dense layer hands it on
    with _span("moe_combine"):
        # rows of no group are whatever the grouped kernel left there: cut them
        y = jnp.where(valid[:, None], y, 0).astype(_F32) * w_sorted[:, None]
        out = jnp.zeros((T, D), _F32).at[token].add(y)
    counters = {
        "expert_rows": rows.astype(_F32),
        "expert_load_max_over_mean": jnp.max(counts).astype(_F32) * held
        / jnp.maximum(rows, 1).astype(_F32),
        "dropped_rows": jnp.maximum(rows - R, 0).astype(_F32),
    }
    return out, counters


def dropless_moe(
    x: jax.Array,
    p: Dict[str, jax.Array],
    *,
    top_k: int,
    first_expert: int = 0,
    rows_bound: Optional[int] = None,
    renormalize: bool = True,
    route: Callable = route_topk,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Routed experts (the held ones' part) plus, where the model has one, the
    shared expert.

    ``x``: ``(T, D)``. ``p``: ``router (D, E)``, ``w_up`` / ``w_down`` and for
    SwiGLU experts ``w_gate`` (stacked over the held experts); for a model
    whose routed experts work in a latent, ``fc1_latent (D, Dl)`` /
    ``fc2_latent (Dl, D)`` (the experts are then ``Dl`` wide); for a model with
    a shared expert ``shared_w_up`` / ``shared_w_down`` and, if it is gated,
    ``shared_w_gate`` and ``shared_score (D, 1)``: without them the layer is its
    routed part alone, and the ``moe_shared`` span does not open. ``route`` is
    the router, called ``route(x, router, top_k, renormalize=renormalize)``: a
    partial of :func:`route_sigmoid`, say. Returns ``(y (T, D) in x's dtype, counters)``."""
    dt = x.dtype
    with _span("moe"):
        with _span("moe_route"):
            weights, idx = route(x, p["router"], top_k, renormalize=renormalize)
        latent = x
        if "fc1_latent" in p:
            with _span("moe_latent"):
                latent = x @ p["fc1_latent"].astype(dt)
        routed, counters = dropless_experts(
            latent, weights, idx, {n: p[n] for n in ("w_gate", "w_up", "w_down") if n in p},
            first_expert=first_expert, rows_bound=rows_bound, impl=impl)
        if "fc2_latent" in p:
            with _span("moe_latent"):
                routed = jnp.dot(routed.astype(dt), p["fc2_latent"].astype(dt),
                                 preferred_element_type=_F32)
        if "shared_w_up" in p:
            with _span("moe_shared"):
                if "shared_w_gate" in p:
                    shared = shared_expert(x, p["shared_w_gate"], p["shared_w_up"],
                                           p["shared_w_down"], p["shared_score"])
                else:
                    shared = relu2_mlp(x, p["shared_w_up"], p["shared_w_down"])
            with _span("moe_combine"):
                routed = routed + shared.astype(_F32)
        with _span("moe_combine"):
            y = routed.astype(x.dtype)
    return y, counters
