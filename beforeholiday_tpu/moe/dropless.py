"""Dropless top-k routing over the experts one chip holds.

``moe/router.py`` is the GShard recipe: top-1/2, a static per-expert capacity,
``(T, E, C)`` one-hot dispatch tensors, overflowing tokens dropped. Models that
route each token to ten of several hundred narrow experts (``top_k = 10`` of
512 at width 512) cannot go through it: the one-hots alone would be
``T x E x C`` floats, and they may not drop a token. This module is the other
recipe (MegaBlocks, Gale et al. 2022; see PAPERS.md): sort the ``(token,
choice)`` assignments by expert, run each expert's SwiGLU as one group of a
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

Counters come back as device scalars (no host sync): ``expert_rows`` (rows
routed to held experts), ``expert_load_max_over_mean`` (the fullest held
expert's rows over the mean), ``dropped_rows``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from beforeholiday_tpu.monitor.spans import span as _span
from beforeholiday_tpu.ops.grouped_matmul import grouped_matmul as _grouped_matmul

__all__ = [
    "dropless_experts",
    "dropless_moe",
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


def swiglu(x, w_gate, w_up, w_down):
    """``(silu(x w_gate) * (x w_up)) w_down``, bias-free, in ``x``'s dtype."""
    dt = x.dtype
    h = jax.nn.silu(x @ w_gate.astype(dt)) * (x @ w_up.astype(dt))
    return h @ w_down.astype(dt)


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
    """The held experts' part of ``sum_e w_e * swiglu_e(x)``.

    ``x``: ``(T, D)``; ``weights`` / ``idx``: ``(T, k)`` from :func:`route_topk`;
    ``experts``: ``w_gate`` / ``w_up`` ``(E_held, D, F)`` and ``w_down``
    ``(E_held, F, D)`` for expert ids ``first_expert .. first_expert + E_held``.
    ``impl`` is :func:`~beforeholiday_tpu.ops.grouped_matmul.grouped_matmul`'s.
    Returns ``(y (T, D) float32, counters)``."""
    T, D = x.shape
    k = idx.shape[1]
    held = experts["w_gate"].shape[0]
    worst = T * min(k, held)
    R = worst if rows_bound is None else min(int(rows_bound), worst)

    with _span("moe_dispatch"):
        local = idx - first_expert
        key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
        # assignments in expert order, the ones for absent experts last
        order = jnp.argsort(key, stable=True)[:R]
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
        h = jax.nn.silu(grouped(xs, experts["w_gate"], _F32)) * grouped(xs, experts["w_up"], _F32)
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
    impl: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Routed experts (the held ones' part) plus, where the model has one, the
    gated shared expert.

    ``x``: ``(T, D)``. ``p``: ``router (D, E)``, ``w_gate`` / ``w_up`` /
    ``w_down`` (stacked over the held experts) and, for a model with a shared
    expert, ``shared_w_gate`` / ``shared_w_up`` / ``shared_w_down`` and
    ``shared_score (D, 1)``: without them the layer is its routed part alone,
    and the ``moe_shared`` span does not open. Returns
    ``(y (T, D) in x's dtype, counters)``."""
    with _span("moe"):
        with _span("moe_route"):
            weights, idx = route_topk(x, p["router"], top_k, renormalize=renormalize)
        routed, counters = dropless_experts(
            x, weights, idx, {n: p[n] for n in ("w_gate", "w_up", "w_down")},
            first_expert=first_expert, rows_bound=rows_bound, impl=impl)
        if "shared_w_gate" in p:
            with _span("moe_shared"):
                shared = shared_expert(x, p["shared_w_gate"], p["shared_w_up"],
                                       p["shared_w_down"], p["shared_score"])
            with _span("moe_combine"):
                routed = routed + shared.astype(_F32)
        with _span("moe_combine"):
            y = routed.astype(x.dtype)
    return y, counters
