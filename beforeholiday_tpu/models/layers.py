"""What the decoder models share: rotary tables, the causal depthwise
convolution of the recurrent mixers, cross entropy, the layer stack's plumbing
and the reduction of the MoE counters over layers."""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

_F32 = jnp.float32


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


def causal_depthwise_conv(x, w):
    """``y[t, c] = sum_j w[c, j] * x[t - (K-1) + j, c]``, zeros before the
    start. ``x``: ``(B, S, C)``, ``w``: ``(C, K)``."""
    K, S = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    w = w.astype(_F32)
    y = sum(xp[:, j:j + S].astype(_F32) * w[:, j] for j in range(K))
    return y.astype(x.dtype)


def cross_entropy(logits, targets):
    """Mean next-token cross entropy, the log-sum-exp in float32."""
    logz = jax.nn.logsumexp(logits.astype(_F32), axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked.astype(_F32))


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
