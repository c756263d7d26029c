"""The gated-DeltaNet layer outside its recurrence: two fused passes.

Between the ``qkvz`` projection and the delta rule's kernels
(``ops.gated_delta``), and between those kernels and the output projection, a
DeltaNet layer is elementwise work on ``(S, H d)`` activations. As a chain of
``jax.numpy`` calls XLA splits it into many fusions, each a pass over HBM, and
their transposes into as many again. Here it is two Pallas kernels, each with
a hand-written backward kernel behind a ``jax.custom_vjp`` whose residuals are
the kernel's own inputs:

* :func:`deltanet_qkv` — the causal depthwise convolution, SiLU, the L2 norms
  of ``q`` (times ``d_k^-1/2``) and ``k``, each key head written once for every
  value head it serves, all **heads first** ``(B, H_v, S, d)`` as the delta
  rule's kernels read them. The columns come **by key head**
  (:func:`by_key_head`): head ``h``'s ``q``, its ``k`` and the ``v`` of its value
  heads side by side, so that a grid step reads one block and the backward
  kernel writes the columns' cotangent as one block, once. The caller permutes
  the projection's weight columns (50 MB), not the activation.
* :func:`deltanet_gate` — ``rms_norm(o) * w * silu(z)`` from ``o`` heads first,
  as the scan kernel leaves it, to ``(B, S, H_v d_v)``, the output projection's
  operand.

Everything between a kernel's read and its write is float32 and is rounded
once, where it reaches HBM. A grid step holds one row tile of one key head
(512 lanes at the published widths) as whole arrays: walking the tile in
chunks of 16 to 64 rows, to keep the chain in registers, was slower at every
chunk size than leaving the tile to Mosaic (PERF.md, PR 37). The convolution's
rows before a tile come in as a halo block (zeros before row 0); in the
backward kernel the tiles of a sequence run last to first and the rows *after*
a tile are the first rows of the tile the step before handled, kept in VMEM.
The filter's and the norm weight's gradients accumulate in float32 across the
grid.

``impl="jnp"`` is the same mathematics as the chain it replaces, with that
chain's roundings (the convolution's output and SiLU's are rounded to the
activation dtype): the parity oracle, the off-TPU default and what a shape the
kernels do not take runs.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from beforeholiday_tpu.guard.dispatch import checked_impl as _checked_impl
from beforeholiday_tpu.monitor.spans import span as _span
from beforeholiday_tpu.ops._pallas_util import (
    dispatch as _dispatch,
    interpret_default as _interpret_default,
)

__all__ = ["by_key_head", "deltanet_gate", "deltanet_qkv", "is_kernel_available"]

_F32 = jnp.float32
_LANES = 128
_HALO = 16              # rows of a halo block: one bfloat16 sublane tile
_TAIL = 8               # rows a tile takes from its neighbour: one float32 sublane tile
MAX_FILTER_WIDTH = _TAIL    # a filter reaches width - 1 <= 7 rows back
_ROW_TILES = (512, 256, 128, 64, 32, 16)
_L2_EPS = 1e-6


class _Plan(NamedTuple):
    """What the ``deltanet_qkv`` kernels are built for (static: the key of their
    ``jax.jit``)."""
    Hk: int             # key heads
    r: int              # value heads a key head serves
    dk: int
    dv: int
    K: int              # filter width
    tile: Optional[int] # rows a grid step (None: no tile divides the sequence)

    @property
    def G(self) -> int:         # a key head's columns: q, k, its value heads' v
        return 2 * self.dk + self.r * self.dv


def _row_tile(S: int) -> Optional[int]:
    return next((t for t in _ROW_TILES if S % t == 0), None)


def is_kernel_available(S: int, d_k: int, d_v: int, filter_width: int = 1) -> bool:
    """Shape gate of both kernels: head dims that fill the lanes, a sequence of
    whole row tiles, a filter that reaches no further back than one float32
    sublane tile."""
    return (d_k % _LANES == 0 and d_v % _LANES == 0 and _row_tile(S) is not None
            and 1 <= filter_width <= MAX_FILTER_WIDTH)


def by_key_head(t, *, key_heads: int, value_heads: int, d_k: int, d_v: int, axis: int = -1):
    """``t``'s ``axis`` from ``[q | k | v]`` (all heads of each, as the published
    projection has them) to key head by key head ``[q_h | k_h | v of the value
    heads h serves]``: the column order :func:`deltanet_qkv` reads."""
    axis = axis % t.ndim
    Hk, r = key_heads, value_heads // key_heads
    nq = Hk * d_k
    if value_heads != Hk * r or t.shape[axis] != 2 * nq + value_heads * d_v:
        raise ValueError(
            f"by_key_head: axis {axis} of {t.shape} is not {key_heads} key heads of {d_k} "
            f"twice and {value_heads} value heads of {d_v}")
    # plain slices side by side, no reshape to (heads, width): on the weight's
    # tiled layout splitting the column axis is a re-layout, and XLA then paid for
    # it with a transposed copy of the 134 MB cotangent (PERF.md, PR 37)
    part = lambda a, w: lax.slice_in_dim(t, a, a + w, axis=axis)
    parts = []
    for h in range(Hk):
        parts += [part(h * d_k, d_k), part(nq + h * d_k, d_k),
                  part(2 * nq + h * r * d_v, r * d_v)]
    return jnp.concatenate(parts, axis=axis)


# ---------------------------------------------------------------------------------
# the jnp chain: oracle and fallback
# ---------------------------------------------------------------------------------


def _l2_normalize(x):
    x = x.astype(_F32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _qkv_jnp(cols, filt, p: _Plan):
    B, S, _ = cols.shape
    dt = cols.dtype
    xp = jnp.pad(cols, ((0, 0), (p.K - 1, 0), (0, 0)))
    w = filt.astype(_F32)
    y = sum(xp[:, j:j + S].astype(_F32) * w[:, j] for j in range(p.K)).astype(dt)
    a = jax.nn.silu(y).reshape(B, S, p.Hk, p.G)
    q = (_l2_normalize(a[..., :p.dk]) * p.dk ** -0.5).astype(dt)
    k = _l2_normalize(a[..., p.dk:2 * p.dk]).astype(dt)
    v = a[..., 2 * p.dk:].reshape(B, S, p.Hk * p.r, p.dv)
    if p.r != 1:                        # each key head serves r value heads
        q, k = (jnp.repeat(t, p.r, axis=2) for t in (q, k))
    return tuple(jnp.moveaxis(t, 2, 1) for t in (q, k, v))


def _gate_jnp(o, z, weight, eps, activation):
    from beforeholiday_tpu.ops.normalization import fused_rms_norm

    B, H, S, dv = o.shape
    o = fused_rms_norm(jnp.moveaxis(o, 1, 2), weight, eps=eps)
    act = jax.nn.silu if activation == "silu" else jax.nn.sigmoid
    return (o * act(z.reshape(B, S, H, dv))).reshape(B, S, H * dv)


# ---------------------------------------------------------------------------------
# what the kernel bodies share
# ---------------------------------------------------------------------------------


def _f32(x):
    return x if x.dtype == _F32 else lax.convert_element_type(x, _F32)


def _rowsum(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _conv_silu(x, before, w_ref, K):
    """``x (T, G)`` float32 behind the 8 rows ``before`` it. Returns the
    convolution ``y``, ``sigmoid(y)`` and the taps ``x[t - (K-1) + j]``, ``j = 0
    .. K-1`` (summed oldest first, as the chain sums them)."""
    T = x.shape[0]
    window = jnp.concatenate([before, x], axis=0)
    taps = [window[_TAIL - (K - 1) + j:_TAIL - (K - 1) + j + T] for j in range(K - 1)] + [x]
    y = taps[0] * w_ref[0:1, :]
    for j in range(1, K):
        y = y + taps[j] * w_ref[j:j + 1, :]
    return y, lax.logistic(y), taps


def _halo_tail(halo_ref, is_start):
    """The 8 rows before a tile, float32; zeros before a sequence's first row."""
    tail = _f32(halo_ref[0])[_HALO - _TAIL:]
    return jnp.where(is_start, 0.0, tail)


# ---------------------------------------------------------------------------------
# deltanet_qkv: kernels
# ---------------------------------------------------------------------------------


def _qkv_fwd_kernel(p: _Plan, x_ref, halo_ref, w_ref, q_ref, k_ref, v_ref):
    dk, dv, dt = p.dk, p.dv, q_ref.dtype
    y, sig, _ = _conv_silu(_f32(x_ref[0]), _halo_tail(halo_ref, pl.program_id(2) == 0),
                           w_ref, p.K)
    a = y * sig
    q, k = a[:, :dk], a[:, dk:2 * dk]
    q = (q * (lax.rsqrt(_rowsum(q * q) + _L2_EPS) * dk ** -0.5)).astype(dt)
    k = (k * lax.rsqrt(_rowsum(k * k) + _L2_EPS)).astype(dt)
    for j in range(p.r):                # the key head's repetition: r writes of one tile
        q_ref[0, 0, j] = q
        k_ref[0, 0, j] = k
        v_ref[0, 0, j] = a[:, 2 * dk + j * dv:2 * dk + (j + 1) * dv].astype(dt)


def _qkv_bwd_kernel(p: _Plan, x_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref,
                    dx_ref, dw_ref, head_ref):
    """Tiles run last to first (the index maps reverse ``program_id(2)``):
    ``head_ref`` holds the convolution's cotangent on the first 8 rows of the
    tile the step before handled, the rows after this one."""
    K, dk, T = p.K, p.dk, p.tile
    i, tiles = pl.program_id(2), pl.num_programs(2)

    @pl.when(i == 0)
    def _():
        head_ref[...] = jnp.zeros_like(head_ref)

    @pl.when(jnp.logical_and(i == 0, pl.program_id(1) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def norm_bwd(x, g):
        """``d/dx`` of ``x * rsqrt(sum(x^2) + eps)`` applied to ``g``."""
        inv = lax.rsqrt(_rowsum(x * x) + _L2_EPS)
        xn = x * inv
        return inv * (g - xn * _rowsum(g * xn))

    def group(ref):                     # the repetition's transpose: a sum in VMEM
        out = _f32(ref[0, 0, 0])
        for j in range(1, p.r):
            out = out + _f32(ref[0, 0, j])
        return out

    # the sequence's first rows are the grid's last tile
    y, sig, taps = _conv_silu(_f32(x_ref[0]), _halo_tail(halo_ref, i == tiles - 1), w_ref, K)
    a = y * sig
    da = jnp.concatenate(
        [norm_bwd(a[:, :dk], group(dq_ref) * dk ** -0.5), norm_bwd(a[:, dk:2 * dk], group(dk_ref))]
        + [_f32(dv_ref[0, 0, j]) for j in range(p.r)], axis=1)
    dy = da * (sig * (1.0 + y * (1.0 - sig)))
    for j in range(K):
        dw_ref[j:j + 1, :] += jnp.sum(dy * taps[j], axis=0, keepdims=True)
    ahead = jnp.concatenate([dy, head_ref[...]], axis=0)
    dx = dy * w_ref[K - 1:K, :]
    for j in range(K - 1):              # dx[t] = sum_j w[j] dy[t + (K-1) - j]
        dx = dx + ahead[K - 1 - j:K - 1 - j + T] * w_ref[j:j + 1, :]
    dx_ref[0] = dx.astype(dx_ref.dtype)
    head_ref[...] = dy[:_TAIL]


_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 3)


def _qkv_specs(p: _Plan, row):
    """Block specs on the grid ``(key head, batch, tile)``; ``row(i)`` is the tile."""
    per = p.tile // _HALO
    cols = pl.BlockSpec((1, p.tile, p.G), lambda h, b, i: (b, row(i), h))
    halo = pl.BlockSpec((1, _HALO, p.G), lambda h, b, i: (b, jnp.maximum(row(i) * per - 1, 0), h))
    filt = pl.BlockSpec((_TAIL, p.G), lambda h, b, i: (0, h))
    heads = lambda d: pl.BlockSpec((1, 1, p.r, p.tile, d), lambda h, b, i: (b, h, 0, row(i), 0))
    return cols, halo, filt, heads(p.dk), heads(p.dv)


@functools.partial(jax.jit, static_argnames=("p",))
def _qkv_fwd(cols, filt8, p: _Plan):
    B, S, _ = cols.shape
    cspec, hspec, fspec, kspec, vspec = _qkv_specs(p, lambda i: i)
    out = lambda d: jax.ShapeDtypeStruct((B, p.Hk, p.r, S, d), cols.dtype)
    q, k, v = pl.pallas_call(
        functools.partial(_qkv_fwd_kernel, p),
        grid=(p.Hk, B, S // p.tile),
        in_specs=[cspec, hspec, fspec],
        out_specs=[kspec, kspec, vspec],
        out_shape=[out(p.dk), out(p.dk), out(p.dv)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * 3),
        interpret=_interpret_default(),
        name="deltanet_qkv_fwd",
    )(cols, cols, filt8)
    return tuple(t.reshape(B, p.Hk * p.r, S, t.shape[-1]) for t in (q, k, v))


@functools.partial(jax.jit, static_argnames=("p",))
def _qkv_bwd(cols, filt8, dq, dk, dv, p: _Plan):
    B, S, _ = cols.shape
    tiles = S // p.tile
    cspec, hspec, fspec, kspec, vspec = _qkv_specs(p, lambda i: tiles - 1 - i)
    grouped = lambda t: t.astype(cols.dtype).reshape(B, p.Hk, p.r, S, t.shape[-1])
    return pl.pallas_call(
        functools.partial(_qkv_bwd_kernel, p),
        grid=(p.Hk, B, tiles),
        in_specs=[cspec, hspec, fspec, kspec, kspec, vspec],
        out_specs=[cspec, fspec],
        out_shape=[jax.ShapeDtypeStruct(cols.shape, cols.dtype),
                   jax.ShapeDtypeStruct(filt8.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((_TAIL, p.G), _F32)],
        compiler_params=_SEQUENTIAL,
        interpret=_interpret_default(),
        name="deltanet_qkv_bwd",
    )(cols, cols, filt8, grouped(dq), grouped(dk), grouped(dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _qkv_pallas(cols, filt8, p: _Plan):
    return _qkv_fwd(cols, filt8, p=p)


def _qkv_pallas_fwd(cols, filt8, p):
    return _qkv_fwd(cols, filt8, p=p), (cols, filt8)


def _qkv_pallas_bwd(p, res, cts):
    return tuple(_qkv_bwd(*res, *cts, p=p))


_qkv_pallas.defvjp(_qkv_pallas_fwd, _qkv_pallas_bwd)


def _filter_rows(filt):
    """``(C, K) -> (8, C)`` float32: a tap a row, so that a kernel reads it along
    the lanes."""
    return jnp.pad(filt.astype(_F32).T, ((0, _TAIL - filt.shape[1]), (0, 0)))


def _probe_qkv(cols, filt8, p):
    """Guard probe: both kernels must build."""
    out, vjp = jax.vjp(lambda c, f: _qkv_pallas(c, f, p), cols, filt8)
    vjp(jax.tree.map(jnp.zeros_like, out))
    return out


# ---------------------------------------------------------------------------------
# deltanet_gate: kernels
# ---------------------------------------------------------------------------------


def _gate_fwd_kernel(eps, silu, o_ref, z_ref, w_ref, y_ref):
    group, _, dv = o_ref.shape[1:]
    for j in range(group):
        lanes = slice(j * dv, (j + 1) * dv)
        o, z = _f32(o_ref[0, j]), _f32(z_ref[0, :, lanes])
        inv = lax.rsqrt(_rowsum(o * o) * (1.0 / dv) + eps)
        y_ref[0, :, lanes] = (o * inv * w_ref[...] * (
            z * lax.logistic(z) if silu else lax.logistic(z))).astype(y_ref.dtype)


def _gate_bwd_kernel(eps, silu, o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref):
    group, _, dv = o_ref.shape[1:]
    w = w_ref[...]

    @pl.when(jnp.logical_and(jnp.logical_and(pl.program_id(0) == 0, pl.program_id(1) == 0),
                             pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for j in range(group):
        lanes = slice(j * dv, (j + 1) * dv)
        o, z, dy = _f32(o_ref[0, j]), _f32(z_ref[0, :, lanes]), _f32(dy_ref[0, :, lanes])
        sig = lax.logistic(z)
        inv = lax.rsqrt(_rowsum(o * o) * (1.0 / dv) + eps)
        xn = o * inv
        da = dy * (z * sig if silu else sig)
        dz_ref[0, :, lanes] = (dy * (xn * w) * (
            sig * (1.0 + z * (1.0 - sig)) if silu else sig * (1.0 - sig))).astype(dz_ref.dtype)
        dw_ref[...] += jnp.sum(da * xn, axis=0, keepdims=True)
        dn = da * w
        do_ref[0, j] = (inv * (dn - xn * (_rowsum(dn * xn) * (1.0 / dv)))).astype(do_ref.dtype)


def _gate_specs(group, tile, dv):
    """Block specs on the grid ``(batch, tile, head group)``."""
    heads = pl.BlockSpec((1, group, tile, dv), lambda b, i, h: (b, h, i, 0))
    cols = pl.BlockSpec((1, tile, group * dv), lambda b, i, h: (b, i, h))
    weight = pl.BlockSpec((1, dv), lambda b, i, h: (0, 0))
    return heads, cols, weight


# ``group`` heads and ``tile`` rows a grid step, and whether the gate is SiLU (else
# the sigmoid): static, the key of the ``jax.jit``
_GATE_STATICS = ("group", "tile", "eps", "silu")


@functools.partial(jax.jit, static_argnames=_GATE_STATICS)
def _gate_fwd(o, z, w, group: int, tile: int, eps: float, silu: bool = True):
    B, H, S, dv = o.shape
    heads, cols, weight = _gate_specs(group, tile, dv)
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, eps, silu),
        grid=(B, S // tile, H // group),
        in_specs=[heads, cols, weight],
        out_specs=cols,
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * 3),
        interpret=_interpret_default(),
        name="deltanet_gate_fwd",
    )(o, z, w)


@functools.partial(jax.jit, static_argnames=_GATE_STATICS)
def _gate_bwd(o, z, w, dy, group: int, tile: int, eps: float, silu: bool = True):
    B, H, S, dv = o.shape
    heads, cols, weight = _gate_specs(group, tile, dv)
    return pl.pallas_call(
        functools.partial(_gate_bwd_kernel, eps, silu),
        grid=(B, S // tile, H // group),
        in_specs=[heads, cols, weight, cols],
        out_specs=[heads, cols, weight],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype), jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct(w.shape, _F32)],
        compiler_params=_SEQUENTIAL,
        interpret=_interpret_default(),
        name="deltanet_gate_bwd",
    )(o, z, w, dy.astype(z.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gate_pallas(o, z, w, group: int, tile: int, eps: float, silu: bool):
    return _gate_fwd(o, z, w, group=group, tile=tile, eps=eps, silu=silu)


def _gate_pallas_fwd(o, z, w, group, tile, eps, silu=True):
    return _gate_fwd(o, z, w, group=group, tile=tile, eps=eps, silu=silu), (o, z, w)


def _gate_pallas_bwd(group, tile, eps, silu, res, dy):
    return tuple(_gate_bwd(*res, dy, group=group, tile=tile, eps=eps, silu=silu))


_gate_pallas.defvjp(_gate_pallas_fwd, _gate_pallas_bwd)


def _probe_gate(o, z, w, group, tile, eps, silu):
    """Guard probe: both kernels must build."""
    y, vjp = jax.vjp(lambda *a: _gate_pallas(*a, group, tile, eps, silu), o, z, w)
    vjp(jnp.zeros_like(y))
    return y


# ---------------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------------


def deltanet_qkv(cols: jax.Array, filt: jax.Array, *, key_heads: int, value_heads: int,
                 d_k: int, d_v: int, impl: Optional[str] = None):
    """The projection's convolved columns to the delta rule's ``q, k, v``.

    ``cols``: ``(B, S, C)``, ``C = 2 H_k d_k + H_v d_v``, **by key head**
    (:func:`by_key_head`); ``filt``: ``(C, K)`` in the same order. Returns ``q, k
    (B, H_v, S, d_k)`` and ``v (B, H_v, S, d_v)`` in ``cols``'s dtype: the causal
    depthwise convolution (``y[t] = sum_j filt[:, j] x[t - (K-1) + j]``, zeros
    before the start), SiLU, ``q`` and ``k`` L2-normalised over the head (``q``
    times ``d_k^-1/2``) and repeated over the value heads a key head serves."""
    B, S, C = cols.shape
    K = filt.shape[-1]
    r = value_heads // max(key_heads, 1)
    if key_heads * r != value_heads or C != 2 * key_heads * d_k + value_heads * d_v \
            or filt.shape != (C, K):
        raise ValueError(
            f"deltanet_qkv shapes mismatch: cols {cols.shape} filt {filt.shape} for "
            f"{key_heads} key heads of {d_k}, {value_heads} value heads of {d_v}")
    p = _Plan(key_heads, r, d_k, d_v, K, _row_tile(S))
    impl, forced = _dispatch(
        "deltanet_qkv", impl, is_kernel_available(S, d_k, d_v, K),
        f"S {S} is not whole tiles of {_ROW_TILES[-1]} rows, d_k {d_k} / d_v {d_v} not "
        f"multiples of {_LANES}, or the filter's width {K} is over {MAX_FILTER_WIDTH}",
        cols, filt, statics=(key_heads, value_heads))
    with _span("deltanet_qkv"):
        if impl == "pallas":
            filt8 = _filter_rows(filt)
            if forced or _checked_impl("deltanet_qkv", impl, _probe_qkv, cols, filt8, p) == impl:
                return _qkv_pallas(cols, filt8, p)
        return _qkv_jnp(cols, filt, p)


def deltanet_gate(o: jax.Array, z: jax.Array, weight: jax.Array, *, eps: float,
                  activation: str = "silu", impl: Optional[str] = None) -> jax.Array:
    """``rms_norm(o) * weight * act(z)``, heads first to columns; ``activation``:
    ``"silu"`` (gated DeltaNet) or ``"sigmoid"`` (Kimi Delta Attention).

    ``o``: ``(B, H, S, d_v)`` (the delta rule's output as its scan leaves it),
    ``z``: ``(B, S, H d_v)``, ``weight``: ``(d_v,)``. Returns ``(B, S, H d_v)`` in
    ``z``'s dtype, the RMS norm over each head's ``d_v``."""
    B, H, S, dv = o.shape
    if z.shape != (B, S, H * dv) or weight.shape != (dv,):
        raise ValueError(
            f"deltanet_gate shapes mismatch: o {o.shape} z {z.shape} weight {weight.shape}")
    if activation not in ("silu", "sigmoid"):
        raise ValueError(f"activation must be 'silu' or 'sigmoid', got {activation!r}")
    statics = (next(g for g in (4, 2, 1) if H % g == 0),    # heads a grid step: 512 lanes at 128
               _row_tile(S), float(eps), activation == "silu")
    impl, forced = _dispatch(
        "deltanet_gate", impl, is_kernel_available(S, dv, dv),
        f"S {S} is not whole tiles of {_ROW_TILES[-1]} rows or d_v {dv} not a multiple "
        f"of {_LANES}", o, z, statics=(activation,))
    with _span("deltanet_gate"):
        if impl == "pallas":
            w = weight.astype(_F32).reshape(1, dv)
            if forced or _checked_impl("deltanet_gate", impl, _probe_gate, o, z, w, *statics) == impl:
                return _gate_pallas(o, z, w, *statics)
        return _gate_jnp(o, z, weight, eps, activation)
