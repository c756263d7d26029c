"""The double-gated short convolution of an LFM2 mixer: one fused pass each way.

Between its two projections an LFM2 convolution mixer is elementwise work on
``(S, 3 D)`` activations: ``[B | C | x~] = u W_in``, ``z = B * x~``, a causal
depthwise convolution of ``K`` taps over ``z`` and the gate ``C``. There is no
activation function; the two gates are the non-linearity. As a chain of
``jax.numpy`` calls XLA makes several fusions of it, each a pass over HBM, and as
many again of its transpose (what ``ops/deltanet.py`` found of the like chain of
a DeltaNet layer: PERF.md, PR 37). Here it is one Pallas kernel forward and one
backward behind a ``jax.custom_vjp`` whose residuals are the kernel's inputs:

* forward: reads ``bcx`` once, writes ``y = C * conv_K(B * x~)`` once;
* backward: reads ``bcx``, ``w`` and ``dy``, recomputes ``z`` and the
  convolution, writes ``d bcx`` once (its three parts side by side, one block)
  and accumulates ``dw`` in float32 across the grid.

Everything between a kernel's read and its write is float32 and is rounded
once, where it reaches HBM. A grid step holds one row tile at the full width
``3 D`` as whole arrays (``ops/deltanet.py``'s finding: Mosaic schedules such a
chain as well as a hand-cut one), the tile sized so that ``rows x D`` stays near
256 K elements. The ``K - 1`` rows of ``z`` before a tile: the forward kernel
walks a sequence first to last and keeps the last rows of the tile before in
VMEM; the backward kernel walks last to first, reads them as a halo block
(zeros before row 0) and keeps the convolution's cotangent on the first rows of
the tile the step before handled, the rows *after* this one
(``ops/deltanet.py``'s scheme at these taps, without its norms and layouts).

``impl="jnp"`` is the plain chain with that chain's roundings (``z`` and the
convolution's output are rounded to the activation dtype): the parity oracle,
the off-TPU default and what a shape the kernels do not take runs.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from beforeholiday_tpu.guard.dispatch import checked_impl as _checked_impl
from beforeholiday_tpu.monitor.counters import book_tiles as _book_tiles
from beforeholiday_tpu.monitor.spans import span as _span
from beforeholiday_tpu.ops._pallas_util import (
    dispatch as _dispatch,
    interpret_default as _interpret_default,
)

__all__ = ["gated_short_conv", "is_kernel_available"]

_F32 = jnp.float32
_LANES = 128
_HALO = 16              # rows of a halo block: one bfloat16 sublane tile
_TAIL = 8               # rows a tile takes from its neighbour: one float32 sublane tile
MAX_FILTER_WIDTH = _TAIL    # a filter reaches width - 1 <= 7 rows back
_ROW_TILES = (512, 256, 128, 64, 32, 16)
_TILE_ELEMENTS = 2 ** 18    # rows x D a grid step: 128 rows at D = 2048
_VMEM_LIMIT = 64 * 2 ** 20  # the backward step holds ~20 float32 tiles of it


class _Plan(NamedTuple):
    """What the kernels are built for (static: the key of their ``jax.jit``)."""
    D: int              # channels: a third of the input's columns
    K: int              # filter width
    tile: int           # rows a grid step


def _row_tile(S: int, D: int) -> Optional[int]:
    return next((t for t in _ROW_TILES if S % t == 0 and t * D <= _TILE_ELEMENTS), None)


def is_kernel_available(S: int, D: int, filter_width: int = 1) -> bool:
    """Shape gate of both kernels: channels that fill the lanes, a sequence of
    whole row tiles, a filter that reaches no further back than one float32
    sublane tile."""
    return (D % _LANES == 0 and _row_tile(S, D) is not None
            and 1 <= filter_width <= MAX_FILTER_WIDTH)


# ---------------------------------------------------------------------------------
# the jnp chain: oracle and fallback
# ---------------------------------------------------------------------------------


def _chain(bcx, w):
    Bm, C, x = jnp.split(bcx, 3, axis=-1)
    z = Bm * x
    K, S = w.shape[-1], z.shape[1]
    zp = jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(_F32)
    conv = sum(zp[:, j:j + S].astype(_F32) * wf[:, j] for j in range(K)).astype(z.dtype)
    return C * conv


# ---------------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------------


def _f32(x):
    return x if x.dtype == _F32 else x.astype(_F32)


def _taps(z, before, K):
    """``z[t - (K-1) + j]`` for ``j = 0 .. K-1``: ``z (T, D)`` float32 behind the
    8 rows ``before`` it (summed oldest first, as the chain sums them)."""
    T = z.shape[0]
    window = jnp.concatenate([before, z], axis=0)
    return [window[_TAIL - (K - 1) + j:_TAIL - (K - 1) + j + T] for j in range(K - 1)] + [z]


def _conv(taps, w_ref):
    out = taps[0] * w_ref[0:1, :]
    for j in range(1, len(taps)):
        out = out + taps[j] * w_ref[j:j + 1, :]
    return out


def _fwd_kernel(p: _Plan, x_ref, w_ref, y_ref, tail_ref):
    """A sequence's tiles run first to last: ``tail_ref`` holds ``z`` on the
    last 8 rows of the tile before."""
    D = p.D

    @pl.when(pl.program_id(1) == 0)
    def _():
        tail_ref[...] = jnp.zeros_like(tail_ref)

    z = _f32(x_ref[0, :, 0:D]) * _f32(x_ref[0, :, 2 * D:3 * D])
    conv = _conv(_taps(z, tail_ref[...], p.K), w_ref)
    y_ref[0] = (_f32(x_ref[0, :, D:2 * D]) * conv).astype(y_ref.dtype)
    tail_ref[...] = z[p.tile - _TAIL:]


def _bwd_kernel(p: _Plan, x_ref, hb_ref, hx_ref, w_ref, dy_ref, dx_ref, dw_ref, head_ref):
    """Tiles run last to first (the index maps reverse ``program_id(1)``):
    ``head_ref`` holds the convolution's cotangent on the first 8 rows of the
    tile the step before handled, the rows after this one."""
    D, K, T = p.D, p.K, p.tile
    i, tiles = pl.program_id(1), pl.num_programs(1)

    @pl.when(i == 0)
    def _():
        head_ref[...] = jnp.zeros_like(head_ref)

    @pl.when(jnp.logical_and(i == 0, pl.program_id(0) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    b, c, x = (_f32(x_ref[0, :, k * D:(k + 1) * D]) for k in range(3))
    # the sequence's first rows are the grid's last tile: zeros before them
    before = _f32(hb_ref[0])[_HALO - _TAIL:] * _f32(hx_ref[0])[_HALO - _TAIL:]
    taps = _taps(b * x, jnp.where(i == tiles - 1, 0.0, before), K)
    dy = _f32(dy_ref[0])
    dx_ref[0, :, D:2 * D] = (dy * _conv(taps, w_ref)).astype(dx_ref.dtype)
    dc = dy * c
    for j in range(K):
        dw_ref[j:j + 1, :] += jnp.sum(dc * taps[j], axis=0, keepdims=True)
    ahead = jnp.concatenate([dc, head_ref[...]], axis=0)
    dz = dc * w_ref[K - 1:K, :]
    for j in range(K - 1):              # dz[t] = sum_j w[j] dc[t + (K-1) - j]
        dz = dz + ahead[K - 1 - j:K - 1 - j + T] * w_ref[j:j + 1, :]
    dx_ref[0, :, 0:D] = (dz * x).astype(dx_ref.dtype)
    dx_ref[0, :, 2 * D:3 * D] = (dz * b).astype(dx_ref.dtype)
    head_ref[...] = dc[:_TAIL]


def _book(kernel: str, bcx, p: _Plan):
    steps = bcx.shape[0] * (bcx.shape[1] // p.tile)
    _book_tiles("short_conv", kernel, tuple(bcx.shape) + (str(bcx.dtype),) + tuple(p),
                total=steps, live=steps, masked=0)


# Each kernel call is a ``jax.jit`` function, as in ``ops/deltanet.py``: the
# mixers of a model that share their shapes are traced and lowered once a step.

@functools.partial(jax.jit, static_argnames=("p",))
def _fwd(bcx, w8, p: _Plan):
    B, S, _ = bcx.shape
    _book("fwd", bcx, p)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p),
        grid=(B, S // p.tile),
        in_specs=[pl.BlockSpec((1, p.tile, 3 * p.D), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((_TAIL, p.D), lambda b, i: (0, 0))],
        out_specs=pl.BlockSpec((1, p.tile, p.D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, p.D), bcx.dtype),
        scratch_shapes=[pltpu.VMEM((_TAIL, p.D), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
        name="short_conv_fwd",
    )(bcx, w8)


@functools.partial(jax.jit, static_argnames=("p",))
def _bwd(bcx, w8, dy, p: _Plan):
    B, S, _ = bcx.shape
    tiles, per = S // p.tile, p.tile // _HALO
    _book("bwd", bcx, p)
    row = lambda i: tiles - 1 - i
    halo = lambda part: pl.BlockSpec(
        (1, _HALO, p.D), lambda b, i: (b, jnp.maximum(row(i) * per - 1, 0), part))
    cols = pl.BlockSpec((1, p.tile, 3 * p.D), lambda b, i: (b, row(i), 0))
    filt = pl.BlockSpec((_TAIL, p.D), lambda b, i: (0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p),
        grid=(B, tiles),
        in_specs=[cols, halo(0), halo(2), filt,
                  pl.BlockSpec((1, p.tile, p.D), lambda b, i: (b, row(i), 0))],
        out_specs=[cols, filt],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct(w8.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((_TAIL, p.D), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
        name="short_conv_bwd",
    )(bcx, bcx, bcx, w8, dy.astype(bcx.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pallas(bcx, w8, p: _Plan):
    return _fwd(bcx, w8, p=p)


def _pallas_fwd(bcx, w8, p):
    return _fwd(bcx, w8, p=p), (bcx, w8)


def _pallas_bwd(p, res, dy):
    return tuple(_bwd(*res, dy, p=p))


_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def _filter_rows(w):
    """``(D, K) -> (8, D)`` float32: a tap a row, so that a kernel reads it along
    the lanes."""
    return jnp.pad(w.astype(_F32).T, ((0, _TAIL - w.shape[1]), (0, 0)))


def _probe(bcx, w8, p):
    """Guard probe: both kernels must build."""
    y, vjp = jax.vjp(lambda a, f: _pallas(a, f, p), bcx, w8)
    vjp(jnp.zeros_like(y))
    return y


# ---------------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------------


def gated_short_conv(bcx: jax.Array, w: jax.Array, *, impl: Optional[str] = None) -> jax.Array:
    """``C * conv_K(B * x~)``: the in-projection's columns to the out-projection's
    operand.

    ``bcx``: ``(B, S, 3 D)``, the columns ``[B | C | x~]`` in that order;
    ``w``: ``(D, K)``. Returns ``(B, S, D)`` in ``bcx``'s dtype: ``z = B * x~``,
    the causal depthwise convolution ``c[t] = sum_j w[:, j] z[t - (K-1) + j]``
    (zeros before the start), ``y = C * c``. ``impl``: ``None`` takes the Pallas
    kernels where the traced program owns its device and the shape is theirs
    (:func:`is_kernel_available`), else the ``jnp`` chain, counted by
    ``guard.dispatch``; ``"pallas"`` / ``"jnp"`` force one."""
    B, S, C3 = bcx.shape
    D, K = w.shape
    if C3 != 3 * D:
        raise ValueError(f"gated_short_conv shapes mismatch: bcx {bcx.shape} is not "
                         f"(B, S, 3 x {D}) for a filter {w.shape}")
    impl, forced = _dispatch(
        "short_conv", impl, is_kernel_available(S, D, K),
        f"S {S} is not whole tiles of {_ROW_TILES[-1]} rows, D {D} not a multiple of "
        f"{_LANES}, or the filter's width {K} is over {MAX_FILTER_WIDTH}", bcx, w, statics=())
    with _span("short_conv"):
        if impl == "pallas":
            p, w8 = _Plan(D, K, _row_tile(S, D)), _filter_rows(w)
            if forced or _checked_impl("short_conv", impl, _probe, bcx, w8, p) == impl:
                return _pallas(bcx, w8, p)
        return _chain(bcx, w)
