"""Grouped matmul — the experts' products over rows sorted by expert.

``grouped_matmul(lhs (R, K), rhs (E, K, N), group_sizes (E,))`` multiplies the
first ``group_sizes[0]`` rows of ``lhs`` by ``rhs[0]``, the next
``group_sizes[1]`` by ``rhs[1]`` and so on: ``jax.lax.ragged_dot``'s contract,
rows past the last group's end left unspecified in the result and in the
cotangent of ``lhs`` (the caller masks both sides, as ``moe/dropless.py`` does).

XLA's own grouped kernel streams an expert's ``(K, N)`` panel again for every
few rows and ran at 17 % of the MXU's peak in both 8k cells (PERF.md, PRs 30 and
31). The Pallas kernels here walk the row tiles in order (MegaBlocks, Gale et
al. 2022; see PAPERS.md): a table built on the device from ``group_sizes`` and
prefetched to scalar memory gives each grid step its row tile and its expert. A
tile that two experts share is visited once for each and masked by row, so no
padded copy of the rows is ever made. The panel's block index is the expert:
the pipeline fetches it when the expert changes and it stays in VMEM while that
expert's row tiles go by. Grid steps past the last group's end are clamped to
the last visit: they fetch nothing and compute nothing.

* ``grouped_matmul_fwd`` and ``grouped_matmul_dlhs`` are one body (rows x
  panel; ``d lhs`` contracts the cotangent with the same panel along its other
  axis, so no transposed copy of the weights is made either).
* ``grouped_matmul_drhs`` (rows^T x rows) keeps an expert's ``(K, N)`` float32
  accumulator in VMEM while its row tiles go by and writes it once, when the
  expert changes; an expert with no rows writes zeros.

Operands keep their dtype (bfloat16 or float32, both sides alike), every
product accumulates in float32, and a cotangent enters the backward kernels in
the operands' dtype: what XLA's default precision makes of ``ragged_dot``'s
transposes on this chip. Row tile and panel split come from :func:`plan`, a
function of what the call can see and of nothing else.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from beforeholiday_tpu.guard.dispatch import (
    checked_impl as _checked_impl,
    count_tiles as _count_tiles,
)
from beforeholiday_tpu.ops._pallas_util import (
    dispatch as _dispatch,
    interpret_default as _interpret_default,
)

__all__ = ["Plan", "grouped_matmul", "is_kernel_available", "plan"]

_F32 = jnp.float32
_I32 = jnp.int32
_LANES = 128
_ROW_TILES = (512, 256, 128)
# what a kernel's blocks (each double-buffered by the pipeline), its float32
# accumulator and the body's temporaries may take of the chip's 128 MiB of VMEM
_VMEM_BUDGET = 48 * 2 ** 20
_VMEM_LIMIT = 96 * 2 ** 20


class Plan(NamedTuple):
    """The tiling of one call: ``tm`` rows a grid step, ``tn`` columns of the
    panel a pass of the outer grid axis holds (``splits`` passes), ``steps``
    grid steps a pass: the row tiles of the buffer plus the ``E - 1`` visits a
    tile shared by two experts can add."""
    tm: int
    tn: int
    splits: int
    steps: int


def _itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _vmem_bytes(kernel: str, tm: int, tn: int, width: int, dtype, out_dtype) -> int:
    """Bytes of VMEM one grid step holds: ``width`` is the contracted dimension
    of the rows x panel kernels, ``K`` of the accumulator of ``drhs``."""
    b, bo = _itemsize(dtype), _itemsize(out_dtype)
    if kernel == "drhs":
        return 2 * tm * (width + tn) * b + 2 * width * tn * bo + 2 * width * tn * 4
    return 2 * tm * width * b + 2 * width * tn * b + 2 * tm * tn * bo + tm * tn * 4


def plan(kernel: str, R: int, E: int, K: int, N: int, dtype, out_dtype) -> Optional[Plan]:
    """The tile plan of ``kernel`` (``"fwd"``, ``"dlhs"`` or ``"drhs"``) for
    ``R`` rows in ``E`` groups against ``(K, N)`` panels, or ``None`` where no
    plan fits :data:`_VMEM_BUDGET`.

    The row tile follows the rows an expert can expect, ``R / E`` as the
    buffer's bound allows: every expert's edge costs one more visit of a whole
    tile, and a kernel's code grows with its tile, so tiles stay under an eighth
    of an expert's share (at most 512 rows, at least 128: the MXU's own edge;
    at the 8k cells' shapes 128 and 256 rows run alike and 512 slower: PERF.md,
    PR 32). The panel is held whole where it fits;
    otherwise its output dimension is halved, and the outer grid axis walks the
    parts, so that a part still changes only at an expert's edge."""
    width, out = (N, K) if kernel == "dlhs" else (K, N)
    tm = next((t for t in _ROW_TILES if 8 * t <= R // E), _ROW_TILES[-1])
    while True:
        tn = out
        while tn % _LANES == 0:
            if _vmem_bytes(kernel, tm, tn, width, dtype, out_dtype) <= _VMEM_BUDGET:
                return Plan(tm, tn, out // tn, pl.cdiv(R, tm) + E - 1)
            if tn % (2 * _LANES):
                break
            tn //= 2
        if tm == _ROW_TILES[-1]:
            return None
        tm //= 2


def is_kernel_available(R: int, E: int, K: int, N: int, dtype, rhs_dtype=None,
                        out_dtype=None) -> bool:
    """Shape gate of the three kernels: both operands bfloat16 or both float32,
    ``K`` and ``N`` whole lane tiles, a row, a group, and a plan of each kernel in
    the VMEM budget. No size gate: at both 8k cells' shapes (164 and 1,017 rows an
    expert) every kernel beats ``lax.ragged_dot`` 1.3 to 4.5 times (PERF.md, PR 32)."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return False
    if rhs_dtype is not None and jnp.dtype(rhs_dtype) != dtype:
        return False
    if K % _LANES or N % _LANES or R < 1 or E < 1:
        return False
    out_dtype = dtype if out_dtype is None else out_dtype
    return (plan("fwd", R, E, K, N, dtype, out_dtype) is not None
            and plan("dlhs", R, E, K, N, dtype, dtype) is not None
            and plan("drhs", R, E, K, N, dtype, dtype) is not None)


# ---------------------------------------------------------------------------------
# the visit table
# ---------------------------------------------------------------------------------

# what a grid step does, as bits of ``flags[t]``; a dead step's flags are zero
_ROWS = 1       # the tile holds rows of the group: multiply them, masked by row
_OPENS = 2      # the group's first visit (``drhs`` clears its accumulator)
_CLOSES = 4     # the group's last visit (``drhs`` writes its accumulator)


@functools.partial(jax.jit, static_argnames=("R", "tm", "steps", "visit_empty"))
def _visits(group_sizes, R: int, tm: int, steps: int, *, visit_empty: bool):
    """``(flags, lo, hi, group, tile)``, int32 ``(steps,)`` each: grid step ``t``
    works on rows ``tile[t] * tm ..`` for group ``group[t]``, whose rows are
    ``lo[t] .. hi[t]``, and does what ``flags[t]`` says. A group's visits are
    consecutive and so are a tile's. Steps past the last visit repeat it with
    no flag set: their blocks are the ones already in VMEM, and nothing is
    computed. ``visit_empty`` gives a group of no rows one visit (``drhs``
    writes its zeros there: the flags open and close it and nothing else);
    otherwise it has none. A ``jax.jit`` function of the row tile alone, so that
    the kernels of a layer that share it trace the table once."""
    E = group_sizes.shape[0]
    sizes = group_sizes.astype(_I32)
    ends = jnp.cumsum(sizes)
    first = jnp.minimum((ends - sizes) // tm, pl.cdiv(R, tm) - 1)
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, int(visit_empty))
    stop = jnp.cumsum(count)
    steps = jnp.arange(steps, dtype=_I32)
    t = jnp.minimum(steps, jnp.maximum(stop[-1] - 1, 0))
    group = jnp.minimum(jnp.searchsorted(stop, t, side="right"), E - 1).astype(_I32)
    nth = t - (stop - count)[group]                 # which of the group's visits
    flags = (_ROWS * (sizes[group] > 0) + _OPENS * (nth == 0)
             + _CLOSES * (nth == count[group] - 1))
    return (jnp.where(steps < stop[-1], flags, 0).astype(_I32), (ends - sizes)[group],
            ends[group], group, (first[group] + nth).astype(_I32))


# ---------------------------------------------------------------------------------
# the kernels (bodies in ``lax``: PERF.md, PRs 28 and 30, on what a body's trace costs)
# ---------------------------------------------------------------------------------
#
# Every visit is masked by row, the ones that hold a group's rows alone too: the
# selects ride beside the MXU's work, and a second, mask-free copy of the product
# doubled the kernels' code (the step's executable is loaded in ``setup_s``).


def _does(flags, bit):
    return lax.ne(lax.bitwise_and(flags, _I32(bit)), _I32(0))


def _mine(shape, row0, lo, hi):
    """By element of ``shape`` (rows first, the first being row ``row0`` of the
    buffer): ``(rows of lo .. hi, rows before lo)``."""
    rows = lax.add(lax.broadcasted_iota(_I32, shape, 0), row0)
    return lax.bitwise_and(lax.ge(rows, lo), lax.lt(rows, hi)), lax.lt(rows, lo)


def _gmm_kernel(flags, lo, hi, group, tile, lhs_ref, rhs_ref, out_ref, *, tm, dims):
    t = pl.program_id(1)

    @pl.when(_does(flags[t], _ROWS))
    def _():
        acc = lax.dot_general(lhs_ref[...], rhs_ref[...], (dims, ((), ())),
                              preferred_element_type=_F32)
        acc = lax.convert_element_type(acc, out_ref.dtype)
        # rows of an earlier group were written by its visit, the step before;
        # rows of a later group, or of none, read zero until theirs
        mine, before = _mine(acc.shape, lax.mul(tile[t], _I32(tm)), lo[t], hi[t])
        rest = lax.select(before, out_ref[...], lax.full_like(acc, 0))
        out_ref[...] = lax.select(mine, acc, rest)


def _tgmm_kernel(flags, lo, hi, group, tile, lhs_ref, ct_ref, out_ref, acc_ref, *, tm):
    t = pl.program_id(1)
    f = flags[t]

    @pl.when(_does(f, _OPENS))
    def _():
        acc_ref[...] = lax.full(acc_ref.shape, 0.0, _F32)

    @pl.when(_does(f, _ROWS))
    def _():
        # both sides cut: what lies in another group's rows, or in none, may be anything
        cut = lambda x: lax.select(
            _mine(x.shape, lax.mul(tile[t], _I32(tm)), lo[t], hi[t])[0], x, lax.full_like(x, 0))
        acc_ref[...] = lax.add(acc_ref[...], lax.dot_general(
            cut(lhs_ref[...]), cut(ct_ref[...]), (((0,), (0,)), ((), ())),
            preferred_element_type=_F32))

    @pl.when(_does(f, _CLOSES))
    def _():
        out_ref[...] = lax.convert_element_type(acc_ref[...], out_ref.dtype)


def _book(kernel, p: Plan, R, E, K, N, dtype):
    _count_tiles("grouped_matmul", kernel, (R, E, K, N, str(jnp.dtype(dtype)), p.tm, p.tn),
                 total=p.splits * p.steps, live=p.splits * pl.cdiv(R, p.tm),
                 masked=p.splits * (E - 1))


def _at_tile(split: bool):
    """Index map of a ``(tm, .)`` block of rows: the step's tile, and the pass's
    part of the columns (``split``) or all of them."""
    return lambda n, t, flags, lo, hi, group, tile: (tile[t], n if split else 0)


def _at_group(axis: int):
    """Index map of the step's expert's panel, the pass's part along ``axis``."""
    return lambda n, t, flags, lo, hi, group, tile: (
        (group[t], n, 0) if axis == 1 else (group[t], 0, n))


def _call(kernel, body, p: Plan, in_specs, out_spec, out_shape, width, dtype, scratch=()):
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,      # what ``_visits`` returns
            grid=(p.splits, p.steps),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(_VMEM_LIMIT, 16 * 2 ** 20 + 2 * _vmem_bytes(
                kernel, p.tm, p.tn, width, dtype, out_shape.dtype))),
        interpret=_interpret_default(),
        name=f"grouped_matmul_{kernel}",
    )


# Each kernel call is a ``jax.jit`` function: the products of a layer that share
# their shapes (``w_gate`` and ``w_up``; every layer of a model) are traced once
# and lowered to Mosaic once a step program, not once a call site (36 lowerings
# of the Mellum cell's step cost 4 s of ``setup_s``: PERF.md, PR 32).


@functools.partial(jax.jit, static_argnames=("out_dtype", "transpose_rhs"))
def _gmm(lhs, rhs, group_sizes, out_dtype, *, transpose_rhs: bool):
    """Rows x panel: ``lhs (R, K) @ rhs[g] (K, N)`` by group, or with
    ``transpose_rhs`` ``lhs (R, N) @ rhs[g]^T``."""
    R, width = lhs.shape
    E, K, N = rhs.shape
    kernel = "dlhs" if transpose_rhs else "fwd"
    p = plan(kernel, R, E, K, N, lhs.dtype, out_dtype)
    _book(kernel, p, R, E, K, N, lhs.dtype)
    if transpose_rhs:
        out, dims, panel = K, ((1,), (1,)), pl.BlockSpec((None, p.tn, N), _at_group(1))
    else:
        out, dims, panel = N, ((1,), (0,)), pl.BlockSpec((None, K, p.tn), _at_group(2))
    return _call(
        kernel, functools.partial(_gmm_kernel, tm=p.tm, dims=dims), p,
        [pl.BlockSpec((p.tm, width), _at_tile(False)), panel],
        pl.BlockSpec((p.tm, p.tn), _at_tile(True)),
        jax.ShapeDtypeStruct((R, out), out_dtype), width, lhs.dtype,
    )(*_visits(group_sizes, R, p.tm, p.steps, visit_empty=False), lhs, rhs)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def _tgmm(lhs, ct, group_sizes, out_dtype):
    """Rows^T x rows: ``lhs[rows of g]^T (K, r) @ ct[rows of g] (r, N)`` for
    every group ``g``, ``(E, K, N)``."""
    R, K = lhs.shape
    N = ct.shape[1]
    E = group_sizes.shape[0]
    p = plan("drhs", R, E, K, N, lhs.dtype, out_dtype)
    _book("drhs", p, R, E, K, N, lhs.dtype)
    return _call(
        "drhs", functools.partial(_tgmm_kernel, tm=p.tm), p,
        [pl.BlockSpec((p.tm, K), _at_tile(False)), pl.BlockSpec((p.tm, p.tn), _at_tile(True))],
        pl.BlockSpec((None, K, p.tn), _at_group(2)),
        jax.ShapeDtypeStruct((E, K, N), out_dtype), K, lhs.dtype,
        scratch=[pltpu.VMEM((K, p.tn), _F32)],
    )(*_visits(group_sizes, R, p.tm, p.steps, visit_empty=True), lhs, ct)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, group_sizes, out_dtype):
    return _gmm(lhs, rhs, group_sizes, out_dtype, transpose_rhs=False)


def _grouped_fwd(lhs, rhs, group_sizes, out_dtype):
    return _grouped(lhs, rhs, group_sizes, out_dtype), (lhs, rhs, group_sizes)


def _grouped_bwd(out_dtype, res, ct):
    lhs, rhs, group_sizes = res
    ct = ct.astype(lhs.dtype)
    return (_gmm(ct, rhs, group_sizes, lhs.dtype, transpose_rhs=True),
            _tgmm(lhs, ct, group_sizes, rhs.dtype), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def _probe(lhs, rhs, group_sizes, out_dtype):
    """Guard probe: the three kernels must build."""
    out, vjp = jax.vjp(lambda a, b: _grouped(a, b, group_sizes, out_dtype), lhs, rhs)
    vjp(jnp.zeros_like(out))
    return out


# ---------------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------------


def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    group_sizes: jax.Array,
    *,
    preferred_element_type=None,
    impl: Optional[str] = None,
) -> jax.Array:
    """``lhs (R, K)`` times ``rhs (E, K, N)`` by groups of consecutive rows:
    ``(R, N)`` in ``preferred_element_type`` (``lhs``'s dtype if ``None``).

    ``group_sizes (E,)`` integers that sum to at most ``R``. Rows past the last
    group's end are unspecified, in the result and in ``lhs``'s cotangent;
    ``rhs``'s cotangent never reads them. Off the kernels' shapes
    (:func:`is_kernel_available`) and off the TPU the call is
    ``jax.lax.ragged_dot``; ``impl="pallas"`` forced there raises."""
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1] \
            or group_sizes.shape != rhs.shape[:1]:
        raise ValueError(
            f"grouped_matmul shapes mismatch: lhs {lhs.shape} rhs {rhs.shape} "
            f"group_sizes {group_sizes.shape}")
    out_dtype = jnp.dtype(lhs.dtype if preferred_element_type is None
                          else preferred_element_type)
    R, (E, K, N) = lhs.shape[0], rhs.shape
    impl, forced = _dispatch(
        "grouped_matmul", impl,
        is_kernel_available(R, E, K, N, lhs.dtype, rhs.dtype, out_dtype),
        f"lhs {lhs.shape} {lhs.dtype} x rhs {rhs.shape} {rhs.dtype} is off the kernels' "
        f"shapes (K and N multiples of {_LANES}, both operands bfloat16 or both float32)",
        lhs, rhs, statics=(str(out_dtype),))
    if impl == "pallas" and not forced:
        impl = _checked_impl("grouped_matmul", impl, _probe, lhs, rhs, group_sizes, out_dtype)
    if impl == "pallas":
        return _grouped(lhs, rhs, group_sizes, out_dtype)
    return lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=out_dtype)
