"""Rows summed onto their tokens — ``out[token[r]] += rows[r] * scale[r]`` as a
product with a 0/1 matrix.

XLA's scatter-add on this chip is a serial read-modify-write of one ``(1, D)``
float32 row at a time (96-145 ns a row at the 8k cells' widths, no MXU work:
PERF.md, PR 34). The other way to the same sum has two steps, and this module is
both:

1. :func:`token_order` lists the rows that landed **by tile of ``tokens``
   tokens** (a stable sort of ``R`` int32 keys, ``token // tokens``: inside a
   tile the rows keep the buffer's order) and builds the table that walks them
   a tile at a time. A layer's two sums (the combine's forward, the dispatch's
   transpose) have the same token list and share one :class:`TokenOrder`.
2. the caller moves the rows into that order with the gather it already has
   (``moe/dropless.py:_gather_loop``: whole rows, zero from the last landed row
   on), and :func:`segment_sum` sums them: after the move a tile of tokens
   owns a run of consecutive rows, walked
   in aligned chunks of ``rows`` rows; a chunk's contribution to the tile is
   ``onehot @ chunk`` with ``onehot[t, c] = (token_of_row[c] == t0 + t)`` built
   from an iota compare, accumulated in a ``(tokens, D)`` float32 block in VMEM
   and written once. A chunk that straddles two tiles is visited once for each;
   the other tile's rows meet a zero column.

**The arithmetic** is the loop's: every term ``rows[r] * scale[r]`` to float32
accuracy, the sum in float32, one rounding to ``out_dtype`` at the end. The MXU
multiplies bfloat16, so a float32 factor enters as its three bfloat16 parts
(``x = hi + mid + lo`` exactly: 3 x 8 bits of significand), every partial
product exact in float32: bfloat16 rows without a scale take one pass, with a
scale three (the scale rides in the matrix, ``onehot * scale`` in three parts),
float32 rows three and nine. What may differ from the loop is the float32
rounding of the order in which a token's terms (and their parts) are added.
A row at or past ``n_valid`` is listed under a token past every tile and
matches no column; the caller's gather has already replaced whatever it held
(NaN, maybe) by zero, since ``0 * NaN`` on the MXU is NaN.

The grid is the visit table of ``ops/grouped_matmul.py`` with a tile of tokens
as the group: steps past the last visit repeat it and do nothing.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

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
from beforeholiday_tpu.ops.grouped_matmul import (
    _CLOSES,
    _OPENS,
    _ROWS,
    _does,
    _visits,
)

__all__ = ["Plan", "TokenOrder", "is_kernel_available", "plan", "segment_sum", "token_order",
           "unwritten_like"]

_F32, _I32, _BF16 = jnp.float32, jnp.int32, jnp.bfloat16
_LANES = 128
_TOKEN_TILES = (256, 128)
_ROW_CHUNK = 256
# the accumulator, the result's block twice (the pipeline's) and a product's
# temporary are float32 ``(tokens, D)``; the chunk of rows is there twice too
_VMEM_BUDGET = 40 * 2 ** 20
_VMEM_LIMIT = 96 * 2 ** 20


class Plan(NamedTuple):
    """``tokens`` tokens a tile (the result's block), ``rows`` rows a chunk,
    ``steps`` grid steps: the buffer's chunks plus the one more visit each
    tile's edge can add."""
    tokens: int
    rows: int
    steps: int


class TokenOrder(NamedTuple):
    """What :func:`token_order` hands both sums of a layer: integers, and the
    scaled sum's factors where they rode the sort."""
    perm: jax.Array                 # (Rp,) the buffer's row that is listed j-th
    token: jax.Array                # (Rp / rows, 1, rows) its token; past every tile from n_valid on
    visits: Tuple[jax.Array, ...]   # (flags, tile of tokens, chunk), (steps,) each
    scale: Optional[jax.Array] = None   # (Rp / rows, 1, rows) float32, in this order


def _vmem_bytes(tokens: int, rows: int, D: int) -> int:
    return 4 * tokens * D * 4 + 2 * rows * D * 4


def plan(R: int, T: int, D: int) -> Optional[Plan]:
    """The tiling for ``R`` buffer rows of width ``D`` summed onto ``T`` tokens,
    from what the call can see (not the rows' dtype: a layer's sums share one
    :class:`TokenOrder`); ``None`` where no tile fits the VMEM budget."""
    for tokens in _TOKEN_TILES:
        tokens = min(tokens, -(-T // 8) * 8)
        if _vmem_bytes(tokens, _ROW_CHUNK, D) <= _VMEM_BUDGET:
            return Plan(tokens, _ROW_CHUNK, pl.cdiv(R, _ROW_CHUNK) + pl.cdiv(T, tokens) - 1)
    return None


def is_kernel_available(R: int, T: int, D: int, dtype) -> bool:
    """Rows of whole lane tiles, bfloat16 or float32, and a plan in the budget."""
    return (jnp.dtype(dtype) in (jnp.dtype(_BF16), jnp.dtype(_F32)) and D % _LANES == 0
            and R >= 1 and T >= 1 and plan(R, T, D) is not None)


def unwritten_like(rows: jax.Array, shape, dtype) -> jax.Array:
    """``shape`` of ``dtype`` that nobody has written: the result of a
    kernel that stores nothing. For a buffer a loop fills as far as it is read
    (the zero-fill of ``24576 x 2304`` bfloat16 is 0.32 ms, eight times a Mellum
    step). It takes ``rows`` (and touches nothing of it) so that the compiler
    cannot allocate it before ``rows`` exists: ``lax.empty`` — ``AllocateBuffer``
    here — and a kernel without an operand are placed at the head of the program,
    every layer's buffer alive at once: + 0.49 GiB in the Mellum step, 17
    rematerialised ops for 9 in the Qwen step (PERF.md, PR 47). Zeros in the
    interpreter."""
    return pl.pallas_call(
        lambda rows_ref, out_ref: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY), interpret=_interpret_default(),
        name="unwritten")(rows)


# ---------------------------------------------------------------------------------
# token order, once a layer
# ---------------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("out_rows", "p"))
def _order(token, n_valid, scale, out_rows: int, p: Plan) -> TokenOrder:
    R = token.shape[0]
    chunks = pl.cdiv(R, p.rows)
    tiles = pl.cdiv(out_rows, p.tokens)
    last = tiles * p.tokens         # past every token of every tile: matches no column
    whole = lambda a, fill: jnp.pad(a, (0, chunks * p.rows - R), constant_values=fill)
    # the rows that landed by their tile of tokens, the rest (and the padding to
    # whole chunks) after them. Stable, and by tile only: inside a tile the rows
    # keep the buffer's order, expert by expert and ascending inside an expert,
    # so the gather that follows reads runs of neighbouring rows (a list in full
    # token order takes each row from another expert's group: 58.6 ns a row at
    # 24,576 x 2,304 (this list by tile read 58.3: the runs of neighbours bought nothing on the chip); PERF.md, PR 47); the kernel's one-hot
    # product takes a tile's rows in any order
    token = whole(jnp.where(jnp.arange(R, dtype=_I32) < n_valid, token.astype(_I32), last), last)
    tile_of, perm, token, *carried = lax.sort(
        (token // p.tokens, jnp.arange(chunks * p.rows, dtype=_I32), token)
        + (() if scale is None else (whole(scale.astype(_F32), 0.0),)),
        num_keys=1, is_stable=True)
    # the first listed row of every tile: a comparison against all the keys, which
    # XLA fuses into one reduction (a binary search is a loop a bound)
    first = jnp.sum(tile_of[None, :] < jnp.arange(tiles + 1, dtype=_I32)[:, None], axis=1,
                    dtype=_I32)
    flags, _, _, tile, chunk = _visits(jnp.diff(first), chunks * p.rows, p.rows, p.steps,
                                       visit_empty=True)
    by_chunk = lambda a: a.reshape(chunks, 1, p.rows)
    return TokenOrder(jnp.minimum(perm, R - 1), by_chunk(token), (flags, tile, chunk),
                      by_chunk(carried[0]) if carried else None)


def token_order(token: jax.Array, n_valid, *, out_rows: int, width: int, dtype,
                scale: Optional[jax.Array] = None,
                impl: Optional[str] = None) -> Optional[TokenOrder]:
    """The rows ``r < n_valid`` of a buffer listed by ascending ``token[r]``, for
    :func:`segment_sum` over rows of ``width`` columns of ``dtype`` onto
    ``out_rows`` tokens; ``None`` where the sum stays the caller's loop: off the
    TPU, under GSPMD, and off the kernel's shapes (:func:`is_kernel_available`;
    counted by ``guard.dispatch`` under ``segment_sum``). ``scale (R,)``: the
    factors of the layer's scaled sum, which then ride the sort (no gradient
    passes here: the sum's own ``scale`` has it) and spare that sum a gather of
    ``R`` scalars, 8.6 ns each on this chip. ``impl``: ``"pallas"`` / ``"jnp"``
    force one, and ``"pallas"`` off the shapes raises."""
    R = token.shape[0]
    impl, _ = _dispatch(
        "segment_sum", impl, is_kernel_available(R, out_rows, width, dtype),
        f"rows ({R}, {width}) {jnp.dtype(dtype)} are not whole lane tiles of bfloat16 or "
        "float32", token, statics=(out_rows, width, str(jnp.dtype(dtype))))
    if impl != "pallas":
        return None
    return _order(token, jnp.asarray(n_valid, _I32),
                  None if scale is None else lax.stop_gradient(scale), out_rows,
                  plan(R, out_rows, width))


# ---------------------------------------------------------------------------------
# the kernel (body in ``lax``, as ``ops/grouped_matmul.py``'s)
# ---------------------------------------------------------------------------------


def _bf16_parts(x):
    """``x`` as bfloat16 arrays that sum to it exactly: itself, or a float32's
    three parts."""
    if x.dtype == _BF16:
        return [x]
    parts = []
    for _ in range(3):
        part = lax.convert_element_type(x, _BF16)
        parts.append(part)
        x = lax.sub(x, lax.convert_element_type(part, _F32))
    return parts


def _kernel(flags, tile, chunk, token_ref, *refs, tokens: int, scaled: bool):
    scale_ref = refs[0] if scaled else None
    rows_ref, out_ref, acc_ref = refs[-3:]
    t = pl.program_id(0)
    f = flags[t]

    @pl.when(_does(f, _OPENS))
    def _():
        acc_ref[...] = lax.full(acc_ref.shape, 0.0, _F32)

    @pl.when(_does(f, _ROWS))
    def _():
        shape = (tokens, token_ref.shape[-1])
        across = lambda ref: lax.broadcast_in_dim(ref[...], shape, (0, 1))
        mine = lax.eq(across(token_ref), lax.add(lax.broadcasted_iota(_I32, shape, 0),
                                                 lax.mul(tile[t], _I32(tokens))))
        if scaled:      # a select, not a product: the matrix is finite whatever the scale
            onehot = _bf16_parts(lax.select(mine, across(scale_ref), lax.full(shape, 0.0, _F32)))
        else:
            onehot = [lax.convert_element_type(mine, _F32).astype(_BF16)]
        acc = acc_ref[...]
        for rows in _bf16_parts(rows_ref[...]):
            for part in onehot:
                acc = lax.add(acc, lax.dot_general(part, rows, (((1,), (0,)), ((), ())),
                                                   preferred_element_type=_F32))
        acc_ref[...] = acc

    @pl.when(_does(f, _CLOSES))
    def _():
        out_ref[...] = lax.convert_element_type(acc_ref[...], out_ref.dtype)


# A ``jax.jit`` function: a layer's two sums and every layer after it trace the
# body and lower it to Mosaic once a shape (PERF.md, PRs 27, 32 and 34).

@functools.partial(jax.jit, static_argnames=("out_rows", "out_dtype", "p"))
def _segment_sum(rows, token, scale, flags, tile, chunk, out_rows: int, out_dtype, p: Plan):
    D = rows.shape[1]
    tiles = pl.cdiv(out_rows, p.tokens)
    # grid steps / the buffer's chunks / the visits the tiles' edges can add
    _count_tiles("moe_rows", "segment_sum", (rows.shape[0], D, str(rows.dtype), p.tokens),
                 total=p.steps, live=p.steps - (tiles - 1), masked=tiles - 1)
    by_chunk = pl.BlockSpec((None, 1, p.rows), lambda t, flags, tile, chunk: (chunk[t], 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, tokens=p.tokens, scaled=scale is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(p.steps,),
            in_specs=[by_chunk] * (1 if scale is None else 2) + [
                pl.BlockSpec((p.rows, D), lambda t, flags, tile, chunk: (chunk[t], 0))],
            out_specs=pl.BlockSpec((p.tokens, D), lambda t, flags, tile, chunk: (tile[t], 0)),
            scratch_shapes=[pltpu.VMEM((p.tokens, D), _F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * p.tokens, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(_VMEM_LIMIT, 16 * 2 ** 20 + 2 * _vmem_bytes(
                p.tokens, p.rows, D))),
        interpret=_interpret_default(),
        name="segment_sum",
    )(flags, tile, chunk, token, *(() if scale is None else (scale,)), rows)
    return out if out.shape[0] == out_rows else out[:out_rows]


def segment_sum(rows: jax.Array, order: TokenOrder, *, out_rows: int, out_dtype,
                scale: Optional[jax.Array] = None) -> Optional[jax.Array]:
    """``(out_rows, D)`` of ``out_dtype``: row ``t`` is the float32 sum of
    ``rows[j] * scale[j]`` over the listed rows ``j`` of token ``t``.

    ``rows (Rp, D)`` and ``scale`` (``Rp`` float32: ``order.scale``, or the
    caller's own under ``order.perm``) are **in ``order``'s order**; ``rows``
    hold no NaN in the chunks that list a row (zeros from the last listed row
    on). ``None`` where the kernel's probe failed (``guard.dispatch``, once a
    key, with its warning): the caller keeps its loop."""
    p = plan(rows.shape[0], out_rows, rows.shape[1])
    if p is None or order.token.shape != (rows.shape[0] // p.rows, 1, p.rows) \
            or order.visits[0].shape != (p.steps,):
        raise ValueError(f"segment_sum: rows {rows.shape} onto {out_rows} tokens are not "
                         f"what this order lists ({order.token.shape})")
    out_dtype = jnp.dtype(out_dtype)
    scale = None if scale is None else scale.astype(_F32).reshape(order.token.shape)
    args = (rows, order.token, scale, *order.visits, out_rows, out_dtype, p)
    if _checked_impl("segment_sum", "pallas", _segment_sum, *args) != "pallas":
        return None
    return _segment_sum(*args)
