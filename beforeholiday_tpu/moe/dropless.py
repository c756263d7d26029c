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
here), which can never overflow; assignments beyond a tighter bound are
*counted* (``dropped_rows``) so that the caller can fail the step: they are
never silently lost. **The buffer's empty tail is not walked**: rows reach the
buffer and leave it through :func:`gather_rows` and :func:`scatter_add_rows`,
loops over row tiles whose trip count is the rows that landed (a device scalar),
where XLA's static gather and scatter-add move every row of the buffer. What a
tighter bound still saves is memory, in proportion, and the experts' elementwise
work (``silu``, the products, the converts) over the empty rows. **On the TPU
the two sums (the combine's, and the dispatch's transpose) are no scatter-add**
(PR 47): :func:`token_order` lists the landed rows by tile of 256 tokens once
a layer, each sum moves its rows into that list with the gather loop and
``ops/segment_sum.py`` adds a tile's rows as a one-hot product on the MXU, in
the loop's arithmetic but for the order of a token's float32 additions; XLA's
scatter-add cost 96-145 ns a row and was more than half of the sort's two
sides. Off the TPU, under GSPMD and off the kernel's shapes (``D`` not whole
lane tiles, a dtype other than bfloat16 / float32) the scatter-add loop stays,
and is the oracle. A buffer that is full (every expert held and no bound: every
choice lands) walks every tile and paid the loops 25-40 % over the static ops
at the smaller buffers (PR 34); with the sums by token a layer's four movements
take 5.71 ms against the loops' 7.70 and the static ops' 8.38 at 24,576 x 2,304, 2.74 against 3.81 and 4.01 at 12,288 x 2,048 (:func:`_row_tile`; PERF.md, PRs 34 and 47).

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

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from beforeholiday_tpu.monitor.counters import book_tiles as _book_tiles
from beforeholiday_tpu.monitor.spans import span as _span
from beforeholiday_tpu.ops.grouped_matmul import grouped_matmul as _grouped_matmul
from beforeholiday_tpu.ops.segment_sum import (
    TokenOrder,
    segment_sum as _segment_sum,
    token_order,
    unwritten_like as _unwritten_like,
)

__all__ = [
    "dropless_experts",
    "dropless_moe",
    "gather_rows",
    "relu2_mlp",
    "route_sigmoid",
    "route_topk",
    "scatter_add_rows",
    "shared_expert",
    "swiglu",
    "token_order",
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
    scale: float = 1.0, renormalize: bool = True, eps: float = 1e-20
) -> Tuple[jax.Array, jax.Array]:
    """``(weights (T, k) float32, expert ids (T, k) int32)``: each of the
    router's outputs through a sigmoid in float32; the ``top_k`` largest of
    ``score + bias`` (``bias (E,)``: it enters the choice only, so it has no
    gradient); the chosen *scores* divided by their sum (plus ``eps``) where
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
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
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


# -- the sort's two sides: rows moved tile by tile, as far as rows landed ------------

def _row_tile(D: int) -> int:
    """Rows one trip of the loops below moves, from what the code sees.

    A trip is a handful of op launches whatever it moves, and the last trip
    walks up to a tile of rows nobody needs: small tiles pay the first, large
    ones the second. On a v5e, ms a layer for the four movements at the three
    8k cells' buffers (``testing/tpu_checks.py`` ``moe_rows/*``, PR 34; the
    one-shot form walks the whole buffer):

    ====================  ========  =====  =====  ======
    ``R x D``, rows in    one-shot  512    1024   2048
    ====================  ========  =====  =====  ======
    16384 x 2048,  5126   4.47      2.52   2.61   4.02
    24576 x 2304, 16216   9.10      6.51   6.29   16.00
    8192 x 1024,   2607   1.57      1.35   1.37   1.48
    ====================  ========  =====  =====  ======

    With every row landed (``n_valid = R``) the same three buffers take 4.45 /
    9.07 / 1.54 ms one-shot and 5.57 / 8.97 / 2.17 at 1024: the loop wins below
    about three quarters full and loses above. (All timed while the dispatch's
    transpose still summed in bfloat16; it sums in float32 since.) 512 and 1024
    are within 4 % of each other everywhere; at 2048 rows a float32 tile of 2304
    columns no longer stays where the smaller ones do and a row costs 2.5 x. So:
    1024 rows up to these widths (a wider row would want fewer: hold ``tile x D``
    near 2.4 M elements); the callers cap the tile at the buffer.

    Since PR 47 the two sums are taken by token (``ops/segment_sum.py``) and the
    loops left are gathers, at this tile. ``moe_rows/*`` then, ms a layer for
    the four movements and the token order as ONE program (a call alone costs
    the chip machine's host 0.2 ms), rows in = the cell's / ``R / 8`` / ``R``:

    ====================  ========  =====  =============
    ``R x D``, rows in    one-shot  loops  sums by token
    ====================  ========  =====  =============
    24576 x 2304, 16216   8.36      5.43   4.11
    24576 x 2304,  3072   8.37      1.68   1.50
    24576 x 2304, 24576   8.38      7.70   5.71
    12288 x 2048,  8149   4.01      2.72   2.05
    12288 x 2048,  1536   4.02      1.03   0.93
    12288 x 2048, 12288   4.01      3.81   2.74
    ====================  ========  =====  =============

    (the 24,576-row readings the final tree's, the 12,288-row ones its first
    tree's, which listed the rows in full token order: the two read alike). The two sums alone,
    loops -> by token: 3.88 -> 2.61 ms (0.67 x) and 1.98 -> 1.22; of the 2.61, the
    two gathers into the list 0.945 each (58 ns a row), the kernel 0.32 (one
    pass) and 0.58 (three)."""
    return max(256, min(1024, 2_400_000 // D // 256 * 256))


def _trips(n_valid, tile: int):
    return (n_valid + (tile - 1)) // tile


# The two loops are ``jax.jit`` functions: a layer's four movements are traced
# once a shape and served to every layer and program after it from jit's cache
# (untraced, 32 loop bodies a step cost the chip machine's host 3.5 s of set-up).

@functools.partial(jax.jit, static_argnames=("tile", "out_dtype", "fill"))
def _gather_loop(src, token, n_valid, scale, dot_with, tile: int, out_dtype, fill: bool = True):
    """``(rows (R, D) out_dtype, dots (R,) float32)``: for the tiles that reach
    below ``n_valid``, ``rows[r] = src[token[r]] * scale[r]`` (the product in
    float32, rounded once) and ``dots[r] = sum(src[token[r]] * dot_with[r])``
    in float32, from the same fetch; rows at or past ``n_valid`` are zero in
    both. Either is ``None`` where it was not asked for (``out_dtype`` /
    ``dot_with`` ``None``). Where ``R`` is no multiple of the tile, the last
    tile is moved back to end at ``R`` and writes some rows a second time.
    Without ``fill`` the rows of the tiles the loop never reached are left
    unwritten, for a reader that stops where the loop stopped."""
    R, D = token.shape[0], src.shape[1]

    def body(i, carry):
        rows, dots = carry
        start = jnp.minimum(i * tile, R - tile)
        live = start + jnp.arange(tile, dtype=jnp.int32) < n_valid
        got = src[lax.dynamic_slice(token, (start,), (tile,))]
        if dot_with is not None:
            other = lax.dynamic_slice(dot_with, (start, 0), (tile, D))
            dot = jnp.sum(got.astype(_F32) * other.astype(_F32), axis=-1)
            dots = lax.dynamic_update_slice(dots, jnp.where(live, dot, 0.0), (start,))
        if rows is not None:
            if scale is not None:
                got = got.astype(_F32) \
                    * lax.dynamic_slice(scale, (start,), (tile,)).astype(_F32)[:, None]
            got = jnp.where(live[:, None], got, 0).astype(out_dtype)
            rows = lax.dynamic_update_slice(rows, got, (start, 0))
        return rows, dots

    rows = None if out_dtype is None else jnp.zeros((R, D), out_dtype) if fill \
        else _unwritten_like(src, (R, D), out_dtype)
    init = (rows, None if dot_with is None else jnp.zeros((R,), _F32))
    return lax.fori_loop(0, _trips(n_valid, tile), body, init)


@functools.partial(jax.jit, static_argnames=("tile", "out_rows", "out_dtype"))
def _scatter_add_loop(rows, token, n_valid, scale, tile: int, out_rows: int, out_dtype):
    """``out[token[r]] += rows[r] * scale[r]`` for ``r < n_valid``, tile by tile
    in the buffer's order, on one accumulator ``(out_rows, D)`` of
    ``out_dtype``; the product in float32. Rows at or past ``n_valid`` (and the
    rows a moved-back last tile would add twice) are selected to zero *before*
    the product: they hold whatever the grouped kernel left there."""
    R, D = rows.shape

    def body(i, acc):
        start = jnp.minimum(i * tile, R - tile)
        at = start + jnp.arange(tile, dtype=jnp.int32)
        live = (at >= i * tile) & (at < n_valid)
        add = jnp.where(live[:, None], lax.dynamic_slice(rows, (start, 0), (tile, D)), 0)
        if scale is not None:
            add = add.astype(_F32) \
                * lax.dynamic_slice(scale, (start,), (tile,)).astype(_F32)[:, None]
        return acc.at[lax.dynamic_slice(token, (start,), (tile,))].add(add.astype(out_dtype))

    return lax.fori_loop(0, _trips(n_valid, tile), body, jnp.zeros((out_rows, D), out_dtype))


def _sum_by_token(rows, n_valid, scale, order, tile: int, out_rows: int, out_dtype):
    """``out[token[r]] += rows[r] * scale[r]`` over ``r < n_valid`` without a
    scatter-add, or ``None`` where the caller's loop stays (no ``order``: off the
    TPU, off the kernel's shapes). The rows that landed are moved into token
    order by the gather loop — whole rows, the tail selected to zero on the way
    — and summed a tile of tokens at a time on the MXU (``ops/segment_sum.py``):
    the terms and their sum in float32 as the loop's, rounded once to
    ``out_dtype``."""
    if order is None:
        return None
    listed, chunk = order.perm.shape[0], order.token.shape[2]
    if not 0 <= listed - rows.shape[0] < chunk:
        raise ValueError(f"this order lists a buffer of {listed} rows (whole chunks of "
                         f"{chunk}), not one of {rows.shape[0]}")
    tile = min(tile, listed)
    # tiles of whole chunks write every chunk that lists a row, and the kernel reads
    # no other: no zero-fill of the rest
    by_token = _gather_loop(rows, order.perm, n_valid, None, None, tile, rows.dtype,
                            fill=tile % chunk != 0)[0]
    if scale is not None:
        scale = scale[order.perm] if order.scale is None else order.scale
    return _segment_sum(by_token, order, out_rows=out_rows, out_dtype=out_dtype, scale=scale)


# Each is the other's transpose, and a loop with a trip count the device knows
# has no transpose of its own. ``static`` is the tile and what the backward
# needs of an operand it does not keep (its rows, its dtype): no residual is
# held for a shape. ``order`` is integers, like ``token``: no cotangent.

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gather(src, token, n_valid, scale, order, static):
    tile, _, dtype = static
    return _gather_loop(src, token, n_valid, scale, None, tile, dtype)[0]


def _gather_fwd(src, token, n_valid, scale, order, static):
    return (_gather(src, token, n_valid, scale, order, static),
            (None if scale is None else src, token, n_valid, scale, order))


def _gather_bwd(static, res, ct):
    tile, src_rows, dtype = static
    src, token, n_valid, scale, order = res
    # the factors an order carries are its scaled sum's (the combine's), not these
    d_src = _sum_by_token(ct, n_valid, scale, order and order._replace(scale=None), tile,
                          src_rows, dtype)
    if d_src is None:
        # a token's rows lie in different tiles: the sum stays float32 across the
        # trips and is rounded once, as XLA's one-shot scatter-add of bfloat16 is
        d_src = _scatter_add_loop(ct, token, n_valid, scale, tile, src_rows, _F32).astype(dtype)
    d_scale = None
    if scale is not None:
        d_scale = _gather_loop(src, token, n_valid, None, ct, tile, None)[1].astype(scale.dtype)
    return d_src, None, None, d_scale, None


_gather.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scatter_add(rows, token, n_valid, scale, order, static):
    tile, out_rows, out_dtype, _ = static
    out = _sum_by_token(rows, n_valid, scale, order, tile, out_rows, out_dtype)
    if out is None:
        out = _scatter_add_loop(rows, token, n_valid, scale, tile, out_rows, out_dtype)
    return out


def _scatter_add_fwd(rows, token, n_valid, scale, order, static):
    return (_scatter_add(rows, token, n_valid, scale, order, static),
            (None if scale is None else rows, token, n_valid, scale))


def _scatter_add_bwd(static, res, ct):
    tile, _, _, rows_dtype = static
    rows, token, n_valid, scale = res
    d_rows, d_scale = _gather_loop(ct, token, n_valid, scale, rows, tile, rows_dtype)
    return d_rows, None, None, None if scale is None else d_scale.astype(scale.dtype), None


_scatter_add.defvjp(_scatter_add_fwd, _scatter_add_bwd)


def _n_valid(n_valid, R: int):
    return jnp.clip(jnp.asarray(n_valid, jnp.int32), 0, R)


def gather_rows(src: jax.Array, token: jax.Array, n_valid, *,
                scale: Optional[jax.Array] = None,
                order: Optional[TokenOrder] = None) -> jax.Array:
    """``(R, D)`` in ``src``'s dtype: row ``r`` is ``src[token[r]]`` (times
    ``scale[r]``, the product in float32 and rounded once) for ``r < n_valid``
    and zero from there on.

    ``src``: ``(T, D)``; ``token``: ``(R,)`` row numbers; ``n_valid``: a device
    scalar. A loop walks the tiles (:func:`_row_tile` rows each) that reach
    below ``n_valid`` and no others: the tail of the buffer costs its zero-fill.
    Its transpose is :func:`scatter_add_rows`, with the sum taken in float32
    and rounded once to ``src``'s dtype; ``order`` is that sum's."""
    R = token.shape[0]
    tile = min(R, _row_tile(src.shape[1]))
    _book("gather", R, src.shape[1], src.dtype, tile)
    return _gather(src, token, _n_valid(n_valid, R), scale, order,
                   (tile, src.shape[0], src.dtype))


def scatter_add_rows(rows: jax.Array, token: jax.Array, n_valid, *, out_rows: int,
                     scale: Optional[jax.Array] = None, out_dtype=None,
                     order: Optional[TokenOrder] = None) -> jax.Array:
    """``(out_rows, D)`` of ``out_dtype`` (``rows``' own by default):
    ``out[token[r]] += rows[r] * scale[r]`` over ``r < n_valid``, a token's rows
    in the order the buffer holds them, the product in float32.

    What ``rows`` holds at or past ``n_valid`` is never read into a sum (it may
    be NaN). The same loop as :func:`gather_rows`, whose transpose this is; with
    a ``scale`` its backward takes the scale's cotangent from the same fetch.

    ``order``: :func:`token_order` of the same ``token``, ``n_valid`` and
    ``out_rows`` (one for a layer's two sums), or ``None``. With one the sum is no
    scatter-add: the landed rows go into token order through the gather loop and
    are summed a tile of tokens at a time by a one-hot product on the MXU
    (``ops/segment_sum.py``); a token's terms are then added in another order,
    in float32 as here, and nothing else differs. An order made with a ``scale``
    carries it: it must be this ``scale``."""
    R = token.shape[0]
    out_dtype = jnp.dtype(rows.dtype if out_dtype is None else out_dtype)
    tile = min(R, _row_tile(rows.shape[1]))
    _book("scatter_add", R, rows.shape[1], rows.dtype, tile)
    return _scatter_add(rows, token, _n_valid(n_valid, R), scale, order,
                        (tile, out_rows, out_dtype, rows.dtype))


@jax.custom_vjp
def _settled(rows, live):
    """``rows`` through one masking pass that changes no value (the loop left
    zeros where ``live`` is false). ``xs`` lives until the backward pass (the
    weights' cotangents read it), and the chip's compiler holds a loop's own
    result that long at twice its size: + ``R x D`` a layer, 0.42 GiB in the
    Mellum cell's step (PR 34). The cotangent passes as it is: the loop that
    takes it masks the tail itself."""
    return jnp.where(live[:, None], rows, 0)


_settled.defvjp(lambda rows, live: (_settled(rows, live), None), lambda _, ct: (ct, None))


def _book(kernel: str, R: int, D: int, dtype, tile: int):
    """``monitor.tile_records()``: the buffer's tiles (the most trips a loop
    makes; how many it makes is ``expert_rows`` over the tile, on the device)."""
    _book_tiles("moe_rows", kernel, (R, D, str(jnp.dtype(dtype)), tile),
                 total=-(-R // tile), live=-(-R // tile), masked=1)


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
    ``impl`` is :func:`~beforeholiday_tpu.ops.grouped_matmul.grouped_matmul`'s
    and :func:`token_order`'s.
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
        n_valid = ends[-1]                      # the rows that landed: the loops' bound
        w_sorted = weights.reshape(-1)[order]
        # the rows of no group hold whatever the grouped kernel leaves there,
        # in its outputs AND in the cotangent it hands back for ``xs``: both
        # loops and their transposes select them out (and never walk the tiles
        # past the last row that landed)
        # both sums of the layer (the combine's, this gather's transpose) onto the
        # same tokens: one token order, where the backend and the shapes take it
        by_token = token_order(token, n_valid, out_rows=T, width=D, dtype=x.dtype,
                               scale=w_sorted, impl=impl)
        xs = _settled(gather_rows(x, token, n_valid, order=by_token), jnp.arange(R) < n_valid)
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
        # ``w * y`` a tile at a time in float32, summed onto the tokens in float32
        out = scatter_add_rows(y, token, n_valid, scale=w_sorted, out_rows=T, out_dtype=_F32,
                               order=by_token)
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
    a shared expert ``shared_w_up`` / ``shared_w_down``, which select its form
    with the keys beside them: ``shared_w_gate`` and ``shared_score (D, 1)`` a
    SwiGLU gated per token by ``sigmoid(x shared_score)``; ``shared_w_gate``
    alone an ungated SwiGLU; neither the ungated ``relu^2`` pair. Without
    ``shared_w_up`` the layer is its routed part alone, and the ``moe_shared``
    span does not open. ``route`` is
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
                if "shared_score" in p:
                    shared = shared_expert(x, p["shared_w_gate"], p["shared_w_up"],
                                           p["shared_w_down"], p["shared_score"])
                elif "shared_w_gate" in p:
                    shared = swiglu(x, p["shared_w_gate"], p["shared_w_up"],
                                    p["shared_w_down"])
                else:
                    shared = relu2_mlp(x, p["shared_w_up"], p["shared_w_down"])
            with _span("moe_combine"):
                routed = routed + shared.astype(_F32)
        with _span("moe_combine"):
            y = routed.astype(x.dtype)
    return y, counters
