"""A learned key selection in front of attention: index scores and an exact top-k.

A "lightning indexer" (DeepSeek-V3.2-Exp's sparse attention) scores every
(query, key) pair with a few small heads on ONE shared key,

    ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``,   ``s <= t``,

and the attention that follows sees, for query ``t``, only the ``min(t + 1,
topk)`` keys of largest ``I[t, s]``, ties to the lower ``s``, the same set for
every attention head. :func:`index_select` computes that set and returns it as
what ``ops.flash_attention(selected=)`` consumes: an int8 mask ``(B, S, S)``,
1 where query ``t`` keeps key ``s``. A mask and not a threshold a query: with
the mask the attention kernels need nothing of the indexer's operands, and one
byte a pair (67 MB a layer at S = 8192) is a quarter of the float32 scores,
which are never in HBM.

The arithmetic of both forms: the products take the operands as they are
(bfloat16 on the training path) and accumulate in float32; the ReLU, the
weighting by ``w`` (float32), the sum over the heads in the heads' order and
every comparison are float32. ``-0.0`` counts as ``0.0``. The selection passes
no gradient (its result is an integer mask); callers hand the operands under
``stop_gradient``.

``impl="jnp"`` (the oracle, and the off-TPU default): ``I`` a block of query
rows at a time, ``lax.top_k`` (a sort, stable: the lower index first among
equals) and a scatter of the chosen indices.

``impl="pallas"``: one kernel, grid ``(B, S / rows)``. A step holds ``rows``
queries against the whole key matrix in VMEM and works a chunk of keys at a
time, the chunks a causal key falls in and no other (a dynamic trip count):

1. the chunk's scores, and from them their *order-preserving integer image*
   (``bits`` of a non-negative float32, ``bits ^ 0x7fffffff`` of a negative one:
   ``a < b`` as floats iff ``image(a) < image(b)`` as int32), keys after the
   query at the least int32, into an int32 scratch ``(chunks, rows, chunk)``;
2. the row's ``topk``-th largest image ``T`` by bisection: 32 passes over the
   scratch, each setting one bit of ``T`` from the top where at least ``topk``
   images stay at or above the candidate. Exact: it is a selection, not an
   estimate, and no value is moved or rounded;
3. ties: ``need = topk - count(image > T)`` of the keys AT ``T`` are kept, the
   lowest indices — the index ``J`` of the ``need``-th such key by a second
   bisection over the ``log2(S)`` bits of an index;
4. the mask ``(image > T) | (image == T & s <= J)``, and ``s <= t``, as int8.

A row with fewer than ``topk`` causal keys ends at ``T`` = the least int32 and
keeps them all. A sequence that is not whole tiles is padded (a padded key is
after every real query, a padded query is cut off).
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

__all__ = ["index_scores", "index_select", "is_kernel_available", "selected_pairs"]

_F32, _I32 = jnp.float32, jnp.int32
_LANES = 128
_INT_MIN = -2 ** 31
_ROW_TILES = (256, 128)         # queries a grid step
_KEY_CHUNKS = (512, 256, 128)   # keys a pass of a step's loops
_JNP_ROWS = 256                 # query rows the jnp form scores at a time
_VMEM_LIMIT = 64 * 2 ** 20      # the image scratch is rows x S x 4: 8 MiB at S = 8192


def selected_pairs(seq_len: int, topk: int) -> int:
    """(query, key) pairs an exact selection keeps in one sequence:
    ``sum_t min(t + 1, topk)``."""
    k = min(topk, seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k


def is_kernel_available(seq_len: int, heads: int, head_dim: int) -> bool:
    """The kernel takes any sequence (it pads to whole tiles); the operands must
    sit in a step's VMEM: a few heads of at most one lane tile."""
    return seq_len >= 1 and 1 <= heads <= 64 and 8 <= head_dim <= _LANES


def _zero_sign(x):
    """``-0.0 -> 0.0`` (the two compare equal, their bits do not)."""
    return jnp.where(x == 0.0, 0.0, x)


def index_scores(q, k, w, rows=None):
    """``I (B, R, S)`` float32 of query rows ``rows`` (a slice; all of them when
    None): ``q (B, S, Hi, d)``, ``k (B, S, d)``, ``w (B, S, Hi)``. No mask."""
    if rows is not None:
        q, w = q[:, rows], w[:, rows]
    s = jnp.einsum("bthd,bsd->bths", q, k, preferred_element_type=_F32)
    s = jnp.maximum(s, 0.0) * w.astype(_F32)[..., None]
    total = s[:, :, 0]
    for j in range(1, s.shape[2]):          # the heads' order, as the kernel sums them
        total = total + s[:, :, j]
    return _zero_sign(total)


def _select_jnp(q, k, w, topk):
    B, S, Hi, d = q.shape
    rows = min(_JNP_ROWS, S)
    Sp = -(-S // rows) * rows
    if Sp != S:                              # padded queries are cut off again
        q, w = (jnp.pad(t, ((0, 0), (0, Sp - S)) + ((0, 0),) * (t.ndim - 2)) for t in (q, w))
    kk = min(topk, S)
    key = jnp.arange(S, dtype=_I32)

    def block(start):
        qb = lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        wb = lax.dynamic_slice_in_dim(w, start, rows, axis=1)
        scores = index_scores(qb, k, wb)                        # (B, rows, S)
        causal = key[None, :] <= (start + jnp.arange(rows, dtype=_I32))[:, None]
        _, idx = lax.top_k(jnp.where(causal[None], scores, -jnp.inf), kk)
        chosen = jnp.zeros((B, rows, S), jnp.bool_)
        chosen = chosen.at[jnp.arange(B)[:, None, None], jnp.arange(rows)[None, :, None],
                           idx].set(True)
        return (chosen & causal[None]).astype(jnp.int8)

    out = lax.map(block, jnp.arange(0, Sp, rows, dtype=_I32))    # (n, B, rows, S)
    return jnp.moveaxis(out, 0, 1).reshape(B, Sp, S)[:, :S]


# ---------------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------------


class _Plan(NamedTuple):
    topk: int
    rows: int           # queries a grid step
    chunk: int          # keys a pass of the loops
    seq: int            # the padded sequence


def _plan(seq_len: int, topk: int) -> _Plan:
    Sp = -(-seq_len // _LANES) * _LANES
    first = lambda sizes: next(t for t in sizes if Sp % t == 0)
    return _Plan(int(topk), first(_ROW_TILES), first(_KEY_CHUNKS), Sp)


def _image(x):
    """The order-preserving int32 image of float32 ``x``."""
    bits = pltpu.bitcast(x, _I32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _lanes_sum(x):
    """``(rows, chunk)`` int32 -> ``(rows, 128)``: the chunk's lane tiles added
    up, vector adds only (the one cross-lane sum comes at a pass's end)."""
    out = x[:, :_LANES]
    for at in range(_LANES, x.shape[1], _LANES):
        out = out + x[:, at:at + _LANES]
    return out


def _select_kernel(p: _Plan, q_ref, k_ref, w_ref, out_ref, img_ref):
    i = pl.program_id(1)
    R, C = p.rows, p.chunk
    heads = q_ref.shape[1]
    live = ((i + 1) * R + (C - 1)) // C                # chunks with a causal key
    row = i * R + lax.broadcasted_iota(_I32, (R, 1), 0)
    col0 = lax.broadcasted_iota(_I32, (1, C), 1)
    w = w_ref[0]                                       # (R, heads) float32

    def score(c, carry):
        at = pl.multiple_of(c * C, C)
        k = k_ref[0, pl.ds(at, C), :]
        total = None
        for j in range(heads):
            s = lax.dot_general(q_ref[0, j], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32)
            s = jnp.maximum(s, 0.0) * w[:, j:j + 1]
            total = s if total is None else total + s
        image = _image(_zero_sign(total))
        img_ref[c] = jnp.where(col0 + at <= row, image, jnp.int32(_INT_MIN))
        return carry

    lax.fori_loop(0, live, score, 0)

    def count(keep):
        """Per row, how many images of the live chunks ``keep(image, first key)``
        holds for: ``(R, 1)`` int32."""
        def body(c, acc):
            return acc + _lanes_sum(keep(img_ref[c], c * C).astype(_I32))
        acc = lax.fori_loop(0, live, body, jnp.zeros((R, _LANES), _I32))
        return jnp.sum(acc, axis=1, keepdims=True)

    # the topk-th largest image of each row, bit by bit from the top; the sign
    # bit first: as int32 a candidate with it cleared is the larger
    kth = jnp.full((R, 1), p.topk, _I32)

    def value_bit(b, t):
        cand = jnp.where(b == 0, jnp.zeros_like(t), t | (jnp.int32(1) << (31 - b)))
        return jnp.where(count(lambda im, _: im >= cand) >= kth, cand, t)

    T = lax.fori_loop(0, 32, value_bit, jnp.full((R, 1), _INT_MIN, _I32))
    need = kth - count(lambda im, _: im > T)            # >= 1 of the keys at T

    # the index of the need-th key at T: the least J with that many at or below it
    def index_bit(_, lo_hi):
        lo, hi = lo_hi
        mid = (lo + hi) >> 1
        enough = count(lambda im, at: (im == T) & (col0 + at <= mid)) >= need
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    J, _ = lax.fori_loop(0, max(p.seq - 1, 1).bit_length(), index_bit,
                         (jnp.zeros((R, 1), _I32), jnp.full((R, 1), p.seq - 1, _I32)))

    # a chunk past the live ones was never written: whatever it holds, its keys
    # are after every query of the step
    for c in range(p.seq // C):
        im, col = img_ref[c], col0 + c * C
        keep = ((im > T) | ((im == T) & (col <= J))) & (col <= row)
        out_ref[0, :, c * C:(c + 1) * C] = keep.astype(_I32).astype(jnp.int8)


def _select_pallas(q, k, w, p: _Plan):
    """``q (B, Hi, Sp, d)``, ``k (B, Sp, d)``, ``w (B, Sp, Hi)`` float32, padded."""
    B, Hi, Sp, d = q.shape
    return pl.pallas_call(
        functools.partial(_select_kernel, p),
        grid=(B, Sp // p.rows),
        in_specs=[pl.BlockSpec((1, Hi, p.rows, d), lambda b, i: (b, 0, i, 0)),
                  pl.BlockSpec((1, Sp, d), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, p.rows, Hi), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((1, p.rows, Sp), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, Sp), jnp.int8),
        scratch_shapes=[pltpu.VMEM((Sp // p.chunk, p.rows, p.chunk), _I32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
        name="index_select",
    )(q, k, w)


def _pallas(q, k, w, p: _Plan):
    S = q.shape[1]
    pad = lambda t: jnp.pad(t, ((0, 0), (0, p.seq - S)) + ((0, 0),) * (t.ndim - 2))
    if p.seq != S:
        q, k, w = pad(q), pad(k), pad(w)
    mask = _select_pallas(q.transpose(0, 2, 1, 3), k, w.astype(_F32), p)
    return mask if p.seq == S else mask[:, :S, :S]


# ---------------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------------


def index_select(q: jax.Array, k: jax.Array, w: jax.Array, *, topk: int,
                 impl: Optional[str] = None) -> jax.Array:
    """The keys each query keeps: int8 ``(B, S, S)``, 1 at ``[b, t, s]`` where
    key ``s <= t`` is among the ``min(t + 1, topk)`` of largest index score
    ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])``, ties to the lower ``s``.

    ``q (B, S, Hi, d)`` and ``k (B, S, d)`` (one key for the ``Hi`` heads) in the
    dtype the products take, ``w (B, S, Hi)``. ``impl``: ``None`` takes the Pallas
    kernel where the traced program owns its device, else the ``jnp`` form,
    counted by ``guard.dispatch``; ``"pallas"`` / ``"jnp"`` force one."""
    if q.ndim != 4 or k.shape != (q.shape[0], q.shape[1], q.shape[3]) \
            or w.shape != q.shape[:3]:
        raise ValueError(f"index_select shapes mismatch: q {q.shape} is not (B, S, Hi, d) "
                         f"over k {k.shape} (B, S, d) and w {w.shape} (B, S, Hi)")
    topk = int(topk)
    if topk < 1:
        raise ValueError(f"topk must keep at least one key, got {topk}")
    _, S, Hi, d = q.shape
    impl, forced = _dispatch(
        "index_select", impl, is_kernel_available(S, Hi, d),
        f"{Hi} heads of {d} are not 1..64 heads of 8..{_LANES}", q, k, w, statics=(topk,))
    with _span("index_select"):
        if impl == "pallas":
            p = _plan(S, topk)
            if forced or _checked_impl("index_select", impl, _pallas, q, k, w, p) == impl:
                return _pallas(q, k, w, p)
        return _select_jnp(q, k, w, topk)
