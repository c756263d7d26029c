"""Fused multi-head attention — a Pallas flash attention for TPU.

TPU-native counterpart of the reference's two fused-attention extensions:

* ``apex.contrib.fmha`` (ref: apex/contrib/fmha/fmha.py:33-60) — CUTLASS
  fused MHA, SM80-only, seq <= 512, variable-length via cu_seqlens;
* ``apex.contrib.multihead_attn`` (ref:
  apex/contrib/multihead_attn/self_multihead_attn.py:22) — fused
  self/enc-dec attention kernels.

Both exist to avoid materializing the (B*H, S, S) score tensor. The TPU
design is a single flash-attention kernel family instead of per-module CUDA:
the forward streams K/V blocks through VMEM with an online softmax
(running max ``m``, running sum ``l``), the backward recomputes block scores
from the saved (q, k, v, lse) — the same rematerialization trade the
reference's backward kernels make, shaped for the MXU: every inner op is a
(BQ, D) x (D, BK)-style matmul, fp32 accumulation.

Queries and keys share one width ``Dk`` and values have their own ``Dv`` (latent
attention: 192-wide scores over 128-wide values); every kernel computes its
scores at ``Dk`` and its values at ``Dv``, nothing is padded to a common width,
and a call with ``Dv == Dk`` is the one-width call it always was.

A call is scheduled on two levels (:class:`TilePlan`), both a function of
``(Sq, Sk, Dk, Dv, causal, window)`` alone. The grid hands the kernels large *blocks*
(:func:`_block_size`: few grid steps, few copies); inside a block that the
causal diagonal crosses the kernels walk *tiles*, so that the triangle above
the diagonal is not computed and only the tiles on it build a mask — at
S = 1024 a head is ONE block, and all of the skipping happens inside it. A
non-causal call is one tile a block. A causal call with a ``window`` (sliding-
window attention: the last W keys) goes further: its GRID is the band of
blocks the window reaches, through index maps offset from the outer block, so
that a block outside the band costs no grid step and no copy, and the blocks
the window's lower edge crosses are walked as the diagonal's are. A causal
call of several blocks WITHOUT a window names live blocks only, too: the
forward's last grid axis is the blocks on and below the diagonal, read from two
int32 tables in SMEM (``TilePlan.live_axis``, :func:`_live_grid`), so every step
computes and every copy is issued under a product; the dq and dkv kernels keep
the square's grid, their carried sums being what they are, and clamp their
index maps onto the diagonal (:func:`_block_maps`), where a repeated block is
not copied again.
A causal call may also take the keys each query keeps as an operand
(``selected``: int8 ``(B, Sq, Sk)``, made at run time by ``ops.index_select``, the
same for a batch row's heads): the plan is then the causal plan with
``TilePlan.selected``, every kernel family takes the operand's block beside its
own (:func:`_sel_spec`) and :func:`_mask` reads it where it would have compared
positions. Every causal block is walked; what the selection leaves out is
masked, not skipped.
``guard.dispatch.count_tiles`` books what each traced kernel's plan computes
and, for the forward, the grid steps and copies a head takes
(``monitor.tile_records()``).

The backward is one kernel or two by what the plan says carries over between
grid steps (:func:`_fa_bwd_pallas`). Where a head is one block
(``TilePlan.one_pass``: causal, S <= 1024 at D <= 128, S <= 512 above) nothing
does, and ONE call recomputes the scores once and returns dq, dk and dv
(booked as ``dqkv``). Where a causal head is several blocks (S = 8192: 8 x 8 or
16 x 16, or a window's band of them) dk / dv sum over query blocks in a
block-sized accumulator and dq over key blocks in a float32 scratch that holds
the whole head in VMEM (``Sq * Dk * 4 <= _HEAD_DQ_BYTES``), so again ONE call on
the dkv kernel's grid — the square's, or the band's — recomputes once (booked as
``dqkv_blocks``). Everywhere else — a non-causal call, a head too long for VMEM —
two grid orders, so a dq call and a dkv call each recompute the scores (booked
as ``dq`` and ``dkv``).

Variable-length batches are expressed as per-sequence key lengths
(``kv_lens``) rather than the reference's packed cu_seqlens: on TPU the
padded-dense layout keeps shapes static for XLA while the kernel masks
``k >= len`` in-block, which is the moral equivalent of fmha's seqlen
handling without the gather/scatter traffic.

Dispatch follows the repo-wide policy (`_pallas_util.resolve_impl`): Pallas
on single-device TPU or inside fully-manual shard_map, jnp (unfused but
GSPMD-partitionable) elsewhere; plus a shape gate like the reference's
``is_kernel_available`` (fused_softmax.py:164).
"""

from __future__ import annotations

import functools
import itertools
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from beforeholiday_tpu.guard.dispatch import (
    checked_impl as _checked_impl,
    count_forced as _count_forced,
    count_tiles as _count_tiles,
)
from beforeholiday_tpu.monitor.spans import span as _span
from beforeholiday_tpu.remat.policies import (
    TAG_ATTN_OUT as _TAG_ATTN_OUT,
    TAG_FLASH_LSE as _TAG_FLASH_LSE,
)
from beforeholiday_tpu.ops._autocast import autocast_dtype
from beforeholiday_tpu.ops._pallas_util import (
    interpret_default as _interpret_default,
    resolve_impl as _resolve_impl,
)

_NEG = -1e30  # mask fill; large-negative (not -inf) keeps exp/max NaN-free

_MIN_BLOCK = 128
# Strips a block on the causal diagonal is cut into (_tile_plan). Measured on a
# v5e at the GPT cells' call (64 heads, S=1024, D=64, bf16; fwd + dq + dkv device
# ms, and the seconds the chip's host took to trace the three kernels once;
# PR 28): one masked block 1.022 ms / 0.139 s; 2 strips 0.721 / 0.082; 4 strips
# 0.623 / 0.147; 8 strips 0.571 / 0.267. A kernel's body is traced three to
# seven times at every start of a program and inside the data-parallel step's
# backward each traced operation cost ~6x what it costs alone, so the body's
# size is set-up time: 4 keeps it what one masked block cost.
_DIAG_STRIPS = 4


def _block_size(seq_len: int, head_dim: int = 64, v_head_dim: Optional[int] = None) -> int:
    """Largest GRID block (query rows == key cols) that tiles the sequence —
    the outer of the two levels of :class:`TilePlan`.

    Bigger blocks amortize per-grid-step overhead and give the MXU larger
    matmuls: at S=8192/D=64 (32 heads, where a head is 8 x 8 blocks of 1024)
    the causal forward measured 30.0 ms with 1024-blocks vs 31.4 (512) vs
    43.8 (256) on a v5e. 1024 is allowed only where the values are one lane tile
    wide (``v_head_dim <= 128``) and the queries and keys at most two
    (``head_dim <= 256``) — the dkv backward holds ~6 operand blocks plus two
    fp32 scratch accumulators and (bq, bk) fp32 intermediates, which with wider
    values would push past the ~16 MB VMEM budget Mosaic gives a kernel that
    asks for none (the fused backward of several blocks runs the same body and
    asks for its own: :func:`_blocks_vmem_bytes`). For a call of one width that
    is D <= 128, as it always was. Of two widths, 192 / 128 (latent attention:
    three of the five operands and both outputs of the forward are 128 wide)
    takes 1024 and is faster for it: one layer at (32 heads, S=8192), fwd / dq +
    dkv device ms on a v5e, 11.98 / 27.25 with blocks of 512 and 7.52 / 22.25
    with blocks of 1024 (PR 42). The block is NOT what decides how much of the
    causal triangle is skipped: at S=1024 one head is a single block, and the
    skipping happens inside it, tile by tile (:func:`_tile_plan`)."""
    v_head_dim = head_dim if v_head_dim is None else v_head_dim
    ladder = (1024, 512, 256) if head_dim <= 256 and v_head_dim <= 128 else (512, 256)
    for cand in ladder:
        if seq_len % cand == 0:
            return cand
    return _MIN_BLOCK


def _window_block(seq_len: int, head_dim: int, window: int,
                  v_head_dim: Optional[int] = None) -> int:
    """Grid block of a windowed call: :func:`_block_size`'s, halved only while
    the half still holds the whole window (a block of twice the window or more
    would be mostly outside the band).

    Large blocks win with a window as they do without one. Measured on a v5e
    at the Mellum cell's call (32 heads, S=8192, D=128, W=1024, bf16; fwd / dq +
    dkv device ms of one layer; PR 31), where the plain causal call takes
    4.98 / 14.17: blocks of 1024 in 4 strips (a band of 2 blocks, 150 of 1024
    tiles) 1.75 / 3.52; 1024 in 8 strips (540 of 4096) 1.58 / 3.25; 512 in 4
    strips (a band of 3) 2.54 / 4.51; 512 in 2 strips 2.79 / 4.66; 256 in 2
    strips (a band of 5) 4.01 / 7.64; 256 whole 5.09 / 8.00. A block of the
    window's size walks 20 tiles for the 16 the mask needs, and a smaller one
    walks fewer only to pay more grid steps and more copies. Eight strips are
    8 % faster at twice the kernel body, which ``_DIAG_STRIPS`` weighs the
    same way."""
    block = _block_size(seq_len, head_dim, v_head_dim)
    while block > _MIN_BLOCK and block // 2 >= window and seq_len % (block // 2) == 0:
        block //= 2
    return block


class TilePlan(NamedTuple):
    """The two-level schedule of one flash call, shared by the forward and the
    backward kernels so that all agree on which tile is which.

    The *block* (``bq`` x ``bk``) is what the grid hands a kernel: large, so
    that a head costs few grid steps and few copies. Inside a block the
    kernels walk *tiles* (``tq`` x ``tk``). Off the causal path a block is
    one tile — the kernel body of a non-causal call. On it (blocks are square
    there) a block above the diagonal is skipped, a block below it is one
    unmasked piece, and a block the diagonal crosses is walked strip by strip
    (:meth:`walk`): the run of tiles wholly below the diagonal is one piece
    with no mask arithmetic, the tile on it is the one piece that builds the
    iota mask, the tiles above it are not computed. What is left out is
    exactly what the mask zeroes, so the results are those of the whole
    masked block, bit for bit. The walk is static and short (``_DIAG_STRIPS``
    strips, two score pieces each, every other product once per strip), so
    the kernel body stays small: it is traced and lowered at every start of a
    program (PR 28: set-up time is a budget). Tile ids (dropout re-seeds the
    PRNG per tile) count the tiles of the whole score square row-major."""

    sq: int
    sk: int
    bq: int
    bk: int
    tq: int
    tk: int
    causal: bool
    window: Optional[int] = None    # keys a query sees, itself among them
    selected: bool = False          # a (B, Sq, Sk) int8 operand says which keys a query keeps

    @property
    def nq(self) -> int:
        return self.sq // self.bq

    @property
    def nk(self) -> int:
        return self.sk // self.bk

    @property
    def one_pass(self) -> bool:
        """A causal call whose block holds a whole row of the square (one
        block a head): nothing carries over between grid steps. The forward's
        strips go from their scores to their result in one pass, the VMEM
        accumulators left alone — no init, no rescale, no final copy — and the
        backward is one kernel, not two (:func:`_fa_bwd_pallas`).
        (A non-causal call keeps the bodies it had.)"""
        return self.causal and self.nq == 1

    @property
    def live_axis(self) -> bool:
        """A causal call of several blocks without a window: the forward's last
        grid axis names the blocks on and below the diagonal and no other
        (:meth:`fwd_steps`), so a block above it costs no grid step and no copy,
        and every copy is issued under a step that computes."""
        return self.causal and self.window is None and self.nq > 1

    def fwd_steps(self):
        """``[(i, j)]``: the query block and the key block the forward's index
        maps name at each grid step of one head, in the grid's order. With
        :attr:`live_axis` these ARE the grid (the two tables its steps read
        their blocks from); a windowed plan walks its band, clamped into the
        sequence; every other plan walks the whole row of key blocks."""
        if self.window is not None:
            back = self.band - 1
            return [(i, max(i + s - back, 0)) for i in range(self.nq) for s in range(self.band)]
        return [(i, j) for i in range(self.nq)
                for j in range(i + 1 if self.live_axis else self.nk)]

    def walk(self, by_cols: bool, diag: bool):
        """Static walk of one block: ``[(fixed, [(moving, on_diag), ...])]``,
        spans as block-local slices. ``fixed`` runs along the kernel's own
        axis (query rows for fwd/dq, key columns for dkv: ``by_cols``) and
        ``moving`` along the other; ``on_diag`` marks the tile the diagonal
        crosses. ``diag=False`` is the whole block in one piece."""
        outer, inner = (self.bk, self.bq) if by_cols else (self.bq, self.bk)
        if not diag:
            return [(slice(0, outer), [(slice(0, inner), False)])]
        t = self.tq
        strips = []
        for at in range(0, outer, t):
            on = (slice(at, at + t), True)
            if by_cols:  # the tile on the diagonal, then every row below it
                pieces = [on, (slice(at + t, inner), False)]
            else:        # every key left of the diagonal, then the tile on it
                pieces = [(slice(0, at), False), on]
            strips.append((slice(at, at + t),
                           [p for p in pieces if p[0].stop > p[0].start]))
        return strips

    # -- a windowed plan: the grid is the band ---------------------------------

    @property
    def band(self) -> int:
        """Key blocks a query block's grid steps visit in a windowed plan: the
        block the window's lower edge falls in for the block's first row, up
        to the diagonal. The last grid axis has this many steps, not ``nk``:
        step ``s`` of query block ``i`` is key block ``i - (band - 1) + s``
        (fwd, dq), step ``s`` of key block ``j`` is query block ``j + s``
        (dkv, and the one-call backward that walks as dkv does:
        :func:`_fa_bwd_blocks`), through index maps offset from the outer
        block. The ``band - 1`` steps that fall before the sequence's start
        (or, key block outer, past its end) are clamped onto the first (last)
        block, so they copy nothing new, and compute nothing."""
        return min(self.nq, -(-(self.window - 1) // self.bk) + 1)

    def band_walk(self, by_cols: bool, d: int):
        """Static walk of the block ``d`` blocks below the diagonal of a
        windowed plan, in :meth:`walk`'s form; the second member of a piece is
        0 for tiles wholly inside the band (no mask arithmetic), else the
        edges that cross it: 1 the causal diagonal, 2 the window's lower edge,
        3 both. Tiles outside the band are in no piece, and a strip with none
        is left out. ``q - k`` of a block-local ``(r, c)`` is ``d * bq + r - c``,
        kept where ``0 <= q - k < window``."""
        b, t, strips = self.bq, self.tq, []
        for at in range(0, b, t):
            pieces = []
            for mv in range(0, b, t):
                r0, c0 = (mv, at) if by_cols else (at, mv)
                lo, hi = d * b + r0 - c0 - (t - 1), d * b + r0 - c0 + (t - 1)
                if hi < 0 or lo >= self.window:
                    continue
                edge = (1 if lo < 0 else 0) | (2 if hi >= self.window else 0)
                if pieces and pieces[-1][1] == edge:
                    pieces[-1] = (slice(pieces[-1][0].start, mv + t), edge)
                else:
                    pieces.append((slice(mv, mv + t), edge))
            if pieces:
                strips.append((slice(at, at + t), pieces))
        return strips

    def band_walks(self, by_cols: bool):
        """``[(walk, [d, ...])]``: the distinct walks of a windowed plan's
        band and the block distances each serves (the blocks wholly inside the
        band share one)."""
        out = []
        for d in range(self.band):
            walk = self.band_walk(by_cols, d)
            for known, ds in out:
                if known == walk:
                    ds.append(d)
                    break
            else:
                out.append((walk, [d]))
        return out

    def counts(self, has_lens: bool, fwd: bool = False) -> Dict[str, int]:
        """Tiles of the score square: ``total``, ``live`` (computed) and
        ``masked`` (computed through a mask). The same for all three kernels.
        With ``kv_lens`` a key length can fall inside any tile, so every
        computed tile takes the length test. ``fwd`` adds the forward's grid:
        the ``steps`` it takes a head and the K + V blocks it ``copies`` (a step
        of a query block's walk that names the key block the step before it
        named copies nothing)."""
        total = (self.sq // self.tq) * (self.sk // self.tk)
        if self.window is not None:
            live = masked = 0
            for d in range(self.band):      # nq - d blocks lie d below the diagonal
                for _, pieces in self.band_walk(False, d):
                    for span, edge in pieces:
                        n = (span.stop - span.start) // self.tk * (self.nq - d)
                        live += n
                        masked += n if edge or has_lens else 0
        elif not self.causal:
            live = masked = total
        else:
            n = self.sq // self.tq
            live = n * (n + 1) // 2
            masked = live if has_lens or self.selected else n
        out = {"total": total, "live": live, "masked": masked}
        if fwd:
            steps = self.fwd_steps()
            out.update(copies=sum(a != b for a, b in zip([None] + steps, steps)),
                       steps=len(steps))
        return out


@functools.lru_cache(maxsize=None)
def _tile_plan(sq: int, sk: int, head_dim: int, causal: bool,
               window: Optional[int] = None, v_head_dim: Optional[int] = None,
               selected: bool = False) -> TilePlan:
    """The schedule of a call from what it can observe; no knob. ``head_dim``
    is the queries' and keys', ``v_head_dim`` the values' (the same where not
    given).

    Blocks are :func:`_block_size`'s. A causal call's blocks are square
    (``sq == sk``) and the ones on the diagonal are walked in ``_DIAG_STRIPS``
    strips of square tiles, never smaller than the 128-lane minimum. With a
    ``window`` (fewer keys than the sequence has) the blocks are
    :func:`_window_block`'s and the grid is the band (:attr:`TilePlan.band`).
    ``selected`` (a causal call whose keys are chosen per query, by an operand):
    the causal plan, every computed piece masked by the operand's block."""
    bq, bk = _block_size(sq, head_dim, v_head_dim), _block_size(sk, head_dim, v_head_dim)
    if not causal:
        return TilePlan(sq, sk, bq, bk, bq, bk, False)
    if sq != sk:
        raise ValueError(f"causal attention needs matching q/k lengths, got {sq} vs {sk}")
    if window is not None:
        bq = bk = _window_block(sq, head_dim, window, v_head_dim)
    t = max(_MIN_BLOCK, bq // _DIAG_STRIPS)
    return TilePlan(sq, sk, bq, bk, t, t, True, window, selected)


# Above this many bytes of materialized (BH, S, Sk) fp32 scores the jnp
# oracle stops being a viable degradation target: the unfused path holds the
# score/probability tensors live through autodiff (several copies across
# forward + backward), so "degrade to jnp" would trade a kernel bug for an
# OOM. Past the budget the Pallas kernel is the ONLY dispatch path — no
# probe, no downgrade, the dispatch is booked via ``count_forced`` so the
# counters prove the oracle was never taken (e.g. the S=8192 backward rung).
_ORACLE_SCORE_BYTES_CAP = 1 << 30  # 1 GiB


def set_oracle_score_budget(nbytes: int) -> int:
    """Set the max materialized-scores footprint (bytes of fp32 (BH, S, Sk))
    at which the jnp oracle is still considered a viable fallback; returns
    the previous budget. Unit tests shrink it to force the flash-only path
    on small shapes."""
    global _ORACLE_SCORE_BYTES_CAP
    prev = _ORACLE_SCORE_BYTES_CAP
    _ORACLE_SCORE_BYTES_CAP = int(nbytes)
    return prev


def oracle_score_budget() -> int:
    return _ORACLE_SCORE_BYTES_CAP


def is_flash_available(seq_len: int, head_dim: int, v_head_dim: Optional[int] = None) -> bool:
    """Shape gate for the Pallas kernel (ref: fused_softmax.py:164
    ``is_kernel_available`` plays the same role for the softmax kernels).

    Requires the sequence to tile exactly into (BQ, BK) blocks and head dims
    (``head_dim`` of queries and keys, ``v_head_dim`` of values: the same where
    not given) that fit VMEM comfortably alongside the accumulators.
    """
    dims = (head_dim, head_dim if v_head_dim is None else v_head_dim)
    return seq_len % _MIN_BLOCK == 0 and all(8 <= d <= 512 for d in dims)


# ---------------------------------------------------------------------------------
# forward kernel: grid (BH, nq, nk), or (BH, live blocks) where a causal head is
# several blocks; key blocks innermost so the VMEM accumulators (acc, m, l) carry
# across the key blocks of one query block
# ---------------------------------------------------------------------------------


# The walk's pieces are written with ``jax.lax`` primitives, not ``jnp``: every
# ``jnp`` function and array operator is a nested ``jit`` whose trace costs
# several primitive binds, a kernel body is traced again at every start of a
# program (three to seven times a step, PR 28), and a walk repeats its piece's
# operations once per piece. ``lax`` broadcasts a size-1 dimension as ``jnp``
# does; the helpers below are what ``keepdims`` and ``x[:, 0:1]`` expand to.


def _col(x):
    """(rows, 128) lane-replicated per-row values -> their first lane, (rows, 1)."""
    return lax.slice_in_dim(x, 0, 1, axis=1)


def _row_reduce(reduce, x):
    """``reduce`` over the columns of ``x``, kept as (rows, 1)."""
    return lax.broadcast_in_dim(reduce(x, (1,)), (x.shape[0], 1), (0,))


def _dot(a, b, contract):
    """MXU product in the operands' dtype (bf16 on the native path) with fp32
    accumulation — casting up first would force the slow multi-pass fp32 mode.
    ``contract`` = (dimension of a, dimension of b)."""
    return lax.dot_general(a, b, (((contract[0],), (contract[1],)), ((), ())),
                           preferred_element_type=jnp.float32)


def _at(block, size, offset):
    """Index ``offset`` into block ``block`` of ``size`` (a static 0 adds no op)."""
    at = lax.mul(block, size)
    return lax.add(at, offset) if offset else at


def _mask(plan, on_diag, i, j, rows, cols, lens, sel_ref=None):
    """Mask predicate of the score piece ``rows`` x ``cols`` of block (i, j).
    True = masked out; None where nothing in the piece can be. ``lens`` is a
    scalar int32 (this sequence's key length), or None when the call has no
    ``kv_lens``: every key is then in range, statically. ``on_diag`` says
    which edges cross the piece (:meth:`TilePlan.band_walk`): 1 (or True) the
    causal diagonal, 2 a window's lower edge, 3 both. ``sel_ref`` (a selected
    plan): the block of the keys each query keeps, which holds the diagonal too
    (a kept key is never after its query), so the piece's mask is read and
    nothing is computed from positions."""
    if sel_ref is not None:
        return lax.eq(sel_ref[0, rows, cols].astype(jnp.int32), 0)
    if lens is None and not on_diag:
        return None
    shape = (rows.stop - rows.start, cols.stop - cols.start)
    kj = lax.add(lax.broadcasted_iota(jnp.int32, shape, 1), _at(j, plan.bk, cols.start))
    masked = None if lens is None else lax.ge(kj, lens)
    if on_diag:
        qi = lax.add(lax.broadcasted_iota(jnp.int32, shape, 0), _at(i, plan.bq, rows.start))
        for bit, outside in ((1, lambda: lax.gt(kj, qi)),
                             (2, lambda: lax.le(lax.add(kj, plan.window), qi))):
            if on_diag & bit:
                over = outside()
                masked = over if masked is None else lax.bitwise_or(masked, over)
    return masked


def _keep_mask(seed_ref, b, i, j, nq, nk, shape, keep_prob):
    """In-kernel dropout keep-mask for score tile (b, i, j) — the TPU
    counterpart of the reference's curand path in its fused kernels
    (ref: apex/contrib/csrc/multihead_attn/dropout.cuh:1-272, consumed by
    every *_func variant, self_multihead_attn_func.py:148-186).

    The PRNG is RE-SEEDED per (batch*head, q-tile, k-tile) from the caller's
    seed plus a mixed tile id, then one (TQ, TK) draw is taken — so the
    forward and every backward kernel regenerate the exact same mask for a
    tile regardless of their different grid orders and walks, the same
    counter-per-block contract as Philox offsets in the reference."""
    block_id = (b * nq + i) * nk + j
    # Knuth multiplicative mix: adjacent block ids land far apart in seed
    # space (raw adjacent seeds risk correlated low bits)
    pltpu.prng_seed(seed_ref[0], block_id * -1640531527)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    # top 24 bits -> [0, 1): the shifted value fits int32, which IS castable
    # to f32 on the VPU (a direct uint32->f32 cast is not)
    u = pltpu.bitcast(bits >> 8, jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))
    return u < keep_prob


def _keep_tiles(plan, seed_ref, b, ti0, tj0, shape, keep_prob):
    """Keep-mask of a panel of ``shape`` whose first tile is (ti0, tj0) of the
    square: the panel's tiles drawn one by one under their own ids, so a
    tile's mask does not depend on which panel a kernel computes it in."""
    tq, tk = plan.tq, plan.tk
    rows = [
        [_keep_mask(seed_ref, b, ti0 + r, tj0 + c, plan.sq // tq, plan.sk // tk,
                    (tq, tk), keep_prob) for c in range(shape[1] // tk)]
        for r in range(shape[0] // tq)
    ]
    if len(rows) == 1 and len(rows[0]) == 1:
        return rows[0][0]
    return jnp.concatenate([jnp.concatenate(r, axis=1) for r in rows], axis=0)


class _Panel(NamedTuple):
    """One strip of a block's walk as a kernel sees it: ``rows`` x ``cols`` of
    the block, one of them the strip itself and the other the run of its
    pieces. The scores are computed piece by piece (``pieces``: row slice,
    column slice, mask or None), so that only the piece on the diagonal pays
    for a mask, and joined along ``axis``; from there on the panel is one
    array and takes one matmul per product. ``masked`` is the panel's whole
    mask where a row of it may have no live key (a call with ``kv_lens``) and
    its probabilities need the explicit zero, else None: causality alone
    leaves every row its diagonal, so exp underflows to exactly 0 there.
    ``keep`` is the dropout keep-mask or None."""

    rows: slice
    cols: slice
    pieces: list
    axis: int
    masked: Optional[jax.Array]
    keep: Optional[jax.Array]


def _join(parts, axis):
    """The pieces of a panel side by side (or stacked) as one array."""
    return parts[0] if len(parts) == 1 else lax.concatenate(parts, axis)


def _panels(plan, by_cols, walk, b, i, j, lens, seed_ref, rate, sel_ref=None):
    """The walk of block (i, j) as ``_Panel``s, every mask built."""
    out = []
    for fixed, moving in walk:
        run = slice(moving[0][0].start, moving[-1][0].stop)
        pieces = []
        for span, on_diag in moving:
            rows, cols = (span, fixed) if by_cols else (fixed, span)
            pieces.append((rows, cols, _mask(plan, on_diag, i, j, rows, cols, lens, sel_ref)))
        rows, cols = (run, fixed) if by_cols else (fixed, run)
        axis = 0 if by_cols else 1
        keep = None
        if rate > 0.0:
            keep = _keep_tiles(
                plan, seed_ref, b,
                _at(i, plan.bq // plan.tq, rows.start // plan.tq),
                _at(j, plan.bk // plan.tk, cols.start // plan.tk),
                (rows.stop - rows.start, cols.stop - cols.start), 1.0 - rate)
        masked = None if lens is None else _join([m for *_, m in pieces], axis)
        out.append(_Panel(rows, cols, pieces, axis, masked, keep))
    return out


def _panel_scores(panel, q_ref, k_ref, scale, fill=_NEG):
    """Scaled, masked scores of a panel, piece by piece and joined."""
    parts = []
    for rows, cols, masked in panel.pieces:
        s = lax.mul(_dot(q_ref[0, rows, :], k_ref[0, cols, :], (1, 1)), scale)
        parts.append(s if masked is None else lax.select(masked, lax.full_like(s, fill), s))
    return _join(parts, panel.axis)


def _fill(plan):
    """Mask fill of a plan's scores. A windowed plan's lies BELOW the running
    max's initial value: in the first block of a band the window's lower edge
    can mask a whole row of a panel while the row's max still stands at its
    initial ``_NEG``, and ``exp(_NEG - _NEG)`` would count every masked key as
    one; ``exp(2 * _NEG - _NEG)`` is exactly 0. (On the diagonal alone every
    row has its own key, which is why the plain causal plan needs none of it.)
    A selected plan's too: a query may keep no key of a block, its own included."""
    return _NEG if plan.window is None and not plan.selected else 2 * _NEG


def _grid_ids(plan, by_cols):
    """``(b, i, j, step)`` of a grid step: batch x head, query block, key
    block, and the position on the last grid axis (the accumulators start at
    step 0 and are written out at ``_steps(plan) - 1``). Off a window the last
    axis IS the other side's block; with one it is the place in the band."""
    b, outer, step = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    if plan.window is None:
        return (b, step, outer, step) if by_cols else (b, outer, step, step)
    if by_cols:                                  # diagonal first, then downwards
        return b, lax.add(outer, step), outer, step
    return b, outer, lax.add(outer, lax.sub(step, plan.band - 1)), step


def _steps(plan, by_cols=False):
    """Length of the last grid axis: of the fwd and dq kernels, or of dkv."""
    if plan.window is not None:
        return plan.band
    return plan.nq if by_cols else plan.nk


def _walk_band(plan, by_cols, i, j, step, block):
    """:func:`_walk_block` of a windowed plan: the block's distance below the
    diagonal is a function of the grid step alone, so each distinct walk of
    the band is emitted once, under the steps it serves, and only where the
    step is inside the sequence."""
    inside = lax.lt(i, plan.nq) if by_cols else lax.ge(j, 0)
    for walk, ds in plan.band_walks(by_cols):
        steps = [d if by_cols else plan.band - 1 - d for d in ds]
        lo, hi = min(steps), max(steps)          # a walk's distances are a run
        at = lax.eq(step, lo) if lo == hi else lax.bitwise_and(
            lax.ge(step, lo), lax.le(step, hi))
        pl.when(lax.bitwise_and(at, inside))(lambda walk=walk: block(walk))


def _walk_block(plan, by_cols, i, j, block, step=None):
    """Run ``block(walk)`` for grid step (i, j): the diagonal's walk where
    the causal diagonal crosses the block, the one-piece walk for a block
    wholly below it and for every block of a non-causal call; nothing for a
    block above it."""
    if plan.window is not None:
        return _walk_band(plan, by_cols, i, j, step, block)
    if plan.causal:
        pl.when(i == j)(lambda: block(plan.walk(by_cols, True)))
    if not plan.causal or plan.nq > 1:
        below = (j < i) if plan.causal else (j >= 0)
        pl.when(below)(lambda: block(plan.walk(by_cols, False)))


def _kernel_scalars(refs, has_lens, rate):
    """(lens ref or None, seed ref or None, the other refs)."""
    refs = list(refs)
    lens_ref = refs.pop(0) if has_lens else None
    seed_ref = refs.pop(0) if rate > 0.0 else None
    return lens_ref, seed_ref, refs


def _fa_fwd_kernel(plan, scale, has_lens, rate, *refs):
    lens_ref, seed_ref, refs = _kernel_scalars(refs, has_lens, rate)
    if plan.live_axis:
        # the grid is (BH, live blocks): a step reads its blocks from the two
        # tables (TilePlan.fwd_steps), which go key block 0 to the diagonal
        i_ref, j_ref, *refs = refs
        b, step = pl.program_id(0), pl.program_id(1)
        i, j = i_ref[step], j_ref[step]
        first, last = (lambda: j == 0), (lambda: j == i)
    else:
        b, i, j, step = _grid_ids(plan, False)
        # compared where the accumulators are set up and written out, not here:
        # a one_pass plan does neither, and its body stays what it was
        first, last = (lambda: step == 0), (lambda: step == _steps(plan) - 1)
    q_ref, k_ref, v_ref, *refs = refs
    sel_ref = refs.pop(0) if plan.selected else None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    lens = lens_ref[b] if has_lens else None
    one_pass = plan.one_pass
    fill = _fill(plan)

    if not one_pass:
        @pl.when(first())
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def finish(pn, m, l, acc):
        """A strip's rows of the output and of lse from their final (rows, 1)
        statistics, as ``_final`` makes them from the accumulators."""
        if pn.masked is None:        # every row has its diagonal: l > 0
            o, lse = lax.div(acc, l), lax.add(m, lax.log(l))
        else:
            nonempty = lax.gt(l, 0.0)
            safe = lax.select(nonempty, l, lax.full_like(l, 1.0))
            o = lax.select(lax.broadcast_in_dim(nonempty, acc.shape, (0, 1)),
                           lax.div(acc, safe), lax.full_like(acc, 0.0))
            lse = lax.select(nonempty, lax.add(m, lax.log(safe)), lax.full_like(m, _NEG))
        o_ref[0, pn.rows, :] = o.astype(o_ref.dtype)
        lse_ref[0, pn.rows, :] = lax.broadcast_in_dim(lse, (lse.shape[0], 128), (0, 1))

    # A walk is emitted phase by phase, not strip by strip: the strips are
    # independent, and with each phase's panels side by side the scheduler
    # overlaps one panel's matmul with another's vector work instead of
    # waiting out every strip's matmul -> max -> exp -> matmul chain
    def block(walk):
        panels = _panels(plan, False, walk, b, i, j, lens, seed_ref, rate, sel_ref)
        # phase 1 — the scores of every panel
        scores = [_panel_scores(pn, q_ref, k_ref, scale, fill) for pn in panels]
        # phase 2 — the (running) max: one cross-lane reduction per strip
        stats = []
        for pn, s in zip(panels, scores):
            m_new = _row_reduce(lax.reduce_max, s)
            if one_pass:
                stats.append((m_new, m_new, None))
            else:
                m_prev = m_ref[pn.rows, :]           # (rows, 128) lane-replicated
                m_new = lax.max(m_prev, m_new)
                stats.append((m_new, _col(m_new), lax.exp(lax.sub(m_prev, m_new))))
        # phase 3 — probabilities, normalizer and output
        for pn, s, (m_new, m_col, alpha) in zip(panels, scores, stats):
            p = lax.exp(lax.sub(s, m_col))
            if pn.masked is not None:
                # explicit zero on masked slots: when a whole row is masked
                # s == m_new == _NEG and exp(s - m) would be 1, not 0
                p = lax.select(pn.masked, lax.full_like(p, 0.0), p)
            # the softmax normalizer l sums the UNDROPPED p: out_i = (1/l_i)
            # sum_j mask_ij/keep * p_ij v_j == softmax->dropout->matmul
            # (torch's order, self_multihead_attn_func.py:148-186) — dropping
            # after normalization, expressed online
            l = _row_reduce(lax.reduce_sum, p)
            if rate > 0.0:
                p = lax.select(pn.keep, lax.mul(p, 1.0 / (1.0 - rate)),
                               lax.full_like(p, 0.0))
            pv = _dot(p.astype(v_ref.dtype), v_ref[0, pn.cols, :], (1, 0))
            if one_pass:
                finish(pn, m_new, l, pv)
            else:
                l_ref[pn.rows, :] = lax.add(lax.mul(alpha, l_ref[pn.rows, :]), l)
                acc_ref[pn.rows, :] = lax.add(lax.mul(acc_ref[pn.rows, :], _col(alpha)), pv)
                m_ref[pn.rows, :] = m_new

    _walk_block(plan, False, i, j, block, step)

    if not one_pass:
        @pl.when(last())
        def _final():
            l = l_ref[:, 0:1]
            nonempty = l > 0.0
            o = jnp.where(nonempty, acc_ref[...] / jnp.where(nonempty, l, 1.0), 0.0)
            o_ref[0] = o.astype(o_ref.dtype)
            # lane-replicated (BQ, 128) — the TPU-native layout for per-row
            # scalars (a (1, BQ) block fails Mosaic's (8, 128) tiling rule)
            lse_ref[0] = jnp.where(
                nonempty, m_ref[...] + jnp.log(jnp.where(nonempty, l_ref[...], 1.0)), _NEG
            )


def _widths(q, v):
    """``(Dk, Dv)`` of a call's ``(BH, S, D)`` operands: the width of queries
    and keys (the scores' depth) and the width of values and of the result."""
    return q.shape[2], v.shape[2]


def _book_tiles(plan, widths, has_lens, *kernels):
    """Book the plan's tile counts once per kernel traced with it (a windowed
    plan's key ends in its window; a call whose values are as wide as its keys
    books the one width, as it always did, the others ``(Dk, Dv)``)."""
    dk, dv = widths
    key = (plan.sq, plan.sk, dk if dk == dv else widths, plan.causal, has_lens)
    if plan.window is not None:
        key += (plan.window,)
    if plan.selected:
        key += ("selected",)
    for kernel in kernels:
        _count_tiles("flash_attention", kernel, key, **plan.counts(has_lens, kernel == "fwd"))


def _block_maps(plan):
    """Index maps ``(own, keys, queries)`` of the (1, block, Dk or Dv) operands:
    ``own`` follows a kernel's outer block; ``keys`` (fwd, dq: query block
    outer) and ``queries`` (dkv: key block outer) follow the last grid axis.
    In a windowed plan that axis walks the band, offset from the outer block
    and clamped into the sequence (:attr:`TilePlan.band`). In a causal plan of
    several blocks it is clamped onto the diagonal: a step above it names the
    block the diagonal step named, and a repeated block is not copied again
    (the forward of such a plan takes no such step: :func:`_live_grid`)."""
    own = lambda b, o, s, *_: (b, o, 0)
    if plan.window is None:
        if plan.causal and plan.nq > 1:
            keys = lambda b, i, s, *_: (b, lax.min(s, i), 0)
            queries = lambda b, j, s, *_: (b, lax.max(s, j), 0)
            return own, keys, queries
        other = lambda b, o, s, *_: (b, s, 0)
        return own, other, other
    back, last = plan.band - 1, plan.nq - 1
    keys = lambda b, i, s, *_: (b, jnp.maximum(i + s - back, 0), 0)
    queries = lambda b, j, s, *_: (b, jnp.minimum(j + s, last), 0)
    return own, keys, queries


def _kernel_name(plan, kernel):
    """A windowed plan's kernels, and a selected plan's, carry their own names
    in the device trace, under the op's prefix; the others keep the scope's
    (``%flash_attention.N``)."""
    if plan.selected:
        return f"flash_attention_sparse_{kernel}"
    return None if plan.window is None else f"flash_attention_window_{kernel}"


def _sel_spec(plan, sel, BH, q_map, k_map):
    """``[BlockSpec]`` of a selected plan's operand ``sel (B, Sq, Sk)`` (``[]``
    where the call has none): the ``(1, bq, bk)`` block of the step's query block
    and key block, as ``q_map`` and ``k_map`` (the index maps of the kernel's
    query-side and key-side operands) name them, of the batch row that head ``b``
    of the ``BH`` belongs to — the heads of a row share one selection."""
    if sel is None:
        return []
    heads = BH // sel.shape[0]
    return [pl.BlockSpec((1, plan.bq, plan.bk),
                         lambda b, *at: (b // heads, q_map(b, *at)[1], k_map(b, *at)[1]))]


def _scalar_operands(lens, seed, rate):
    """Scalar-prefetch operands: the key lengths (when the call has any) and
    the dropout seed (when active). They ride SMEM (a (1,1)-blocked SMEM
    operand fails Mosaic's tiling check); index maps receive the scalar refs
    last — ``*_`` absorbs however many there are."""
    scalars = [] if lens is None else [lens.astype(jnp.int32)]
    if rate > 0.0:
        scalars.append(seed.astype(jnp.int32))
    return scalars


def _live_grid(plan, BH):
    """``(grid, tables, own, keys)`` of the forward of a plan whose last axis
    names live blocks only (:attr:`TilePlan.live_axis`): ``(BH, live blocks)``,
    the query block and the key block of each step as two int32 tables that ride
    SMEM after the other scalars, and the index maps that read them."""
    i_tab, j_tab = np.asarray(plan.fwd_steps(), np.int32).T
    own = lambda b, s, *scalars: (b, scalars[-2][s], 0)
    keys = lambda b, s, *scalars: (b, scalars[-1][s], 0)
    return (BH, len(i_tab)), [jnp.asarray(i_tab), jnp.asarray(j_tab)], own, keys


def _fa_fwd_pallas(q, k, v, lens, causal, scale, interpret, rate=0.0, seed=None,
                   window=None, sel=None):
    """``lens=None``: the call has no ``kv_lens`` — no length test anywhere.
    ``sel``: the kept keys of a selected call, int8 ``(B, Sq, Sk)``."""
    BH, Sq, _ = q.shape
    Dk, Dv = _widths(q, v)
    plan = _tile_plan(Sq, k.shape[1], Dk, causal, window, Dv, sel is not None)
    bq, bk = plan.bq, plan.bk
    _book_tiles(plan, (Dk, Dv), lens is not None, "fwd")
    scalars = _scalar_operands(lens, seed, rate)
    if plan.live_axis:
        grid, tables, own, keys = _live_grid(plan, BH)
        scalars += tables
    else:
        grid = (BH, plan.nq, _steps(plan))
        own, keys, _ = _block_maps(plan)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bq, Dk), own), pl.BlockSpec((1, bk, Dk), keys),
                  pl.BlockSpec((1, bk, Dv), keys)] + _sel_spec(plan, sel, BH, own, keys),
        out_specs=[
            pl.BlockSpec((1, bq, Dv), own),
            pl.BlockSpec((1, bq, 128), own),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        functools.partial(_fa_fwd_kernel, plan, scale, lens is not None, rate),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1) + ("arbitrary",)
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, Sq, 128), jnp.float32),
        ],
        interpret=interpret,
        name=_kernel_name(plan, "fwd"),
    )(*scalars, q, k, v, *(() if sel is None else (sel,)))
    return o, lse


# ---------------------------------------------------------------------------------
# backward: dq kernel (grid BH, nq, nk) + dkv kernel (grid BH, nk, nq), or the two
# in one: where a head is one block (grid BH, 1, 1), and where a causal head of
# several blocks keeps its dq in VMEM (grid BH, nk, nq; BH, nk, band with a
# window); all recompute block scores from (q, k, lse) — flash-attention
# rematerialization
# ---------------------------------------------------------------------------------


def _row_delta(do, o):
    """delta_i = rowsum(dO_i * O_i) as (rows, 1), recomputed from the o/do
    blocks (cheap VPU work vs another HBM residual)."""
    return _row_reduce(lax.reduce_sum,
                       lax.mul(do.astype(jnp.float32), o.astype(jnp.float32)))


def _panel_p_ds(scale, s, dp, lse, delta, dlse, panel, rate):
    """Shared recompute: dv-side probabilities z and score-grad ds of a panel
    from its scores ``s`` (:func:`_panel_scores`) and ``dp = do . v``.
    ``lse`` and ``dlse`` are (rows, 128) lane-replicated, ``delta``
    (:func:`_row_delta`) is (rows, 1). ``dlse`` (or None) is the cotangent of
    the EXPOSED lse output (zero for plain attention; nonzero when the caller
    merges chunk outputs by lse, as ring attention does — d lse_i/d s_ij =
    p_ij adds dlse_i inside the parens).

    With dropout (``rate > 0``) the forward computed out_i = sum_j z_ij v_j
    with z = keep/(1-rate) * softmax(s); the same mask regenerates here
    (:func:`_keep_mask` is deterministic per tile). The chain rule gives
    dp~_ij = (do_i . v_j) * keep_ij/(1-rate), and the softmax-backward
    rowsum term STAYS delta_i = do_i . o_i because
    sum_k dp~_ik p_ik = sum_k (do.v_k) z_ik = do_i . o_i — the undropped
    p carries the Jacobian, the dropped z carries dv."""
    p = lax.exp(lax.sub(s, _col(lse)))
    if panel.masked is not None:  # as in the forward: a row with no live key
        p = lax.select(panel.masked, lax.full_like(p, 0.0), p)
    if rate > 0.0:
        inv = 1.0 / (1.0 - rate)
        zero = lax.full_like(p, 0.0)
        z = lax.select(panel.keep, lax.mul(p, inv), zero)
        dp = lax.select(panel.keep, lax.mul(dp, inv), zero)
    else:
        z = p
    inner = lax.add(lax.sub(dp, delta), _col(dlse) if dlse is not None else 0.0)
    return z, lax.mul(lax.mul(p, inner), scale)


def _bwd_refs(refs, has_dlse, selected=False):
    """Split a backward kernel's refs after the scalars: the six operands,
    dlse (or None) and a selected plan's block of kept keys (or None), then the
    outputs and accumulators."""
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest = refs
    dlse_ref = rest.pop(0) if has_dlse else None
    sel_ref = rest.pop(0) if selected else None
    return (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref, sel_ref), rest


def _fa_dq_kernel(plan, scale, has_lens, has_dlse, rate, *refs):
    lens_ref, seed_ref, refs = _kernel_scalars(refs, has_lens, rate)
    (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref, sel_ref), rest = _bwd_refs(
        refs, has_dlse, plan.selected)
    dq_ref, dq_acc = rest
    b, i, j, step = _grid_ids(plan, False)
    lens = lens_ref[b] if has_lens else None
    fill = _fill(plan)

    @pl.when(step == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def block(walk):  # phase by phase, as the forward
        panels = _panels(plan, False, walk, b, i, j, lens, seed_ref, rate, sel_ref)
        scores = []
        for pn in panels:
            do = do_ref[0, pn.rows, :]
            scores.append((_panel_scores(pn, q_ref, k_ref, scale, fill),
                           _dot(do, v_ref[0, pn.cols, :], (1, 1)),
                           _row_delta(do, o_ref[0, pn.rows, :])))
        grads = [
            _panel_p_ds(scale, s, dp, lse_ref[0, pn.rows, :], delta,
                        dlse_ref[0, pn.rows, :] if has_dlse else None, pn, rate)[1]
            for pn, (s, dp, delta) in zip(panels, scores)]
        for pn, ds in zip(panels, grads):
            dq = _dot(ds.astype(k_ref.dtype), k_ref[0, pn.cols, :], (1, 0))
            dq_acc[pn.rows, :] = lax.add(dq_acc[pn.rows, :], dq)

    _walk_block(plan, False, i, j, block, step)

    @pl.when(step == _steps(plan) - 1)
    def _final():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_block(plan, scale, rate, walk, b, i, j, lens, seed_ref, operands,
               dk_acc, dv_acc, dq_acc=None):
    """One block of the key-block-outer walk: dk and dv of its panels (strips
    of key columns) added to the key block's accumulators and, where the kernel
    sums dq too (``dq_acc``: the head's float32 dq, ``(nq, bq, Dk)``), query
    block ``i``'s share of dq from the same ``ds``. Phase by phase, as the
    forward."""
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref, sel_ref = operands
    panels = _panels(plan, True, walk, b, i, j, lens, seed_ref, rate, sel_ref)
    # once for the block: a row's delta serves every strip that reaches it
    delta = _row_delta(do_ref[0], o_ref[0])
    scores = []
    for pn in panels:
        do = do_ref[0, pn.rows, :]
        scores.append((_panel_scores(pn, q_ref, k_ref, scale, _fill(plan)),
                       _dot(do, v_ref[0, pn.cols, :], (1, 1)), do))
    grads = [
        _panel_p_ds(scale, s, dp, lse_ref[0, pn.rows, :],
                    delta[pn.rows, :],
                    dlse_ref[0, pn.rows, :] if dlse_ref is not None else None, pn, rate)
        for pn, (s, dp, _) in zip(panels, scores)]
    for pn, (z, ds), (_, _, do) in zip(panels, grads, scores):
        q = q_ref[0, pn.rows, :]
        # dv sees the DROPPED probabilities z (dropout sits between
        # softmax and the @v matmul); dk/dq flow through ds, whose
        # rowsum term keeps the undropped p Jacobian — _panel_p_ds
        dv = _dot(z.astype(do.dtype), do, (0, 0))
        ds = ds.astype(q.dtype)
        dk = _dot(ds, q, (0, 0))
        dv_acc[pn.cols, :] = lax.add(dv_acc[pn.cols, :], dv)
        dk_acc[pn.cols, :] = lax.add(dk_acc[pn.cols, :], dk)
        if dq_acc is not None:
            dq = _dot(ds, k_ref[0, pn.cols, :], (1, 0))
            dq_acc[i, pn.rows, :] = lax.add(dq_acc[i, pn.rows, :], dq)


def _fa_dkv_kernel(plan, scale, has_lens, has_dlse, rate, *refs):
    lens_ref, seed_ref, refs = _kernel_scalars(refs, has_lens, rate)
    operands, rest = _bwd_refs(refs, has_dlse, plan.selected)
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    # k block outer, q block inner
    b, i, j, step = _grid_ids(plan, True)
    lens = lens_ref[b] if has_lens else None

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def block(walk):  # as the dq kernel's; strips are key columns
        _dkv_block(plan, scale, rate, walk, b, i, j, lens, seed_ref, operands,
                   dk_acc, dv_acc)

    _walk_block(plan, True, i, j, block, step)

    @pl.when(step == _steps(plan, True) - 1)
    def _final():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_dqkv_kernel(plan, scale, has_lens, has_dlse, rate, *refs):
    """The whole backward of a head that is one block (``plan.one_pass``): the
    dq kernel's row walk, with dk and dv taken from the same ``z`` and ``ds``
    instead of from a second recompute of them. A strip's dq rows are final
    when the strip is done. A key tile's dk and dv sum over every strip that
    reaches it, in float32 and rounded once: the first of those strips stores
    its share in VMEM, the ones between add theirs, the last adds and writes
    the result out — the walk is static, so which strip is which is known
    here, and nothing is zeroed first or copied out afterwards."""
    lens_ref, seed_ref, refs = _kernel_scalars(refs, has_lens, rate)
    (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dlse_ref, sel_ref), rest = _bwd_refs(
        refs, has_dlse, plan.selected)
    dq_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    b, i, j, _ = _grid_ids(plan, False)
    lens = lens_ref[b] if has_lens else None
    fill = _fill(plan)
    # the one block is on the diagonal, and at the window's lower edge if any
    walk = plan.walk(False, True) if plan.window is None else plan.band_walk(False, 0)

    panels = _panels(plan, False, walk, b, i, j, lens, seed_ref, rate, sel_ref)
    delta = _row_delta(do_ref[0], o_ref[0])      # once for the block
    # the key tiles each strip reaches, and the first and last strip to reach each
    tiles = [range(pn.cols.start // plan.tk, pn.cols.stop // plan.tk) for pn in panels]
    first = {c: n for n in reversed(range(len(panels))) for c in tiles[n]}
    last = {c: n for n in range(len(panels)) for c in tiles[n]}

    def recompute(pn):
        do = do_ref[0, pn.rows, :]
        return (_panel_scores(pn, q_ref, k_ref, scale, fill),
                _dot(do, v_ref[0, pn.cols, :], (1, 1)), do)

    # A strip's two score products are emitted one strip ahead of its three
    # result products, so the MXU, which bounds this kernel (at D = 64 half of
    # each pass is idle), has the next strip's scores to make while the vector
    # units turn this strip's into p and ds. On a v5e at the GPT cells' call
    # (S=1024, D=64; ms for 64 heads, the operands' copies included; PR 41):
    # every phase for all strips first, as the forward has it, 0.628; strip
    # after strip 0.619; this 0.611.
    ahead = recompute(panels[0])
    for n, pn in enumerate(panels):
        s, dp, do = ahead
        if n + 1 < len(panels):
            ahead = recompute(panels[n + 1])
        z, ds = _panel_p_ds(scale, s, dp, lse_ref[0, pn.rows, :], delta[pn.rows, :],
                            dlse_ref[0, pn.rows, :] if has_dlse else None, pn, rate)
        ds = ds.astype(k_ref.dtype)
        dq = _dot(ds, k_ref[0, pn.cols, :], (1, 0))
        dq_ref[0, pn.rows, :] = dq.astype(dq_ref.dtype)
        # dv sees the DROPPED probabilities z, dk the score-grad ds, as in the
        # dkv kernel
        sums = ((dv_acc, dv_ref, _dot(z.astype(do.dtype), do, (0, 0))),
                (dk_acc, dk_ref, _dot(ds, q_ref[0, pn.rows, :], (0, 0))))
        for (new, done), run in itertools.groupby(
                tiles[n], lambda c: (first[c] == n, last[c] == n)):
            run = list(run)
            cols = slice(run[0] * plan.tk, (run[-1] + 1) * plan.tk)
            for acc, out, part in sums:
                part = lax.slice_in_dim(part, cols.start - pn.cols.start,
                                        cols.stop - pn.cols.start)
                if not new:
                    part = lax.add(acc[cols, :], part)
                if done:
                    out[0, cols, :] = part.astype(out.dtype)
                else:
                    acc[cols, :] = part


def _fa_dqkv_blocks_kernel(plan, scale, has_lens, has_dlse, rate, *refs):
    """The whole backward of a causal head of several blocks: the dkv kernel's
    walk (key block ``j`` outer, query block ``i`` inner, from the diagonal
    downwards), with dq taken from the same ``ds`` instead of from a second
    recompute of it. dk and dv sum over ``i`` in their ``(bk, D)`` scratch as in
    the dkv kernel. dq sums over ``j``, the OUTER axis, so the float32 dq of the
    whole head stays in VMEM (``dq_acc``: one block a query block): step
    ``(j, i)`` adds ``ds @ k_j`` to block ``i``. With ``j`` ascending, query
    block ``j`` has every key block before ``j`` in it when the diagonal step
    ``(j, j)`` — the first live step of ``j``'s walk — adds the last, so it is
    rounded and written to the ``(1, bq, Dk)`` output block there and leaves
    when ``j`` moves on: dq never sits in HBM in float32, and is summed in the
    order the dq kernel sums it.

    A windowed plan is the same walk cut to its band (``TilePlan.band``: query
    blocks ``j .. j + band - 1``, the diagonal still first, so the last key block
    of query block ``j`` is still ``j``). Only where a query block's sum STARTS
    moves: at the band's far end, its last step — or anywhere in key block 0's
    walk, which is the first to reach the first ``band`` query blocks. The steps
    clamped past the sequence's end compute nothing and touch no slot."""
    lens_ref, seed_ref, refs = _kernel_scalars(refs, has_lens, rate)
    operands, rest = _bwd_refs(refs, has_dlse, plan.selected)
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
    b, i, j, step = _grid_ids(plan, True)
    lens = lens_ref[b] if has_lens else None

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # query block i's first term: key block 0's without a window; with one, the
    # band's far end (its last step) or, in key block 0's walk, every step
    first = j == 0
    if plan.window is not None:
        first = lax.bitwise_and(lax.bitwise_or(first, step == plan.band - 1), i < plan.nq)

    @pl.when(first)
    def _init_dq():
        dq_acc[i] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    def block(walk):
        _dkv_block(plan, scale, rate, walk, b, i, j, lens, seed_ref, operands,
                   dk_acc, dv_acc, dq_acc)

    _walk_block(plan, True, i, j, block, step)

    @pl.when(i == j)
    def _dq_final():
        dq_ref[0] = dq_acc[i].astype(dq_ref.dtype)

    @pl.when(step == _steps(plan, True) - 1)
    def _final():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_call(body, kernel, plan, args, *, grid, in_specs, out_specs, out_like, scratch,
              sel=None, sel_maps=None, **compiler_params):
    """One backward ``pallas_call`` of ``args`` (:func:`_fa_bwd_pallas`'s, from
    ``q`` on): ``body`` over the scalars, the six operands — ``in_specs``, whose
    last serves ``dlse`` too — a selected plan's kept keys ``sel`` under
    ``sel_maps`` (:func:`_sel_spec`'s two maps), the outputs shaped like ``out_like`` and
    float32 ``scratch``; books the plan's tiles under ``kernel``.
    ``compiler_params`` replace Mosaic's defaults and the (parallel, parallel,
    arbitrary) grid."""
    *operands, dlse, lens, scale, interpret, rate, seed = args
    has_dlse = dlse is not None
    scalars = _scalar_operands(lens, seed, rate)
    _book_tiles(plan, _widths(operands[0], operands[2]), lens is not None, kernel)
    compiler_params.setdefault("dimension_semantics", ("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(body, plan, scale, lens is not None, has_dlse, rate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs + ([in_specs[-1]] if has_dlse else [])
            + _sel_spec(plan, sel, operands[0].shape[0], *(sel_maps or ())),
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in out_like],
        compiler_params=pltpu.CompilerParams(**compiler_params),
        interpret=interpret,
        name=_kernel_name(plan, kernel),
    )(*scalars, *operands, *((dlse,) if has_dlse else ()), *(() if sel is None else (sel,)))


def _fa_bwd_fused(plan, *args, sel=None):
    """(dq, dk, dv) of a ``one_pass`` plan from one call on a (BH, 1, 1) grid."""
    q, k, v = args[:3]
    BH, (Dk, Dv) = q.shape[0], _widths(q, v)
    own, _, _ = _block_maps(plan)
    at_k, at_v = (pl.BlockSpec((1, plan.bq, D), own) for D in (Dk, Dv))
    return _bwd_call(
        _fa_dqkv_kernel, "dqkv", plan, args, grid=(BH, 1, 1),
        in_specs=[at_k, at_k, at_v, at_v, at_v, pl.BlockSpec((1, plan.bq, 128), own)],
        out_specs=[at_k, at_k, at_v], out_like=(q, k, v),
        scratch=[(plan.bk, Dk), (plan.bk, Dv)], sel=sel, sel_maps=(own, own))


def _fa_bwd_two_calls(plan, *args, sel=None):
    """(dq, dk, dv) from the dq kernel (grid BH, nq, nk: dq carries over key
    blocks) and the dkv kernel (grid BH, nk, nq: dk and dv over query blocks)."""
    q, k, v = args[:3]
    BH, (Dk, Dv) = q.shape[0], _widths(q, v)
    bq, bk = plan.bq, plan.bk
    own, keys, queries = _block_maps(plan)
    # q, k (and dq, dk) are Dk wide; v, do, o (and dv) Dv wide
    spec = lambda rows, D, at: pl.BlockSpec((1, rows, D), at)
    (dq,) = _bwd_call(
        _fa_dq_kernel, "dq", plan, args, grid=(BH, plan.nq, _steps(plan)),
        in_specs=[spec(bq, Dk, own), spec(bk, Dk, keys), spec(bk, Dv, keys),
                  spec(bq, Dv, own), spec(bq, Dv, own), spec(bq, 128, own)],
        out_specs=[spec(bq, Dk, own)], out_like=(q,), scratch=[(bq, Dk)],
        sel=sel, sel_maps=(own, keys))
    # dkv grid: (BH, k-block, q-block) — q-side operands indexed by the INNER id
    dk, dv = _bwd_call(
        _fa_dkv_kernel, "dkv", plan, args, grid=(BH, plan.nk, _steps(plan, True)),
        in_specs=[spec(bq, Dk, queries), spec(bk, Dk, own), spec(bk, Dv, own),
                  spec(bq, Dv, queries), spec(bq, Dv, queries), spec(bq, 128, queries)],
        out_specs=[spec(bk, Dk, own), spec(bk, Dv, own)], out_like=(k, v),
        scratch=[(bk, Dk), (bk, Dv)], sel=sel, sel_maps=(queries, own))
    return dq, dk, dv


# Float32 bytes of one head's dq (``Sq * Dk * 4``) that the fused backward of
# several blocks may keep in VMEM for the head's whole walk. 8 MiB admits every
# cell at S = 8192 (D = 256: 8 MiB; 192: 6; 128: 4; 64: 2 — in VMEM a row takes
# whole lane tiles, so 192 holds 8 and 64 holds 4: ``_blocks_vmem_bytes``) and
# S = 16,384 at D = 128, and with the dkv kernel's own ~16 MiB of blocks and
# intermediates beside it stays under a third of a v5e's 128 MiB. A longer or
# wider head keeps the two calls.
_HEAD_DQ_BYTES = 8 * 2 ** 20


def _blocks_vmem_bytes(plan, Dk, Dv, itemsize):
    """VMEM the fused backward of several blocks asks Mosaic for (a ceiling,
    not a reservation), from the plan's own bytes: every operand and result
    block twice (the pipeline's two buffers), the three float32 accumulators —
    the head's dq among them — and six (bq, bk) float32 intermediates (s, dp,
    p, ds and the two cast for the MXU, transposed for dk and dv)."""
    bq, bk = plan.bq, plan.bk
    wide, narrow = (-(-d // 128) * 128 for d in (Dk, Dv))     # a row takes whole lane tiles
    blocks = itemsize * (2 * (bq + bk) * wide + (2 * bq + 2 * bk) * narrow)   # q dq k dk, do o v dv
    blocks += 2 * 4 * bq * 128                                                # lse, dlse
    scratch = 4 * (plan.sq * wide + bk * (wide + narrow))
    if plan.selected:       # the kept keys' int8 block, and its int32 copy among the six
        blocks += bq * bk
        scratch += 4 * bq * bk
    return 2 * blocks + scratch + 6 * 4 * bq * bk


def _fa_bwd_blocks(plan, *args, sel=None):
    """(dq, dk, dv) of a causal plan of several blocks from ONE call on the dkv
    kernel's grid — (BH, nk, nq), or (BH, nk, band) with a window — a head's dq
    summed in VMEM (:func:`_fa_dqkv_blocks_kernel`). The key axis carries that
    scratch, so it is ``arbitrary`` too. The query side's maps are the dkv
    kernel's (:func:`_block_maps`): clamped onto the diagonal, where the steps
    above it name the block the diagonal step takes and copy nothing; with a
    window the band's, clamped onto the last block."""
    q, k, v = args[:3]
    BH, (Dk, Dv) = q.shape[0], _widths(q, v)
    bq, bk = plan.bq, plan.bk
    own, _, queries = _block_maps(plan)
    spec = lambda rows, D, at: pl.BlockSpec((1, rows, D), at)
    return _bwd_call(
        _fa_dqkv_blocks_kernel, "dqkv_blocks", plan, args,
        grid=(BH, plan.nk, _steps(plan, True)),
        in_specs=[spec(bq, Dk, queries), spec(bk, Dk, own), spec(bk, Dv, own),
                  spec(bq, Dv, queries), spec(bq, Dv, queries), spec(bq, 128, queries)],
        out_specs=[spec(bq, Dk, own), spec(bk, Dk, own), spec(bk, Dv, own)],
        out_like=(q, k, v), scratch=[(plan.nq, bq, Dk), (bk, Dk), (bk, Dv)],
        sel=sel, sel_maps=(queries, own),
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_blocks_vmem_bytes(plan, Dk, Dv, q.dtype.itemsize))


def _bwd_of(plan, Dk):
    """Which backward a plan takes, from what the call can observe: by what
    carries over between grid steps, and whether VMEM can hold it."""
    if plan.one_pass:
        return _fa_bwd_fused
    if plan.causal and plan.sq * Dk * 4 <= _HEAD_DQ_BYTES:
        return _fa_bwd_blocks
    return _fa_bwd_two_calls


def _fa_bwd_pallas(q, k, v, do, o, lse, dlse, lens, causal, scale, interpret,
                   rate=0.0, seed=None, window=None, sel=None):
    """The backward of a flash call, by what its plan says carries over between
    grid steps (:func:`_bwd_of`); every plan recomputes the scores, ``do . v``,
    the ``exp``, the masks and ``ds`` from (q, k, lse).

    Where a head is ONE block (``plan.one_pass``: causal, S <= 1024 at D <= 128,
    S <= 512 above) nothing carries over, and one call (:func:`_fa_bwd_fused`)
    recomputes once for dq, dk and dv: five products and one vector pass over
    the live tiles, every operand read once.

    Where a causal head is several blocks, with or without a window, dk / dv sum
    over a key block's query blocks and dq over a query block's key blocks. One grid
    order keeps only one of them in a block-sized accumulator, but the other
    fits VMEM whole: one call (:func:`_fa_bwd_blocks`) walks the square by key
    block, holds the head's float32 dq (``Sq * Dk * 4 <= _HEAD_DQ_BYTES``) and
    again recomputes once — five products and one vector pass. The diagonal
    makes it possible: query block ``j``'s dq is complete when key block ``j``
    is done, so it leaves as a block, in the order of the walk. A window only
    shortens the walk to its band (grid ``(BH, nk, band)``).

    Everything else — a non-causal call (dq is final only after the LAST key
    block), a head too long for VMEM — takes two calls
    (:func:`_fa_bwd_two_calls`), each walking the square its own way and each
    recomputing: seven products and two vector passes.

    ``dlse=None`` (the plain-attention path) omits the operand entirely —
    an all-zero lane-replicated dlse would otherwise add an arena-sized HBM
    read to every backward kernel for nothing. ``lens=None``: no ``kv_lens``.
    ``sel``: a selected call's kept keys; its plan is the causal one, so the rule
    above picks its backward too."""
    Dk, Dv = _widths(q, v)
    plan = _tile_plan(q.shape[1], k.shape[1], Dk, causal, window, Dv, sel is not None)
    return _bwd_of(plan, Dk)(
        plan, q, k, v, do, o, lse, dlse, lens, scale, interpret, rate, seed, sel=sel)


# ---------------------------------------------------------------------------------
# custom VJP over the (BH, S, D) view (Pallas path)
# ---------------------------------------------------------------------------------


def _zeros_like(lens):
    """Cotangent of the key lengths, which may be absent (``None``)."""
    return None if lens is None else jnp.zeros_like(lens)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash3(q, k, v, lens, seed, causal, scale, rate, window=None):
    o, _ = _fa_fwd_pallas(q, k, v, lens, causal, scale, _interpret_default(),
                          rate, seed, window)
    return o


def _flash3_fwd(q, k, v, lens, seed, causal, scale, rate, window):
    o, lse = _fa_fwd_pallas(q, k, v, lens, causal, scale, _interpret_default(),
                            rate, seed, window)
    # remat boundary tag: under a save_only_these_names policy the (BH, S)
    # lse rows survive checkpointing so the flash backward can rebuild the
    # probabilities without a full forward re-run (identity otherwise)
    lse = _checkpoint_name(lse, _TAG_FLASH_LSE)
    return o, (q, k, v, lens, seed, o, lse)


def _flash3_bwd(causal, scale, rate, window, res, do):
    q, k, v, lens, seed, o, lse = res
    dq, dk, dv = _fa_bwd_pallas(
        q, k, v, do, o, lse, None, lens, causal, scale, _interpret_default(),
        rate, seed, window,
    )
    return dq, dk, dv, _zeros_like(lens), jnp.zeros_like(seed)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


# --- the selected-keys call: causal, the kept keys an int8 operand ---------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash3_selected(q, k, v, sel, scale):
    o, _ = _fa_fwd_pallas(q, k, v, None, True, scale, _interpret_default(), sel=sel)
    return o


def _flash3_selected_fwd(q, k, v, sel, scale):
    o, lse = _fa_fwd_pallas(q, k, v, None, True, scale, _interpret_default(), sel=sel)
    lse = _checkpoint_name(lse, _TAG_FLASH_LSE)
    return o, (q, k, v, sel, o, lse)


def _flash3_selected_bwd(scale, res, do):
    q, k, v, sel, o, lse = res
    dq, dk, dv = _fa_bwd_pallas(
        q, k, v, do, o, lse, None, None, True, scale, _interpret_default(), sel=sel)
    return dq, dk, dv, np.zeros(sel.shape, jax.dtypes.float0)


_flash3_selected.defvjp(_flash3_selected_fwd, _flash3_selected_bwd)


def _probe_flash_selected(q3, k3, v3, sel, *, scale):
    """Guard probe of the selected-keys call: its forward and backward kernels."""
    o, vjp = jax.vjp(lambda q, k, v: _flash3_selected(q, k, v, sel, scale), q3, k3, v3)
    vjp(jnp.zeros_like(o))
    return o


# --- (o, lse) variant for chunk-merging callers (ring attention) ----------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash3_lse(q, k, v, lens, causal, scale, window=None):
    o, lse = _fa_fwd_pallas(q, k, v, lens, causal, scale, _interpret_default(),
                            window=window)
    return o, lse[..., 0]


def _flash3_lse_fwd(q, k, v, lens, causal, scale, window):
    o, lse = _fa_fwd_pallas(q, k, v, lens, causal, scale, _interpret_default(),
                            window=window)
    lse = _checkpoint_name(lse, _TAG_FLASH_LSE)
    return (o, lse[..., 0]), (q, k, v, lens, o, lse)


def _flash3_lse_bwd(causal, scale, window, res, cts):
    do, dlse_row = cts
    q, k, v, lens, o, lse = res
    dlse = jnp.broadcast_to(dlse_row[..., None], lse.shape)
    dq, dk, dv = _fa_bwd_pallas(
        q, k, v, do, o, lse, dlse, lens, causal, scale, _interpret_default(),
        window=window,
    )
    return dq, dk, dv, _zeros_like(lens)


_flash3_lse.defvjp(_flash3_lse_fwd, _flash3_lse_bwd)


def _probe_flash_pallas(q3, k3, v3, lens_bh, seed, *, causal, scale, rate,
                        window=None):
    """Guard probe: forward AND backward flash kernels must build for the key
    (the bwd pass launches one or two more pallas_calls with their own specs)."""

    def f(q, k, v):
        return _flash3(q, k, v, lens_bh, seed, causal, scale, rate, window)

    o, vjp = jax.vjp(f, q3, k3, v3)
    vjp(jnp.zeros_like(o))
    return o


def _seed_from_key(key: jax.Array) -> jax.Array:
    """(1,) int32 kernel seed derived from a PRNG key — the key stays the
    user-facing contract (fold_in composability with the RNG tracker), the
    kernel consumes a raw counter seed like the reference's Philox offset."""
    bits = jax.random.bits(key, (1,), jnp.uint32)
    return jax.lax.bitcast_convert_type(bits, jnp.int32)


def flash_attention_with_lse(q3, k3, v3, *, causal, scale, kv_lens=None,
                             window=None):
    """``q3, k3 (BH, S, Dk)``, ``v3 (BH, Sk, Dv)`` flash attention returning
    ``(o (BH, S, Dv), lse (BH, S))`` — the merge
    interface for blockwise/ring composition (lse = m + log l per row;
    fully-masked rows carry lse = -1e30 so their merge weight underflows to
    exactly zero). Differentiable in q/k/v AND through lse (the backward
    kernels take the dlse cotangent). ``window``: as :func:`flash_attention`'s,
    within this one chunk (positions count from the chunk's start)."""
    if kv_lens is not None:
        kv_lens = kv_lens.astype(jnp.float32)
    elif not causal:
        kv_lens = jnp.full((q3.shape[0],), float(k3.shape[1]), jnp.float32)
    window = _checked_window(window, causal, q3.shape[1])
    return _flash3_lse(q3, k3, v3, kv_lens, causal, scale, window)


# ---------------------------------------------------------------------------------
# jnp oracle — unfused but GSPMD-transparent; autodiff provides the backward
# ---------------------------------------------------------------------------------


def _attn_jnp(q, k, v, lens, causal, scale, dropout_rate=0.0, dropout_key=None,
              window=None, selected=None):
    BH, S, D = q.shape
    Sk = k.shape[1]
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    kj = jnp.arange(Sk)
    masked = kj[None, None, :].astype(jnp.float32) >= lens[:, None, None]
    if causal:
        masked |= kj[None, :] > jnp.arange(S)[:, None]
    if window is not None:
        masked |= kj[None, :] <= jnp.arange(S)[:, None] - window
    if selected is not None:    # (B, S, Sk): a batch row's heads share one selection
        masked |= jnp.repeat(selected == 0, BH // selected.shape[0], axis=0)
    s = jnp.where(masked, _NEG, s)
    m = jnp.max(s, axis=-1, keepdims=True)
    # zero masked slots explicitly: for a fully-masked row s == m == _NEG and
    # exp(s - m) would be 1, not 0 (same guard as the Pallas kernel)
    e = jnp.where(masked, 0.0, jnp.exp(s - m))
    l = jnp.sum(e, axis=-1, keepdims=True)
    nonempty = l > 0.0
    p = jnp.where(nonempty, e / jnp.where(nonempty, l, 1.0), 0.0)
    if dropout_rate > 0.0:
        # softmax -> dropout -> @v, torch's ordering (the reference kernels
        # drop the probabilities in-kernel, dropout.cuh); inverted scaling
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------------


def _checked_window(window, causal, seq_len):
    """The window a call's kernels are planned with: ``None`` where it masks
    nothing (no window, or one that holds the whole sequence), so that such a
    call IS the plain causal call."""
    if window is None:
        return None
    if not causal:
        raise ValueError("window= needs causal=True (a window looks back from the query)")
    window = int(window)
    if window < 1:
        raise ValueError(f"window must hold at least the query's own key, got {window}")
    return None if window >= seq_len else window



def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_lens: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    dropout_key: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    window: Optional[int] = None,
    selected: Optional[jax.Array] = None,
) -> jax.Array:
    """Fused scaled-dot-product attention.

    q: (B, H, S, Dk), k: (B, H, Sk, Dk), v: (B, H, Sk, Dv) — values may be
    narrower or wider than queries and keys (latent attention: ``Dk`` 192 over
    ``Dv`` 128); the kernels compute scores at ``Dk`` and values at ``Dv``, with
    no copy padded to a common width. ``kv_lens``: optional (B,) int key
    lengths — keys at index >= len are masked out (the reference fmha's
    variable-seqlen support, ref: apex/contrib/fmha/fmha.py:33-60, expressed
    padded-dense). Returns (B, H, S, Dv) in q's dtype; ``scale`` defaults to
    ``Dk^-1/2``. fp32 accumulation throughout.

    ``window`` (with ``causal=True``): sliding-window attention — query ``i``
    sees the ``window`` keys ``i - window < j <= i``, itself among them. The
    kernels' grid is then the band of blocks the window reaches and not the
    square (:attr:`TilePlan.band`); a window that holds the whole sequence is
    the plain causal call.

    ``selected`` (with ``causal=True``, and no window, key lengths or dropout):
    attention over a set of keys per query that arrives at run time — int8
    ``(B, S, S)``, nonzero at ``[b, t, s]`` where query ``t`` keeps key ``s``, the
    same for every head; a kept key is never after its query (``s <= t``:
    ``ops.index_select`` makes such a mask), and every query keeps at least one.
    The softmax runs over the kept keys only. The kernels walk every causal
    block and mask each score by the operand's block (named
    ``flash_attention_sparse_*`` in the device trace); a selection of all causal
    keys gives the plain causal call's result, bit for bit. It passes no gradient.

    ``dropout_rate``/``dropout_key``: attention-probability dropout in
    torch's softmax->dropout->matmul order (ref:
    apex/contrib/multihead_attn/self_multihead_attn.py:32 ``dropout=`` and
    dropout.cuh). On TPU the Pallas kernel drops IN-KERNEL via the hardware
    PRNG (deterministic per-block reseeding, so forward and backward
    regenerate identical masks — see :func:`_keep_mask`), keeping the O(S)
    memory profile for long-sequence training. The jnp oracle path uses
    ``jax.random.bernoulli`` (a different RNG stream: same distribution, not
    the same draws). Interpret mode (CPU tests) has no PRNG lowering and
    falls back to jnp.
    """
    if q.ndim != 4:
        raise ValueError(f"expected (B, H, S, D) inputs, got {q.shape}")
    # FP16_FUNCS-style autocast applied by hand: only q/k/v are compute
    # tensors — kv_lens is integer-semantic and must never be rounded
    act = autocast_dtype()
    if act is not None:
        q, k, v = q.astype(act), k.astype(act), v.astype(act)
    B, H, S, D = q.shape
    Sk = k.shape[2]
    if v.ndim != 4 or k.shape[:3] != v.shape[:3] or k.shape[:2] != q.shape[:2] \
            or k.shape[3] != D:
        raise ValueError(
            f"expected q (B, H, S, Dk), k (B, H, Sk, Dk), v (B, H, Sk, Dv), got "
            f"{q.shape}/{k.shape}/{v.shape}")
    Dv = v.shape[3]
    if causal and Sk != S:
        raise ValueError(
            f"causal attention needs matching q/k lengths, got {S} vs {Sk}"
        )
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    window = _checked_window(window, causal, S)
    if selected is not None:
        if not causal or window is not None or kv_lens is not None or dropout_rate > 0.0:
            raise ValueError("selected= needs causal=True and takes no window, kv_lens or "
                             "dropout")
        if selected.shape != (B, S, Sk):
            raise ValueError(f"selected must be (B, S, Sk) = {(B, S, Sk)}, got {selected.shape}")
        selected = selected.astype(jnp.int8)
    # a windowed call's probe key and kernels carry the window; a call without
    # one passes nothing, and its key and kernels are what they were
    windowed = {} if window is None else {"window": window}
    if dropout_rate > 0.0 and dropout_key is None:
        raise ValueError("dropout_rate > 0 requires a dropout_key")
    forced = impl is not None
    impl = _resolve_impl(impl)
    if impl == "pallas" and dropout_rate > 0.0 and _interpret_default():
        # the in-kernel PRNG has no interpret-mode lowering; CPU test runs
        # take the jnp path (same distribution, different draws)
        if forced:
            raise ValueError(
                "impl='pallas' with dropout needs a real TPU (the Pallas "
                "interpreter has no PRNG lowering); pass impl=None for the "
                "jnp dropout path"
            )
        impl = "jnp"
    if impl == "pallas" and not (
        is_flash_available(S, D, Dv) and is_flash_available(Sk, D, Dv)
    ):
        if forced:
            # resolve_impl's contract: an explicit impl= is always honored —
            # so an impossible forced request errors instead of a silent swap
            raise ValueError(
                f"impl='pallas' forced but shapes don't tile the kernel: "
                f"q len {S} / kv len {Sk} (both need % {_MIN_BLOCK} == 0), "
                f"head dims {D} (q, k) / {Dv} (v) (both need 8..512); pass impl=None for automatic "
                f"fallback"
            )
        impl = "jnp"

    if kv_lens is None:
        lens = jnp.full((B,), float(Sk), jnp.float32)
    else:
        lens = kv_lens.astype(jnp.float32)
    lens_bh = jnp.repeat(lens, H)  # (B*H,): per-head copy of each seq length
    # a causal call without kv_lens hands its kernels no lengths at all: every
    # key is in range, statically, and only the diagonal's tiles build a mask
    # (a non-causal call keeps the length test, and the kernel body it had)
    lens_pallas = None if causal and kv_lens is None else lens_bh

    q3 = q.reshape(B * H, S, D)
    k3 = k.reshape(B * H, Sk, D)
    v3 = v.reshape(B * H, Sk, Dv)
    with _span("flash_attention"):  # XProf range (NVTX idiom); stays innermost
        if impl == "pallas":
            if dropout_rate > 0.0:
                seed = _seed_from_key(dropout_key)
            else:
                seed = jnp.zeros((1,), jnp.int32)
            if not forced:
                # default-on dispatch is guarded (a forced impl='pallas' keeps the
                # honor-or-raise contract above); a selected call has its own key
                if selected is not None:
                    probe, args, statics = _probe_flash_selected, (q3, k3, v3, selected), {}
                else:
                    probe, args = _probe_flash_pallas, (q3, k3, v3, lens_pallas, seed)
                    statics = dict(causal=causal, rate=float(dropout_rate), **windowed)
                if 4 * B * H * S * Sk > _ORACLE_SCORE_BYTES_CAP:
                    # no viable oracle at this shape: the jnp fallback would
                    # materialize > budget of fp32 scores through autodiff.
                    # Flash is the only path — book it, skip probe/downgrade.
                    _count_forced("flash_attention", impl, *args, scale=scale, **statics)
                else:
                    impl = _checked_impl("flash_attention", impl, probe, *args, scale=scale,
                                         **statics)
        if impl == "pallas" and selected is not None:
            o = _flash3_selected(q3, k3, v3, selected, scale)
        elif impl == "pallas":
            o = _flash3(q3, k3, v3, lens_pallas, seed, causal, scale,
                        float(dropout_rate), window)
        else:
            o = _attn_jnp(q3, k3, v3, lens_bh, causal, scale,
                          dropout_rate, dropout_key, window, selected)
    # remat boundary tag: the attention context is a cheap (B, H, S, Dv)
    # save point vs the O(S^2) score/prob intermediates behind it
    return _checkpoint_name(o.reshape(B, H, S, Dv), _TAG_ATTN_OUT)


def self_attention(
    x: jax.Array,
    w_qkv: jax.Array,
    b_qkv: Optional[jax.Array],
    w_out: jax.Array,
    b_out: Optional[jax.Array],
    n_heads: int,
    *,
    causal: bool = False,
    kv_lens: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    dropout_key: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused self-attention block: QKV projection → flash attention → output
    projection (ref: apex/contrib/multihead_attn/self_multihead_attn.py:22,
    whose CUDA Functions fuse exactly this chain). x: (B, S, D)."""
    B, S, D = x.shape
    act = autocast_dtype()
    if act is not None:  # cast compute tensors only, not kv_lens
        x = x.astype(act)
        w_qkv, w_out = w_qkv.astype(act), w_out.astype(act)
        b_qkv = b_qkv.astype(act) if b_qkv is not None else None
        b_out = b_out.astype(act) if b_out is not None else None
    hd = D // n_heads
    if hd * n_heads != D:
        raise ValueError(f"d_model {D} not divisible by n_heads {n_heads}")
    qkv = x @ w_qkv.astype(x.dtype)
    if b_qkv is not None:
        qkv = qkv + b_qkv.astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(B, S, n_heads, hd).transpose(0, 2, 1, 3)

    ctx = flash_attention(
        heads(q), heads(k), heads(v), causal=causal, kv_lens=kv_lens,
        dropout_rate=dropout_rate, dropout_key=dropout_key, impl=impl,
        window=window,
    )
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, D)
    out = ctx @ w_out.astype(x.dtype)
    if b_out is not None:
        out = out + b_out.astype(x.dtype)
    return out
