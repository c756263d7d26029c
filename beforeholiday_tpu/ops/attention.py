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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from beforeholiday_tpu.guard.dispatch import (
    checked_impl as _checked_impl,
    count_forced as _count_forced,
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


def _block_size(seq_len: int, head_dim: int = 64) -> int:
    """Largest block (query rows == key cols) that tiles the sequence.

    Bigger blocks amortize per-grid-step overhead and give the MXU larger
    matmuls: at S=8192/D=64 the causal forward measured 30.0 ms with
    1024-blocks vs 31.4 (512) vs 43.8 (256) on a v5e. 1024 is allowed only
    for head_dim <= 128 — the dkv backward holds ~6 operand blocks plus two
    (bk, D) fp32 scratch accumulators and (bq, bk) fp32 intermediates, which
    at D > 128 would push past the ~16 MB VMEM budget."""
    ladder = (1024, 512, 256) if head_dim <= 128 else (512, 256)
    for cand in ladder:
        if seq_len % cand == 0:
            return cand
    return _MIN_BLOCK


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


def is_flash_available(seq_len: int, head_dim: int) -> bool:
    """Shape gate for the Pallas kernel (ref: fused_softmax.py:164
    ``is_kernel_available`` plays the same role for the softmax kernels).

    Requires the sequence to tile exactly into (BQ, BK) blocks and a head
    dim that fits VMEM comfortably alongside the accumulators.
    """
    return seq_len % _MIN_BLOCK == 0 and 8 <= head_dim <= 512


# ---------------------------------------------------------------------------------
# forward kernel: grid (BH, nq, nk); nk innermost so the VMEM accumulators
# (acc, m, l) carry across key blocks of one query block
# ---------------------------------------------------------------------------------


def _mask(causal, i, j, lens, shape, bq, bk):
    """Additive-mask predicate for score block (i, j). True = masked out.
    ``lens`` is a scalar int32 (this sequence's key length)."""
    kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    masked = kj >= lens
    if causal:
        qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        masked |= kj > qi
    return masked


def _keep_mask(seed_ref, b, i, j, nq, nk, shape, keep_prob):
    """In-kernel dropout keep-mask for score block (b, i, j) — the TPU
    counterpart of the reference's curand path in its fused kernels
    (ref: apex/contrib/csrc/multihead_attn/dropout.cuh:1-272, consumed by
    every *_func variant, self_multihead_attn_func.py:148-186).

    The PRNG is RE-SEEDED per (batch*head, q-block, k-block) from the caller's
    seed plus a mixed block id, then one (BQ, BK) draw is taken — so the
    forward and BOTH backward kernels regenerate the exact same mask for a
    block regardless of their different grid orders, the same
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


def _fa_fwd_kernel(causal, scale, nq, nk, bq, bk, rate, *refs):
    if rate > 0.0:
        (lens_ref, seed_ref, q_ref, k_ref, v_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        (lens_ref, q_ref, k_ref, v_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
        seed_ref = None
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    seq_len = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (j * bk <= i * bq + (bq - 1)) if causal else (j >= 0)

    @pl.when(live)
    def _compute():
        # matmuls keep the input dtype (bf16 on the MXU's native path) with
        # fp32 accumulation via preferred_element_type — casting up first
        # would force the slow multi-pass fp32 MXU mode
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        masked = _mask(causal, i, j, seq_len, s.shape, bq, bk)
        s = jnp.where(masked, _NEG, s)
        m_prev = m_ref[...]                      # (BQ, 128) lane-replicated
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit zero on masked slots: when a whole row is masked s == m_new
        # == _NEG and exp(s - m) would be 1, not 0
        p = jnp.where(masked, 0.0, jnp.exp(s - m_new[:, 0:1]))
        # the softmax normalizer l accumulates the UNDROPPED p: out_i =
        # (1/l_i) sum_j mask_ij/keep * p_ij v_j == softmax->dropout->matmul
        # (torch's order, self_multihead_attn_func.py:148-186) — dropping
        # after normalization, expressed online
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        if rate > 0.0:
            keep = _keep_mask(seed_ref, b, i, j, nq, nk, p.shape, 1.0 - rate)
            pd = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
        else:
            pd = p
        acc_ref[...] = acc_ref[...] * alpha[:, 0:1] + jax.lax.dot_general(
            pd.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
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


def _fa_fwd_pallas(q, k, v, lens, causal, scale, interpret, rate=0.0, seed=None):
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    bq, bk = _block_size(Sq, D), _block_size(Sk, D)
    nq, nk = Sq // bq, Sk // bk
    # lens (and the dropout seed when active) ride scalar-prefetch SMEM (a
    # (1,1)-blocked SMEM operand fails Mosaic's tiling check); index maps
    # receive the scalar refs last — *_ absorbs however many there are
    qspec = pl.BlockSpec((1, bq, D), lambda b, i, j, *_: (b, i, 0))
    kspec = pl.BlockSpec((1, bk, D), lambda b, i, j, *_: (b, j, 0))
    scalars = [lens.astype(jnp.int32)]
    if rate > 0.0:
        scalars.append(seed.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(BH, nq, nk),
        in_specs=[qspec, kspec, kspec],
        out_specs=[
            qspec,
            pl.BlockSpec((1, bq, 128), lambda b, i, j, *_: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        functools.partial(_fa_fwd_kernel, causal, scale, nq, nk, bq, bk, rate),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((BH, Sq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*scalars, q, k, v)
    return o, lse


# ---------------------------------------------------------------------------------
# backward: dq kernel (grid BH, nq, nk) + dkv kernel (grid BH, nk, nq); both
# recompute block scores from (q, k, lse) — flash-attention rematerialization
# ---------------------------------------------------------------------------------


def _block_p_ds(causal, scale, b, i, j, lens, q, k, v, do, o, lse, dlse,
                bq, bk, rate, nq, nk, seed_ref):
    """Shared recompute: dv-side probabilities z and score-grad ds for block
    (b, i, j). ``lse``/``dlse``: (BQ, 128) lane-replicated; delta_i =
    rowsum(dO_i * O_i) is recomputed here from the o/do blocks (cheap VPU
    work vs another HBM residual). ``dlse`` is the cotangent of the EXPOSED
    lse output (zero for plain attention; nonzero when the caller merges
    chunk outputs by lse, as ring attention does — d lse_i/d s_ij = p_ij
    adds dlse_i inside the parens). Matmuls run in the input dtype with fp32
    accumulation.

    With dropout (``rate > 0``) the forward computed out_i = sum_j z_ij v_j
    with z = keep/(1-rate) * softmax(s); the same mask regenerates here
    (:func:`_keep_mask` is deterministic per block). The chain rule gives
    dp~_ij = (do_i . v_j) * keep_ij/(1-rate), and the softmax-backward
    rowsum term STAYS delta_i = do_i . o_i because
    sum_k dp~_ik p_ik = sum_k (do.v_k) z_ik = do_i . o_i — the undropped
    p carries the Jacobian, the dropped z carries dv."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    masked = _mask(causal, i, j, lens, s.shape, bq, bk)
    p = jnp.where(masked, 0.0, jnp.exp(jnp.where(masked, _NEG, s) - lse[:, 0:1]))
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if rate > 0.0:
        keep = _keep_mask(seed_ref, b, i, j, nq, nk, p.shape, 1.0 - rate)
        inv = 1.0 / (1.0 - rate)
        z = jnp.where(keep, p * inv, 0.0)
        dp = jnp.where(keep, dp * inv, 0.0)
    else:
        z = p
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    extra = dlse[:, 0:1] if dlse is not None else 0.0
    ds = p * (dp - delta + extra) * scale
    return z, ds


def _fa_dq_kernel(causal, scale, nq, nk, bq, bk, has_dlse, rate, *refs):
    if rate > 0.0:
        lens_ref, seed_ref, *refs = refs
    else:
        lens_ref, *refs = refs
        seed_ref = None
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest = refs
    if has_dlse:
        dlse_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
        dlse_ref = None
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = (j * bk <= i * bq + (bq - 1)) if causal else (j >= 0)

    @pl.when(live)
    def _compute():
        _, ds = _block_p_ds(
            causal, scale, b, i, j, lens_ref[b],
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], o_ref[0], lse_ref[0],
            dlse_ref[0] if has_dlse else None, bq, bk, rate, nq, nk, seed_ref,
        )
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    @pl.when(j == nk - 1)
    def _final():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_dkv_kernel(causal, scale, nq, nk, bq, bk, has_dlse, rate, *refs):
    if rate > 0.0:
        lens_ref, seed_ref, *refs = refs
    else:
        lens_ref, *refs = refs
        seed_ref = None
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest = refs
    if has_dlse:
        dlse_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
        dlse_ref = None
    # k block outer, q block inner
    b, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = (i * bq + (bq - 1) >= j * bk) if causal else (i >= 0)

    @pl.when(live)
    def _compute():
        z, ds = _block_p_ds(
            causal, scale, b, i, j, lens_ref[b],
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], o_ref[0], lse_ref[0],
            dlse_ref[0] if has_dlse else None, bq, bk, rate, nq, nk, seed_ref,
        )
        # dv sees the DROPPED probabilities z (dropout sits between softmax
        # and the @v matmul); dk/dq flow through ds, whose rowsum term keeps
        # the undropped p Jacobian — see _block_p_ds
        dv_acc[...] += jax.lax.dot_general(
            z.astype(do_ref.dtype), do_ref[0],
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0],
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    @pl.when(i == nq - 1)
    def _final():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_bwd_pallas(q, k, v, do, o, lse, dlse, lens, causal, scale, interpret,
                   rate=0.0, seed=None):
    """``dlse=None`` (the plain-attention path) omits the operand entirely —
    an all-zero lane-replicated dlse would otherwise add an arena-sized HBM
    read to BOTH backward kernels for nothing."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    bq, bk = _block_size(Sq, D), _block_size(Sk, D)
    nq, nk = Sq // bq, Sk // bk
    has_dlse = dlse is not None
    dlse_ops = (dlse,) if has_dlse else ()
    scalars = [lens.astype(jnp.int32)]
    if rate > 0.0:
        scalars.append(seed.astype(jnp.int32))
    qspec_i = pl.BlockSpec((1, bq, D), lambda b, i, j, *_: (b, i, 0))
    kspec_j = pl.BlockSpec((1, bk, D), lambda b, i, j, *_: (b, j, 0))
    lse_i = pl.BlockSpec((1, bq, 128), lambda b, i, j, *_: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_fa_dq_kernel, causal, scale, nq, nk, bq, bk,
                          has_dlse, rate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(BH, nq, nk),
            in_specs=[qspec_i, kspec_j, kspec_j, qspec_i, qspec_i, lse_i]
                     + ([lse_i] if has_dlse else []),
            out_specs=qspec_i,
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*scalars, q, k, v, do, o, lse, *dlse_ops)

    # dkv grid: (BH, k-block, q-block) — q-side operands indexed by the INNER id
    qspec_in = pl.BlockSpec((1, bq, D), lambda b, j, i, *_: (b, i, 0))
    kspec_out = pl.BlockSpec((1, bk, D), lambda b, j, i, *_: (b, j, 0))
    lse_in = pl.BlockSpec((1, bq, 128), lambda b, j, i, *_: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_fa_dkv_kernel, causal, scale, nq, nk, bq, bk,
                          has_dlse, rate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(BH, nk, nq),
            in_specs=[qspec_in, kspec_out, kspec_out, qspec_in, qspec_in, lse_in]
                     + ([lse_in] if has_dlse else []),
            out_specs=[kspec_out, kspec_out],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*scalars, q, k, v, do, o, lse, *dlse_ops)
    return dq, dk, dv


# ---------------------------------------------------------------------------------
# custom VJP over the (BH, S, D) view (Pallas path)
# ---------------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash3(q, k, v, lens, seed, causal, scale, rate):
    o, _ = _fa_fwd_pallas(q, k, v, lens, causal, scale, _interpret_default(),
                          rate, seed)
    return o


def _flash3_fwd(q, k, v, lens, seed, causal, scale, rate):
    o, lse = _fa_fwd_pallas(q, k, v, lens, causal, scale, _interpret_default(),
                            rate, seed)
    # remat boundary tag: under a save_only_these_names policy the (BH, S)
    # lse rows survive checkpointing so the flash backward can rebuild the
    # probabilities without a full forward re-run (identity otherwise)
    lse = _checkpoint_name(lse, _TAG_FLASH_LSE)
    return o, (q, k, v, lens, seed, o, lse)


def _flash3_bwd(causal, scale, rate, res, do):
    q, k, v, lens, seed, o, lse = res
    dq, dk, dv = _fa_bwd_pallas(
        q, k, v, do, o, lse, None, lens, causal, scale, _interpret_default(),
        rate, seed,
    )
    return dq, dk, dv, jnp.zeros_like(lens), jnp.zeros_like(seed)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


# --- (o, lse) variant for chunk-merging callers (ring attention) ----------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash3_lse(q, k, v, lens, causal, scale):
    o, lse = _fa_fwd_pallas(q, k, v, lens, causal, scale, _interpret_default())
    return o, lse[..., 0]


def _flash3_lse_fwd(q, k, v, lens, causal, scale):
    o, lse = _fa_fwd_pallas(q, k, v, lens, causal, scale, _interpret_default())
    lse = _checkpoint_name(lse, _TAG_FLASH_LSE)
    return (o, lse[..., 0]), (q, k, v, lens, o, lse)


def _flash3_lse_bwd(causal, scale, res, cts):
    do, dlse_row = cts
    q, k, v, lens, o, lse = res
    dlse = jnp.broadcast_to(dlse_row[..., None], lse.shape)
    dq, dk, dv = _fa_bwd_pallas(
        q, k, v, do, o, lse, dlse, lens, causal, scale, _interpret_default()
    )
    return dq, dk, dv, jnp.zeros_like(lens)


_flash3_lse.defvjp(_flash3_lse_fwd, _flash3_lse_bwd)


def _probe_flash_pallas(q3, k3, v3, lens_bh, seed, *, causal, scale, rate):
    """Guard probe: forward AND backward flash kernels must build for the key
    (the bwd pass launches two extra pallas_calls with their own specs)."""

    def f(q, k, v):
        return _flash3(q, k, v, lens_bh, seed, causal, scale, rate)

    o, vjp = jax.vjp(f, q3, k3, v3)
    vjp(jnp.zeros_like(o))
    return o


def _seed_from_key(key: jax.Array) -> jax.Array:
    """(1,) int32 kernel seed derived from a PRNG key — the key stays the
    user-facing contract (fold_in composability with the RNG tracker), the
    kernel consumes a raw counter seed like the reference's Philox offset."""
    bits = jax.random.bits(key, (1,), jnp.uint32)
    return jax.lax.bitcast_convert_type(bits, jnp.int32)


def flash_attention_with_lse(q3, k3, v3, *, causal, scale, kv_lens=None):
    """(BH, S, D) flash attention returning (o, lse (BH, S)) — the merge
    interface for blockwise/ring composition (lse = m + log l per row;
    fully-masked rows carry lse = -1e30 so their merge weight underflows to
    exactly zero). Differentiable in q/k/v AND through lse (the backward
    kernels take the dlse cotangent)."""
    BH, S, D = q3.shape
    if kv_lens is None:
        kv_lens = jnp.full((BH,), float(k3.shape[1]), jnp.float32)
    return _flash3_lse(q3, k3, v3, kv_lens.astype(jnp.float32), causal, scale)


# ---------------------------------------------------------------------------------
# jnp oracle — unfused but GSPMD-transparent; autodiff provides the backward
# ---------------------------------------------------------------------------------


def _attn_jnp(q, k, v, lens, causal, scale, dropout_rate=0.0, dropout_key=None):
    BH, S, D = q.shape
    Sk = k.shape[1]
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    kj = jnp.arange(Sk)
    masked = kj[None, None, :].astype(jnp.float32) >= lens[:, None, None]
    if causal:
        masked |= kj[None, :] > jnp.arange(S)[:, None]
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
) -> jax.Array:
    """Fused scaled-dot-product attention.

    q, k, v: (B, H, S, D). ``kv_lens``: optional (B,) int key lengths — keys
    at index >= len are masked out (the reference fmha's variable-seqlen
    support, ref: apex/contrib/fmha/fmha.py:33-60, expressed padded-dense).
    Returns (B, H, S, D) in q's dtype. fp32 accumulation throughout.

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
    if k.shape != v.shape or k.shape[:2] != q.shape[:2] or k.shape[3] != D:
        raise ValueError(f"q/k/v shapes mismatch, got {q.shape}/{k.shape}/{v.shape}")
    if causal and Sk != S:
        raise ValueError(
            f"causal attention needs matching q/k lengths, got {S} vs {Sk}"
        )
    scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
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
        is_flash_available(S, D) and is_flash_available(Sk, D)
    ):
        if forced:
            # resolve_impl's contract: an explicit impl= is always honored —
            # so an impossible forced request errors instead of a silent swap
            raise ValueError(
                f"impl='pallas' forced but shapes don't tile the kernel: "
                f"q len {S} / kv len {Sk} (both need % {_MIN_BLOCK} == 0), "
                f"head_dim={D} (needs 8..512); pass impl=None for automatic "
                f"fallback"
            )
        impl = "jnp"

    if kv_lens is None:
        lens = jnp.full((B,), float(Sk), jnp.float32)
    else:
        lens = kv_lens.astype(jnp.float32)
    lens_bh = jnp.repeat(lens, H)  # (B*H,): per-head copy of each seq length

    q3 = q.reshape(B * H, S, D)
    k3 = k.reshape(B * H, Sk, D)
    v3 = v.reshape(B * H, Sk, D)
    with _span("flash_attention"):  # XProf range (NVTX idiom); stays innermost
        if impl == "pallas":
            if dropout_rate > 0.0:
                seed = _seed_from_key(dropout_key)
            else:
                seed = jnp.zeros((1,), jnp.int32)
            if not forced:
                if 4 * B * H * S * Sk > _ORACLE_SCORE_BYTES_CAP:
                    # no viable oracle at this shape: the jnp fallback would
                    # materialize > budget of fp32 scores through autodiff.
                    # Flash is the only path — book it, skip probe/downgrade.
                    _count_forced(
                        "flash_attention", impl,
                        q3, k3, v3, lens_bh, seed,
                        causal=causal, scale=scale, rate=float(dropout_rate),
                    )
                else:
                    # default-on dispatch is guarded; a forced impl='pallas'
                    # keeps the honor-or-raise contract above
                    impl = _checked_impl(
                        "flash_attention", impl, _probe_flash_pallas,
                        q3, k3, v3, lens_bh, seed,
                        causal=causal, scale=scale, rate=float(dropout_rate),
                    )
        if impl == "pallas":
            o = _flash3(q3, k3, v3, lens_bh, seed, causal, scale,
                        float(dropout_rate))
        else:
            o = _attn_jnp(q3, k3, v3, lens_bh, causal, scale,
                          dropout_rate, dropout_key)
    # remat boundary tag: the attention context is a cheap (B, H, S, D)
    # save point vs the O(S^2) score/prob intermediates behind it
    return _checkpoint_name(o.reshape(B, H, S, D), _TAG_ATTN_OUT)


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
    )
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, D)
    out = ctx @ w_out.astype(x.dtype)
    if b_out is not None:
        out = out + b_out.astype(x.dtype)
    return out
