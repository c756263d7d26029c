"""Gated delta rule — the linear-attention recurrence of Gated DeltaNet, chunked.

Per head, with a state ``S`` of shape ``(d_k, d_v)`` that starts at zero
(Yang, Kautz, Hatamizadeh 2024, "Gated Delta Networks"; see PAPERS.md)::

    S~  = a_t * S_{t-1}                          a_t = exp(g_t) in (0, 1]
    S_t = S~ + k_t (b_t (v_t - S~^T k_t))^T      the delta rule, gated
    o_t = S_t^T q_t

A token-by-token scan is 8192 dependent steps of rank-one updates, which no
matrix unit can use. The chunk-wise form (the WY representation of a product
of Householder-like factors, as in the paper's section 3) splits the sequence
into chunks of ``C`` tokens: inside a chunk everything that does not depend on
the incoming state is dense algebra on ``(C, C)`` and ``(C, d)`` tiles, and
only a three-product update carries the state from chunk to chunk.

* the state-free part: the in-chunk decay ``D_ij = exp(G_i - G_j)``, the unit
  lower-triangular system ``(I + strict(b k k^T * D)) [u | w] = [b v | b k e^G]``
  that defines the WY factors (the inverse block by block), the in-chunk scores
  ``P = tril(q k^T * D)``. On the Pallas path two kernels, ``wy_prepare_fwd`` and
  ``wy_prepare_bwd``, hold one chunk's ``(C, C)`` and ``(C, d)`` tiles in VMEM
  from the operands to the factors, a ``custom_vjp`` between them: the backward
  kernel recomputes the forward one's tiles (an inverse kept would be 134 MB of
  float32 a layer) and applies their transposes by hand. What is ``O(C)`` a
  chunk — the cumulative sum ``G``, its exponentials, their chain rule — stays
  in XLA (:func:`_wy_rows`). :func:`wy_prepare` is the same algebra in plain
  ``jax.numpy``, batched over every chunk of every head at once, differentiated
  by XLA and recomputed in the backward pass (``jax.checkpoint``): the parity
  oracle of the kernels, and what ``impl="jnp"`` and a shape the kernels do not
  take run (there the (C, C) float32 tensors stream through HBM).
* the chunk scan — ``delta = u - w S; o = (q e^G) S + P delta;
  S <- e^{G_C} S + (k e^{G_C - G})^T delta`` — is the sequential part: a Pallas
  kernel that walks the chunks of one head with the state in VMEM, and a
  ``custom_vjp`` whose backward kernel walks them in reverse with the state's
  cotangent in VMEM. The chunk-start states are recomputed by one more forward
  sweep at the start of the backward pass instead of being kept alive from the
  forward one (0.27 GB a layer at 32 heads x 8192 tokens).
* ``impl="jnp"`` runs :func:`wy_prepare` and the same chunk scan as a
  ``lax.scan`` (autodiff gives its backward): the parity oracle and the
  off-TPU default.

Every exponent is of a difference that is <= 0, so nothing overflows however
strong the decay; no quotient of decays is ever formed.
"""

from __future__ import annotations

from typing import Optional

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

__all__ = ["gated_delta_rule", "is_kernel_available", "unit_lower_inverse", "wy_prepare"]

_F32 = jnp.float32
DEFAULT_CHUNK = 128
_LANES = 128


def is_kernel_available(chunk: int, d_k: int, d_v: int) -> bool:
    """Shape gate of the Pallas kernels, the chunk scan's and the WY factors':
    head dims that fill the lanes, a chunk of whole bfloat16 sublane tiles (the
    in-chunk scores are then a block as wide as their array, which Mosaic takes
    below 128 lanes too)."""
    return chunk % 64 == 0 and d_k % _LANES == 0 and d_v % _LANES == 0


# ---------------------------------------------------------------------------------
# (I + L)^-1 for a strictly lower-triangular L, by blocks
# ---------------------------------------------------------------------------------

_BASE = 16


def _mm(a, b):
    # three bfloat16 passes: float32 to ~1e-6, half the time of ``highest``
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)


def _inverse(L):
    C = L.shape[-1]
    base = min(_BASE, C)
    idx = jnp.arange(C)
    same = lambda size: (idx[:, None] // size) == (idx[None, :] // size)
    # the diagonal blocks, all at once as one block-diagonal matrix (products of
    # block-diagonal matrices stay block-diagonal): L_bb is nilpotent of order
    # ``base``, so (I + L_bb)^-1 = (I - L_bb)(I + L_bb^2)(I + L_bb^4)... exactly
    eye = jnp.eye(C, dtype=L.dtype)
    power = -jnp.where(same(base), L, 0.0)
    T = eye + power
    for _ in range(max(base.bit_length() - 2, 0)):
        power = _mm(power, power)
        T = _mm(T, eye + power)
    size = base
    while size < C:
        # [[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]] for every pair of
        # blocks at once: T <- T - T B T, B the corners of L at this level
        corners = jnp.where(same(2 * size) & ~same(size), L, 0.0)
        T = T - _mm(_mm(T, corners), T)
        size *= 2
    return T


@jax.custom_vjp
def unit_lower_inverse(L):
    """``(I + L)^-1`` for strictly lower-triangular ``L (..., C, C)``, ``C`` a
    power of two: the 16 x 16 diagonal blocks by their (finite) Neumann product,
    then block by block. Every product is a full ``C x C`` batched matmul (a
    batch of 16 x 16 products is padded to the MXU's tile and costs more); no
    power of a block wider than 16 is ever formed, so keys that repeat (entries
    of ``L`` near one) cost at most ``C(15, 7)`` ~ 6e3 of cancellation, not
    ``C(127, 63)``. XLA's own ``triangular_solve`` is exact too, but on a v5e it
    took 20.8 ms for 2048 systems of 128 (my chip run, PR 26)."""
    return _inverse(L)


def _unit_lower_inverse_fwd(L):
    T = _inverse(L)
    return T, T


def _unit_lower_inverse_bwd(T, dT):
    # d(I + L)^-1 = -T dL T
    Tt = jnp.swapaxes(T, -1, -2)
    C = T.shape[-1]
    strict = jnp.arange(C)[:, None] > jnp.arange(C)[None, :]
    return (jnp.where(strict, -_mm(_mm(Tt, dT), Tt), 0.0),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


# ---------------------------------------------------------------------------------
# the state-free part (jnp; autodiff provides its backward)
# ---------------------------------------------------------------------------------


def wy_prepare(q, k, v, g, beta):
    """The chunk-local factors. ``q, k``: ``(..., C, d_k)``, ``v``:
    ``(..., C, d_v)``, ``g`` (log decay, <= 0) and ``beta``: ``(..., C)``
    float32. Returns ``(w, u, qg, kd, p, gl)``: the WY factors ``w (.., C, d_k)``
    and ``u (.., C, d_v)``, the decayed queries and keys, the in-chunk scores
    ``p (.., C, C)`` and the whole chunk's decay ``gl (..,)``."""
    C, dt = q.shape[-2], v.dtype
    gc = jnp.cumsum(g.astype(_F32), axis=-1)
    diff = gc[..., :, None] - gc[..., None, :]
    idx = jnp.arange(C)
    lower = idx[:, None] >= idx[None, :]
    strict = idx[:, None] > idx[None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    beta = beta.astype(_F32)[..., None]
    kb = (k.astype(_F32) * beta).astype(dt)
    kk = jnp.einsum("...id,...jd->...ij", kb, k, preferred_element_type=_F32)
    gamma = jnp.exp(gc)[..., None]
    rhs = jnp.concatenate([v.astype(_F32) * beta, kb.astype(_F32) * gamma], axis=-1)
    sol = _mm(unit_lower_inverse(jnp.where(strict, kk * decay, 0.0)), rhs)
    u, w = sol[..., :v.shape[-1]], sol[..., v.shape[-1]:]
    qk = jnp.einsum("...id,...jd->...ij", q, k, preferred_element_type=_F32)
    p = jnp.where(lower, qk * decay, 0.0)
    qg = q.astype(_F32) * gamma
    kd = k.astype(_F32) * jnp.exp(gc[..., -1:] - gc)[..., None]
    return (w.astype(dt), u.astype(dt), qg.astype(dt), kd.astype(dt),
            p.astype(dt), gamma[..., -1, 0])


# ---------------------------------------------------------------------------------
# the state-free part, Pallas: a few chunks a grid step, every (C, C) tile in VMEM
# ---------------------------------------------------------------------------------
#
# The kernel bodies are written in ``lax``: a body is traced three (forward) or
# two (backward) times per call site, a ``lax`` bind costs a third of a ``jnp``
# call, and set-up time is a judged number (PERF.md, PRs 28 and 30).

_BF16 = jnp.bfloat16
_ROWS = 8               # one float32 sublane tile: gc, beta, e^gc, e^(gc_C - gc), 4 unused


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=_F32)


_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b^T
_TN = ((0,), (0,))      # a^T @ b


def _cast(x, dtype):
    return x if x.dtype == dtype else lax.convert_element_type(x, dtype)


def _bcast(x, shape):
    """Broadcast the size-one dimensions of ``x`` (same rank) to ``shape``."""
    return lax.broadcast_in_dim(x, shape, tuple(range(len(shape))))


def _bdot(a, b, dims):
    """:func:`_dot` chunk by chunk: ``a`` and ``b`` lead with the same dimension."""
    (lhs,), (rhs,) = dims
    return lax.dot_general(a, b, (((lhs + 1,), (rhs + 1,)), ((0,), (0,))),
                           preferred_element_type=_F32)


def _split(x):
    """float32 -> bfloat16 ``(hi, lo)`` with ``hi + lo = x`` to 2^-17."""
    hi = _cast(x, _BF16)
    return hi, _cast(lax.sub(x, _cast(hi, _F32)), _BF16)


def _mm3(a, b, dims=_NN):
    """``Precision.HIGH`` by hand, which Mosaic does not take on a float32
    ``dot_general``: three bfloat16 passes of operands already split."""
    (ah, al), (bh, bl) = a, b
    return lax.add(lax.add(_bdot(ah, bl, dims), _bdot(al, bh, dims)), _bdot(ah, bh, dims))


def _tile_inverse(L, eye, block, zero):
    """``(I + L)^-1`` of strictly lower-triangular tiles ``L (G, C, C)`` inside a
    kernel, by the scheme of ``_inverse``; ``eye`` and ``block = i ^ j`` are the
    caller's masks of that shape. I + power splits into (I + hi, lo), so
    T (I + power) = T + T power and the identity is never split."""
    cc = L.shape
    C = cc[-1]
    base = min(_BASE, C)
    within = lambda size: lax.lt(block, lax.full(cc, size, jnp.int32))
    power = lax.neg(lax.select(within(base), L, zero))
    T = lax.add(lax.select(eye, lax.full(cc, 1.0, _F32), zero), power)
    power = _split(power)
    for _ in range(max(base.bit_length() - 2, 0)):
        power = _split(_mm3(power, power))
        T = lax.add(T, _mm3(_split(T), power))
    size = base
    while size < C:
        corners = lax.select(lax.ne(within(2 * size), within(size)), L, zero)
        Ts = _split(T)
        T = lax.sub(T, _mm3(_split(_mm3(Ts, _split(corners))), Ts))
        size *= 2
    return T


@jax.jit      # traced once a shape: each later trace of a kernel body binds one call
def _wy_tiles(q, k, v, rows):
    """What both kernels compute of the ``G`` chunks of a grid step: ``q, k
    (G, C, d_k)``, ``v (G, C, d_v)``, ``rows (G, 8, C)`` float32 (see
    :func:`_wy_rows`). All float32 but ``kb``; the names are :func:`wy_prepare`'s."""
    dt = v.dtype
    G, C, _ = q.shape
    cc = (G, C, C)
    ii = lax.broadcasted_iota(jnp.int32, cc, 1)
    jj = lax.broadcasted_iota(jnp.int32, cc, 2)
    eye, lower, strict = lax.eq(ii, jj), lax.ge(ii, jj), lax.gt(ii, jj)
    block = lax.bitwise_xor(ii, jj)    # i, j in one aligned block of 2^n  <=>  i ^ j < 2^n
    zero = lax.full(cc, 0.0, _F32)

    def across(r):                     # row r of ``rows`` along the lanes: x_j
        return _bcast(lax.slice_in_dim(rows, r, r + 1, axis=1), cc)

    def down(r):                       # the same values along the sublanes: x_i, (G, C, 1)
        return lax.expand_dims(lax.reduce_sum(lax.select(eye, across(r), zero), (2,)), (2,))

    beta, gamma, kappa = down(1), down(2), down(3)
    # every exponent is of a difference <= 0: the module's overflow rule
    diff = lax.sub(_bcast(down(0), cc), across(0))
    decay = lax.select(lower, lax.exp(lax.select(lower, diff, zero)), zero)
    qf, kf, vf = _cast(q, _F32), _cast(k, _F32), _cast(v, _F32)
    kb = _cast(lax.mul(kf, _bcast(beta, kf.shape)), dt)
    kbf = _cast(kb, _F32)
    kk = _bdot(kb, k, _NT)
    L = lax.select(strict, lax.mul(kk, decay), zero)
    T = _tile_inverse(L, eye, block, zero)
    rhs = lax.concatenate([lax.mul(vf, _bcast(beta, vf.shape)),
                           lax.mul(kbf, _bcast(gamma, kf.shape))], 2)
    Ts = _split(T)
    return dict(beta=beta, gamma=gamma, kappa=kappa, decay=decay, eye=eye, lower=lower,
                strict=strict, zero=zero, qf=qf, kf=kf, vf=vf, kb=kb, kbf=kbf, kk=kk,
                qk=_bdot(q, k, _NT), Ts=Ts, sol=_mm3(Ts, _split(rhs)))      # sol = [u | w]


def _wy_fwd_kernel(q_ref, k_ref, v_ref, rows_ref, w_ref, u_ref, qg_ref, kd_ref, p_ref):
    t = _wy_tiles(q_ref[...], k_ref[...], v_ref[...], rows_ref[...])
    dt, dv = w_ref.dtype, u_ref.shape[-1]
    sol, qf, kf = t["sol"], t["qf"], t["kf"]
    u_ref[...] = _cast(lax.slice_in_dim(sol, 0, dv, axis=2), dt)
    w_ref[...] = _cast(lax.slice_in_dim(sol, dv, sol.shape[2], axis=2), dt)
    qg_ref[...] = _cast(lax.mul(qf, _bcast(t["gamma"], qf.shape)), dt)
    kd_ref[...] = _cast(lax.mul(kf, _bcast(t["kappa"], kf.shape)), dt)
    p_ref[...] = _cast(lax.select(t["lower"], lax.mul(t["qk"], t["decay"]), t["zero"]), dt)


def _wy_bwd_kernel(q_ref, k_ref, v_ref, rows_ref, dw_ref, du_ref, dqg_ref, dkd_ref,
                   dp_ref, dq_ref, dk_ref, dv_ref, drows_ref):
    """The transposes of :func:`_wy_tiles`, which is recomputed: a (C, C) float32
    ``T`` kept from the forward pass would be 134 MB a layer. ``gc, beta, e^gc``
    and ``e^(gc_C - gc)`` count as four independent inputs here (``drows``);
    XLA differentiates :func:`_wy_rows`."""
    q, k = q_ref[...], k_ref[...]
    t = _wy_tiles(q, k, v_ref[...], rows_ref[...])
    dt, dv = q.dtype, v_ref.shape[-1]
    beta, gamma, kappa, decay, zero = (t[n] for n in ("beta", "gamma", "kappa", "decay", "zero"))
    qf, kf, vf, kb, kbf, kk, qk, sol = (
        t[n] for n in ("qf", "kf", "vf", "kb", "kbf", "kk", "qk", "sol"))
    wide = lambda col: _bcast(col, kf.shape)
    f32 = lambda ref: _cast(ref[...], _F32)
    # sol = T rhs:  drhs = T^T dsol;  dT = dsol rhs^T and dL = -T^T dT T^T, that
    # is  dL = -strict(drhs sol^T): one product over d_v + d_k, no (C, C, C) one
    drhs = _mm3(t["Ts"], _split(lax.concatenate([f32(du_ref), f32(dw_ref)], 2)), _TN)
    dL = lax.select(t["strict"], lax.neg(_mm3(_split(drhs), _split(sol), _NT)), zero)
    dP = lax.select(t["lower"], f32(dp_ref), zero)
    drv = lax.slice_in_dim(drhs, 0, dv, axis=2)
    drk = lax.slice_in_dim(drhs, dv, drhs.shape[2], axis=2)
    # the score products' transposes at the precision XLA gives them: operands in
    # the input dtype, float32 accumulation
    dkk, dqk = _cast(lax.mul(dL, decay), dt), _cast(lax.mul(dP, decay), dt)
    dkb = lax.add(_bdot(dkk, k, _NN), lax.mul(drk, wide(gamma)))
    dkd, dqg = f32(dkd_ref), f32(dqg_ref)
    dk = lax.add(lax.add(_bdot(dkk, kb, _TN), _bdot(dqk, q, _TN)),
                 lax.add(lax.mul(dkb, wide(beta)), lax.mul(dkd, wide(kappa))))
    dq_ref[...] = _cast(lax.add(_bdot(dqk, k, _NN), lax.mul(dqg, wide(gamma))), dt)
    dk_ref[...] = _cast(dk, dt)
    dv_ref[...] = _cast(lax.mul(drv, _bcast(beta, vf.shape)), dt)
    # the per-row scalars, every sum in float32: along the lanes to (G, C) ...
    rowsum = lambda x: lax.reduce_sum(x, (2,))
    E = lax.mul(lax.add(lax.mul(dL, kk), lax.mul(dP, qk)), decay)      # dD * D
    dbeta = lax.add(rowsum(lax.mul(dkb, kf)), rowsum(lax.mul(drv, vf)))
    dgamma = lax.add(rowsum(lax.mul(drk, kbf)), rowsum(lax.mul(dqg, qf)))
    dkappa = rowsum(lax.mul(dkd, kf))

    def as_row(col):    # ... and from the sublanes to the lanes, as the rows they are stored in
        spread = _bcast(lax.expand_dims(col, (2,)), decay.shape)
        return lax.expand_dims(lax.reduce_sum(lax.select(t["eye"], spread, zero), (1,)), (1,))

    dgc = lax.sub(as_row(rowsum(E)), lax.expand_dims(lax.reduce_sum(E, (1,)), (1,)))
    drows_ref[...] = lax.full(drows_ref.shape, 0.0, _F32)
    for r, row in enumerate((dgc, as_row(dbeta), as_row(dgamma), as_row(dkappa))):
        drows_ref[:, r:r + 1, :] = row


_WY_GROUP = 4


def _wy_call(kernel, name, q, v, ins, outs):
    """``G`` chunks a grid step: each chunk is a chain of dependent products,
    and one chunk's product hides another's latency (a layer's forward kernel
    took 4.6 ms at one chunk a step, 3.5 at two, 2.9 at four, 2.7 at eight;
    sixteen do not fit VMEM: PERF.md, PR 30). ``ins`` / ``outs`` name each
    operand's and result's tile: ``k`` is ``(C, d_k)``, ``v`` ``(C, d_v)``,
    ``p`` ``(C, C)``, ``r`` the float32 rows."""
    M, C, dk = q.shape
    G = next(g for g in (_WY_GROUP, 2, 1) if M % g == 0)
    tiles = {"k": (C, dk), "v": (C, v.shape[2]), "p": (C, C), "r": (_ROWS, C)}
    spec = lambda t: pl.BlockSpec((G,) + tiles[t], lambda i: (i, 0, 0))
    shape = lambda t: jax.ShapeDtypeStruct((M,) + tiles[t], _F32 if t == "r" else v.dtype)
    return pl.pallas_call(
        kernel,
        grid=(M // G,),
        in_specs=[spec(t) for t in ins],
        out_specs=[spec(t) for t in outs],
        out_shape=[shape(t) for t in outs],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=_interpret_default(),
        name=name,
    )


def _wy_fwd_pallas(q, k, v, rows):
    """``q, k (M, C, d_k)``, ``v (M, C, d_v)``, ``rows (M, 8, C)``: ``M`` chunks.
    Returns ``w, u, qg, kd, p``."""
    return _wy_call(_wy_fwd_kernel, "wy_prepare_fwd", q, v, "kkvr", "kvkkp")(q, k, v, rows)


def _wy_bwd_pallas(q, k, v, rows, dw, du, dqg, dkd, dp):
    """The cotangents ``dq, dk, dv, drows`` of :func:`_wy_fwd_pallas`'s operands."""
    cts = (t.astype(v.dtype) for t in (dw, du, dqg, dkd, dp))
    return _wy_call(_wy_bwd_kernel, "wy_prepare_bwd", q, v, "kkvr" + "kvkkp", "kkvr")(
        q, k, v, rows, *cts)


@jax.custom_vjp
def _wy_kernels(q, k, v, rows):
    return tuple(_wy_fwd_pallas(q, k, v, rows))


def _wy_kernels_fwd(q, k, v, rows):
    return tuple(_wy_fwd_pallas(q, k, v, rows)), (q, k, v, rows)


def _wy_kernels_bwd(res, cts):
    return tuple(_wy_bwd_pallas(*res, *cts))


_wy_kernels.defvjp(_wy_kernels_fwd, _wy_kernels_bwd)


def _wy_rows(g, beta):
    """What is O(C) a chunk stays in XLA: ``(rows (..., 8, C), gl (...,))``, the
    rows being ``gc = cumsum(g)``, ``beta``, ``e^gc``, ``e^(gc_C - gc)`` and
    four of zeros, each a row so that the kernels read them along the lanes."""
    gc = jnp.cumsum(g.astype(_F32), axis=-1)
    gamma = jnp.exp(gc)
    rows = jnp.stack([gc, beta.astype(_F32), gamma, jnp.exp(gc[..., -1:] - gc)], axis=-2)
    return jnp.pad(rows, ((0, 0),) * (rows.ndim - 2) + ((0, _ROWS - 4), (0, 0))), gamma[..., -1]


def _wy_pallas(q, k, v, g, beta):
    """:func:`wy_prepare` with the chunk-local algebra in the two kernels;
    operands ``(BH, N, C, .)`` as the chunk scan takes them."""
    rows, gl = _wy_rows(g, beta)
    flat = lambda t: t.reshape(-1, *t.shape[2:])
    out = _wy_kernels(flat(q), flat(k), flat(v), flat(rows))
    return tuple(t.reshape(*q.shape[:2], *t.shape[1:]) for t in out) + (gl,)


# ---------------------------------------------------------------------------------
# the chunk scan, jnp oracle: (BH, N, C, .) operands, lax.scan over N
# ---------------------------------------------------------------------------------


def _scan_jnp(w, u, qg, kd, p, gl):
    def step(S, xs):
        w, u, qg, kd, p, gl = xs
        Sb = S.astype(w.dtype)
        delta = u.astype(_F32) - jnp.einsum(
            "bcd,bdv->bcv", w, Sb, preferred_element_type=_F32)
        o = jnp.einsum("bcd,bdv->bcv", qg, Sb, preferred_element_type=_F32)
        db = delta.astype(w.dtype)
        o = o + jnp.einsum("bij,bjv->biv", p, db, preferred_element_type=_F32)
        S = gl[:, None, None] * S + jnp.einsum(
            "bcd,bcv->bdv", kd, db, preferred_element_type=_F32)
        return S, o.astype(u.dtype)

    BH, N, C, dk = w.shape
    S0 = jnp.zeros((BH, dk, u.shape[-1]), _F32)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (w, u, qg, kd, p, gl))
    _, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1)


# ---------------------------------------------------------------------------------
# the chunk scan, Pallas: grid (BH, N), N sequential, the state in VMEM scratch
# ---------------------------------------------------------------------------------


def _fwd_kernel(w_ref, u_ref, qg_ref, kd_ref, p_ref, gl_ref, o_ref, s0_ref, s_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    S = s_ref[...]
    s0_ref[0, 0] = S                      # the state this chunk starts from
    dt = w_ref.dtype
    Sb = S.astype(dt)
    delta = u_ref[0].astype(_F32) - _dot(w_ref[0], Sb, _NN)
    db = delta.astype(dt)
    o = _dot(qg_ref[0], Sb, _NN) + _dot(p_ref[0], db, _NN)
    o_ref[0] = o.astype(o_ref.dtype)
    s_ref[...] = gl_ref[0, 0] * S + _dot(kd_ref[0], db, _TN)


def _bwd_kernel(w_ref, u_ref, qg_ref, kd_ref, p_ref, gl_ref, s0_ref, do_ref,
                dw_ref, du_ref, dqg_ref, dkd_ref, dp_ref, dgl_ref, ds_ref):
    @pl.when(pl.program_id(1) == 0)      # the LAST chunk: the grid runs reversed
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dt = w_ref.dtype
    S, dS = s0_ref[0, 0], ds_ref[...]
    Sb, dSb = S.astype(dt), dS.astype(dt)
    w, qg, kd, do = w_ref[0], qg_ref[0], kd_ref[0], do_ref[0]
    delta = u_ref[0].astype(_F32) - _dot(w, Sb, _NN)
    db = delta.astype(dt)
    ddelta = _dot(p_ref[0], do, _TN) + _dot(kd, dSb, _NN)
    ddb = ddelta.astype(dt)
    du_ref[0] = ddb
    dw_ref[0] = (-_dot(ddb, Sb, _NT)).astype(dt)
    dqg_ref[0] = _dot(do, Sb, _NT).astype(dt)
    dkd_ref[0] = _dot(db, dSb, _NT).astype(dt)
    dp_ref[0] = _dot(do, db, _NT).astype(dt)
    total = jnp.sum(jnp.sum(S * dS, axis=0, keepdims=True), axis=1, keepdims=True)
    dgl_ref[0, 0] = jnp.broadcast_to(total, (1, _LANES))
    ds_ref[...] = gl_ref[0, 0] * dS + _dot(qg, do, _TN) - _dot(w, ddb, _TN)


def _specs(C, dk, dv, index):
    """Block specs of one chunk of one head; ``index(b, n)`` gives the chunk."""
    def rows(width):
        return pl.BlockSpec((1, C, width), lambda b, n: (b, index(n), 0))

    per_chunk = lambda *tail: pl.BlockSpec(
        (1, 1) + tail, lambda b, n: (b, index(n)) + (0,) * len(tail))
    return rows(dk), rows(dv), rows(C), per_chunk(1, _LANES), per_chunk(dk, dv)


def _flat(t):
    """(BH, N, C, d) -> (BH, N*C, d)."""
    return t.reshape(t.shape[0], -1, t.shape[-1])


def _lanes(gl):
    """(BH, N) -> (BH, N, 1, 128), lane-replicated: the TPU layout of a scalar
    per block."""
    return jnp.broadcast_to(gl.astype(_F32)[..., None, None], gl.shape + (1, _LANES))


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _fwd_pallas(w, u, qg, kd, p, gl):
    """``(o (BH, N, C, dv), chunk-start states (BH, N, dk, dv) float32)``."""
    BH, N, C, dk = w.shape
    dv = u.shape[-1]
    kspec, vspec, pspec, gspec, sspec = _specs(C, dk, dv, lambda n: n)
    o, s0 = pl.pallas_call(
        _fwd_kernel,
        grid=(BH, N),
        in_specs=[kspec, vspec, kspec, kspec, pspec, gspec],
        out_specs=[vspec, sspec],
        out_shape=[jax.ShapeDtypeStruct((BH, N * C, dv), u.dtype),
                   jax.ShapeDtypeStruct((BH, N, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_PARAMS,
        interpret=_interpret_default(),
        name="gated_delta_fwd",
    )(_flat(w), _flat(u), _flat(qg), _flat(kd), _flat(p), _lanes(gl))
    return o.reshape(BH, N, C, dv), s0


def _bwd_pallas(w, u, qg, kd, p, gl, s0, do):
    BH, N, C, dk = w.shape
    dv, dt = u.shape[-1], w.dtype
    kspec, vspec, pspec, gspec, sspec = _specs(C, dk, dv, lambda n: N - 1 - n)
    rows = lambda width: jax.ShapeDtypeStruct((BH, N * C, width), dt)
    dw, du, dqg, dkd, dp, dgl = pl.pallas_call(
        _bwd_kernel,
        grid=(BH, N),
        in_specs=[kspec, vspec, kspec, kspec, pspec, gspec, sspec, vspec],
        out_specs=[kspec, vspec, kspec, kspec, pspec, gspec],
        out_shape=[rows(dk), rows(dv), rows(dk), rows(dk), rows(C),
                   jax.ShapeDtypeStruct((BH, N, 1, _LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_PARAMS,
        interpret=_interpret_default(),
        name="gated_delta_bwd",
    )(_flat(w), _flat(u), _flat(qg), _flat(kd), _flat(p), _lanes(gl), s0,
      _flat(do.astype(dt)))
    shape = lambda t, like: t.reshape(like.shape)
    return (shape(dw, w), shape(du, u), shape(dqg, qg), shape(dkd, kd),
            shape(dp, p), dgl[:, :, 0, 0].astype(gl.dtype))


@jax.custom_vjp
def _scan_pallas(w, u, qg, kd, p, gl):
    return _fwd_pallas(w, u, qg, kd, p, gl)[0]


def _scan_pallas_fwd(w, u, qg, kd, p, gl):
    # the chunk-start states are NOT kept: the backward sweep recomputes them
    return _fwd_pallas(w, u, qg, kd, p, gl)[0], (w, u, qg, kd, p, gl)


def _scan_pallas_bwd(res, do):
    # behind a barrier with the cotangent, or XLA merges this sweep with the
    # forward pass's identical call and keeps its states alive until here
    res, do = jax.lax.optimization_barrier((res, do))
    _, s0 = _fwd_pallas(*res)
    return _bwd_pallas(*res, s0, do)


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)


def _probe_pallas(q, k, v, g, beta):
    """Guard probe: the four kernels must build, the chunk-local algebra's
    forward and backward and the scan's."""
    o, vjp = jax.vjp(lambda *a: _scan_pallas(*_wy_pallas(*a)), q, k, v, g, beta)
    vjp(jnp.zeros_like(o))
    return o


# ---------------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------------


def gated_delta_rule(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    chunk: int = DEFAULT_CHUNK,
    impl: Optional[str] = None,
    heads_first: bool = False,
) -> jax.Array:
    """The gated delta rule over whole sequences, state zero at the start.

    ``q, k``: ``(B, S, H, d_k)`` (scaled and normalised by the caller; a key
    head that serves several value heads arrives once for each of them: the
    caller repeats it, or writes it so, as ``ops.deltanet.deltanet_qkv`` does),
    ``v``: ``(B, S, H, d_v)``, ``g`` (the log of the decay, <= 0) and ``beta``:
    ``(B, S, H)``. Returns ``o (B, S, H, d_v)`` in ``v``'s dtype. Matmul
    operands keep the input dtype, accumulation and the state are float32.

    ``heads_first``: the operands are ``(B, H, S, d)`` and ``(B, H, S)`` and so is
    ``o``, which is how the kernels read and write them: a caller that holds them
    so spares the two re-layouts.

    A sequence that is not a multiple of ``chunk`` is padded at its end with
    steps that leave the state alone (``beta = 0``, ``g = 0``) and whose
    outputs are cut off."""
    if heads_first:
        B, H, S, dk = q.shape
    else:
        B, S, H, dk = q.shape
    dv = v.shape[-1]
    if k.shape != q.shape or v.shape[:3] != q.shape[:3] or g.shape != q.shape[:3] \
            or beta.shape != g.shape:
        raise ValueError(
            f"gated_delta_rule shapes mismatch: q {q.shape} k {k.shape} "
            f"v {v.shape} g {g.shape} beta {beta.shape}")
    if chunk < 1 or chunk & (chunk - 1):      # the blockwise inverse halves its way down
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    impl, forced = _dispatch(
        "gated_delta_rule", impl, is_kernel_available(chunk, dk, dv),
        f"chunk {chunk} is not a multiple of 64 or d_k {dk} / d_v {dv} not of {_LANES}",
        q, k, v, statics=(chunk,))
    pad = -S % chunk
    N = (S + pad) // chunk

    def chunks(t):
        """(B, S, H, ...) or (B, H, S, ...) -> (B*H, N, C, ...), the tail padded
        with zeros."""
        if not heads_first:
            t = jnp.moveaxis(t, 2, 1)
        if pad:
            t = jnp.pad(t, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 3))
        return t.reshape(B * H, N, chunk, *t.shape[3:])

    with _span("gated_delta"):
        chunked = tuple(chunks(t) for t in (q, k, v, g, beta))
        if impl == "pallas" and not forced:
            impl = _checked_impl("gated_delta_rule", impl, _probe_pallas, *chunked)
        # either way only the five operands live on to the backward pass, which
        # recomputes the float32 intermediates (the decay mask, the triangular
        # system and its solution): in XLA they are ~1 GB a layer at 32 heads x
        # 8192 tokens, the operands 0.3 GB; in the kernels they never leave VMEM
        wy, scan = ((_wy_pallas, _scan_pallas) if impl == "pallas"
                    else (jax.checkpoint(wy_prepare), _scan_jnp))
        operands = wy(*chunked)
        with _span("gated_delta_scan"):     # innermost: names the scan's kernels
            o = scan(*operands)
    o = o.reshape(B, H, N * chunk, dv)[:, :, :S]
    return o if heads_first else jnp.moveaxis(o, 1, 2)
