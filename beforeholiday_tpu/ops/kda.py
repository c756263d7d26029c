"""Kimi Delta Attention's recurrence — a gated delta rule whose decay is a
vector over the key's channels — chunked.

Per head, with a state ``S`` of shape ``(d_k, d_v)`` that starts at zero (Kimi
Linear, Moonshot AI 2025; see PAPERS.md)::

    S~  = Diag(a_t) S_{t-1}                      a_t = exp(g_t) in (0, 1]^{d_k}
    S_t = S~ + k_t (b_t (v_t - S~^T k_t))^T      the delta rule
    o_t = S_t^T q_t

``ops.gated_delta`` is the same rule under ONE decay a head, and the chunk-wise
form is the same too — WY factors from a unit lower-triangular system, then a
three-product update from chunk to chunk — but with ``G`` the running sum of
``g`` inside a chunk the in-chunk matrices are

    A_ij = b_i sum_c k_ic k_jc e^{G_ic - G_jc}     P_ij = sum_c q_ic k_jc e^{G_ic - G_jc}

(``i >= j``): the decay sits INSIDE the ``d_k``-deep contraction and no mask on a
finished product gives it. Splitting it as ``(q_i e^{G_i}) . (k_j e^{-G_j})`` forms
a quotient of decays, which overflows (a channel's log-decay reaches -64 a
token). Here the lower triangle is cut by halves, as a blockwise inverse cuts it
(:func:`_decayed_scores`): at a level of half-width ``s`` the rows ``[s, 2s)`` of
every aligned block of ``2s`` meet its rows ``[0, s)``, and both sides are
measured from the block's middle row ``r``: ``(q_i e^{G_i - G_r}) . (k_j e^{G_r -
G_j})`` with ``i >= r > j`` — **every exponent is of a difference that is <= 0**,
whatever the decay, and each level is one product on the MXU. ``log2(C)`` levels
reach every pair ``i > j``; the diagonal of ``P`` is ``q_i . k_i``. ``G_r`` is
read by a product with a 0/1 matrix on three bfloat16 parts of ``G`` whose sum
is ``G`` to the last bit (:func:`_rows_through`).

The rest takes a ``(C, d_k)`` or ``(d_k,)`` decay where the scalar form has
``(C,)`` or ``()``: the system ``(I + strict(A)) [u | w] = [b v | b k e^G]``
(``ops.gated_delta``'s blockwise inverse), the decayed queries ``q e^G`` and keys
``k e^{G_C - G}``, and the scan ``delta = u - w S; o = (q e^G) S + P delta; S <-
Diag(e^{G_C}) S + (k e^{G_C - G})^T delta``.

* :func:`kda_prepare` is the chunk-local algebra in plain ``jax.numpy``, every
  chunk of every head at once, differentiated by XLA and recomputed in the
  backward pass: the kernels' parity oracle, ``impl="jnp"`` and the off-TPU
  default (with :func:`_scan_jnp`, a ``lax.scan`` over chunks).
* On the Pallas path ``kda_prepare_fwd`` / ``kda_prepare_bwd`` hold a chunk's
  tiles in VMEM from the operands to the factors, the running sum ``G`` among
  them (a product with the triangular ones on three exact parts of ``g``:
  :func:`_rows_through`); the backward kernel recomputes them and transposes the SAME
  function (``jax.vjp`` inside the kernel body: the three pieces that must not be
  differentiated as written — the exact row read and running sum, the score
  product, the triangular solve — carry their own transposes). The chunk scan
  ``kda_scan_fwd`` / ``kda_scan_bwd`` keeps the state in VMEM TRANSPOSED, ``(d_v,
  d_k)``, so that its decay is a row along the lanes. One ``custom_vjp`` spans
  the four (:func:`_rule_pallas`): its residuals are the operands, and the
  backward pass runs the two forward kernels once more for the factors and the
  chunk-start states.

Exponentials, running sums, the state and the inverse are float32; matrix
operands keep the input dtype (the inverse and its solve in three bfloat16
passes, as in the scalar form).
"""

from __future__ import annotations

import functools
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
from beforeholiday_tpu.ops.gated_delta import (
    _NN, _NT, _TN, _ROWS, _bdot, _dot, _flat, _mm3, _split, _tile_inverse,
    is_kernel_available,
)

__all__ = ["kda_prepare", "kda_rule", "is_kernel_available"]

_F32 = jnp.float32
_BF16 = jnp.bfloat16
DEFAULT_CHUNK = 64


# ---------------------------------------------------------------------------------
# the three pieces with transposes of their own
# ---------------------------------------------------------------------------------


def _iotas(G, C):
    """Row and column indices of a ``(G, C, C)`` tile."""
    cc = (G, C, C)
    return lax.broadcasted_iota(jnp.int32, cc, 1), lax.broadcasted_iota(jnp.int32, cc, 2)


def _pick(G, C, half):
    """The 0/1 matrix of :func:`_rows_through`, bfloat16: row ``i`` has its one at
    the middle row of ``i``'s aligned block of ``2 * half``; ``half`` None: ones at
    every row up to ``i`` (the lower triangle: a running sum)."""
    ii, jj = _iotas(G, C)
    if half is None:
        return lax.convert_element_type(lax.ge(ii, jj), _BF16)
    middle = lax.add(lax.sub(ii, lax.bitwise_and(ii, lax.full(ii.shape, 2 * half - 1, jnp.int32))),
                     lax.full(ii.shape, half, jnp.int32))
    return lax.convert_element_type(lax.eq(jj, middle), _BF16)


def _thirds(x):
    """float32 -> three bfloat16 parts whose sum is ``x`` exactly (8 + 8 + 8 bits)."""
    hi = lax.convert_element_type(x, _BF16)
    rest = lax.sub(x, lax.convert_element_type(hi, _F32))
    mid = lax.convert_element_type(rest, _BF16)
    return hi, mid, lax.convert_element_type(
        lax.sub(rest, lax.convert_element_type(mid, _F32)), _BF16)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rows_through(x, half):
    """``_pick(half) @ x`` for ``x (G, C, d)`` float32, on three bfloat16 parts of
    ``x`` whose sum is ``x`` exactly, accumulated in float32 on the MXU. With a
    ``half``: row ``i`` replaced by the middle row of ``i``'s aligned block of ``2 *
    half`` rows, bit for bit (one non-zero term a sum: a gather). With None: the
    running sum down each chunk's rows, what a float32 ``cumsum`` gives (XLA's is
    a ``reduce_window`` that took a layer's 134 MB longer than the kernel that
    reads it). The transpose is the transposed product on two parts (2^-17)."""
    pick = _pick(x.shape[0], x.shape[1], half)
    hi, mid, lo = _thirds(x)
    return lax.add(lax.add(_bdot(pick, hi, _NN), _bdot(pick, mid, _NN)), _bdot(pick, lo, _NN))


def _rows_through_bwd(half, _, ct):
    pick = _pick(ct.shape[0], ct.shape[1], half)
    hi, lo = _split(ct)
    return (lax.add(_bdot(pick, hi, _TN), _bdot(pick, lo, _TN)),)


_rows_through.defvjp(lambda x, half: (_rows_through(x, half), None), _rows_through_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _scores(left, right, dt):
    """``left (G, R, d) @ right (G, C, d)^T`` with float32 operands rounded to
    ``dt`` at the product and float32 cotangents."""
    return _bdot(lax.convert_element_type(left, dt), lax.convert_element_type(right, dt), _NT)


def _scores_fwd(left, right, dt):
    return _scores(left, right, dt), (left, right)


def _scores_bwd(dt, res, ct):
    # at the precision XLA gives a product's transposes: operands in the input
    # dtype, float32 accumulation, and the cotangents stay float32
    left, right = (lax.convert_element_type(t, dt) for t in res)
    ct = lax.convert_element_type(ct, dt)
    return _bdot(ct, right, _NN), _bdot(ct, left, _TN)


_scores.defvjp(_scores_fwd, _scores_bwd)


def _solve_fwd(L, rhs):
    ii, jj = _iotas(*L.shape[:2])
    zero = lax.full(L.shape, 0.0, _F32)
    Ts = _split(_tile_inverse(L, lax.eq(ii, jj), lax.bitwise_xor(ii, jj), zero))
    sol = _mm3(Ts, _split(rhs))
    return sol, (Ts, sol)


def _solve_bwd(res, dsol):
    # sol = T rhs:  drhs = T^T dsol;  dT = dsol rhs^T and dL = -T^T dT T^T, that
    # is  dL = -strict(drhs sol^T): one product over d_v + d_k, no (C, C, C) one
    Ts, sol = res
    drhs = _mm3(Ts, _split(dsol), _TN)
    ii, jj = _iotas(*sol.shape[:2])
    dL = lax.select(lax.gt(ii, jj), lax.neg(_mm3(_split(drhs), _split(sol), _NT)),
                    lax.full(ii.shape, 0.0, _F32))
    return dL, drhs


@jax.custom_vjp
def _solve(L, rhs):
    """``(I + L)^-1 rhs`` for strictly lower-triangular ``L (G, C, C)``: the
    blockwise inverse of ``ops.gated_delta`` and its product, three bfloat16
    passes each."""
    return _solve_fwd(L, rhs)[0]


_solve.defvjp(_solve_fwd, _solve_bwd)


# ---------------------------------------------------------------------------------
# the state-free part: one function, in XLA (jnp) and in both kernels
# ---------------------------------------------------------------------------------


def _decayed_scores(lefts, k, G, dt):
    """``[sum_c a_ic k_jc e^{G_ic - G_jc} for i > j, zero elsewhere]`` for each
    ``a`` of ``lefts``, all ``(G, C, d)`` float32: the lower triangle cut by
    halves (the module's docstring). One exponential a level serves both sides:
    a row of a block's upper half is a key there and takes ``e^{G_r - G_j}``, a
    row of its lower half a query and takes ``e^{G_i - G_r}``."""
    n, C, d = k.shape
    ii, jj = _iotas(n, C)
    row = lax.broadcasted_iota(jnp.int32, k.shape, 1)
    zero, zero_cc = lax.full(k.shape, 0.0, _F32), lax.full(ii.shape, 0.0, _F32)
    out = [zero_cc] * len(lefts)
    half = C // 2
    while half >= 1:
        width = lax.full(row.shape, 2 * half - 1, jnp.int32)
        lower = lax.ge(lax.bitwise_and(row, width), lax.full(row.shape, half, jnp.int32))
        away = lax.sub(G, _rows_through(G, half))          # G_i - G_r
        e = lax.exp(lax.select(lower, away, lax.neg(away)))    # both branches <= 0
        stacked = lax.concatenate(
            [lax.select(lower, lax.mul(a, e), zero) for a in lefts], 1)
        m = _scores(stacked, lax.select(lower, zero, lax.mul(k, e)), dt)
        same = lax.lt(lax.bitwise_xor(ii, jj), lax.full(ii.shape, 2 * half, jnp.int32))
        out = [lax.add(o, lax.select(same, lax.slice_in_dim(m, t * C, (t + 1) * C, axis=1),
                                     zero_cc)) for t, o in enumerate(out)]
        half //= 2
    return out


def _chunk_factors(q, k, v, g, beta):
    """``q, k (n, C, d_k)``, ``v (n, C, d_v)`` in the matmul dtype, ``g (n, C,
    d_k)`` float32 (the log-decay), ``beta (n, C, 1)`` float32. Returns ``w, u,
    qg, kd`` and the in-chunk scores ``p (n, C, C)``, all in ``v``'s dtype."""
    dt = v.dtype
    G = _rows_through(g, None)        # the running sum inside the chunk
    f32 = lambda t: lax.convert_element_type(t, _F32)
    qf, kf, vf = f32(q), f32(k), f32(v)
    wide = lambda col, like: lax.broadcast_in_dim(col, like.shape, (0, 1, 2))
    kbf = lax.mul(kf, wide(beta, kf))
    below, L = _decayed_scores([qf, kbf], kf, G, dt)
    ii, jj = _iotas(*below.shape[:2])
    # q_i . k_i: no decay at i = j
    diagonal = lax.expand_dims(lax.reduce_sum(lax.mul(qf, kf), (2,)), (2,))
    p = lax.add(below, lax.select(lax.eq(ii, jj), wide(diagonal, below),
                                  lax.full(ii.shape, 0.0, _F32)))
    gamma = lax.exp(G)
    rhs = lax.concatenate([lax.mul(vf, wide(beta, vf)), lax.mul(kbf, gamma)], 2)
    sol = _solve(L, rhs)                                     # [u | w]
    dv = v.shape[2]
    u = lax.slice_in_dim(sol, 0, dv, axis=2)
    w = lax.slice_in_dim(sol, dv, sol.shape[2], axis=2)
    # the chunk's last row (a sum of one term and zeros) over every row: G_C - G <= 0
    row = lax.broadcasted_iota(jnp.int32, G.shape, 1)
    last = lax.reduce_sum(lax.select(lax.eq(row, lax.full(row.shape, G.shape[1] - 1, jnp.int32)),
                                     G, lax.full(G.shape, 0.0, _F32)), (1,))
    kd = lax.mul(kf, lax.exp(lax.sub(lax.broadcast_in_dim(last, G.shape, (0, 2)), G)))
    cast = lambda t: lax.convert_element_type(t, dt)
    return cast(w), cast(u), cast(lax.mul(qf, gamma)), cast(kd), cast(p)


def _whole_decay(g):
    """``e^{G_C} (..., d_k)``: the decay of each whole chunk of ``g (..., C, d_k)``."""
    return jnp.exp(jnp.sum(g.astype(_F32), axis=-2))


def kda_prepare(q, k, v, g, beta):
    """The chunk-local factors. ``q, k``: ``(..., C, d_k)``, ``v``: ``(..., C,
    d_v)``, ``g`` (log decay, <= 0): ``(..., C, d_k)`` and ``beta``: ``(..., C)``
    float32. Returns ``(w, u, qg, kd, p, gl)``: the WY factors, the decayed
    queries and keys, the in-chunk scores ``p (.., C, C)`` and the whole chunk's
    decay ``gl (.., d_k)``."""
    lead = q.shape[:-2]
    flat = lambda t: t.reshape(-1, *t.shape[len(lead):])
    out = _chunk_factors(flat(q), flat(k), flat(v), flat(g.astype(_F32)),
                         flat(beta.astype(_F32))[..., None])
    return tuple(t.reshape(*lead, *t.shape[1:]) for t in out) + (_whole_decay(g),)


# ---------------------------------------------------------------------------------
# the state-free part, Pallas: one chunk's tiles in VMEM from operands to factors
# ---------------------------------------------------------------------------------


def _column(rows):
    """Row 0 of ``rows (G, 8, C)`` (``beta`` along the lanes) as ``(G, C, 1)``."""
    n, _, C = rows.shape
    ii, jj = _iotas(n, C)
    across = lax.broadcast_in_dim(lax.slice_in_dim(rows, 0, 1, axis=1), ii.shape, (0, 1, 2))
    return lax.expand_dims(lax.reduce_sum(
        lax.select(lax.eq(ii, jj), across, lax.full(ii.shape, 0.0, _F32)), (2,)), (2,))


def _factors_of_refs(q, k, v, g, rows):
    return _chunk_factors(q, k, v, g, _column(rows))


def _prepare_fwd_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref,
                        w_ref, u_ref, qg_ref, kd_ref, p_ref):
    out = _factors_of_refs(q_ref[...], k_ref[...], v_ref[...], g_ref[...], rows_ref[...])
    for ref, t in zip((w_ref, u_ref, qg_ref, kd_ref, p_ref), out):
        ref[...] = t


def _prepare_bwd_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref, dw_ref, du_ref, dqg_ref,
                        dkd_ref, dp_ref, dlast_ref, dq_ref, dk_ref, dv_ref, dg_ref, drows_ref):
    """The factors recomputed and their function transposed where it stands: a
    kept ``T`` would be 67 MB of float32 a layer, and a second spelling of seven
    levels' chain rule one more thing to keep equal to the first. ``dlast`` (row 0
    of its 8) is the cotangent of the chunk's whole log-decay ``G_C``, which the
    scan's ``e^{G_C}`` hands back: it joins the function as ``sum(G_C * dlast)``."""
    dlast = lax.slice_in_dim(dlast_ref[...], 0, 1, axis=1)              # (n, 1, d_k)

    def with_last(q, k, v, g, rows):
        return _factors_of_refs(q, k, v, g, rows), lax.reduce_sum(g, (1,))

    _, vjp = jax.vjp(with_last, q_ref[...], k_ref[...], v_ref[...], g_ref[...], rows_ref[...])
    cts = vjp((tuple(r[...] for r in (dw_ref, du_ref, dqg_ref, dkd_ref, dp_ref)),
               lax.squeeze(dlast, (1,))))
    for ref, t in zip((dq_ref, dk_ref, dv_ref, dg_ref, drows_ref), cts):
        ref[...] = t.astype(ref.dtype)


# chunks a grid step: each chunk is a chain of dependent products and one chunk's product
# hides another's latency (a layer's forward kernel took 7.7 ms at one chunk of 128 a step,
# 6.3 at two, 4.9 at four, the backward one 13.3 / 10.1 / 8.2: my chip run, PR 49)
_GROUP = 4
_VMEM_LIMIT = 64 * 1024 * 1024


def _prepare_call(kernel, name, q, v, ins, outs):
    """``ins`` / ``outs`` name each operand's and result's tile: ``k`` is ``(C,
    d_k)``, ``v`` ``(C, d_v)``, ``p`` ``(C, C)``, ``g`` ``(C, d_k)`` float32, ``r``
    the float32 rows ``(8, C)``, ``l`` float32 rows ``(8, d_k)``."""
    M, C, dk = q.shape
    n = next(n for n in (_GROUP, 2, 1) if M % n == 0)
    tiles = {"k": (C, dk), "v": (C, v.shape[2]), "p": (C, C), "g": (C, dk), "r": (_ROWS, C),
             "l": (_ROWS, dk)}
    spec = lambda t: pl.BlockSpec((n,) + tiles[t], lambda i: (i, 0, 0))
    shape = lambda t: jax.ShapeDtypeStruct((M,) + tiles[t], _F32 if t in "grl" else v.dtype)
    return pl.pallas_call(
        kernel,
        grid=(M // n,),
        in_specs=[spec(t) for t in ins],
        out_specs=[spec(t) for t in outs],
        out_shape=[shape(t) for t in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_default(),
        name=name,
    )


def _prepare_fwd(q, k, v, g, rows):
    """``q, k (M, C, d_k)``, ``v (M, C, d_v)``, ``g (M, C, d_k)``, ``rows (M, 8, C)``
    (``beta`` in row 0): ``w, u, qg, kd, p`` of ``M`` chunks."""
    return tuple(_prepare_call(_prepare_fwd_kernel, "kda_prepare_fwd", q, v, "kkvgr", "kvkkp")(
        q, k, v, g, rows))


def _prepare_bwd(q, k, v, g, rows, dw, du, dqg, dkd, dp, dlast):
    """The cotangents ``dq, dk, dv, dg, drows`` of :func:`_prepare_fwd`'s operands
    and of the chunk's whole log-decay (``dlast (M, d_k)``)."""
    cts = tuple(t.astype(v.dtype) for t in (dw, du, dqg, dkd, dp))
    dlast = jnp.pad(dlast.astype(_F32)[:, None, :], ((0, 0), (0, _ROWS - 1), (0, 0)))
    return tuple(_prepare_call(
        _prepare_bwd_kernel, "kda_prepare_bwd", q, v, "kkvgr" + "kvkkp" + "l", "kkvgr")(
            q, k, v, g, rows, *cts, dlast))


# ---------------------------------------------------------------------------------
# the chunk scan, jnp oracle: (BH, N, C, .) operands, lax.scan over N
# ---------------------------------------------------------------------------------


def _scan_jnp(w, u, qg, kd, p, gl):
    def step(S, xs):
        w, u, qg, kd, p, gl = xs
        Sb = S.astype(w.dtype)
        delta = u.astype(_F32) - jnp.einsum("bcd,bdv->bcv", w, Sb, preferred_element_type=_F32)
        db = delta.astype(w.dtype)
        o = jnp.einsum("bcd,bdv->bcv", qg, Sb, preferred_element_type=_F32) \
            + jnp.einsum("bij,bjv->biv", p, db, preferred_element_type=_F32)
        S = gl[:, :, None] * S + jnp.einsum("bcd,bcv->bdv", kd, db, preferred_element_type=_F32)
        return S, o.astype(u.dtype)

    BH, N, C, dk = w.shape
    S0 = jnp.zeros((BH, dk, u.shape[-1]), _F32)
    _, o = lax.scan(step, S0, tuple(jnp.moveaxis(t, 1, 0) for t in (w, u, qg, kd, p, gl)))
    return jnp.moveaxis(o, 0, 1)


# ---------------------------------------------------------------------------------
# the chunk scan, Pallas: grid (BH, N), N sequential, the state TRANSPOSED in VMEM
# ---------------------------------------------------------------------------------


def _scan_fwd_kernel(w_ref, u_ref, qg_ref, kd_ref, p_ref, gl_ref, o_ref, s0_ref, s_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    St = s_ref[...]                       # (d_v, d_k): a channel's decay is a lane's
    s0_ref[0, 0] = St                     # the state this chunk starts from
    dt = w_ref.dtype
    Sb = St.astype(dt)
    delta = u_ref[0].astype(_F32) - _dot(w_ref[0], Sb, _NT)
    db = delta.astype(dt)
    o_ref[0] = (_dot(qg_ref[0], Sb, _NT) + _dot(p_ref[0], db, _NN)).astype(o_ref.dtype)
    s_ref[...] = gl_ref[0, 0] * St + _dot(db, kd_ref[0], _TN)


def _scan_bwd_kernel(w_ref, u_ref, qg_ref, kd_ref, p_ref, gl_ref, s0_ref, do_ref,
                     dw_ref, du_ref, dqg_ref, dkd_ref, dp_ref, dgl_ref, ds_ref):
    @pl.when(pl.program_id(1) == 0)      # the LAST chunk: the grid runs reversed
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dt = w_ref.dtype
    St, dSt = s0_ref[0, 0], ds_ref[...]
    Sb, dSb = St.astype(dt), dSt.astype(dt)
    w, qg, kd, do = w_ref[0], qg_ref[0], kd_ref[0], do_ref[0]
    delta = u_ref[0].astype(_F32) - _dot(w, Sb, _NT)
    db = delta.astype(dt)
    ddelta = _dot(p_ref[0], do, _TN) + _dot(kd, dSb, _NT)
    ddb = ddelta.astype(dt)
    du_ref[0] = ddb
    dw_ref[0] = (-_dot(ddb, Sb, _NN)).astype(dt)
    dqg_ref[0] = _dot(do, Sb, _NN).astype(dt)
    dkd_ref[0] = _dot(db, dSb, _NN).astype(dt)
    dp_ref[0] = _dot(do, db, _NT).astype(dt)
    dgl_ref[0, 0] = jnp.sum(St * dSt, axis=0, keepdims=True)
    ds_ref[...] = gl_ref[0, 0] * dSt + _dot(do, qg, _TN) - _dot(ddb, w, _TN)


def _scan_specs(C, dk, dv, index):
    """Block specs of one chunk of one head; ``index(n)`` gives the chunk."""
    rows = lambda width: pl.BlockSpec((1, C, width), lambda b, n: (b, index(n), 0))
    per_chunk = lambda *tail: pl.BlockSpec((1, 1) + tail, lambda b, n: (b, index(n), 0, 0))
    return rows(dk), rows(dv), rows(C), per_chunk(1, dk), per_chunk(dv, dk)


_SCAN_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _scan_fwd(w, u, qg, kd, p, gl):
    """``(o (BH, N, C, dv), chunk-start states (BH, N, dv, dk) float32)``."""
    BH, N, C, dk = w.shape
    dv = u.shape[-1]
    kspec, vspec, pspec, gspec, sspec = _scan_specs(C, dk, dv, lambda n: n)
    o, s0 = pl.pallas_call(
        _scan_fwd_kernel,
        grid=(BH, N),
        in_specs=[kspec, vspec, kspec, kspec, pspec, gspec],
        out_specs=[vspec, sspec],
        out_shape=[jax.ShapeDtypeStruct((BH, N * C, dv), u.dtype),
                   jax.ShapeDtypeStruct((BH, N, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_SCAN_PARAMS,
        interpret=_interpret_default(),
        name="kda_scan_fwd",
    )(_flat(w), _flat(u), _flat(qg), _flat(kd), _flat(p), gl[:, :, None, :])
    return o.reshape(BH, N, C, dv), s0


def _scan_bwd(w, u, qg, kd, p, gl, s0, do):
    BH, N, C, dk = w.shape
    dv, dt = u.shape[-1], w.dtype
    kspec, vspec, pspec, gspec, sspec = _scan_specs(C, dk, dv, lambda n: N - 1 - n)
    rows = lambda width: jax.ShapeDtypeStruct((BH, N * C, width), dt)
    dw, du, dqg, dkd, dp, dgl = pl.pallas_call(
        _scan_bwd_kernel,
        grid=(BH, N),
        in_specs=[kspec, vspec, kspec, kspec, pspec, gspec, sspec, vspec],
        out_specs=[kspec, vspec, kspec, kspec, pspec, gspec],
        out_shape=[rows(dk), rows(dv), rows(dk), rows(dk), rows(C),
                   jax.ShapeDtypeStruct((BH, N, 1, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_SCAN_PARAMS,
        interpret=_interpret_default(),
        name="kda_scan_bwd",
    )(_flat(w), _flat(u), _flat(qg), _flat(kd), _flat(p), gl[:, :, None, :], s0,
      _flat(do.astype(dt)))
    shape = lambda t, like: t.reshape(like.shape)
    return (shape(dw, w), shape(du, u), shape(dqg, qg), shape(dkd, kd), shape(dp, p),
            dgl[:, :, 0, :])


def _operands(q, k, v, g, beta):
    """The kernels' operands of chunked ``(BH, N, C, .)`` ones: ``(q, k, v, g,
    rows)``, chunks flat, ``beta`` along the lanes of row 0 of ``rows (M, 8, C)``."""
    flat = lambda t: t.reshape(-1, *t.shape[2:])
    rows = jnp.pad(beta.astype(_F32)[..., None, :], ((0, 0), (0, 0), (0, _ROWS - 1), (0, 0)))
    return flat(q), flat(k), flat(v), flat(g.astype(_F32)), flat(rows)


def _factors(res, BH):
    """:func:`_prepare_fwd` of the kernels' operands, as the scan takes them:
    ``(w, u, qg, kd, p, gl)``, ``(BH, N, C, .)`` and ``gl (BH, N, d_k)``."""
    gl = _whole_decay(res[3]).reshape(BH, -1, res[3].shape[-1])
    return tuple(t.reshape(BH, -1, *t.shape[1:]) for t in _prepare_fwd(*res)) + (gl,)


@jax.custom_vjp
def _rule_pallas(q, k, v, g, beta):
    """The four kernels on chunked operands ``(BH, N, C, .)``: ``o (BH, N, C, d_v)``.
    ONE ``custom_vjp`` over the factors and the scan: its residuals are the
    kernels' operands, and the backward pass makes the five factors again (335 MB
    a layer at 32 heads x 8192 tokens, which as residuals of the scan put the
    five-layer step 1.3 GB further over the chip) and the chunk-start states
    (0.27 GB)."""
    return _scan_fwd(*_factors(_operands(q, k, v, g, beta), q.shape[0]))[0]


def _rule_pallas_fwd(q, k, v, g, beta):
    res = _operands(q, k, v, g, beta)
    return _scan_fwd(*_factors(res, q.shape[0]))[0], (res, q.shape)


def _rule_pallas_bwd(saved, do):
    res, (BH, N, C, _) = saved
    # behind a barrier with the cotangent, or XLA merges these calls with the
    # forward pass's identical ones and keeps their results alive until here
    res, do = lax.optimization_barrier((res, do))
    factors = _factors(res, BH)
    _, s0 = _scan_fwd(*factors)
    *dfactors, dgl = _scan_bwd(*factors, s0, do)
    flat = lambda t: t.reshape(-1, *t.shape[2:])
    # gl = e^{G_C}: the cotangent of G_C is dgl * gl
    dq, dk, dv, dg, drows = _prepare_bwd(*res, *(flat(t) for t in dfactors),
                                         flat(dgl * factors[5]))
    chunked = lambda t: t.reshape(BH, N, *t.shape[1:])
    return chunked(dq), chunked(dk), chunked(dv), chunked(dg), chunked(drows[:, 0, :])


_rule_pallas.defvjp(_rule_pallas_fwd, _rule_pallas_bwd)


def _probe_pallas(q, k, v, g, beta):
    """Guard probe: the four kernels must build."""
    o, vjp = jax.vjp(_rule_pallas, q, k, v, g, beta)
    vjp(jnp.zeros_like(o))
    return o


# ---------------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------------


def kda_rule(
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
    """The delta rule under a decay a key channel, over whole sequences, state
    zero at the start.

    ``q, k``: ``(B, S, H, d_k)`` (scaled and normalised by the caller), ``v``:
    ``(B, S, H, d_v)``, ``g`` (the log of the decay, <= 0, float32): ``(B, S, H,
    d_k)``, ``beta``: ``(B, S, H)``. Returns ``o (B, S, H, d_v)`` in ``v``'s dtype.
    Matmul operands keep the input dtype; exponentials, running sums, the inverse
    and the state are float32. ``heads_first``: operands and result are ``(B, H,
    S, ...)``, as the kernels read and write them.

    A sequence that is not a multiple of ``chunk`` is padded at its end with
    steps that leave the state alone (``beta = 0``, ``g = 0``) and whose outputs
    are cut off."""
    if heads_first:
        B, H, S, dk = q.shape
    else:
        B, S, H, dk = q.shape
    dv = v.shape[-1]
    if k.shape != q.shape or v.shape[:3] != q.shape[:3] or g.shape != q.shape \
            or beta.shape != q.shape[:3]:
        raise ValueError(
            f"kda_rule shapes mismatch: q {q.shape} k {k.shape} v {v.shape} g {g.shape} "
            f"beta {beta.shape}")
    if chunk < 1 or chunk & (chunk - 1):      # the triangle and the inverse halve their way down
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    impl, forced = _dispatch(
        "kda_rule", impl, is_kernel_available(chunk, dk, dv),
        f"chunk {chunk} is not a multiple of 64 or d_k {dk} / d_v {dv} not of 128",
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

    with _span("kda"):
        chunked = tuple(chunks(t) for t in (q, k, v, g, beta))
        if impl == "pallas" and not forced:
            impl = _checked_impl("kda_rule", impl, _probe_pallas, *chunked)
        # either way only the operands live on to the backward pass, which
        # recomputes the float32 intermediates and, in the kernels, the factors
        if impl == "pallas":
            o = _rule_pallas(*chunked)
        else:
            o = _scan_jnp(*jax.checkpoint(kda_prepare)(*chunked))
    o = o.reshape(B, H, N * chunk, dv)[:, :, :S]
    return o if heads_first else jnp.moveaxis(o, 1, 2)
