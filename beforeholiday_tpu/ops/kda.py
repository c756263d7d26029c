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
* On the Pallas path ONE kernel a pass makes a grid step's chunk factors and
  walks the chunk scan over them, so that ``w, u, qg, kd, p`` and their
  cotangents live in VMEM alone (335 MB a layer each way at 32 heads x 8192
  tokens, had they gone through HBM). Grid ``(heads, chunks / n)``, a head's
  steps in order, ``n`` chunks a step: ``kda_fwd`` holds the ``n`` chunks' tiles
  in VMEM from the operands to the factors, the running sum ``G`` among them (a
  product with the triangular ones on three exact parts of ``g``:
  :func:`_rows_through`), then takes the chunks in order on a state kept in VMEM
  TRANSPOSED, ``(d_v, d_k)``, so that its decay is a row along the lanes.
  ``kda_bwd`` makes the factors again — ONCE, as the primal half of ``jax.vjp``
  of the same function inside the kernel body (the three pieces that must not be
  differentiated as written — the exact row read and running sum, the score
  product, the triangular solve — carry their own transposes) — walks its chunks
  forward from the step's start state, then back on the state's cotangent, and
  pulls the factors' cotangents through that ``vjp``. One ``custom_vjp`` spans the
  two (:func:`_rule_pallas`): its residuals are the operands and the state each
  grid step starts from.

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
    _NN, _NT, _TN, _ROWS, _bdot, _dot, _mm3, _split, _tile_inverse,
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
# Pallas: a grid step's chunks from operands to factors in VMEM, and the scan over them
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


def _advance(St, w, u, kd, g):
    """One chunk of the scan on the TRANSPOSED state ``St (d_v, d_k)`` (a
    channel's decay is a lane's): ``(S^T and delta in the matmul dtype, e^{G_C}
    (1, d_k), the next chunk's state)``."""
    dt = w.dtype
    Sb = St.astype(dt)
    db = (u.astype(_F32) - _dot(w, Sb, _NT)).astype(dt)
    gl = jnp.exp(jnp.sum(g, axis=0, keepdims=True))
    return Sb, db, gl, gl * St + _dot(db, kd, _TN)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref, o_ref, s0_ref, s_ref):
    """The factors of this step's ``n`` chunks at once (``n`` independent chains
    of products: one hides another's latency), then the chunks in order on the
    state in ``s_ref``. ``s0_ref``: the state this step's FIRST chunk starts
    from."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    g = g_ref[...]
    w, u, qg, kd, p = _factors_of_refs(q_ref[...], k_ref[...], v_ref[...], g, rows_ref[...])
    St = s0_ref[0] = s_ref[...]
    for i in range(g.shape[0]):
        Sb, db, _, St = _advance(St, w[i], u[i], kd[i], g[i])
        o_ref[i] = (_dot(qg[i], Sb, _NT) + _dot(p[i], db, _NN)).astype(o_ref.dtype)
    s_ref[...] = St


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref, s0_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, drows_ref, ds_ref):
    """The factors recomputed ONCE, as the primal half of ``jax.vjp`` of their own
    function where it stands (a kept ``T`` would be 67 MB of float32 a layer, and
    a second spelling of seven levels' chain rule one more thing to keep equal to
    the first); the step's chunks walked first to last from its start state for
    the state each starts from, then last to first on the state's cotangent in
    ``ds_ref`` (the grid runs reversed too), which gives the factors' cotangents;
    then the transposed function. The whole log-decay ``G_C`` of a chunk, which
    the scan's ``e^{G_C}`` hands a cotangent back to, is a sum over the chunk's
    rows: its cotangent joins ``dg`` on every row."""
    @pl.when(pl.program_id(1) == 0)      # the LAST chunks
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    g = g_ref[...]
    (w, u, qg, kd, p), vjp = jax.vjp(
        _factors_of_refs, q_ref[...], k_ref[...], v_ref[...], g, rows_ref[...])
    dt, n = w.dtype, g.shape[0]
    walked, St = [], s0_ref[0]
    for i in range(n):
        Sb, db, gl, after = _advance(St, w[i], u[i], kd[i], g[i])
        walked.append((St, Sb, db, gl))
        St = after
    dSt = ds_ref[...]
    cts, dlast = [None] * n, [None] * n
    for i in reversed(range(n)):
        (St, Sb, db, gl), do = walked[i], do_ref[i]
        dSb = dSt.astype(dt)
        ddb = (_dot(p[i], do, _TN) + _dot(kd[i], dSb, _NT)).astype(dt)
        cts[i] = ((-_dot(ddb, Sb, _NN)).astype(dt), ddb, _dot(do, Sb, _NN).astype(dt),
                  _dot(db, dSb, _NN).astype(dt), _dot(do, db, _NT).astype(dt))
        # gl = e^{G_C}: the cotangent of G_C is that of gl, times gl
        dlast[i] = jnp.sum(St * dSt, axis=0, keepdims=True) * gl
        dSt = gl * dSt + _dot(do, qg[i], _TN) - _dot(ddb, w[i], _TN)
    ds_ref[...] = dSt
    dq, dk, dv, dg, drows = vjp(tuple(jnp.stack(t) for t in zip(*cts)))
    for ref, t in zip((dq_ref, dk_ref, dv_ref, drows_ref), (dq, dk, dv, drows)):
        ref[...] = t.astype(ref.dtype)
    for i in range(n):
        dg_ref[i] = dg[i] + dlast[i]


# chunks a grid step: each chunk is a chain of dependent products and one chunk's product
# hides another's latency (a layer's kda_fwd + kda_bwd took 8.3 + 13.8 ms at one chunk of
# 128 a step, 6.9 + 11.2 at two, 5.5 + 9.5 at four, 5.1 + 9.2 at eight; by the compiler's
# bundle count sixteen gains nothing more: my chip run, PR 50)
_GROUP = 8
_VMEM_LIMIT = 64 * 1024 * 1024


def _call(kernel, name, heads, n, interpret, q, v, ins, outs, reverse):
    """One kernel over ``M = heads * N`` flat chunks, grid ``(heads, N / n)`` with
    ``n`` chunks a step and a head's steps in order (``reverse``: last to first).
    ``ins`` / ``outs`` name each operand's and result's tile a chunk: ``k`` is
    ``(C, d_k)``, ``v`` ``(C, d_v)``, ``g`` ``(C, d_k)`` float32, ``r`` the float32
    rows ``(8, C)``; ``s`` is one float32 state ``(d_v, d_k)`` a STEP."""
    M, C, dk = q.shape
    dv, steps = v.shape[2], M // (heads * n)
    tiles = {"k": (C, dk), "v": (C, dv), "g": (C, dk), "r": (_ROWS, C), "s": (dv, dk)}
    at = (lambda b, j: (b * steps + steps - 1 - j, 0, 0)) if reverse \
        else (lambda b, j: (b * steps + j, 0, 0))
    spec = lambda t: pl.BlockSpec((1 if t == "s" else n,) + tiles[t], at)
    shape = lambda t: jax.ShapeDtypeStruct(
        (M // n if t == "s" else M,) + tiles[t], _F32 if t in "grs" else v.dtype)
    return pl.pallas_call(
        kernel,
        grid=(heads, steps),
        in_specs=[spec(t) for t in ins],
        out_specs=[spec(t) for t in outs],
        out_shape=[shape(t) for t in outs],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )


# Each kernel call is a ``jax.jit`` function, as in ``ops/deltanet.py``: a model's KDA layers
# and the guard's probe trace and lower each body once a shape, not once a layer.
_STATICS = ("heads", "n", "interpret")


@functools.partial(jax.jit, static_argnames=_STATICS)
def _fwd_call(q, k, v, g, rows, *, heads, n, interpret):
    return _call(_fwd_kernel, "kda_fwd", heads, n, interpret, q, v, "kkvgr", "vs", False)(
        q, k, v, g, rows)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _bwd_call(q, k, v, g, rows, s0, do, *, heads, n, interpret):
    return _call(_bwd_kernel, "kda_bwd", heads, n, interpret, q, v, "kkvgr" + "sv", "kkvgr", True)(
        q, k, v, g, rows, s0, do)


def _operands(q, k, v, g, beta):
    """The kernels' operands of chunked ``(BH, N, C, .)`` ones: ``(q, k, v, g,
    rows)``, chunks flat, ``beta`` along the lanes of row 0 of ``rows (M, 8, C)``."""
    flat = lambda t: t.reshape(-1, *t.shape[2:])
    rows = jnp.pad(beta.astype(_F32)[..., None, :], ((0, 0), (0, 0), (0, _ROWS - 1), (0, 0)))
    return flat(q), flat(k), flat(v), flat(g.astype(_F32)), flat(rows)


def _fwd(res, heads):
    """``(o (M, C, d_v), the states (M / n, d_v, d_k) float32 that each grid
    step of n chunks starts from)`` of the kernels' operands."""
    N = res[0].shape[0] // heads
    n = next(n for n in (_GROUP, 4, 2, 1) if N % n == 0)
    return _fwd_call(*res, heads=heads, n=n, interpret=_interpret_default())


@jax.custom_vjp
def _rule_pallas(q, k, v, g, beta):
    """The two kernels on chunked operands ``(BH, N, C, .)``: ``o (BH, N, C, d_v)``.
    The residuals are the kernels' operands and the state at each grid step's
    start (17 MB a layer at 32 heads x 8192 tokens and 8 chunks a step); the five
    factors (335 MB) and their cotangents never leave VMEM."""
    return _rule_pallas_fwd(q, k, v, g, beta)[0]


def _rule_pallas_fwd(q, k, v, g, beta):
    res = _operands(q, k, v, g, beta)
    o, s0 = _fwd(res, q.shape[0])
    return o.reshape(v.shape), (res, s0)


def _rule_pallas_bwd(saved, do):
    res, s0 = saved
    BH, N = do.shape[:2]
    v = res[2]
    dq, dk, dv, dg, drows = _bwd_call(
        *res, s0, do.astype(v.dtype).reshape(v.shape), heads=BH, n=v.shape[0] // s0.shape[0],
        interpret=_interpret_default())
    chunked = lambda t: t.reshape(BH, N, *t.shape[1:])
    return chunked(dq), chunked(dk), chunked(dv), chunked(dg), chunked(drows[:, 0, :])


_rule_pallas.defvjp(_rule_pallas_fwd, _rule_pallas_bwd)


def _probe_pallas(q, k, v, g, beta):
    """Guard probe: both kernels must build."""
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
        # either way the operands live on to the backward pass (and, of the kernels,
        # a state a grid step), which recomputes the float32 intermediates and the factors
        if impl == "pallas":
            o = _rule_pallas(*chunked)
        else:
            o = _scan_jnp(*jax.checkpoint(kda_prepare)(*chunked))
    o = o.reshape(B, H, N * chunk, dv)[:, :, :S]
    return o if heads_first else jnp.moveaxis(o, 1, 2)
