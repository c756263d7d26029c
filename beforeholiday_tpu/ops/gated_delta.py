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

* :func:`wy_prepare` — the state-free part, plain ``jax.numpy`` batched over
  every chunk of every head at once (XLA runs it as full-tile matmuls and
  differentiates it): the in-chunk decay ``D_ij = exp(G_i - G_j)``, the unit
  lower-triangular system ``(I + strict(b k k^T * D)) [u | w] = [b v | b k e^G]``
  that defines the WY factors (:func:`unit_lower_inverse`, block by block), the
  in-chunk scores ``P = tril(q k^T * D)``. It is recomputed in the backward pass.
* the chunk scan — ``delta = u - w S; o = (q e^G) S + P delta;
  S <- e^{G_C} S + (k e^{G_C - G})^T delta`` — is the sequential part: a Pallas
  kernel that walks the chunks of one head with the state in VMEM, and a
  ``custom_vjp`` whose backward kernel walks them in reverse with the state's
  cotangent in VMEM. The chunk-start states are recomputed by one more forward
  sweep at the start of the backward pass instead of being kept alive from the
  forward one (0.27 GB a layer at 32 heads x 8192 tokens).
* ``impl="jnp"`` runs the same chunk scan as a ``lax.scan`` (autodiff gives
  its backward): the parity oracle and the off-TPU default.

Every exponent is of a difference that is <= 0, so nothing overflows however
strong the decay; no quotient of decays is ever formed.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from beforeholiday_tpu.guard.dispatch import checked_impl as _checked_impl
from beforeholiday_tpu.monitor.spans import span as _span
from beforeholiday_tpu.ops._pallas_util import (
    interpret_default as _interpret_default,
    resolve_impl as _resolve_impl,
)

__all__ = ["gated_delta_rule", "is_kernel_available", "unit_lower_inverse", "wy_prepare"]

_F32 = jnp.float32
DEFAULT_CHUNK = 128
_LANES = 128


def is_kernel_available(chunk: int, d_k: int, d_v: int) -> bool:
    """Shape gate of the Pallas chunk scan: head dims that fill the lanes, a
    chunk of whole bfloat16 sublane tiles (the in-chunk scores are then a block
    as wide as their array, which Mosaic takes below 128 lanes too)."""
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


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=_F32)


_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b^T
_TN = ((0,), (0,))      # a^T @ b


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


def _probe_scan_pallas(w, u, qg, kd, p, gl):
    """Guard probe: the forward and the backward kernel must both build."""
    o, vjp = jax.vjp(_scan_pallas, w, u, qg, kd, p, gl)
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
) -> jax.Array:
    """The gated delta rule over whole sequences, state zero at the start.

    ``q, k``: ``(B, S, H, d_k)`` (scaled and normalised by the caller; a key
    head that serves several value heads is repeated by the caller), ``v``:
    ``(B, S, H, d_v)``, ``g`` (the log of the decay, <= 0) and ``beta``:
    ``(B, S, H)``. Returns ``o (B, S, H, d_v)`` in ``v``'s dtype. Matmul
    operands keep the input dtype, accumulation and the state are float32.

    A sequence that is not a multiple of ``chunk`` is padded at its end with
    steps that leave the state alone (``beta = 0``, ``g = 0``) and whose
    outputs are cut off."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    if k.shape != q.shape or v.shape[:3] != q.shape[:3] or g.shape != q.shape[:3] \
            or beta.shape != g.shape:
        raise ValueError(
            f"gated_delta_rule shapes mismatch: q {q.shape} k {k.shape} "
            f"v {v.shape} g {g.shape} beta {beta.shape}")
    if chunk < 1 or chunk & (chunk - 1):      # the blockwise inverse halves its way down
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    forced = impl is not None
    impl = _resolve_impl(impl)
    if impl == "pallas" and not is_kernel_available(chunk, dk, dv):
        if forced:
            raise ValueError(
                f"impl='pallas' forced but chunk {chunk} is not a multiple of 64 or "
                f"d_k {dk} / d_v {dv} not of {_LANES}; pass impl=None for the "
                "automatic fallback")
        impl = "jnp"
    pad = -S % chunk
    N = (S + pad) // chunk

    def chunks(t):
        """(B, S, H, ...) -> (B*H, N, C, ...), the tail padded with zeros."""
        t = jnp.moveaxis(t, 2, 1)
        if pad:
            t = jnp.pad(t, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 3))
        return t.reshape(B * H, N, chunk, *t.shape[3:])

    with _span("gated_delta"):
        # recomputed in the backward pass: its float32 intermediates (the decay
        # mask, the triangular system and its solution) are ~1 GB a layer at
        # 32 heads x 8192 tokens, the five operands it is computed from 0.3 GB
        operands = jax.checkpoint(wy_prepare)(*(chunks(t) for t in (q, k, v, g, beta)))
        if impl == "pallas" and not forced:
            impl = _checked_impl("gated_delta_rule", impl, _probe_scan_pallas, *operands)
        with _span("gated_delta_scan"):     # innermost: names the kernels
            o = (_scan_pallas if impl == "pallas" else _scan_jnp)(*operands)
    o = o.reshape(B, H, N * chunk, dv)[:, :, :S]
    return jnp.moveaxis(o, 1, 2)
