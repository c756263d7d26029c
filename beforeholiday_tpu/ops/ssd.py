"""State-space dual (SSD) — the recurrence of a Mamba-2 mixer, chunked.

Per head ``h`` of group ``g(h)``, with a state ``S`` of shape ``(N, P)`` that
starts at zero (Dao & Gu 2024, "Transformers are SSMs"; see PAPERS.md)::

    S_t = a_t S_{t-1} + dt_t B_t x_t^T        a_t = exp(dt_t A_h) in (0, 1]
    y_t = S_t^T C_t + D_h x_t

``B_t`` and ``C_t`` ``(N,)`` are shared by all the heads of a group; the decay
``a_t`` is a head's own. It is the gated delta rule (``ops/gated_delta.py``)
without the delta (``w = 0, u = v``), so nothing is solved for; but its head
is 64 wide where that kernel's gate wants 128, and the in-chunk scores
``C B^T`` belong to a group, not to a head, so the kernels are this module's.

The chunk-wise form splits the sequence into chunks of ``C`` tokens. With
``gc`` the running sum of ``log a`` inside a chunk (<= 0, falling) and ``gl``
its last value::

    y   = (C B^T * L) (dt x) + e^gc * (C S_0)          L_ij = e^(gc_i - gc_j), i >= j
    S_C = e^gl S_0 + B^T (e^(gl - gc) * dt x)

one ``(C, C)`` product a group, a decay mask a head, and one product in and
one out of the state. Every exponent is of a difference that is <= 0.

* The Pallas kernels (``ssd_fwd``; ``ssd_bwd_states`` and ``ssd_bwd``) walk a
  grid ``(batch x group, chunk, unit)``: a *unit* is 128 lanes of ``x`` — two
  heads of 64 — so every tile fills the lanes; the two heads' masked products
  are taken at full width and selected by lane. The chunks go in order with
  each unit's state in VMEM, the units innermost so that a chunk's ``B``,
  ``C`` and ``C B^T`` are fetched and formed once. The per-token scalars
  (``dt``, ``gc``) reach the kernels lane-packed, a head a lane, and each is
  spread over its head's lanes there. The backward pass first sweeps the
  chunks once more for the chunk-start states (they are not kept from the
  forward pass: 33 MB of float32 a layer at 16 heads x 8192 tokens), then
  walks the chunks in reverse with the state's cotangent in VMEM; ``dB`` and
  ``dC`` add up over a group's units in VMEM.
* ``impl="jnp"`` is the same chunk algebra as a ``lax.scan`` over the chunks,
  differentiated by XLA and recomputed in the backward pass: the parity
  oracle, the off-TPU default and the path of a shape the kernels do not take.

What is ``O(C)`` a chunk (the running sum, its chain rule through ``dt`` and
``A``) and the skip ``D x`` stay in XLA.
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
from beforeholiday_tpu.monitor.spans import span as _span
from beforeholiday_tpu.ops._pallas_util import (
    dispatch as _dispatch,
    interpret_default as _interpret_default,
)
from beforeholiday_tpu.ops.gated_delta import _NN, _NT, _TN, _dot

__all__ = ["ssd", "is_kernel_available"]

_F32 = jnp.float32
DEFAULT_CHUNK = 128
_LANES = 128
_SUBLANES = 8


class _Dims(NamedTuple):
    """The static shape of one call: ``G`` groups of ``Hg`` heads of ``P``,
    state ``N``, chunks of ``C``; a unit is ``hp`` heads, ``W`` lanes."""
    G: int
    Hg: int
    P: int
    N: int
    C: int

    @property
    def hp(self) -> int:
        return max(_LANES // self.P, 1)

    @property
    def W(self) -> int:
        return self.hp * self.P

    @property
    def units(self) -> int:
        return self.Hg // self.hp

    @property
    def rows(self) -> int:
        return -(-self.Hg // _SUBLANES) * _SUBLANES


def is_kernel_available(chunk: int, head_dim: int, state: int, heads_per_group: int) -> bool:
    """Shape gate of the Pallas kernels: whole 128-lane tiles everywhere — the
    chunk and the state multiples of 128, a head that divides 128 lanes (its
    group then holds whole units of ``128 / head_dim`` heads) or fills whole
    tiles itself — and a group's heads within the 128 lanes their per-token
    scalars are packed into."""
    if chunk % _LANES or state % _LANES or not 0 < heads_per_group <= _LANES:
        return False
    if head_dim >= _LANES:
        return head_dim % _LANES == 0
    return head_dim >= _SUBLANES and _LANES % head_dim == 0 \
        and heads_per_group % (_LANES // head_dim) == 0


# ---------------------------------------------------------------------------------
# the chunk scan, jnp oracle: operands by chunk, lax.scan over the chunks
# ---------------------------------------------------------------------------------


def _scan_jnp(x, dt, gc, Bm, Cm):
    """``x (B, n, C, G, Hg, P)``, ``dt, gc (B, n, C, G, Hg)`` float32,
    ``Bm, Cm (B, n, C, G, N)``; returns ``y`` like ``x``."""
    dtype = x.dtype
    C = x.shape[2]
    strict = jnp.arange(C)[:, None] > jnp.arange(C)[None, :]
    eye = jnp.eye(C, dtype=_F32)

    def step(S, xs):
        x, dt, gc, Bm, Cm = xs
        cb = jnp.einsum("bign,bjgn->bgij", Cm, Bm, preferred_element_type=_F32)
        rows = jnp.moveaxis(gc, 1, -1)                          # (B, G, Hg, C)
        diff = rows[..., :, None] - rows[..., None, :]
        # the diagonal is 1 whatever gc is: kept out of the exponential, so that its
        # (large) terms never enter gc's cotangent, where they would only cancel
        L = jnp.where(strict, jnp.exp(jnp.where(strict, diff, 0.0)), eye)
        u = x.astype(_F32) * dt[..., None]
        M = (cb[:, :, None] * L).astype(dtype)
        y = jnp.einsum("bghij,bjghp->bighp", M, u.astype(dtype), preferred_element_type=_F32)
        y = y + jnp.exp(gc)[..., None] * jnp.einsum(
            "bign,bghnp->bighp", Cm, S.astype(dtype), preferred_element_type=_F32)
        gl = gc[:, -1]                                          # (B, G, Hg)
        ud = (u * jnp.exp(gl[:, None] - gc)[..., None]).astype(dtype)
        S = jnp.exp(gl)[..., None, None] * S + jnp.einsum(
            "bjgn,bjghp->bghnp", Bm, ud, preferred_element_type=_F32)
        return S, y.astype(dtype)

    B, _, _, G, Hg, P = x.shape
    S0 = jnp.zeros((B, G, Hg, Bm.shape[-1], P), _F32)
    _, y = lax.scan(step, S0, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, gc, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


# ---------------------------------------------------------------------------------
# the Pallas kernels: grid (batch x group, chunk, unit), chunks in order
# ---------------------------------------------------------------------------------


def _column(cols, h):
    """Lane ``h`` of ``cols (C, 128)`` as a ``(C, 1)`` column."""
    lane = lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    return jnp.sum(jnp.where(lane == h, cols, 0.0), axis=1, keepdims=True)


def _row(rows, h):
    """Sublane ``h`` of ``rows (R, C)`` as a ``(1, C)`` row."""
    sub = lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    return jnp.sum(jnp.where(sub == h, rows, 0.0), axis=0, keepdims=True)


def _unit_tiles(u, dtc, gcc, gcr, d: _Dims):
    """What the kernels need of unit ``u``'s heads: per head ``(lanes, L (C, C),
    e^gl (1, 1))`` — ``lanes (1, W)`` its lanes of the unit — and, spread over
    each head's lanes, ``dt``, ``e^gc``, ``e^(gl - gc)`` ``(C, W)`` and ``e^gl
    (1, W)``. Every exponent is of a difference <= 0."""
    C, W = d.C, d.W
    lane = lax.broadcasted_iota(jnp.int32, (1, W), 1)
    ii = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    jj = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    lower = ii >= jj
    last = lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    heads = []
    spread = [jnp.zeros((C, W), _F32)] * 3 + [jnp.zeros((1, W), _F32)]
    for k in range(d.hp):
        h = u * d.hp + k
        lanes = (lane >= k * d.P) & (lane < (k + 1) * d.P)
        dcol, gcol, grow = _column(dtc, h), _column(gcc, h), _row(gcr, h)
        gl = jnp.sum(jnp.where(last, gcol, 0.0), axis=0, keepdims=True)
        L = jnp.where(lower, jnp.exp(jnp.where(lower, gcol - grow, 0.0)), 0.0)
        el = jnp.exp(gl)
        heads.append((lanes, L, el))
        for i, col in enumerate((dcol, jnp.exp(gcol), jnp.exp(gl - gcol), el)):
            spread[i] = jnp.where(lanes, col, spread[i])
    return heads, spread


def _fwd_kernel(x_ref, dtc_ref, gcc_ref, gcr_ref, b_ref, c_ref, out_ref, s_ref, cb_ref,
                *, d: _Dims, states: bool):
    """One unit of one chunk. ``states``: write the state the chunk starts from
    and no ``y`` (the backward pass's sweep); else ``y``."""
    n, u = pl.program_id(1), pl.program_id(2)

    @pl.when(n == 0)
    def _first_chunk():
        s_ref[u] = jnp.zeros(s_ref.shape[1:], _F32)

    dtype = x_ref.dtype
    Bm, S = b_ref[0], s_ref[u]
    heads, (DT, EG, EK, EL) = _unit_tiles(u, dtc_ref[0], gcc_ref[0], gcr_ref[0, 0], d)
    uf = x_ref[0].astype(_F32) * DT
    if states:
        out_ref[0, 0, 0] = S
    else:
        @pl.when(u == 0)
        def _scores():
            cb_ref[...] = _dot(c_ref[0], Bm, _NT)

        ub, cb = uf.astype(dtype), cb_ref[...]
        y = EG * _dot(c_ref[0], S.astype(dtype), _NN)
        for lanes, L, _ in heads:
            y = y + jnp.where(lanes, _dot((cb * L).astype(dtype), ub, _NN), 0.0)
        out_ref[0] = y.astype(out_ref.dtype)
    s_ref[u] = EL * S + _dot(Bm, (uf * EK).astype(dtype), _TN)


def _bwd_kernel(x_ref, dtc_ref, gcc_ref, gcr_ref, b_ref, c_ref, s0_ref, dy_ref,
                dx_ref, ddtc_ref, dgcc_ref, dgcr_ref, db_ref, dc_ref,
                ds_ref, cb_ref, dcb_ref, *, d: _Dims):
    """The transposes of one unit of one chunk, the chunks in reverse. ``dt`` and
    ``gc`` count as independent inputs here (``gc`` once as columns, once as
    rows); XLA differentiates the running sum."""
    n, u = pl.program_id(1), pl.program_id(2)

    @pl.when(n == 0)                       # the LAST chunk: the grid runs reversed
    def _last_chunk():
        ds_ref[u] = jnp.zeros(ds_ref.shape[1:], _F32)

    dtype = x_ref.dtype
    Bm, Cm = b_ref[0], c_ref[0]

    @pl.when(u == 0)
    def _first_unit():
        cb_ref[...] = _dot(Cm, Bm, _NT)
        for ref in (dcb_ref, ddtc_ref, dgcc_ref, dgcr_ref, db_ref, dc_ref):
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    C, W = d.C, d.W
    S, dS = s0_ref[0, 0, 0], ds_ref[u]
    Sb, dSb = S.astype(dtype), dS.astype(dtype)
    heads, (DT, EG, EK, EL) = _unit_tiles(u, dtc_ref[0], gcc_ref[0], gcr_ref[0, 0], d)
    xf, dy = x_ref[0].astype(_F32), dy_ref[0]
    uf = xf * DT
    ub, udf = uf.astype(dtype), uf * EK
    dyE = dy.astype(_F32) * EG
    dyEb = dyE.astype(dtype)
    cb = cb_ref[...]

    dud = _dot(Bm, dSb, _NN)                                    # d(e^(gl-gc) dt x)
    du = EK * dud
    # per token, as (C, W): what e^gc gains (with C S_0) less what e^(gl-gc) loses
    per_lane = dyE * _dot(Cm, Sb, _NN) - dud * udf
    gl_lane = dud * udf                                         # ... and e^(gl-gc) gains at gl
    state_lane = jnp.sum(S * dS, axis=0, keepdims=True) * EL    # (1, W): e^gl S . dS
    lane128 = lax.broadcasted_iota(jnp.int32, (C, _LANES), 1)
    sub = lax.broadcasted_iota(jnp.int32, (d.rows, C), 0)
    last = lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    dcb = jnp.zeros((C, C), _F32)
    strict = lax.broadcasted_iota(jnp.int32, (C, C), 0) > lax.broadcasted_iota(jnp.int32, (C, C), 1)
    for k, (lanes, L, _) in enumerate(heads):
        h = u * d.hp + k
        M = cb * L
        du = du + jnp.where(lanes, _dot(M.astype(dtype), dy, _TN), 0.0)
        dM = _dot(jnp.where(lanes, dy, jnp.zeros_like(dy)), ub, _NT)
        dcb = dcb + dM * L
        Z = jnp.where(strict, dM * M, 0.0)      # the diagonal's terms would only cancel
        own = lambda t: jnp.sum(jnp.where(lanes, t, 0.0), axis=1, keepdims=True)
        dgl = jnp.sum(own(gl_lane), axis=0, keepdims=True) + own(state_lane)
        dgcol = jnp.sum(Z, axis=1, keepdims=True) + own(per_lane) + jnp.where(last, dgl, 0.0)
        dgcc_ref[0] += jnp.where(lane128 == h, dgcol, 0.0)
        dgcr_ref[0, 0] += jnp.where(sub == h, -jnp.sum(Z, axis=0, keepdims=True), 0.0)
    dcb_ref[...] += dcb
    for k, (lanes, _, _) in enumerate(heads):       # du is whole only now
        ddcol = jnp.sum(jnp.where(lanes, du * xf, 0.0), axis=1, keepdims=True)
        ddtc_ref[0] += jnp.where(lane128 == u * d.hp + k, ddcol, 0.0)
    dx_ref[0] = (du * DT).astype(dx_ref.dtype)
    dc_ref[0] += _dot(dyEb, Sb, _NT)
    db_ref[0] += _dot(udf.astype(dtype), dSb, _NT)
    ds_ref[u] = EL * dS + _dot(Cm, dyEb, _TN)

    @pl.when(u == d.units - 1)
    def _last_unit():
        dcbb = dcb_ref[...].astype(dtype)
        dc_ref[0] += _dot(dcbb, Bm, _NN)
        db_ref[0] += _dot(dcbb, Cm, _TN)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _specs(d: _Dims, n_chunks: int, reverse: bool):
    """Block specs by operand kind: ``x`` a unit of a chunk of ``(B, S, H P)``,
    ``col`` a chunk of the lane-packed scalars ``(B, S, G 128)``, ``row`` the
    same scalars as rows ``(B G, n, rows, C)``, ``bc`` a chunk of ``(B, S, G N)``,
    ``state`` a unit's ``(B G, n, units, N, W)``."""
    G, U = d.G, d.units
    at = (lambda n: n_chunks - 1 - n) if reverse else (lambda n: n)
    return {
        "x": pl.BlockSpec((1, d.C, d.W), lambda b, n, u: (b // G, at(n), (b % G) * U + u)),
        "col": pl.BlockSpec((1, d.C, _LANES), lambda b, n, u: (b // G, at(n), b % G)),
        "row": pl.BlockSpec((1, 1, d.rows, d.C), lambda b, n, u: (b, at(n), 0, 0)),
        "bc": pl.BlockSpec((1, d.C, d.N), lambda b, n, u: (b // G, at(n), b % G)),
        "state": pl.BlockSpec((1, 1, 1, d.N, d.W), lambda b, n, u: (b, at(n), u, 0, 0)),
    }


def _rows_of(gcc, d: _Dims):
    """The lane-packed ``gc (B, S, G 128)`` as rows ``(B G, n, rows, C)``."""
    B, S, _ = gcc.shape
    t = gcc.reshape(B, S // d.C, d.C, d.G, _LANES)[..., :d.rows]
    return t.transpose(0, 3, 1, 4, 2).reshape(B * d.G, S // d.C, d.rows, d.C)


def _cols_of(rows, d: _Dims, B: int):
    """:func:`_rows_of`'s transpose."""
    n = rows.shape[1]
    t = rows.reshape(B, d.G, n, d.rows, d.C).transpose(0, 2, 4, 1, 3)
    t = jnp.pad(t, ((0, 0),) * 4 + ((0, _LANES - d.rows),))
    return t.reshape(B, n * d.C, d.G * _LANES)


def _grid(x, d: _Dims):
    B, S, _ = x.shape
    return B, S // d.C, (B * d.G, S // d.C, d.units)


def _book(kernel, x, d: _Dims):
    B, n, grid = _grid(x, d)
    steps = grid[0] * grid[1] * grid[2]
    _count_tiles("ssd", kernel, (B, x.shape[1]) + tuple(d) + (str(x.dtype),),
                 total=steps, live=steps, masked=steps)


# Each kernel call is a ``jax.jit`` function, as in ``ops/grouped_matmul.py``: the
# blocks of a model that share their shapes are traced and lowered once a step
# program, not once a call site (a body here is ~150 ``jnp`` calls).


@functools.partial(jax.jit, static_argnames=("d", "states"))
def _fwd_pallas(x, dtc, gcc, Bm, Cm, d: _Dims, states: bool):
    """``y (B, S, H P)``, or with ``states`` the chunk-start states
    ``(B G, n, units, N, W)`` float32."""
    B, n, grid = _grid(x, d)
    spec = _specs(d, n, reverse=False)
    _book("bwd_states" if states else "fwd", x, d)
    out_spec, out_shape = (
        (spec["state"], jax.ShapeDtypeStruct((B * d.G, n, d.units, d.N, d.W), _F32))
        if states else (spec["x"], jax.ShapeDtypeStruct(x.shape, x.dtype)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, states=states),
        grid=grid,
        in_specs=[spec[k] for k in ("x", "col", "col", "row", "bc", "bc")],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d.units, d.N, d.W), _F32), pltpu.VMEM((d.C, d.C), _F32)],
        compiler_params=_PARAMS,
        interpret=_interpret_default(),
        name="ssd_bwd_states" if states else "ssd_fwd",
    )(x, dtc, gcc, _rows_of(gcc, d), Bm, Cm)


@functools.partial(jax.jit, static_argnames=("d",))
def _bwd_pallas(x, dtc, gcc, Bm, Cm, s0, dy, d: _Dims):
    B, n, grid = _grid(x, d)
    spec = _specs(d, n, reverse=True)
    _book("bwd", x, d)
    like = lambda t, dtype=_F32: jax.ShapeDtypeStruct(t.shape, dtype)
    rows = _rows_of(gcc, d)
    dx, ddtc, dgcc, dgcr, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d),
        grid=grid,
        in_specs=[spec[k] for k in ("x", "col", "col", "row", "bc", "bc", "state", "x")],
        out_specs=[spec[k] for k in ("x", "col", "col", "row", "bc", "bc")],
        out_shape=[like(x, x.dtype), like(dtc), like(gcc), like(rows), like(Bm), like(Cm)],
        scratch_shapes=[pltpu.VMEM((d.units, d.N, d.W), _F32), pltpu.VMEM((d.C, d.C), _F32),
                        pltpu.VMEM((d.C, d.C), _F32)],
        compiler_params=_PARAMS,
        interpret=_interpret_default(),
        name="ssd_bwd",
    )(x, dtc, gcc, rows, Bm, Cm, s0, dy.astype(x.dtype))
    return dx, ddtc, dgcc + _cols_of(dgcr, d, B), dB.astype(Bm.dtype), dC.astype(Cm.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan_pallas(x, dtc, gcc, Bm, Cm, d: _Dims):
    return _fwd_pallas(x, dtc, gcc, Bm, Cm, d=d, states=False)


def _scan_pallas_fwd(x, dtc, gcc, Bm, Cm, d):
    return _fwd_pallas(x, dtc, gcc, Bm, Cm, d=d, states=False), (x, dtc, gcc, Bm, Cm)


def _scan_pallas_bwd(d, res, dy):
    # the chunk-start states are not kept from the forward pass: one more sweep
    s0 = _fwd_pallas(*res, d=d, states=True)
    return _bwd_pallas(*res, s0, dy, d=d)


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)


def _packed(t, d: _Dims):
    """``(B, S, H)`` float32 per-token scalars, a head a lane: ``(B, S, G 128)``."""
    B, S, _ = t.shape
    t = jnp.pad(t.reshape(B, S, d.G, d.Hg), ((0, 0),) * 3 + ((0, _LANES - d.Hg),))
    return t.reshape(B, S, d.G * _LANES)


def _pallas(x, dt, gc, Bm, Cm, d: _Dims):
    """``x (B, S, H, P)``, ``dt, gc (B, S, H)``, ``Bm, Cm (B, S, G, N)``."""
    B, S, H, P = x.shape
    y = _scan_pallas(x.reshape(B, S, H * P), _packed(dt, d), _packed(gc, d),
                     Bm.reshape(B, S, -1), Cm.reshape(B, S, -1), d)
    return y.reshape(B, S, H, P)


def _probe_pallas(x, dt, gc, Bm, Cm, d):
    """Guard probe: the three kernels must build."""
    y, vjp = jax.vjp(lambda *a: _pallas(*a, d), x, dt, gc, Bm, Cm)
    vjp(jnp.zeros_like(y))
    return y


# ---------------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------------


def ssd(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: jax.Array,
    *,
    chunk: int = DEFAULT_CHUNK,
    impl: Optional[str] = None,
) -> jax.Array:
    """The state-space recurrence over whole sequences, state zero at the start.

    ``x``: ``(batch, S, H, P)``; ``dt`` (the step, > 0, after its softplus):
    ``(batch, S, H)``; ``A`` (< 0) and ``D``: ``(H,)``; ``B, C``:
    ``(batch, S, G, N)``, group ``g`` serving heads ``g H/G .. (g+1) H/G - 1``.
    Returns ``y (batch, S, H, P)`` in ``x``'s dtype. Matmul operands keep
    ``x``'s dtype; the decays, accumulation and the state are float32.

    A sequence that is not a multiple of ``chunk`` is padded at its end with
    steps that leave the state alone (``dt = 0``) and whose outputs are cut off."""
    batch, S, H, P = x.shape
    G, N = B.shape[2:]
    if dt.shape != (batch, S, H) or A.shape != (H,) or D.shape != (H,) \
            or B.shape != (batch, S, G, N) or C.shape != B.shape or H % G:
        raise ValueError(
            f"ssd shapes mismatch: x {x.shape} dt {dt.shape} A {A.shape} B {B.shape} "
            f"C {C.shape} D {D.shape}")
    if chunk < 1:
        raise ValueError(f"chunk must be positive, got {chunk}")
    d = _Dims(G, H // G, P, N, chunk)
    impl, forced = _dispatch(
        "ssd", impl, is_kernel_available(chunk, P, N, d.Hg),
        f"chunk {chunk} / state {N} is not a multiple of {_LANES}, or {d.Hg} heads a group "
        f"of {P} do not fill {_LANES}-lane units", x, B, statics=(chunk,))
    pad = -S % chunk

    def padded(t):
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) if pad else t

    with _span("ssd"):
        dtype = x.dtype
        xp, Bm, Cm = padded(x), padded(B.astype(dtype)), padded(C.astype(dtype))
        dtp = padded(dt.astype(_F32))
        n = (S + pad) // chunk
        # the log of the decay, summed inside each chunk: <= 0 and falling
        gc = jnp.cumsum((dtp * A.astype(_F32)).reshape(batch, n, chunk, H), axis=2)
        gc = gc.reshape(batch, n * chunk, H)
        if impl == "pallas" and not forced:
            impl = _checked_impl("ssd", impl, _probe_pallas, xp, dtp, gc, Bm, Cm, d)
        if impl == "pallas":
            y = _pallas(xp, dtp, gc, Bm, Cm, d)
        else:
            by_chunk = lambda t, *tail: t.reshape(batch, n, chunk, *tail)
            y = jax.checkpoint(_scan_jnp)(
                by_chunk(xp, G, d.Hg, P), by_chunk(dtp, G, d.Hg), by_chunk(gc, G, d.Hg),
                by_chunk(Bm, G, N), by_chunk(Cm, G, N))
            y = y.reshape(batch, n * chunk, H, P)
        y = y[:, :S].astype(_F32) + D.astype(_F32)[:, None] * x.astype(_F32)
    return y.astype(dtype)
