"""Fused LayerNorm / RMSNorm (ref: csrc/layer_norm_cuda_kernel.cu, 1229 LoC).

The reference ships warp-tiled CUDA kernels with saved (mean, invvar) and
``*_mixed_dtypes`` variants where the output dtype follows the parameter dtype
(ref: csrc/layer_norm_cuda.cpp:429-441, Megatron-compat). TPU design:

* one Pallas kernel per pass, gridding row blocks with the full hidden width in
  VMEM; all math fp32 regardless of storage dtype (``compute_type`` in the
  reference's DISPATCH macros);
* backward recomputes (mean, invvar) from x instead of saving them — LN is
  HBM-bound on TPU, the extra VPU reductions over data already resident in
  VMEM are free, and it halves the residual footprint;
* dgamma/dbeta accumulate across the (sequential) TPU grid into a single
  VMEM block, replacing the reference's two-stage partial-buffer reduction
  (layer_norm_cuda_kernel.cu cuComputePartGradGammaBeta);
* ``impl="jnp"`` is the parity oracle and the off-TPU default.

Custom VJP wires the Pallas backward under jax.grad.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from beforeholiday_tpu.guard.dispatch import checked_impl as _checked_impl
from beforeholiday_tpu.monitor.spans import annotate as _annotate
from beforeholiday_tpu.remat.policies import TAG_NORM_OUT as _TAG_NORM_OUT
from beforeholiday_tpu.ops._autocast import float_function
from beforeholiday_tpu.ops._pallas_util import (
    interpret_default as _interpret_default,
    pad_rows as _pad_rows_util,
    resolve_impl as _resolve_impl,
)


def _row_block(hidden: int) -> int:
    """Rows per grid step: target ~512KB fp32 of x in VMEM."""
    target = 128 * 1024  # elements
    br = max(1, target // max(hidden, 1))
    return int(min(256, max(8, 1 << int(np.floor(np.log2(br))))))


# ---------------------------------------------------------------------------------
# forward kernels
# ---------------------------------------------------------------------------------


def _ln_fwd_kernel(rms, scal_ref, x_ref, w_ref, b_ref, y_ref):
    eps = scal_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    if rms:
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        xhat = x * jax.lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        xhat = (x - mu) * jax.lax.rsqrt(var + eps)
    y = xhat * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(rms, scal_ref, x_ref, w_ref, dy_ref, dx_ref, dw_ref, db_ref):
    eps = scal_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)

    if rms:
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        r = jax.lax.rsqrt(var + eps)
        xhat = x * r
        dyw = dy * w
        # dx = r*(dyw - xhat * mean(dyw*xhat))
        dx = r * (dyw - xhat * jnp.mean(dyw * xhat, axis=-1, keepdims=True))
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        r = jax.lax.rsqrt(var + eps)
        xhat = (x - mu) * r
        dyw = dy * w
        m1 = jnp.mean(dyw, axis=-1, keepdims=True)
        m2 = jnp.mean(dyw * xhat, axis=-1, keepdims=True)
        dx = r * (dyw - m1 - xhat * m2)
    dx_ref[...] = dx.astype(dx_ref.dtype)

    # param grads accumulate across the sequential grid
    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dw_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _ln_fwd_pallas(x2d, w, b, eps, rms, out_dtype, interpret):
    hidden = x2d.shape[-1]
    br = _row_block(hidden)
    xp, rows = _pad_rows_util(x2d, br)
    grid = xp.shape[0] // br
    scal = jnp.asarray([[eps]], jnp.float32)
    smem = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    row_spec = pl.BlockSpec((br, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((1, hidden), lambda i: (0, 0), memory_space=pltpu.VMEM)
    y = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, rms),
        grid=(grid,),
        in_specs=[smem, row_spec, w_spec, w_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(xp.shape, out_dtype),
        interpret=interpret,
    )(scal, xp, w.reshape(1, hidden), b.reshape(1, hidden))
    return y[:rows]


def _ln_bwd_pallas(x2d, w, dy2d, eps, rms, interpret):
    hidden = x2d.shape[-1]
    br = _row_block(hidden)
    xp, rows = _pad_rows_util(x2d, br)
    dyp, _ = _pad_rows_util(dy2d, br)
    grid = xp.shape[0] // br
    scal = jnp.asarray([[eps]], jnp.float32)
    smem = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    row_spec = pl.BlockSpec((br, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((1, hidden), lambda i: (0, 0), memory_space=pltpu.VMEM)

    outs = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, rms),
        grid=(grid,),
        in_specs=[smem, row_spec, w_spec, row_spec],
        out_specs=[row_spec, w_spec, w_spec],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x2d.dtype),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
        ],
        interpret=interpret,
    )(scal, xp, w.reshape(1, hidden), dyp)
    return outs[0][:rows], outs[1].reshape(hidden), outs[2].reshape(hidden)


# ---------------------------------------------------------------------------------
# jnp oracle
# ---------------------------------------------------------------------------------


def _ln_fwd_jnp(x2d, w, b, eps, rms, out_dtype):
    x = x2d.astype(jnp.float32)
    if rms:
        xhat = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        xhat = (x - mu) * jax.lax.rsqrt(var + eps)
    y = xhat * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(out_dtype)


# ---------------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _layer_norm(x2d, w, b, eps, rms, out_dtype, impl):
    if impl == "pallas":
        return _ln_fwd_pallas(x2d, w, b, eps, rms, out_dtype, _interpret_default())
    return _ln_fwd_jnp(x2d, w, b, eps, rms, out_dtype)


def _layer_norm_fwd(x2d, w, b, eps, rms, out_dtype, impl):
    y = _layer_norm(x2d, w, b, eps, rms, out_dtype, impl)
    return y, (x2d, w)


def _layer_norm_bwd(eps, rms, out_dtype, impl, res, dy):
    x2d, w = res
    if impl == "pallas":
        dx, dw, db = _ln_bwd_pallas(x2d, w, dy, eps, rms, _interpret_default())
    else:
        x = x2d.astype(jnp.float32)
        dyf = dy.astype(jnp.float32)
        wf = w.astype(jnp.float32)
        if rms:
            r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            xhat = x * r
            dyw = dyf * wf
            dx = r * (dyw - xhat * jnp.mean(dyw * xhat, axis=-1, keepdims=True))
        else:
            mu = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
            r = jax.lax.rsqrt(var + eps)
            xhat = (x - mu) * r
            dyw = dyf * wf
            m1 = jnp.mean(dyw, axis=-1, keepdims=True)
            m2 = jnp.mean(dyw * xhat, axis=-1, keepdims=True)
            dx = r * (dyw - m1 - xhat * m2)
        dw = jnp.sum(dyf * xhat, axis=0)
        db = jnp.sum(dyf, axis=0)
        dx = dx.astype(x2d.dtype)
    return dx, dw.astype(w.dtype), db.astype(w.dtype)


_layer_norm.defvjp(_layer_norm_fwd, _layer_norm_bwd)


@float_function
def fused_layer_norm(
    x: jax.Array,
    weight: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    eps: float = 1e-5,
    memory_efficient: bool = False,  # accepted for API parity; recompute is always on
    impl: Optional[str] = None,
) -> jax.Array:
    """LayerNorm over the last dim (ref: apex/normalization/fused_layer_norm.py:32
    FusedLayerNormAffineFunction). Output dtype = input dtype.
    """
    return _norm_impl(x, weight, bias, eps, rms=False, out_dtype=x.dtype, impl=impl)


@float_function
def fused_rms_norm(
    x: jax.Array,
    weight: jax.Array,
    *,
    eps: float = 1e-5,
    memory_efficient: bool = False,
    impl: Optional[str] = None,
) -> jax.Array:
    """RMSNorm (ref: csrc/layer_norm_cuda.cpp rmsnorm entry points)."""
    return _norm_impl(x, weight, None, eps, rms=True, out_dtype=x.dtype, impl=impl)


def mixed_dtype_fused_layer_norm(
    x: jax.Array,
    weight: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    eps: float = 1e-5,
    impl: Optional[str] = None,
) -> jax.Array:
    """Output dtype follows the *parameter* dtype — the ``*_mixed_dtypes``
    Megatron-compat variant (ref: csrc/layer_norm_cuda.cpp:434)."""
    return _norm_impl(x, weight, bias, eps, rms=False, out_dtype=weight.dtype, impl=impl)


def mixed_dtype_fused_rms_norm(
    x: jax.Array, weight: jax.Array, *, eps: float = 1e-5, impl: Optional[str] = None
) -> jax.Array:
    return _norm_impl(x, weight, None, eps, rms=True, out_dtype=weight.dtype, impl=impl)


def _probe_ln_pallas(x2d, w, b, *, eps, rms, out_dtype):
    """Guard probe: both passes of the norm kernel must build for the key."""
    interp = _interpret_default()
    y = _ln_fwd_pallas(x2d, w, b, eps, rms, out_dtype, interp)
    _ln_bwd_pallas(x2d, w, jnp.zeros(x2d.shape, out_dtype), eps, rms, interp)
    return y


@_annotate("layer_norm")  # the one entry of every public norm; XProf range
def _norm_impl(x, weight, bias, eps, rms, out_dtype, impl):
    requested = impl
    impl = _resolve_impl(impl)
    hidden = x.shape[-1]
    if weight.shape != (hidden,):
        raise ValueError(f"weight shape {weight.shape} != ({hidden},)")
    if bias is not None and bias.shape != (hidden,):
        raise ValueError(f"bias shape {bias.shape} != ({hidden},)")
    x2d = x.reshape(-1, hidden)
    if bias is None:
        # fixed VJP arity: a zero bias whose cotangent is simply discarded
        bias = jnp.zeros((hidden,), weight.dtype)
    if requested is None:
        # default-on dispatch is guarded; an explicit impl= keeps the
        # honor-the-request contract (including its exceptions) untouched
        impl = _checked_impl(
            "layer_norm", impl, _probe_ln_pallas, x2d, weight, bias,
            eps=float(eps), rms=rms, out_dtype=jnp.dtype(out_dtype),
        )
    y = _layer_norm(x2d, weight, bias, float(eps), rms, jnp.dtype(out_dtype), impl)
    # remat boundary tag: a saved norm output lets the matmul that consumes
    # it skip re-running the norm in backward (identity outside checkpoint)
    return _checkpoint_name(y.reshape(x.shape), _TAG_NORM_OUT)
