"""Pallas TPU kernels for the multi-tensor-apply family (``amp_C`` equivalent).

The reference launches one CUDA kernel over a chunked list of tensor pointers
(ref: csrc/multi_tensor_apply.cuh:19-147). On TPU, the tensor list is packed into
a flat HBM arena (see ``arena.py``), viewed as (rows, 128) lanes, and a Pallas
grid walks BLOCK_ROWS-row tiles through VMEM. The reference's device-side
``noop_flag`` becomes either

* an **overflow output**: an SMEM (1,1) int32 accumulated across the (sequential)
  TPU grid — set when any element is non-finite (ref:
  csrc/multi_tensor_scale_kernel.cu checks ``isfinite`` per element), or
* a **found_inf input**: an SMEM scalar that turns the update into an identity
  copy, giving the reference's skip-step semantics with no host sync
  (ref: apex/amp/scaler.py:114-126 device-side ``_overflow_buf``).

All math is fp32 regardless of storage dtype, matching ``MATH_T = float``
(ref: csrc/multi_tensor_adam.cu:22).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .arena import LANES
from ._pallas_util import interpret_default as _interpret_default

# One grid step processes BLOCK_ROWS x 128 lanes = 32768 elements per operand
# (128 KiB fp32) — the same role as the reference's chunk_size 2048*32
# (csrc/multi_tensor_apply.cuh launch config). Arenas are padded to a multiple
# of BLOCK_ELEMS by arena.flatten.
BLOCK_ROWS = 256
BLOCK_ELEMS = BLOCK_ROWS * LANES

# Large arenas step through bigger tiles: per-grid-step overhead (~µs on a
# v5e) dominates 128 KiB blocks on multi-M-element arenas. The largest tile
# in the ladder that DIVIDES the arena's row count is used (rows are always
# a multiple of BLOCK_ROWS via arena.TILE) — dividing exactly avoids any
# pad-copy of the arena; 1024 rows (512 KiB fp32) keeps the widest kernel
# (LAMB, ~8 operands, double-buffered) inside the ~16 MiB VMEM budget.
_ROW_LADDER = (1024, 512, 256)


def _choose_rows(rows: int) -> int:
    for cand in _ROW_LADDER:
        if rows % cand == 0:
            return cand
    return BLOCK_ROWS


def _compiler_params(interpret: bool):
    """Explicitly declare the grid dimension ``arbitrary`` (sequential): the
    overflow/l2norm kernels ACCUMULATE across grid steps, so the grid must not
    be parallelized across cores. This is the TPU default today; declaring it
    pins the correctness requirement. Interpret mode takes no TPU params."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(dimension_semantics=("arbitrary",))}


def ew_call(
    kernel,
    arrays: Sequence[jax.Array],
    scalars: Sequence[float],
    out_dtypes: Sequence,
    *,
    overflow: bool = False,
    found_inf=None,
    aliases: dict | None = None,
    interpret: bool | None = None,
):
    """Run an elementwise arena kernel.

    ``kernel(scal_ref, fi_ref, *in_refs, *out_refs[, oflow_ref])`` over
    (BLOCK_ROWS, LANES) tiles. All ``arrays`` must be flat, equal-length, and
    padded to BLOCK_ELEMS. Returns (outs, overflow_flag | None).

    ``aliases``: {output index -> arrays index} in-place pairs (the updated
    state overwrites the old state's buffer, the reference kernels' native
    mode — they mutate the tensor lists). Measured r5: the aliased Adam
    kernel streams ~1.8x faster than fresh-output buffers (4.2 -> 2.3 ms
    incl. grad refresh at 46M fp32).

    Aliasing safety is OBSERVED XLA:TPU behavior, not a Pallas API contract:
    current XLA inserts a defensive copy when the caller still holds the
    aliased input live, so donation has not been seen to corrupt a live
    value — but ``input_output_aliases`` is documented as a donation hint,
    and a backend/version that honors it more aggressively would make
    aliasing-with-live-input undefined. Callers should treat the input as
    CONSUMED. Note also the silent degrade below: a dtype-mismatched pair is
    dropped from the alias map without warning (the kernel still runs, just
    without in-place reuse), so a wrong-dtype state buffer quietly loses the
    1.8x. ``testing/tpu_checks.py`` is the enforcement point — its
    optimizer parity checks compare aliased against fresh-buffer results on
    real hardware and would surface either failure mode.
    """
    if interpret is None:
        interpret = _interpret_default()
    n = arrays[0].shape[0]
    assert n % BLOCK_ELEMS == 0, f"arena length {n} not padded to {BLOCK_ELEMS}"
    rows = n // LANES
    br = _choose_rows(rows)
    grid = rows // br

    n_scal = max(len(scalars), 1)
    scal = jnp.asarray(list(scalars) or [0.0], dtype=jnp.float32).reshape(1, n_scal)
    if found_inf is None:
        fi = jnp.zeros((1, 1), dtype=jnp.float32)
    else:
        fi = jnp.asarray(found_inf, dtype=jnp.float32).reshape(1, 1)

    smem_spec = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0), memory_space=pltpu.SMEM)
    vmem_spec = pl.BlockSpec((br, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)

    in_specs = [smem_spec((1, n_scal)), smem_spec((1, 1))]
    in_specs += [vmem_spec] * len(arrays)

    out_shape = [jax.ShapeDtypeStruct((rows, LANES), jnp.dtype(d)) for d in out_dtypes]
    out_specs = [vmem_spec] * len(out_dtypes)
    if overflow:
        out_shape.append(jax.ShapeDtypeStruct((1, 1), jnp.int32))
        out_specs.append(smem_spec((1, 1)))

    io_aliases = {}
    for out_idx, arr_idx in (aliases or {}).items():
        if jnp.dtype(out_dtypes[out_idx]) == arrays[arr_idx].dtype:
            # +2: the scalar and found_inf SMEM operands precede the arrays
            io_aliases[arr_idx + 2] = out_idx

    results = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=io_aliases,
        interpret=interpret,
        **_compiler_params(interpret),
    )(scal, fi, *[a.reshape(rows, LANES) for a in arrays])

    if overflow:
        *outs, flag = results
        return [o.reshape(n) for o in outs], (flag[0, 0] != 0)
    return [o.reshape(n) for o in results], None


def _f32(ref):
    return ref[...].astype(jnp.float32)


def _nonfinite(*blocks):
    bad = jnp.zeros((), jnp.bool_)
    for b in blocks:
        bad |= jnp.any(~jnp.isfinite(b))
    return bad


def _accum_flag(oflow_ref, bad):
    @pl.when(pl.program_id(0) == 0)
    def _():
        oflow_ref[0, 0] = 0

    oflow_ref[0, 0] |= bad.astype(jnp.int32)


# --------------------------------------------------------------------------------
# scale / axpby  (ref: csrc/multi_tensor_scale_kernel.cu, multi_tensor_axpby_kernel.cu)
# --------------------------------------------------------------------------------


def _scale_kernel(scal_ref, fi_ref, x_ref, out_ref, oflow_ref):
    x = _f32(x_ref)
    y = x * scal_ref[0, 0]
    out_ref[...] = y.astype(out_ref.dtype)
    _accum_flag(oflow_ref, _nonfinite(x, y))


def scale(x_flat, scale_val, out_dtype=None, *, interpret=None):
    out_dtype = out_dtype or x_flat.dtype
    outs, flag = ew_call(
        _scale_kernel, [x_flat], [scale_val], [out_dtype], overflow=True,
        aliases={0: 0}, interpret=interpret
    )
    return outs[0], flag


def _axpby_kernel(check, scal_ref, fi_ref, x_ref, y_ref, out_ref, oflow_ref):
    x, y = _f32(x_ref), _f32(y_ref)
    out = scal_ref[0, 0] * x + scal_ref[0, 1] * y
    out_ref[...] = out.astype(out_ref.dtype)
    # arg_to_check: -1 both, 0 only x, 1 only y (ref: multi_tensor_axpby_kernel.cu)
    if check == -1:
        bad = _nonfinite(x, y)
    elif check == 0:
        bad = _nonfinite(x)
    else:
        bad = _nonfinite(y)
    _accum_flag(oflow_ref, bad)


def axpby(x_flat, y_flat, a, b, out_dtype=None, *, arg_to_check=-1, interpret=None):
    out_dtype = out_dtype or x_flat.dtype
    outs, flag = ew_call(
        functools.partial(_axpby_kernel, arg_to_check),
        [x_flat, y_flat],
        [a, b],
        [out_dtype],
        overflow=True,
        aliases={0: 0},
        interpret=interpret,
    )
    return outs[0], flag


# --------------------------------------------------------------------------------
# l2norm  (ref: csrc/multi_tensor_l2norm_kernel.cu — global reduction path)
# --------------------------------------------------------------------------------


def _l2norm_kernel(scal_ref, fi_ref, x_ref, acc_ref, oflow_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        acc_ref[0, 0] = 0.0

    x = _f32(x_ref)
    acc_ref[0, 0] += jnp.sum(x * x)
    _accum_flag(oflow_ref, _nonfinite(x))


def l2norm_sq(x_flat, *, interpret=None):
    """Sum of squares of the arena (global l2 norm path). Returns (sq, overflow)."""
    if interpret is None:
        interpret = _interpret_default()
    n = x_flat.shape[0]
    assert n % BLOCK_ELEMS == 0, f"arena length {n} not padded to {BLOCK_ELEMS}"
    rows = n // LANES
    br = _choose_rows(rows)
    grid = rows // br
    smem_spec = lambda: pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    vmem_spec = pl.BlockSpec((br, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    acc, flag = pl.pallas_call(
        _l2norm_kernel,
        grid=(grid,),
        in_specs=[smem_spec(), smem_spec(), vmem_spec],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
        **_compiler_params(interpret),
    )(jnp.zeros((1, 1), jnp.float32), jnp.zeros((1, 1), jnp.float32),
      x_flat.reshape(rows, LANES))
    return acc[0, 0], flag[0, 0] != 0


# --------------------------------------------------------------------------------
# adam  (ref: csrc/multi_tensor_adam.cu AdamFunctor; mode 0 = L2, mode 1 = AdamW)
# --------------------------------------------------------------------------------


def _adam_kernel(mode, scal_ref, fi_ref, g_ref, p_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
                 co_ref=None):
    beta1, beta2 = scal_ref[0, 0], scal_ref[0, 1]
    bc1, bc2 = scal_ref[0, 2], scal_ref[0, 3]
    eps, lr, decay = scal_ref[0, 4], scal_ref[0, 5], scal_ref[0, 6]
    grad_scale = scal_ref[0, 7]
    skip = fi_ref[0, 0] != 0.0

    g, p, m, v = _f32(g_ref) * grad_scale, _f32(p_ref), _f32(m_ref), _f32(v_ref)
    if mode == 0:  # L2: decay folded into the gradient
        g = g + decay * p
    m_new = beta1 * m + (1.0 - beta1) * g
    v_new = beta2 * v + (1.0 - beta2) * g * g
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if mode == 1:  # AdamW: decoupled decay added to the update
        update = update + decay * p
    p_new = p - lr * update

    held = jnp.where(skip, p, p_new)
    po_ref[...] = held.astype(po_ref.dtype)
    mo_ref[...] = jnp.where(skip, m, m_new).astype(mo_ref.dtype)
    vo_ref[...] = jnp.where(skip, v, v_new).astype(vo_ref.dtype)
    if co_ref is not None:
        # low-precision model copy emitted in the same pass — the amp O2/O5
        # master->model cast with zero extra HBM reads (the reference pays a
        # separate _master_params_to_model_params copy,
        # apex/amp/_process_optimizer.py:14-25; its 4-list sgd kernel has the
        # same in-kernel copy idea, multi_tensor_sgd_kernel.cu:61-130)
        co_ref[...] = held.astype(co_ref.dtype)


def adam(
    g_flat,
    p_flat,
    m_flat,
    v_flat,
    *,
    lr,
    beta1,
    beta2,
    eps,
    bias_correction1,
    bias_correction2,
    weight_decay,
    adam_w_mode=True,
    grad_scale=1.0,
    found_inf=None,
    model_copy_dtype=None,
    interpret=None,
):
    out_dtypes = [p_flat.dtype, m_flat.dtype, v_flat.dtype]
    if model_copy_dtype is not None:
        out_dtypes.append(model_copy_dtype)
    outs, _ = ew_call(
        functools.partial(_adam_kernel, 1 if adam_w_mode else 0),
        [g_flat, p_flat, m_flat, v_flat],
        [beta1, beta2, bias_correction1, bias_correction2, eps, lr, weight_decay, grad_scale],
        out_dtypes,
        found_inf=found_inf,
        aliases={0: 1, 1: 2, 2: 3},
        interpret=interpret,
    )
    return tuple(outs)


# --------------------------------------------------------------------------------
# adagrad  (ref: csrc/multi_tensor_adagrad.cu AdagradFunctor)
# --------------------------------------------------------------------------------


def _adagrad_kernel(mode, scal_ref, fi_ref, g_ref, p_ref, h_ref, po_ref, ho_ref):
    eps, lr, decay = scal_ref[0, 0], scal_ref[0, 1], scal_ref[0, 2]
    skip = fi_ref[0, 0] != 0.0
    g, p, h = _f32(g_ref), _f32(p_ref), _f32(h_ref)
    if mode == 0:  # L2
        g = g + decay * p
        h_new = h + g * g
        p_new = p - lr * (g / (jnp.sqrt(h_new) + eps))
    else:  # AdamW-style decoupled decay
        h_new = h + g * g
        p_new = p - lr * (g / (jnp.sqrt(h_new) + eps) + decay * p)
    po_ref[...] = jnp.where(skip, p, p_new).astype(po_ref.dtype)
    ho_ref[...] = jnp.where(skip, h, h_new).astype(ho_ref.dtype)


def adagrad(g_flat, p_flat, h_flat, *, lr, eps, weight_decay, mode=0, found_inf=None, interpret=None):
    outs, _ = ew_call(
        functools.partial(_adagrad_kernel, mode),
        [g_flat, p_flat, h_flat],
        [eps, lr, weight_decay],
        [p_flat.dtype, h_flat.dtype],
        found_inf=found_inf,
        aliases={0: 1, 1: 2},
        interpret=interpret,
    )
    return tuple(outs)


# --------------------------------------------------------------------------------
# sgd  (ref: csrc/multi_tensor_sgd_kernel.cu SGDFunctor)
# --------------------------------------------------------------------------------


def _sgd_kernel(
    flags, scal_ref, fi_ref, g_ref, p_ref, mom_ref, po_ref, momo_ref, copy_ref=None
):
    nesterov, wd_after_momentum, has_momentum = flags
    wd, momentum, damp, lr, gscale = (
        scal_ref[0, 0],
        scal_ref[0, 1],
        scal_ref[0, 2],
        scal_ref[0, 3],
        scal_ref[0, 4],
    )
    # first_run is a runtime scalar (traced step==0 in the optimizer classes):
    # torch SGD seeds the momentum buffer with g, skipping dampening, on the
    # first step only (ref: multi_tensor_sgd_kernel.cu first_run branch)
    first_run = scal_ref[0, 5] != 0.0
    skip = fi_ref[0, 0] != 0.0
    g = _f32(g_ref) * gscale
    p, mom = _f32(p_ref), _f32(mom_ref)

    if not wd_after_momentum:
        g = g + wd * p
    if has_momentum:
        mom_new = jnp.where(first_run, g, mom * momentum + (1.0 - damp) * g)
        step = g + momentum * mom_new if nesterov else mom_new
    else:
        mom_new = mom
        step = g
    if wd_after_momentum:
        step = step + wd * p
    p_new = p - lr * step

    po_ref[...] = jnp.where(skip, p, p_new).astype(po_ref.dtype)
    momo_ref[...] = jnp.where(skip, mom, mom_new).astype(momo_ref.dtype)
    if copy_ref is not None:
        # 4-list variant writes a low-precision model copy of the new params
        # (ref: multi_tensor_sgd_kernel.cu:61-130, amp O2 master-weight path).
        copy_ref[...] = jnp.where(skip, p, p_new).astype(copy_ref.dtype)


def sgd(
    g_flat,
    p_flat,
    mom_flat,
    *,
    lr,
    weight_decay,
    momentum,
    dampening,
    nesterov=False,
    first_run=False,
    wd_after_momentum=False,
    scale=1.0,
    model_copy_dtype=None,
    found_inf=None,
    interpret=None,
):
    flags = (bool(nesterov), bool(wd_after_momentum), momentum != 0.0)
    out_dtypes = [p_flat.dtype, mom_flat.dtype]
    if model_copy_dtype is not None:
        out_dtypes.append(model_copy_dtype)
    outs, _ = ew_call(
        functools.partial(_sgd_kernel, flags),
        [g_flat, p_flat, mom_flat],
        [weight_decay, momentum, dampening, lr, scale,
         jnp.asarray(first_run, jnp.float32)],
        out_dtypes,
        found_inf=found_inf,
        aliases={0: 1, 1: 2},
        interpret=interpret,
    )
    return tuple(outs)


# --------------------------------------------------------------------------------
# lamb stage 1 (ref: csrc/multi_tensor_lamb.cu LAMBStage1Functor) — produces the
# raw update; per-tensor trust ratios are applied by apply_scaled_update below.
# --------------------------------------------------------------------------------


def _lamb1_kernel(mode, scal_ref, fi_ref, g_ref, p_ref, m_ref, v_ref, uo_ref, mo_ref, vo_ref):
    beta1, beta2, beta3 = scal_ref[0, 0], scal_ref[0, 1], scal_ref[0, 2]
    bc1, bc2 = scal_ref[0, 3], scal_ref[0, 4]
    eps, decay, clip = scal_ref[0, 5], scal_ref[0, 6], scal_ref[0, 7]
    skip = fi_ref[0, 0] != 0.0
    g, p, m, v = _f32(g_ref), _f32(p_ref), _f32(m_ref), _f32(v_ref)

    sg = g / clip
    if mode == 0:  # L2
        sg = sg + decay * p
    m_new = m * beta1 + beta3 * sg
    v_new = v * beta2 + (1.0 - beta2) * sg * sg
    update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if mode == 1:  # decoupled decay
        update = update + decay * p
    # skip-step must also hold the moments, or a single overflow step poisons
    # them forever (same noop semantics as the adam/sgd functors)
    uo_ref[...] = jnp.where(skip, 0.0, update).astype(uo_ref.dtype)
    mo_ref[...] = jnp.where(skip, m, m_new).astype(mo_ref.dtype)
    vo_ref[...] = jnp.where(skip, v, v_new).astype(vo_ref.dtype)


def lamb_stage1(
    g_flat,
    p_flat,
    m_flat,
    v_flat,
    *,
    beta1,
    beta2,
    beta3,
    bias_correction1,
    bias_correction2,
    eps,
    weight_decay,
    clipped_global_grad_norm,
    mode=1,
    found_inf=None,
    interpret=None,
):
    outs, _ = ew_call(
        functools.partial(_lamb1_kernel, mode),
        [g_flat, p_flat, m_flat, v_flat],
        [beta1, beta2, beta3, bias_correction1, bias_correction2, eps, weight_decay,
         clipped_global_grad_norm],
        [jnp.float32, m_flat.dtype, v_flat.dtype],
        found_inf=found_inf,
        aliases={0: 0, 1: 2, 2: 3},
        interpret=interpret,
    )
    return tuple(outs)


# --------------------------------------------------------------------------------
# novograd elementwise phase (ref: csrc/multi_tensor_novograd.cu NovoGradFunctor).
# The per-tensor second-moment norm arrives pre-gathered per element.
# --------------------------------------------------------------------------------


def _novograd_kernel(mode, scal_ref, fi_ref, g_ref, p_ref, m_ref, denom_ref, po_ref, mo_ref):
    beta1, beta3, bc1, lr, decay = (
        scal_ref[0, 0],
        scal_ref[0, 1],
        scal_ref[0, 2],
        scal_ref[0, 3],
        scal_ref[0, 4],
    )
    skip = fi_ref[0, 0] != 0.0
    g, p, m, denom = _f32(g_ref), _f32(p_ref), _f32(m_ref), _f32(denom_ref)
    if mode == 0:
        gp = g / denom + decay * p
        m_new = beta1 * m + beta3 * gp
        p_new = p - lr * (m_new / bc1)
    else:
        m_new = beta1 * m + beta3 * g
        update = (m_new / bc1) / denom + decay * p
        p_new = p - lr * update
    po_ref[...] = jnp.where(skip, p, p_new).astype(po_ref.dtype)
    mo_ref[...] = jnp.where(skip, m, m_new).astype(mo_ref.dtype)


def novograd_ew(
    g_flat, p_flat, m_flat, denom_flat, *, beta1, beta3, bias_correction1, lr,
    weight_decay, mode=0, found_inf=None, interpret=None,
):
    outs, _ = ew_call(
        functools.partial(_novograd_kernel, mode),
        [g_flat, p_flat, m_flat, denom_flat],
        [beta1, beta3, bias_correction1, lr, weight_decay],
        [p_flat.dtype, m_flat.dtype],
        found_inf=found_inf,
        aliases={0: 1, 1: 2},
        interpret=interpret,
    )
    return tuple(outs)


# --------------------------------------------------------------------------------
# per-element scaled update: p -= coef * u, coef gathered per tensor (LAMB stage 2
# trust ratios, ref: csrc/multi_tensor_lamb.cu LAMBStage2Functor; LARS apply).
# --------------------------------------------------------------------------------


def _scaled_update_kernel(scal_ref, fi_ref, p_ref, u_ref, c_ref, po_ref, co_ref=None):
    skip = fi_ref[0, 0] != 0.0
    p, u, c = _f32(p_ref), _f32(u_ref), _f32(c_ref)
    p_new = jnp.where(skip, p, p - c * u)
    po_ref[...] = p_new.astype(po_ref.dtype)
    if co_ref is not None:  # in-pass low-precision model copy (see _adam_kernel)
        co_ref[...] = p_new.astype(co_ref.dtype)


def apply_scaled_update(p_flat, u_flat, coef_flat, *, found_inf=None,
                        model_copy_dtype=None, interpret=None):
    out_dtypes = [p_flat.dtype]
    if model_copy_dtype is not None:
        out_dtypes.append(model_copy_dtype)
    outs, _ = ew_call(
        _scaled_update_kernel,
        [p_flat, u_flat, coef_flat],
        [],
        out_dtypes,
        found_inf=found_inf,
        aliases={0: 0},
        interpret=interpret,
    )
    return outs[0] if model_copy_dtype is None else (outs[0], outs[1])
