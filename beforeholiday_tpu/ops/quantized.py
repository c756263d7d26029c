"""fp8-style quantized matmul — the arithmetic core of the O6 autocast tier.

FP8 training per Micikevicius et al. 2022 ("FP8 Formats for Deep Learning"):
forward operands quantize to ``e4m3`` (4 exponent / 3 mantissa bits, max 448,
finite-only), backward cotangents to ``e5m2`` (max 57344, has infinities), each
under a per-tensor scale chosen so the tensor's amax lands near the format max.
The accumulate stays fp32 (``preferred_element_type``), so the MXU runs at the
fp8 peak while the sum keeps bf16-training accumulation semantics.

Scaling regimes (Transformer-Engine-shaped, state layout our own):

* **activations** — just-in-time per-tensor scale computed from the operand
  inside the op. Always available, no state, exact (never saturates).
* **weights / grads** — *delayed* scaling: a device-side amax history (one row
  per role, ``HISTORY_ROLES``) rides inside the ``LossScaler`` state pytree;
  :func:`scales_from_history` turns it into this step's scales and
  ``amp.scaled_value_and_grad`` threads them in through
  :func:`quantized_scope` and folds the step's fresh observations back via
  ``LossScaler.update``. Outside any scope both fall back to just-in-time
  (eval-mode forward "just works").

Overflow contract: weight quantization SATURATES (clips at ±448 — a stale
scale costs accuracy, never NaN); grad quantization does NOT (e5m2 overflow
becomes ±inf, rides into the unscale kernel's ``found_inf``, and the step is
skipped + scale halved through the existing ``StepGuard``/``LossScaler``
machinery — the same event loop as a bf16 loss-scale overflow).

Dispatch is guard-probed like every kernel here: the fast path issues the
dot on native fp8 operands (the MXU/fp8-HW path; booked under the registry's
``"pallas"`` bucket), the oracle upcasts the SAME quantized values to fp32
and dots — bitwise-identical results by construction, so a probe downgrade
changes cost, never values.

Tracer hygiene: the op never exports traced amax values (an observation
captured inside ``lax.scan``/``jax.grad`` could not legally escape its
trace). Observations for the delayed rows are computed at step level from
values already living there: params ARE the quantized weights, and the
still-scaled grads are the same scaling regime the backward quantized.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from beforeholiday_tpu.guard.dispatch import checked_impl as _checked_impl

__all__ = [
    "E4M3_MAX",
    "E4M3_REL",
    "E4M3_TINY",
    "E5M2_MAX",
    "HISTORY_ROLES",
    "amax_of_tree",
    "init_amax_history",
    "jit_scale_e4m3",
    "loss_parity_bound",
    "quantize_e4m3",
    "quantized_matmul",
    "quantized_matmul_error_bound",
    "quantized_scope",
    "scales_from_history",
    "update_amax_history",
]

E4M3 = jnp.float8_e4m3fn
E5M2 = jnp.float8_e5m2
E4M3_MAX = 448.0
E5M2_MAX = 57344.0
# round-to-nearest relative error: half ulp at 3 / 2 mantissa bits
_E4M3_REL = 2.0 ** -4
_E5M2_REL = 2.0 ** -3
# smallest positive subnormals — the absolute-error floor under each format
_E4M3_TINY = 2.0 ** -9
_E5M2_TINY = 2.0 ** -16

# public aliases: the e4m3 error model is shared with the fp8 KV-cache
# (``infer/kvcache.py``), whose dequant bound composes the same two terms
E4M3_REL = _E4M3_REL
E4M3_TINY = _E4M3_TINY

# delayed-scaled roles, in amax-history row order; activations are
# just-in-time-scaled and carry no history
HISTORY_ROLES = ("weight", "grad")

_ALLOWED_DTYPES = (jnp.float16, jnp.bfloat16, jnp.float32)


# ------------------------------------------------------------------ the scope
class _Scope(threading.local):
    scales: Optional[Tuple[Any, Any]] = None


_SCOPE = _Scope()


@contextlib.contextmanager
def quantized_scope(scale_w, scale_g):
    """Provide this step's delayed scales (weight, grad) to every
    :func:`quantized_matmul` in the block. The values are ordinary traced
    scalars — ``scaled_value_and_grad`` derives them from the scaler state at
    the top of the step trace, and closures inside ``scan``/``grad`` capture
    them legally. Nests; per-thread."""
    prev = getattr(_SCOPE, "scales", None)
    _SCOPE.scales = (
        jnp.asarray(scale_w, jnp.float32),
        jnp.asarray(scale_g, jnp.float32),
    )
    try:
        yield
    finally:
        _SCOPE.scales = prev


def _active_scales() -> Optional[Tuple[Any, Any]]:
    return getattr(_SCOPE, "scales", None)


# -------------------------------------------------------------- amax history
def init_amax_history(length: int = 16) -> jax.Array:
    """Fresh (len(HISTORY_ROLES), length) history — zeros mean "no
    observation yet" and :func:`scales_from_history` then falls back to
    scale 1.0 for the role."""
    if length < 1:
        raise ValueError(f"amax history length must be >= 1, got {length}")
    return jnp.zeros((len(HISTORY_ROLES), int(length)), jnp.float32)


def update_amax_history(hist, amax_w, amax_g) -> jax.Array:
    """Roll the newest (weight, grad) amax observations into slot 0.

    Non-finite observations clamp to 0 (ignored): an inf amax — the overflow
    event itself — would otherwise poison the scale forever, and the event is
    already handled by the ``found_inf`` skip-step."""
    obs = jnp.stack([
        jnp.asarray(amax_w, jnp.float32),
        jnp.asarray(amax_g, jnp.float32),
    ])
    obs = jnp.where(jnp.isfinite(obs), obs, 0.0)
    return jnp.concatenate([obs[:, None], hist[:, :-1]], axis=1)


def scales_from_history(hist, *, margin: float = 2.0) -> Tuple[Any, Any]:
    """(scale_w, scale_g) from the rolling amax maxima: each scale maps the
    role's historical amax to ``fmt_max / margin`` (the margin is headroom for
    inter-step amax growth — delayed scales are one step stale by
    construction). Roles with an all-zero history get scale 1.0."""
    if margin < 1.0:
        raise ValueError(f"margin must be >= 1.0, got {margin}")
    amax = jnp.max(hist, axis=1)
    targets = jnp.asarray([E4M3_MAX / margin, E5M2_MAX / margin], jnp.float32)
    return tuple(
        jnp.where(amax[i] > 0.0, targets[i] / amax[i], jnp.float32(1.0))
        for i in range(len(HISTORY_ROLES))
    )


def amax_of_tree(tree) -> jax.Array:
    """max(abs(.)) over every floating leaf — the step-level observation
    helper for the delayed rows (params for ``weight``, still-scaled grads
    for ``grad``). Returns fp32 0.0 for a tree with no floating leaves."""
    amax = jnp.float32(0.0)
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            amax = jnp.maximum(amax, jnp.max(jnp.abs(leaf.astype(jnp.float32))))
    return amax


# --------------------------------------------------------------- quantization
def _jit_scale(a, fmt_max: float) -> jax.Array:
    """Just-in-time per-tensor scale: amax -> fmt_max, 1.0 for a zero tensor."""
    amax = jnp.max(jnp.abs(a))
    return jnp.where(amax > 0.0, fmt_max / amax, jnp.float32(1.0))


def _q_e4m3(a, scale):
    # SATURATING: forward operands must stay finite (e4m3fn has no inf —
    # overflow would manufacture NaN), so a stale delayed scale clips
    return jnp.clip(a * scale, -E4M3_MAX, E4M3_MAX).astype(E4M3)


def _q_e5m2(a, scale):
    # NON-saturating: grad overflow becomes ±inf and is the found_inf signal
    return (a * scale).astype(E5M2)


def jit_scale_e4m3(a, *, margin: float = 1.0) -> jax.Array:
    """Public just-in-time e4m3 scale: amax -> ``E4M3_MAX / margin`` (1.0 for
    an all-zero tensor). ``margin > 1`` leaves saturation headroom for values
    written later under the same frozen scale — the fp8 KV-cache fixes each
    page's scale at first write and saturates subsequent tokens, exactly the
    delayed-scaling overflow contract."""
    if margin < 1.0:
        raise ValueError(f"margin must be >= 1.0, got {margin}")
    return _jit_scale(a, E4M3_MAX / margin)


def quantize_e4m3(a, scale):
    """Public saturating e4m3 cast — ``clip(a * scale, ±E4M3_MAX)`` in e4m3.
    Saturation (never inf/NaN) is the forward-operand contract; the clip
    excess is exactly the term :func:`quantized_matmul_error_bound` and the
    KV-cache's ``kv_dequant_error_bound`` charge for a stale scale."""
    return _q_e4m3(a, scale)


def _fp8_dot(qa, qb, dims):
    # the probed fast path: dot on native fp8 operands, fp32 accumulation
    return jax.lax.dot_general(
        qa, qb, dims, preferred_element_type=jnp.float32
    )


def _oracle_dot(qa, qb, dims):
    # bitwise-identical to _fp8_dot: the quantized values are exactly
    # representable in fp32, and both paths accumulate in fp32
    return jax.lax.dot_general(
        qa.astype(jnp.float32), qb.astype(jnp.float32), dims,
        preferred_element_type=jnp.float32,
    )


def _dispatch_dot(qa, qb, dims, impl):
    chosen = _checked_impl(
        "quantized_matmul", impl,
        lambda a, b: _fp8_dot(a, b, dims), qa, qb, statics=(dims,),
    )
    if chosen == "pallas":
        return _fp8_dot(qa, qb, dims)
    return _oracle_dot(qa, qb, dims)


def _resolve_impl(impl: Optional[str]) -> str:
    # the fast path is XLA's native-fp8 dot, booked under the dispatch
    # registry's "pallas" bucket (the probed-fast-path bucket), "fp8" accepted
    # as the natural spelling
    if impl in (None, "fp8", "pallas"):
        return "pallas"
    if impl == "jnp":
        return "jnp"
    raise ValueError(
        f"impl must be one of None/'fp8'/'pallas'/'jnp', got {impl!r}"
    )


# ------------------------------------------------------------- the custom_vjp
def _fwd_compute(impl, x, w, sw, sg):
    sx = _jit_scale(x, E4M3_MAX)
    # sentinel 0.0 = "no delayed scale in scope" -> just-in-time from w
    sw_eff = jnp.where(sw > 0.0, sw, _jit_scale(w, E4M3_MAX))
    qx = _q_e4m3(x, sx)
    qw = _q_e4m3(w, sw_eff)
    dims = (((x.ndim - 1,), (0,)), ((), ()))
    y = _dispatch_dot(qx, qw, dims, impl) * (1.0 / (sx * sw_eff))
    return y, (qx, qw, sx, sw_eff, sg)


def _qmm(impl, x, w, sw, sg):
    return _fwd_compute(impl, x, w, sw, sg)[0]


def _qmm_fwd(impl, x, w, sw, sg):
    return _fwd_compute(impl, x, w, sw, sg)


def _qmm_bwd(impl, res, dy):
    qx, qw, sx, sw, sg = res
    sg_eff = jnp.where(sg > 0.0, sg, _jit_scale(dy, E5M2_MAX))
    q_dy = _q_e5m2(dy, sg_eff)
    # dx = dy @ w^T: contract dy's N with w's dim 1 -> (..., K)
    dx_dims = (((dy.ndim - 1,), (1,)), ((), ()))
    dx = _dispatch_dot(q_dy, qw, dx_dims, impl) * (1.0 / (sg_eff * sw))
    # dw = x^T @ dy: contract every leading (batch/seq) dim -> (K, N)
    lead = tuple(range(dy.ndim - 1))
    dw_dims = ((lead, lead), ((), ()))
    dw = _dispatch_dot(qx, q_dy, dw_dims, impl) * (1.0 / (sx * sg_eff))
    return dx, dw, jnp.zeros_like(sw), jnp.zeros_like(sg)


_qmm = jax.custom_vjp(_qmm, nondiff_argnums=(0,))
_qmm.defvjp(_qmm_fwd, _qmm_bwd)


def quantized_matmul(x: jax.Array, w: jax.Array, *, impl: Optional[str] = None):
    """``x @ w`` with fp8-quantized operands and fp32 accumulation — the O6
    GEMM. x: (..., K); w: (K, N); returns fp32 (callers cast back, exactly
    like ``ops.dense._matmul``).

    Forward quantizes both operands to e4m3 (x just-in-time, w under the
    scope's delayed scale); the custom-VJP backward quantizes the cotangent
    to e5m2 and computes both grads from the saved fp8 residuals — activation
    residual memory is fp8, half of bf16's. Gradients return in the primal
    dtypes (the boundary casts are transposed by autodiff).

    ``impl``: None/'fp8' = guard-probed native-fp8 dot, 'jnp' = the upcast
    oracle (bitwise-identical values either way).
    """
    for name, a in (("x", x), ("w", w)):
        dt = getattr(a, "dtype", None)
        if dt is None or not any(dt == jnp.dtype(d) for d in _ALLOWED_DTYPES):
            raise TypeError(
                f"quantized_matmul: {name} has unsupported dtype {dt}; O6 "
                f"quantizes float16/bfloat16/float32 operands only"
            )
    if w.ndim != 2 or x.ndim < 1:
        raise ValueError(
            f"quantized_matmul expects x (..., K) and w (K, N); got "
            f"{x.shape} @ {w.shape}"
        )
    scales = _active_scales()
    if scales is None:
        sw = sg = jnp.float32(0.0)  # sentinel: just-in-time inside the op
    else:
        sw, sg = scales
    return _qmm(
        _resolve_impl(impl),
        x.astype(jnp.float32), w.astype(jnp.float32), sw, sg,
    )


# ------------------------------------------------------------- error bounds
def quantized_matmul_error_bound(
    x: jax.Array, w: jax.Array, *, scale_w=None
) -> jax.Array:
    """Analytic per-matmul bound: max-abs elementwise error of
    ``quantized_matmul(x, w)`` vs the fp32 reference ``x @ w`` — the oracle
    the O6 tests compare against.

    Derivation (per output element, K contraction terms): each dequantized
    operand carries ``|â - a| <= REL·|a| + TINY/s`` (round-to-nearest relative
    error plus the subnormal absolute floor, both divided back by the scale),
    plus the explicit clip excess when a stale delayed weight scale saturates.
    A product term then errs by ``ax·ew + aw·ex + ex·ew``; K terms sum; fp32
    accumulation adds ``<= 2·K²·2⁻²⁴·(ax+ex)(aw+ew)`` (both the quantized and
    the reference sum accumulate in fp32). Mirrors the op's actual scale
    selection: x just-in-time, w from ``scale_w``/the active scope, else
    just-in-time."""
    x32 = x.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    ax = jnp.max(jnp.abs(x32))
    aw = jnp.max(jnp.abs(w32))
    sx = _jit_scale(x32, E4M3_MAX)
    if scale_w is None:
        scales = _active_scales()
        scale_w = scales[0] if scales is not None else None
    sw = (
        jnp.asarray(scale_w, jnp.float32)
        if scale_w is not None
        else _jit_scale(w32, E4M3_MAX)
    )
    sw = jnp.where(sw > 0.0, sw, _jit_scale(w32, E4M3_MAX))
    clip_w = jnp.maximum(0.0, aw - E4M3_MAX / sw)
    ex = _E4M3_REL * ax + _E4M3_TINY / sx
    ew = _E4M3_REL * aw + _E4M3_TINY / sw + clip_w
    k = jnp.float32(x.shape[-1])
    quant = k * (ax * ew + aw * ex + ex * ew)
    accum = 2.0 * k * k * 2.0 ** -24 * (ax + ex) * (aw + ew)
    return quant + accum


def loss_parity_bound(
    step,
    *,
    n_matmuls: int,
    loss_ceiling: float,
    growth: float = 1.2,
) -> float:
    """Envelope for ``|loss_O6(t) - loss_O5(t)|`` over a training run — what
    the ≥50-step parity rung asserts against.

    Form: ``loss_ceiling · eps_fwd · growth**step`` where
    ``eps_fwd = (1 + 2·E4M3_REL)**n_matmuls - 1`` is the compounded worst-case
    relative forward perturbation of ``n_matmuls`` quantized GEMMs in
    sequence (each operand pair contributes ≤ 2·2⁻⁴ relative error to its
    output; norm layers re-normalize between them, so per-layer gain ≤ 1),
    ``loss_ceiling`` converts the relative logit perturbation to a loss
    difference (softmax-CE is 1-Lipschitz in the logits per token, so the
    initial loss ≈ ln V is a ceiling on the sensitivity), and ``growth``
    majorizes the per-step divergence rate of two SGD/Adam trajectories under
    persistent relative perturbation (1 + lr·curvature, with generous slack).
    Worst-case-over-everything, hence loose; a run's measured deviation
    (tests/test_quantized.py) is typically orders of magnitude smaller."""
    if n_matmuls < 1:
        raise ValueError(f"n_matmuls must be >= 1, got {n_matmuls}")
    eps_fwd = (1.0 + 2.0 * _E4M3_REL) ** n_matmuls - 1.0
    return float(loss_ceiling) * eps_fwd * float(growth) ** float(step)
