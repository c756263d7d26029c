"""The reference's arithmetic modes: the stated one and the lower ones a control runs in.

``float32`` is the reference proper: every product in float32 with
``precision=HIGHEST`` (on a TPU a float32 matmul is otherwise done in bfloat16
passes). ``fp8`` is the reference *put in the program's place one precision
below bfloat16*, the control of a bfloat16 configuration: the operands of every
matmul and convolution are rounded on the way in to per-tensor amax-scaled
``float8_e4m3fn``, the gradient that flows back through each product is
rounded to ``float8_e5m2``, and products accumulate in float32: the usual fp8
training recipe (Micikevicius et al. 2022, arXiv:2209.05433), which the
program's own amp O6 tier follows. Everything between the products stays
float32, so this is the mildest form that recipe can take, and a limit that
catches it catches harsher ones. (A bfloat16 mode written the same way is
no control on a TPU: XLA removes a float32 -> bfloat16 -> float32 round trip as
excess precision; my chip run, PR 23, read gaps of exactly 0.)
"""

import jax
import jax.numpy as jnp

MODES = ("float32", "fp8")
_MAX = {jnp.float8_e4m3fn: 448.0, jnp.float8_e5m2: 57344.0}


def as_bfloat16_values(x):
    """float32 ``x`` with every value rounded to one a bfloat16 holds. Not
    ``x.astype(bfloat16).astype(float32)``: XLA removes that round trip as
    excess precision in some programs and not in others, so the reference and
    the program would start from weights that differ by the rounding (my chip
    run, PR 23: a residual of 0.106 in one leaf's norm, the same on every seed)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _fp8(x, dtype):
    """``x`` rounded to per-tensor amax-scaled ``dtype``, still float32."""
    scale = _MAX[dtype] / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def round_operand(x, mode):
    """``x`` as the matmul unit of ``mode`` would see it, still float32; the
    rounding is straight-through for the gradient."""
    if mode == "float32":
        return x
    if mode == "fp8":
        return x + jax.lax.stop_gradient(_fp8(x, jnp.float8_e4m3fn) - x)
    raise ValueError(f"unknown precision mode {mode!r}; one of {MODES}")


@jax.custom_vjp
def _e5m2_gradient(y):
    return y


_e5m2_gradient.defvjp(lambda y: (y, None), lambda _, g: (_fp8(g, jnp.float8_e5m2),))


def round_product(y, mode):
    """The product as ``mode`` hands it on: unchanged forward; under ``fp8`` the
    gradient that comes back through it is rounded to ``float8_e5m2``."""
    if mode != "fp8":
        return y
    return _e5m2_gradient(round_operand(y, mode))


def matmul(a, b, mode):
    return round_product(jnp.matmul(round_operand(a, mode), round_operand(b, mode),
                                    precision=jax.lax.Precision.HIGHEST), mode)


def conv_nhwc(x, w, stride, mode):
    """NHWC x HWIO convolution, symmetric padding (k-1)//2 as torchvision's."""
    pad = [((k - 1) // 2, (k - 1) // 2) for k in w.shape[:2]]
    return round_product(jax.lax.conv_general_dilated(
        round_operand(x, mode), round_operand(w, mode), (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST), mode)
