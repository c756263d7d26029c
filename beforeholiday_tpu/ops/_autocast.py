"""Per-op precision policy — the O1/O4 "patch engine" for a functional world.

The reference's O1 monkey-patches ``torch.*``/``torch.nn.functional.*`` with
cast wrappers driven by three lists (ref: apex/amp/lists/
functional_overrides.py:17-91, torch_overrides.py:7-139):

* FP16_FUNCS / BFLOAT16_FUNCS — conv/linear/BLAS run in the low precision;
* FP32_FUNCS — softmax, norms, losses, pointwise transcendentals stay fp32;
* CASTS — multi-argument ops promote to the widest input dtype;
* BANNED_FUNCS — numerically unsafe under fp16 (``binary_cross_entropy``)
  raise instead of silently degrading.

JAX functions cannot be monkey-patched under trace, and shouldn't be: the
TPU-native equivalent is an explicit autocast scope plus *decorated ops*.
Every fused op in ``beforeholiday_tpu.ops`` is tagged with its list membership via
the same decorator names the reference exposes for custom kernels
(``half_function`` / ``float_function`` / ``promote_function``, ref:
apex/amp/amp.py:29-71) — the decorators are inert until an ``autocast``
scope activates a compute dtype (entered by amp's O1/O4 ``apply`` wrapper).
There is no cast cache (apex/amp/utils.py:101-123): jit tracing makes every
cast a compile-time no-op to XLA's CSE.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax._src import config as _jax_config

# The scope must participate in jit's cache key: `jax.jit(fused_dense)` traced
# outside a scope and re-called inside one would otherwise hit the fp32 cache
# entry and silently skip the policy. jax's config-state machinery exposes
# exactly this (include_in_jit_key / include_in_trace_context). It is a
# private API; if it moves the import fails loudly — a thread-local stand-in
# would silently change jit-cache semantics.
_dtype_state = _jax_config.optional_enum_state(
    name="beforeholiday_tpu_autocast_dtype",
    enum_values=["float16", "bfloat16", "float32"],
    default=None,
    help="active autocast compute dtype for the per-op amp cast policy",
    include_in_jit_key=True,
    include_in_trace_context=True,
)
# O6's quantized-matmul routing flag must join the jit key exactly like
# the dtype: `jax.jit(fused_dense)` traced under O5 and re-called under O6
# would otherwise replay the unquantized cache entry.
_quantized_state = _jax_config.optional_enum_state(
    name="beforeholiday_tpu_autocast_quantized",
    enum_values=["on"],
    default=None,
    help="route fused matmuls through the fp8-style quantized path (O6)",
    include_in_jit_key=True,
    include_in_trace_context=True,
)


@contextlib.contextmanager
def autocast(dtype, *, quantized: bool = False):
    """Activate the per-op cast policy with ``dtype`` as the low-precision
    compute type (fp16 for O1, bf16 for O4). ``quantized=True`` additionally
    turns on O6's quantized-matmul routing for the scope (see
    :func:`quantized_compute`)."""
    with _dtype_state(jnp.dtype(dtype).name):
        if quantized:
            with _quantized_state("on"):
                yield
        else:
            yield


@contextlib.contextmanager
def quantized_compute():
    """Route every ``ops.dense`` matmul inside the scope through
    ``ops.quantized.quantized_matmul`` (the O6 tier) WITHOUT activating the
    per-op cast policy — O6 keeps O5's storage-cast semantics (bf16 params,
    fp32 norms) and only swaps the GEMM arithmetic. Participates in the jit
    cache key exactly like :func:`autocast`."""
    with _quantized_state("on"):
        yield


def autocast_dtype() -> Optional[Any]:
    """The active low-precision dtype, or None outside autocast."""
    name = _dtype_state.value
    return jnp.dtype(name) if name else None


def quantized_enabled() -> bool:
    """True inside a :func:`quantized_compute` (or ``autocast(...,
    quantized=True)``) scope — the O6 routing predicate ``ops.dense`` checks."""
    return _quantized_state.value == "on"


def cast_floats(tree, dtype):
    """Cast every floating leaf to ``dtype`` — THE canonical helper (amp's
    frontend and the fused optimizers import it from here)."""
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        tree,
    )


_cast_tree = cast_floats


def _widest_float(tree):
    """jnp's own promotion over the floating leaves — fp16+bf16 promotes to
    fp32 (not whichever 2-byte dtype came first)."""
    widest = None
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            dt = jnp.dtype(leaf.dtype)
            widest = dt if widest is None else jnp.promote_types(widest, dt)
    return widest


def half_function(fn: Callable) -> Callable:
    """Tag an op as low-precision under autocast (ref FP16_FUNCS /
    BFLOAT16_FUNCS; decorator parity: apex/amp/amp.py ``half_function``)."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        dt = autocast_dtype()
        if dt is not None:
            args = _cast_tree(args, dt)
            kwargs = _cast_tree(kwargs, dt)
        return fn(*args, **kwargs)

    wrapped.__amp_list__ = "half"
    return wrapped


# the bf16 tag is behaviorally identical here — the active dtype decides
bfloat16_function = half_function


def float_function(fn: Callable) -> Callable:
    """Tag an op as fp32-only under autocast (ref FP32_FUNCS: softmax, norms,
    losses, transcendentals)."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        if autocast_dtype() is not None:
            args = _cast_tree(args, jnp.float32)
            kwargs = _cast_tree(kwargs, jnp.float32)
        return fn(*args, **kwargs)

    wrapped.__amp_list__ = "float"
    return wrapped


def promote_function(fn: Callable) -> Callable:
    """Tag a multi-input op to promote every floating input to the widest
    input dtype under autocast (ref CASTS promote rule, apex/amp/wrap.py)."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        if autocast_dtype() is not None:
            widest = _widest_float((args, kwargs))
            if widest is not None:
                args = _cast_tree(args, widest)
                kwargs = _cast_tree(kwargs, widest)
        return fn(*args, **kwargs)

    wrapped.__amp_list__ = "promote"
    return wrapped


def banned_function(fn: Callable, name: str, reason: str) -> Callable:
    """Tag an op as unsafe under fp16 autocast — calling it raises, as the
    reference does for ``binary_cross_entropy`` (functional_overrides.py:80-91)."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        dt = autocast_dtype()
        if dt is not None and jnp.dtype(dt) == jnp.float16:
            raise RuntimeError(
                f"amp does not work out-of-the-box with `{name}` under fp16: "
                f"{reason}"
            )
        return fn(*args, **kwargs)

    wrapped.__amp_list__ = "banned"
    return wrapped
