"""Buffer donation for step functions: the in-place reuse the reference gets
from its preallocated ``MemoryBuffer`` views (apex/transformer/tensor_parallel/
memory.py:25-146) is, under XLA, ``jax.jit(donate_argnums=...)``.

On TPU the params + optimizer state of a training step are the largest live
buffers; without donation XLA must hold BOTH the input and output copies
across the step, doubling their footprint. ``jax.jit(donate_argnums=...)``
lets XLA alias input to output storage — but it is easy to wire wrong: donate
a buffer the host still references and the next use raises "Array has been
deleted"; forget to donate the optimizer arena and peak memory silently
doubles. This module centralizes the wiring:

* ``donate_step(fn, donate_argnums=...)`` — ``jax.jit`` with donation plus a
  host-side warn-once when a ``PackedParams`` arena (the repo's fused-optimizer
  parameter arena) is passed in an UNdonated slot: an arena is step state by
  construction, so an undonated arena is almost always a lost aliasing
  opportunity.
* ``donate_optimizer_step(optimizer)`` — a jitted fused-optimizer step with
  params + state (optionally grads) donated, matching the
  ``optimizer.step(params, grads, state, ...)`` signature.

Donation composes with the caller's update loop only if state is REBOUND each
step (``params, state = step(params, grads, state)``); reusing a donated input
afterwards is a crash, not a slowdown — which is why the examples' trainers
rebind. Donation requested on a jit nested inside another jit is ignored by
jax (the outer trace owns the buffers), so donated steps remain safe to call
from wrapper jits such as a multi-step loop.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple, Union

import jax

from beforeholiday_tpu.monitor.program import note_entry
from beforeholiday_tpu.monitor.spans import span
from beforeholiday_tpu.utils.logging import warn_once

__all__ = ["donate_optimizer_step", "donate_step"]

_WARN_PREFIX = "remat.donation"


def _buffer_keys(leaf: Any) -> Tuple[Any, ...]:
    """Hashable identities of a leaf's device storage, one per addressable
    shard; empty for non-arrays. An array replicated over a mesh by
    ``jax.device_put`` may SHARE its source's buffer on the source's own
    device while the other devices get copies, so aliasing has to be judged
    shard by shard. Tracers (a donated step called under an outer jit, where
    jax ignores the donation) and deleted arrays have no buffer to read and
    fall back to object identity."""
    if not isinstance(leaf, jax.Array):
        return ()
    if isinstance(leaf, jax.core.Tracer) or leaf.is_deleted():
        return (id(leaf),)
    return tuple(
        s.data.unsafe_buffer_pointer() for s in leaf.addressable_shards
    )


def _dedupe_donated(args: Tuple[Any, ...], donated: frozenset) -> Tuple[Any, ...]:
    """Copy any donated leaf that shares a buffer with an earlier donated
    leaf, so XLA never sees the same buffer donated twice.

    Aliasing across donated state trees is legal while arrays are immutable —
    e.g. fused optimizers initialize fp32 masters as the params arena itself
    when it is already fp32 (a no-op ``astype``) — but donation makes storage
    mutable, and XLA rejects a twice-donated buffer. The alias only survives
    until the first step (step outputs are fresh buffers), so the copy here is
    a one-time cost, and the walk itself is host-side metadata only."""
    seen = set()
    out = list(args)
    for i in sorted(donated):
        if i >= len(out):
            continue
        leaves, treedef = jax.tree_util.tree_flatten(out[i])
        changed = False
        for j, leaf in enumerate(leaves):
            keys = _buffer_keys(leaf)
            if seen.intersection(keys):
                leaves[j] = jax.numpy.array(leaf)  # fresh buffers break the alias
                changed = True
            else:
                seen.update(keys)
        if changed:
            out[i] = jax.tree_util.tree_unflatten(treedef, leaves)
    return tuple(out)


def _contains_arena(tree: Any) -> bool:
    """True if any node of ``tree`` is a ``PackedParams`` arena."""
    from beforeholiday_tpu.ops.arena import PackedParams  # lazy: avoid cycle

    hit = False

    def _is_leaf(x):
        nonlocal hit
        if isinstance(x, PackedParams):
            hit = True
        return isinstance(x, PackedParams)

    jax.tree_util.tree_flatten(tree, is_leaf=_is_leaf)
    return hit


def donate_step(
    fn: Callable,
    *,
    donate_argnums: Union[int, Sequence[int]] = (0,),
    warn_undonated_arena: bool = True,
    **jit_kwargs: Any,
) -> Callable:
    """``jax.jit(fn, donate_argnums=...)`` with an undonated-arena sentinel.

    The wrapper checks (host-side, shapes-only — no device sync) every
    positional argument OUTSIDE ``donate_argnums`` for a ``PackedParams``
    arena and warns once per (entry, slot) when one is found. The underlying
    jitted function is exposed as ``.jitted`` (for ``.lower()`` / AOT use).
    The first call notes the entry for ``monitor.program_ops()`` (abstract
    values only; nothing is lowered for it until that is asked)."""
    if isinstance(donate_argnums, int):
        donate_argnums = (donate_argnums,)
    donated = frozenset(donate_argnums)
    jitted = jax.jit(fn, donate_argnums=tuple(donate_argnums), **jit_kwargs)
    entry = getattr(fn, "__name__", type(fn).__name__)
    noted = False

    def warn_undonated(args):
        for i, arg in enumerate(args):
            if i not in donated and _contains_arena(arg):
                warn_once(
                    (_WARN_PREFIX, entry, i),
                    "donation: step %r received a PackedParams arena in "
                    "undonated argument %d — an optimizer arena is step "
                    "state; pass its index in donate_argnums or XLA keeps "
                    "two copies live across the step",
                    entry,
                    i,
                )

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        nonlocal noted
        if not noted:  # before the call: the donated arrays are gone after it
            noted = note_entry(entry, jitted, args, kwargs)
        # two host spans, so that a device-idle gap under the caller's
        # dispatch is told apart: this wrapper's own Python, or the jitted call
        with span("donate_step.prepare"):
            if warn_undonated_arena:
                warn_undonated(args)
            args = _dedupe_donated(args, donated)
        with span("donate_step.call"):  # dispatch; the first time, trace + compile
            return jitted(*args, **kwargs)

    wrapper.jitted = jitted
    return wrapper


def donate_optimizer_step(
    optimizer: Any,
    *,
    donate_grads: bool = False,
    **jit_kwargs: Any,
) -> Callable:
    """Jitted fused-optimizer step with params + state donated.

    Returns ``step(params, grads, state, *, found_inf=None, grad_scale=1.0,
    lr=None) -> (params, state)`` matching the fused optimizers' method
    signature; params (slot 0) and state (slot 2) are donated, and grads
    (slot 1) too when ``donate_grads`` — only safe when the caller does not
    reuse the grads after the update (e.g. no post-step grad-norm logging)."""
    donate: Tuple[int, ...] = (0, 1, 2) if donate_grads else (0, 2)

    def _step(params, grads, state, found_inf, grad_scale, lr):
        return optimizer.step(
            params, grads, state,
            found_inf=found_inf, grad_scale=grad_scale, lr=lr,
        )

    _step.__name__ = f"donated_{type(optimizer).__name__}_step"
    inner = donate_step(_step, donate_argnums=donate, **jit_kwargs)

    @functools.wraps(_step)
    def step(params, grads, state, *, found_inf=None, grad_scale=1.0, lr=None):
        return inner(params, grads, state, found_inf, grad_scale, lr)

    step.jitted = inner.jitted
    return step
