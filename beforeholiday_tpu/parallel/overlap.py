"""Backward-time gradient reduction: comms start the moment grads exist.

The reference's ``DistributedDataParallel`` registers a backward hook per
parameter and launches each bucket's NCCL allreduce on a side stream the
instant the bucket fills (apex/parallel/distributed.py:352-409) — the wire
runs UNDER the remaining backward math. The XLA port of that idea is a
``custom_vjp`` identity boundary: forward is a no-op, and the *backward*
rule reduces the cotangent right where autodiff produces it. Placed around a
layer group (or inside a ``lax.scan``-over-layers body), the per-group psum
is emitted in the middle of the backward program instead of one post-backward
sweep, so the latency-hiding scheduler can overlap it with the rest of the
backward. Whether it does is a chip measurement (``collective_exposed_ms``
on ``gpt2-medium.train-dp4``, ROADMAP A3); ``tests/test_overlap_engine.py``
holds that the hooked reduction is bitwise the post-backward one.

Three public pieces:

* :func:`reduction_hook` — the boundary itself. ``reduction_hook(tree)`` is
  the identity on the forward pass; on the backward pass the cotangent of
  ``tree`` comes back reduced over ``axis_name`` with EXACTLY the op
  sequence of ``distributed.reduce_gradients`` (predivide, psum / bucketed
  psum / compressed wire, postdivide) — uncompressed hooks are bitwise
  identical to the post-backward sweep, compressed hooks carry the same
  ``bucketing.compression_error_bound`` analytic bound. Comms flow through
  the ledger under ``site="ddp.overlap_hook:<tag>"`` so attribution keeps
  working.
* :func:`hook_tree` — per-layer-group tagging sugar: hooks each top-level
  child of a dict (or each element of a list/tuple) under its own tag, so a
  params dict ``{"embed": …, "blocks": …, "head": …}`` gets one independent
  backward-time reduction per group, in backward order (head first).
* :func:`per_bucket_found_inf` / :func:`fold_found_inf` — the
  optimizer-in-backward overflow story. Each bucket (``partition_leaves``
  geometry, same as the reduction) reports its own non-finite flag; the fold
  ORs every per-bucket flag (plus the scaler's external sentinel) into ONE
  scalar that gates EVERY leaf's update and the step counter. Whole-step
  skip proof: every kernel call receives the same folded flag, each kernel's
  ``found_inf`` select holds params AND moments, and ``_next_step`` holds
  the counter — so one overflowing bucket skips the entire step, never a
  prefix of it. Only the final cheap selects depend on the flag's value, so
  the heavy per-bucket math still overlaps; nothing commits until the flag
  is known, exactly like the phased path.

No host syncs anywhere (this file is inside the ``tests/test_no_host_sync``
scan with zero sanctions): bucket geometry is static, flags are traced
scalars, and the hook factory caches on hashable config only.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp

from beforeholiday_tpu.monitor import comms
from beforeholiday_tpu.monitor.spans import span
from beforeholiday_tpu.ops.arena import PackedParams
from beforeholiday_tpu.parallel import bucketing
from beforeholiday_tpu.parallel.parallel_state import (
    DATA_AXIS,
    hierarchical_axes,
)

__all__ = [
    "fold_found_inf",
    "hook_tree",
    "per_bucket_found_inf",
    "reduction_hook",
]


def _axis_size(axis_name: Any):
    """Same as ``distributed._axis_size`` (not imported from there:
    ``distributed`` imports this module)."""
    axes = hierarchical_axes(axis_name)
    if axes is not None:
        return _axis_size(axes[0]) * _axis_size(axes[1])
    return jax.lax.axis_size(axis_name)


def _reduce_cotangent(
    ct: Any,
    *,
    axis_name: Any,
    site: str,
    gradient_average: bool,
    gradient_predivide_factor: Optional[float],
    allreduce_always_fp32: bool,
    bucket_bytes: Optional[int],
    compress: bool,
    wire_dtype: Any,
    hierarchical: bool = False,
    compress_intra: bool = False,
    compress_dcn: bool = False,
) -> Any:
    """The body of ``distributed.reduce_gradients`` minus the tripwire —
    the identical pre-scale / reduce / post-scale op sequence, so the hooked
    backward is bitwise-equal to hook-nothing-then-sweep (uncompressed)."""
    world = _axis_size(axis_name)

    def _pre(g):
        if allreduce_always_fp32:
            g = g.astype(jnp.float32)
        if gradient_predivide_factor is not None:
            g = g / gradient_predivide_factor
        return g

    def _post(g, orig_dtype):
        if gradient_average:
            if gradient_predivide_factor is not None:
                g = g / (world / gradient_predivide_factor)
            else:
                g = g / world
        if allreduce_always_fp32:
            g = g.astype(orig_dtype)
        return g

    bucketed = bucket_bytes is not None or compress or hierarchical
    if not bucketed:

        def _reduce(g):
            return _post(comms.psum(_pre(g), axis_name, site=site), g.dtype)

        return jax.tree.map(_reduce, ct)
    if isinstance(ct, PackedParams):
        if hierarchical:
            arenas = [
                _post(
                    bucketing.hierarchical_psum(
                        _pre(a), hierarchical_axes(axis_name), site=site,
                        bucket_bytes=bucket_bytes,
                        compress_intra=compress_intra,
                        compress_dcn=compress_dcn, wire_dtype=wire_dtype,
                    ),
                    a.dtype,
                )
                for a in ct.arenas
            ]
        else:
            arenas = [
                _post(
                    bucketing.bucketed_psum(
                        _pre(a), axis_name, site=site,
                        bucket_bytes=bucket_bytes, compress=compress,
                        wire_dtype=wire_dtype,
                    ),
                    a.dtype,
                )
                for a in ct.arenas
            ]
        return ct.replace_arenas(arenas)
    leaves, treedef = jax.tree_util.tree_flatten(ct)
    red = bucketing.bucketed_tree_psum(
        [_pre(g) for g in leaves], axis_name, site=site,
        bucket_bytes=bucket_bytes, compress=compress, wire_dtype=wire_dtype,
        hierarchical=hierarchical, compress_intra=compress_intra,
        compress_dcn=compress_dcn,
    )
    red = [_post(r, g.dtype) for r, g in zip(red, leaves)]
    return jax.tree_util.tree_unflatten(treedef, red)


@functools.lru_cache(maxsize=None)
def _hook_fn(
    axis_name: Any,
    tag: str,
    gradient_average: bool,
    gradient_predivide_factor: Optional[float],
    allreduce_always_fp32: bool,
    bucket_bytes: Optional[int],
    compress: bool,
    wire_dtype_name: str,
    hierarchical: bool = False,
    compress_intra: bool = False,
    compress_dcn: bool = False,
) -> Callable[[Any], Any]:
    """One cached ``custom_vjp`` identity per hashable reduction config.

    Caching keeps the boundary a stable Python callable across traces, so a
    hook inside a jitted step never shows up as a new primitive identity to
    the recompile sentinel."""
    site = f"ddp.overlap_hook:{tag}"
    wire_dtype = jnp.dtype(wire_dtype_name)

    @jax.custom_vjp
    def _identity(tree):
        return tree

    def _fwd(tree):
        return tree, None

    def _bwd(_, ct):
        with span(f"ddp_overlap_hook:{tag}"):
            return (
                _reduce_cotangent(
                    ct,
                    axis_name=axis_name,
                    site=site,
                    gradient_average=gradient_average,
                    gradient_predivide_factor=gradient_predivide_factor,
                    allreduce_always_fp32=allreduce_always_fp32,
                    bucket_bytes=bucket_bytes,
                    compress=compress,
                    wire_dtype=wire_dtype,
                    hierarchical=hierarchical,
                    compress_intra=compress_intra,
                    compress_dcn=compress_dcn,
                ),
            )

    _identity.defvjp(_fwd, _bwd)
    return _identity


def reduction_hook(
    tree: Any,
    *,
    axis_name: Any = DATA_AXIS,
    tag: str = "grads",
    gradient_average: bool = True,
    gradient_predivide_factor: Optional[float] = None,
    allreduce_always_fp32: bool = False,
    bucket_bytes: Optional[int] = None,
    compress: bool = False,
    wire_dtype: Any = jnp.bfloat16,
    hierarchical: bool = False,
    compress_intra: Optional[bool] = None,
    compress_dcn: Optional[bool] = None,
) -> Any:
    """Identity on ``tree`` whose backward reduces the cotangent in place.

    Apply to (a group of) params before they are used::

        def loss_fn(params, batch):
            params = overlap.reduction_hook(params, tag="all")
            return model(params, batch)

    ``jax.grad(loss_fn)`` then returns grads already reduced over
    ``axis_name`` — with the collective emitted INSIDE the backward at the
    point the group's cotangent is complete, not after the full backward.
    Inside a ``lax.scan``-over-layers body, hook the per-iteration layer
    slice: each backward scan iteration then reduces that layer's grads
    while earlier layers' backward compute is still in flight (the stacked
    result is bitwise-equal to reducing the stacked grads afterwards —
    psum is elementwise over the leading layer axis).

    Scaling knobs mirror ``reduce_gradients`` exactly — including the
    two-level ``hierarchical`` / ``compress_intra`` / ``compress_dcn`` knobs
    (``None`` tier knobs inherit ``compress``); must run inside a binding
    context for ``axis_name`` with varying-axis tracking off (see
    ``reduce_gradients``'s docstring).
    """
    axes = hierarchical_axes(axis_name)
    if hierarchical and axes is None:
        raise ValueError(
            "hierarchical=True needs a (slice, intra) axis spec; got "
            f"{axis_name!r}"
        )
    fn = _hook_fn(
        axes if axes is not None else axis_name,
        tag,
        bool(gradient_average),
        None if gradient_predivide_factor is None
        else float(gradient_predivide_factor),
        bool(allreduce_always_fp32),
        None if bucket_bytes is None else int(bucket_bytes),
        bool(compress),
        jnp.dtype(wire_dtype).name,
        bool(hierarchical),
        bool(compress if compress_intra is None else compress_intra),
        bool(compress if compress_dcn is None else compress_dcn),
    )
    return fn(tree)


def hook_tree(
    tree: Any,
    *,
    tag: str = "params",
    **knobs: Any,
) -> Any:
    """Hook each top-level group of ``tree`` under its own tag.

    A dict hooks per key (``tag.key``), a list/tuple per index
    (``tag.0``, ``tag.1``, …); anything else (including ``PackedParams``
    arenas and namedtuples) gets a single hook. One hook per group means
    one independent backward-time reduction per group — the layer-group
    granularity the reference's bucketed hooks had. Uncompressed, any
    grouping is bitwise-equal to the monolithic sweep (psum is per-leaf
    exact); compressed groupings differ only in concat layout, and every
    layout stays within the same per-element analytic wire bound.
    ``knobs`` are forwarded to :func:`reduction_hook`.
    """
    if type(tree) is dict:
        return {
            k: reduction_hook(v, tag=f"{tag}.{k}", **knobs)
            for k, v in tree.items()
        }
    if isinstance(tree, (list, tuple)) and not hasattr(tree, "_fields"):
        hooked = [
            reduction_hook(v, tag=f"{tag}.{i}", **knobs)
            for i, v in enumerate(tree)
        ]
        return type(tree)(hooked)
    return reduction_hook(tree, tag=tag, **knobs)


# ------------------------------------------------- optimizer-in-backward
def per_bucket_found_inf(
    leaves: Sequence[Any],
    *,
    bucket_bytes: Optional[int] = None,
) -> List[jax.Array]:
    """One non-finite flag per reduction bucket of ``leaves``.

    Buckets are ``bucketing.partition_leaves`` groups — the SAME geometry
    the bucketed reduction used — so each flag is available as soon as its
    bucket's reduced grads are, without waiting for the rest of the
    backward. Non-float leaves can't overflow and contribute False."""
    flags: List[jax.Array] = []
    for group in bucketing.partition_leaves(list(leaves), bucket_bytes):
        flag = jnp.zeros((), jnp.bool_)
        for i in group:
            g = leaves[i]
            if jnp.issubdtype(jnp.result_type(g), jnp.inexact):
                flag = flag | jnp.any(~jnp.isfinite(g.astype(jnp.float32)))
        flags.append(flag)
    return flags


def fold_found_inf(
    flags: Sequence[Any],
    external: Any = None,
) -> jax.Array:
    """OR per-bucket flags (and the scaler's sentinel) into the ONE scalar
    that gates the whole step.

    This fold is what makes optimizer-in-backward safe: every per-leaf
    kernel receives this single flag, so either every update commits or
    none does — a step can never be half-applied because only the last
    bucket overflowed. The dataflow cost is one tree of ORs; the heavy
    per-bucket update math does not depend on the flag until its final
    select, so the overlap the hooks bought is preserved."""
    flag = jnp.zeros((), jnp.bool_)
    for f in flags:
        flag = flag | (jnp.asarray(f) != 0)
    if external is not None:
        flag = flag | (jnp.asarray(external) != 0)
    return flag
