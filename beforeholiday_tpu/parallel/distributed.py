"""Data-parallel gradient reduction (ref: apex/parallel/distributed.py).

The reference's ``DistributedDataParallel`` hooks every parameter's backward,
buckets grads by arrival order, and overlaps NCCL allreduces on side streams
(ref: apex/parallel/distributed.py:129-640). Under XLA none of that machinery
survives: a ``psum`` over the ``data`` mesh axis is one fused ICI collective,
and the latency-hiding scheduler overlaps it with remaining backward compute —
bucketing/stream juggling is the compiler's job. What must be preserved are the
reference's *semantic* knobs:

* ``gradient_average``            — divide by world size after the reduce
* ``gradient_predivide_factor``   — divide by f before, world/f after (:162-175)
* ``allreduce_always_fp32``       — reduce in fp32, cast back (:166)

``reduce_gradients`` is the inside-shard_map primitive; ``DistributedDataParallel``
wraps a loss function into a ``value_and_grad`` that applies it, and ``Reducer``
is the manual call-when-you-want variant (ref: distributed.py:89-126).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from beforeholiday_tpu.monitor import comms
from beforeholiday_tpu.monitor.spans import span
from beforeholiday_tpu.ops.arena import PackedParams
from beforeholiday_tpu.parallel import bucketing, overlap
from beforeholiday_tpu.parallel.parallel_state import (
    DATA_AXIS,
    hierarchical_axes,
)


def _axis_size(axis_name: Any):
    """``jax.lax.axis_size``; a two-level ``("slice", "intra")`` spec is the
    product of its tiers."""
    axes = hierarchical_axes(axis_name)
    if axes is not None:
        return _axis_size(axes[0]) * _axis_size(axes[1])
    return jax.lax.axis_size(axis_name)


def _grad_fingerprint(grads: Any) -> jax.Array:
    """Cheap per-rank summary of a grad pytree: stacked fp32 (sum, sumsq) per
    leaf. Identical local grads => identical fingerprints; a perturbed or
    corrupted rank disagrees with overwhelming probability."""
    parts = []
    for g in jax.tree_util.tree_leaves(grads):
        g32 = g.astype(jnp.float32)
        parts.append(jnp.stack([jnp.sum(g32), jnp.sum(g32 * g32)]))
    if not parts:
        return jnp.zeros((2,), jnp.float32)
    return jnp.concatenate(parts)


def check_replicated_consistency(
    tree: Any,
    axis_name: Any = DATA_AXIS,
    *,
    site: str = "ddp.consistency",
) -> jax.Array:
    """Traced bool: True when any rank's fingerprint of ``tree`` disagrees
    across ``axis_name`` or holds a non-finite value.

    For values that are replicated BY CONSTRUCTION — pre-reduce grads under
    a replicated batch, ZeRO-3 gathered params (every rank all-gathered the
    same shards), broadcast batches — a disagreement means silent LOCAL
    corruption (an SEU, a bad HBM read) that the downstream collective
    would launder into every rank. This is the tripwire the elastic trainer
    treats as a resize/reload event, and the primitive behind
    ``reduce_gradients(check_consistency=True)``.

    Cost: one pmax+pmin of a tiny (2·n_leaves,) vector plus one pmax of the
    combined flag. Never raises; every rank returns the same verdict. Must
    run inside a binding context for ``axis_name``."""
    fp = _grad_fingerprint(tree)
    hi = comms.pmax(fp, axis_name, site=site)
    lo = comms.pmin(fp, axis_name, site=site)
    # the non-finite test is rank-LOCAL (pmax may drop a lone NaN under
    # maxNum semantics), so the combined flag gets its own reduction —
    # every rank must return the same verdict
    local_bad = jnp.any(hi != lo) | jnp.any(~jnp.isfinite(fp))
    return comms.pmax(local_bad.astype(jnp.int32), axis_name, site=site) > 0


def reduce_gradients(
    grads: Any,
    *,
    axis_name: Any = DATA_AXIS,
    gradient_average: bool = True,
    gradient_predivide_factor: Optional[float] = None,
    allreduce_always_fp32: bool = False,
    check_consistency: bool = False,
    bucket_bytes: Optional[int] = None,
    compress: bool = False,
    wire_dtype: Any = jnp.bfloat16,
    hierarchical: bool = False,
    compress_intra: Optional[bool] = None,
    compress_dcn: Optional[bool] = None,
) -> Any:
    """psum a gradient pytree over ``axis_name`` with apex's scaling options.

    Must run inside a binding context for ``axis_name`` (shard_map / pmap)
    **with varying-axis tracking off** (``jax.shard_map(..., check_vma=False)``):
    that is the mode where gradients of replicated
    params come back *local*, matching the reference's per-process grads. With
    tracking ON, shard_map's transpose already psums replicated-param
    cotangents — calling this on top would double-count; there just divide by
    the axis size.
    Semantics match allreduce_fallback (ref: apex/parallel/distributed.py:316-349):
    predivide by f, allreduce, postdivide by world/f when averaging.

    ``check_consistency=True`` changes the return to ``(reduced, mismatch)``:
    ``mismatch`` is a traced bool, True when any rank's pre-reduce grad
    fingerprint (per-leaf fp32 sum/sumsq) disagrees across the axis or is
    non-finite — the silent-corruption tripwire for replicated-grad training
    (a rank whose grads diverged poisons everyone through the psum). It costs
    one pmax+pmin of a tiny vector; feed it into a skip/alarm path, it never
    raises. NOTE: only meaningful when every rank is expected to hold the SAME
    grads pre-reduce (replicated-batch debugging / overfit checks), not for
    ordinary data-parallel steps where per-rank grads legitimately differ.

    ``bucket_bytes`` switches to the bucketed path (``parallel.bucketing``):
    grads go out as independent ~bucket_bytes collectives the latency-hiding
    scheduler can overlap with remaining backward compute — the XLA-era
    analogue of the reference's backward-hook buckets
    (apex/parallel/distributed.py:352-409). ``PackedParams`` grads (arena
    native) bucket their flat arenas directly; tree grads are grouped
    greedily per dtype and each group is ONE variadic psum. Uncompressed
    bucketing is bitwise-identical to the default path. ``compress=True``
    additionally puts ``wire_dtype`` (default bf16) on the wire with fp32
    accumulation — see ``bucketing.compression_error_bound`` for the analytic
    error bound. Default (``bucket_bytes=None, compress=False``) is the
    legacy per-leaf psum, unchanged.

    ``hierarchical=True`` (needs a two-level ``("slice", "intra")``
    ``axis_name``, see ``parallel_state.make_two_level_mesh``) reduces each
    bucket with the two-level engine — intra-slice reduce-scatter, inter-slice
    psum on 1/slice_size of the payload, intra-slice all-gather — so the slow
    DCN tier carries ``1/slice_size`` of the flat bytes (the ledger's
    ``comms_summary()['by_tier']`` proves it). Uncompressed it is
    bitwise-equal to the flat bucketed path over the same two-level spec.
    ``compress_intra`` / ``compress_dcn`` compress each tier independently
    (``None`` inherits ``compress``); the composed analytic bound is
    ``bucketing.hierarchical_compression_error_bound``.
    """
    if hierarchical and hierarchical_axes(axis_name) is None:
        raise ValueError(
            "hierarchical=True needs a (slice, intra) axis spec; got "
            f"{axis_name!r}"
        )
    ci = compress if compress_intra is None else compress_intra
    cd = compress if compress_dcn is None else compress_dcn
    with span("ddp_reduce_gradients"):
        world = _axis_size(axis_name)

        mismatch = None
        if check_consistency:
            mismatch = check_replicated_consistency(
                grads, axis_name, site="ddp.grad_fingerprint"
            )

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
                return _post(
                    comms.psum(
                        _pre(g), axis_name, site="ddp.reduce_gradients"
                    ),
                    g.dtype,
                )

            reduced = jax.tree.map(_reduce, grads)
        elif isinstance(grads, PackedParams):
            # arena-native grads: bucket each flat arena directly
            if hierarchical:
                arenas = [
                    _post(
                        bucketing.hierarchical_psum(
                            _pre(a), hierarchical_axes(axis_name),
                            site="ddp.bucketed_reduce",
                            bucket_bytes=bucket_bytes,
                            compress_intra=ci, compress_dcn=cd,
                            wire_dtype=wire_dtype,
                        ),
                        a.dtype,
                    )
                    for a in grads.arenas
                ]
            else:
                arenas = [
                    _post(
                        bucketing.bucketed_psum(
                            _pre(a), axis_name, site="ddp.bucketed_reduce",
                            bucket_bytes=bucket_bytes, compress=compress,
                            wire_dtype=wire_dtype,
                        ),
                        a.dtype,
                    )
                    for a in grads.arenas
                ]
            reduced = grads.replace_arenas(arenas)
        else:
            leaves, treedef = jax.tree_util.tree_flatten(grads)
            red = bucketing.bucketed_tree_psum(
                [_pre(g) for g in leaves], axis_name,
                site="ddp.bucketed_reduce", bucket_bytes=bucket_bytes,
                compress=compress, wire_dtype=wire_dtype,
                hierarchical=hierarchical, compress_intra=ci,
                compress_dcn=cd,
            )
            red = [_post(r, g.dtype) for r, g in zip(red, leaves)]
            reduced = jax.tree_util.tree_unflatten(treedef, red)
        if check_consistency:
            return reduced, mismatch
        return reduced


class Reducer:
    """Manual allreduce helper (ref: apex/parallel/distributed.py:89-126).

    The reference averages parameters across ranks on construction and exposes
    ``reduce()`` to allreduce whenever the user chooses; here both are explicit
    pytree operations usable inside shard_map.
    """

    def __init__(
        self,
        axis_name: Any = DATA_AXIS,
        *,
        bucket_bytes: Optional[int] = None,
        compress: bool = False,
        wire_dtype: Any = jnp.bfloat16,
        hierarchical: bool = False,
        compress_intra: Optional[bool] = None,
        compress_dcn: Optional[bool] = None,
    ):
        if hierarchical and hierarchical_axes(axis_name) is None:
            raise ValueError(
                "hierarchical=True needs a (slice, intra) axis spec; got "
                f"{axis_name!r}"
            )
        self.axis_name = axis_name
        self.bucket_bytes = bucket_bytes
        self.compress = compress
        self.wire_dtype = wire_dtype
        self.hierarchical = hierarchical
        self.compress_intra = compress_intra
        self.compress_dcn = compress_dcn

    def hook(self, tree: Any, *, tag: str = "reducer") -> Any:
        """Backward-time variant of :meth:`reduce`: identity on ``tree``
        whose backward reduces the cotangent per top-level group, with this
        reducer's bucketing knobs (see ``parallel.overlap.hook_tree``)."""
        return overlap.hook_tree(
            tree, tag=tag, axis_name=self.axis_name,
            bucket_bytes=self.bucket_bytes, compress=self.compress,
            wire_dtype=self.wire_dtype, hierarchical=self.hierarchical,
            compress_intra=self.compress_intra,
            compress_dcn=self.compress_dcn,
        )

    def broadcast_params(self, params: Any) -> Any:
        """Make params exactly rank 0's values on every rank (ref:
        distributed.py:254 broadcasts rank 0 at init). Implemented as a masked
        psum — zero every rank's contribution except rank 0 — which is exact
        both when ranks have diverged (the repair scenario broadcast exists
        for) and when they are already replicated."""
        with span("ddp_broadcast_params"):
            is_src = jax.lax.axis_index(self.axis_name) == 0
            return jax.tree.map(
                lambda p: comms.psum(
                    jnp.where(is_src, p, jnp.zeros((), p.dtype)),
                    self.axis_name,
                    site="ddp.broadcast_params",
                ),
                params,
            )

    def reduce(self, tree: Any, average: bool = True) -> Any:
        return reduce_gradients(
            tree, axis_name=self.axis_name, gradient_average=average,
            bucket_bytes=self.bucket_bytes, compress=self.compress,
            wire_dtype=self.wire_dtype, hierarchical=self.hierarchical,
            compress_intra=self.compress_intra,
            compress_dcn=self.compress_dcn,
        )


class DistributedDataParallel:
    """Functional DDP: loss fn → data-parallel value_and_grad.

    Usage inside ``shard_map`` over the ``data`` axis (or any mapped axis):

        ddp = DistributedDataParallel(allreduce_always_fp32=True)
        loss, grads = ddp.value_and_grad(loss_fn)(params, local_batch)

    Grads come back identical on every rank — the invariant the reference's
    bucketed backward-hook allreduce maintains (apex/parallel/distributed.py:352-409),
    with XLA providing the compute/communication overlap the reference builds
    from CUDA side streams.
    """

    def __init__(
        self,
        *,
        axis_name: Any = DATA_AXIS,
        gradient_average: bool = True,
        gradient_predivide_factor: Optional[float] = None,
        allreduce_always_fp32: bool = False,
        bucket_bytes: Optional[int] = None,
        compress: bool = False,
        wire_dtype: Any = jnp.bfloat16,
        overlap_backward: bool = False,
        hierarchical: bool = False,
        compress_intra: Optional[bool] = None,
        compress_dcn: Optional[bool] = None,
    ):
        if hierarchical and hierarchical_axes(axis_name) is None:
            raise ValueError(
                "hierarchical=True needs a (slice, intra) axis spec; got "
                f"{axis_name!r}"
            )
        self.axis_name = axis_name
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.bucket_bytes = bucket_bytes
        self.compress = compress
        self.wire_dtype = wire_dtype
        self.overlap_backward = overlap_backward
        self.hierarchical = hierarchical
        self.compress_intra = compress_intra
        self.compress_dcn = compress_dcn

    def reduce(self, grads: Any) -> Any:
        return reduce_gradients(
            grads,
            axis_name=self.axis_name,
            gradient_average=self.gradient_average,
            gradient_predivide_factor=self.gradient_predivide_factor,
            allreduce_always_fp32=self.allreduce_always_fp32,
            bucket_bytes=self.bucket_bytes,
            compress=self.compress,
            wire_dtype=self.wire_dtype,
            hierarchical=self.hierarchical,
            compress_intra=self.compress_intra,
            compress_dcn=self.compress_dcn,
        )

    def hook(self, tree: Any, *, tag: str = "ddp") -> Any:
        """Backward-time reduction boundary with this DDP's knobs: identity
        on ``tree``; its cotangent comes back reduced per top-level group,
        launched inside the backward (the apex ``delay_allreduce=False``
        hook path; see ``parallel.overlap``)."""
        return overlap.hook_tree(
            tree, tag=tag, axis_name=self.axis_name,
            gradient_average=self.gradient_average,
            gradient_predivide_factor=self.gradient_predivide_factor,
            allreduce_always_fp32=self.allreduce_always_fp32,
            bucket_bytes=self.bucket_bytes, compress=self.compress,
            wire_dtype=self.wire_dtype, hierarchical=self.hierarchical,
            compress_intra=self.compress_intra,
            compress_dcn=self.compress_dcn,
        )

    def value_and_grad(
        self, loss_fn: Callable, *, has_aux: bool = False
    ) -> Callable:
        if self.overlap_backward:
            # hook the params at the loss boundary: autodiff then reduces
            # each top-level group's cotangent inside the backward, so no
            # post-backward sweep is needed (bitwise-equal uncompressed)
            def hooked(params, *args, **kw):
                return loss_fn(self.hook(params), *args, **kw)

            return jax.value_and_grad(hooked, has_aux=has_aux)

        vag = jax.value_and_grad(loss_fn, has_aux=has_aux)

        def wrapped(params, *args, **kw):
            out, grads = vag(params, *args, **kw)
            return out, self.reduce(grads)

        return wrapped
