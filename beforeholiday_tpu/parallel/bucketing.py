"""Bucketed + dtype-compressed collectives over flat gradient arenas.

Heritage: Apex's ``DistributedDataParallel`` splits gradients into
``allreduce_communicators`` buckets so NCCL all-reduces overlap with the rest
of backward (apex/parallel/distributed.py), and ZeRO shards the reduction as
a reduce-scatter (Rajbhandari et al., 2020). Under jit the overlap mechanism
is different — XLA's latency-hiding scheduler interleaves collectives with
compute on its own — but it can only overlap INDEPENDENT ops. One monolithic
psum over a 46M-param arena is a single serialized blob; this module slices
the same arena into right-sized buckets issued as independent collectives the
scheduler is free to hoist between the remaining backward work.

Three guarantees every helper here keeps:

* **Static geometry.** Bucket offsets/lengths and the axis size are host
  Python ints derived at trace time (``lax.axis_size`` is static under
  ``shard_map``); nothing here branches on
  a traced value and nothing reads back to the host
  (``tests/test_no_host_sync.py`` scans this file).
* **fp32 accumulation under compression.** ``compress=True`` casts each
  bucket to the wire dtype ONCE, exchanges rank-major rows via
  ``all_to_all`` (a reduce-scatter in disguise), and sums the received rows
  in fp32 — the reduction tree itself never rounds in bf16. The elementwise
  error versus the exact fp32 reduce is bounded by
  ``wire_eps(wire_dtype) * psum(|x|)`` — one input rounding per rank plus
  (for the all-reduce form) one output rounding of the fp32 sum.
* **Ledger-visible.** Every collective routes through
  ``monitor.comms`` wrappers: per-site ``calls`` is the bucket count,
  ``bytes`` the actual wire payload (bf16 when compressed), and
  ``logical_bytes``/``compression_ratio`` quantify what compression saved.
  On a two-level ``(slice, intra)`` mesh every record also lands on an
  interconnect tier ("ici"/"dcn"), so the per-tier rollup proves the
  hierarchical engines move 1/slice_size of the flat payload over DCN.

The two-level section below adds the multi-slice decomposition
(``hierarchical_psum`` / ``hierarchical_psum_scatter`` /
``hierarchical_all_gather``): intra-slice reduce-scatter, inter-slice psum
on the 1/slice_size chunk, intra-slice all-gather — bitwise-equal to the
flat path uncompressed, with independent per-tier wire compression.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from beforeholiday_tpu.monitor import comms
from beforeholiday_tpu.ops.arena import LANES
from beforeholiday_tpu.parallel.parallel_state import (
    DATA_AXIS,
    hierarchical_axes,
)

__all__ = [
    "BucketedReduce",
    "DEFAULT_BUCKET_BYTES",
    "bucket_slices",
    "bucketed_all_gather",
    "bucketed_psum",
    "bucketed_psum_scatter",
    "bucketed_tree_psum",
    "chunked_all_gather",
    "chunked_reduce_scatter",
    "compression_error_bound",
    "hierarchical_all_gather",
    "hierarchical_compression_error_bound",
    "hierarchical_psum",
    "hierarchical_psum_scatter",
    "n_buckets",
    "partition_leaves",
    "static_axis_size",
    "wire_eps",
]

# ~4 MiB: large enough that per-collective launch latency amortizes, small
# enough that several buckets are in flight while backward still computes
# (same sweet spot Apex and PyTorch DDP converged on: 25 MB default there is
# for NVLink-size links; ICI latency is lower, so buckets can be smaller)
DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024

# unit roundoff of the supported wire dtypes (2^-(mantissa_bits + 1))
_WIRE_EPS = {"bfloat16": 2.0 ** -8, "float16": 2.0 ** -11}


def wire_eps(wire_dtype: Any) -> float:
    """Unit roundoff of a supported wire dtype (bf16: 2^-8, fp16: 2^-11)."""
    name = np.dtype(wire_dtype).name
    try:
        return _WIRE_EPS[name]
    except KeyError:
        raise ValueError(
            f"unsupported wire dtype {name!r}; use bfloat16 or float16"
        ) from None


def compression_error_bound(sum_abs, wire_dtype: Any = jnp.bfloat16):
    """Elementwise analytic bound on ``|compressed_reduce - exact_reduce|``.

    ``sum_abs`` is ``psum(|x|)`` (the cross-rank sum of absolute values).
    Each rank's contribution rounds once on the wire (relative error <=
    ``wire_eps``), the accumulation is exact in fp32, and the all-reduce form
    adds one more wire rounding of the result — both effects are covered by
    ``2 * wire_eps * sum_abs``; the reduce-scatter form (result stays fp32)
    is within ``wire_eps * sum_abs``. This returns the looser all-reduce
    bound."""
    return 2.0 * wire_eps(wire_dtype) * sum_abs


def hierarchical_compression_error_bound(
    sum_abs,
    *,
    compress_intra: bool = False,
    compress_dcn: bool = False,
    wire_dtype: Any = jnp.bfloat16,
):
    """Composed elementwise bound for a two-level reduce with per-tier
    compression: ``|hierarchical_reduce - exact_reduce|``.

    Each compressed tier contributes the flat all-reduce budget — one wire
    rounding of its inputs plus one of its output, ``2 * wire_eps`` relative
    to ``sum_abs = psum(|x|)`` over the FULL (slice x intra) world. The tiers
    compose multiplicatively (the DCN stage re-rounds partials that already
    carry intra-tier error), so the bound is ``((1 + 2e)^k - 1) * sum_abs``
    with ``k`` the number of compressed tiers — first order ``2e`` per tier,
    exactly ``compression_error_bound`` when one tier compresses and neither
    tier compressing gives 0 (the uncompressed path is bitwise)."""
    eps = wire_eps(wire_dtype)
    factor = 1.0
    if compress_intra:
        factor *= 1.0 + 2.0 * eps
    if compress_dcn:
        factor *= 1.0 + 2.0 * eps
    return (factor - 1.0) * sum_abs


def static_axis_size(axis_name: Any) -> int:
    """The mesh axis size as a host Python int, inside a ``shard_map`` trace.

    A tuple spec (the two-level ``(slice, intra)`` convention) returns the
    product of the per-axis sizes — the flat world size."""
    if isinstance(axis_name, (tuple, list)):
        size = 1
        for ax in axis_name:
            size *= static_axis_size(ax)
        return size
    return jax.lax.axis_size(axis_name)


@functools.lru_cache(maxsize=4096)
def bucket_slices(
    n: int,
    itemsize: int,
    bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES,
    align: int = LANES,
) -> Tuple[Tuple[int, int], ...]:
    """Static (offset, length) covering ``[0, n)`` in ~``bucket_bytes`` steps.

    Offsets are multiples of ``align`` (LANES keeps arena slices on lane
    boundaries so the 2D row-view trick below applies); only the final bucket
    may be ragged. ``bucket_bytes=None`` means one bucket."""
    if n <= 0:
        raise ValueError(f"cannot bucket an empty payload (n={n})")
    if bucket_bytes is None:
        return ((0, n),)
    per = max(int(bucket_bytes) // int(itemsize), 1)
    per = max(per - per % align, align)
    return tuple((off, min(per, n - off)) for off in range(0, n, per))


def n_buckets(
    n_elements: int,
    itemsize: int,
    bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES,
) -> int:
    """How many buckets a payload splits into (for bench/ledger reporting)."""
    return len(bucket_slices(n_elements, itemsize, bucket_bytes))


def _slice_flat(flat, off: int, ln: int):
    # LANES-aligned slices go through a (rows, LANES) view: row slices of a
    # 2D array keep the TPU tiled layout trivial, where a large 1D slice can
    # force a relayout pass (same hazard ops.arena.unflatten documents)
    if off % LANES == 0 and ln % LANES == 0 and flat.shape[0] % LANES == 0:
        rows = flat.reshape(flat.shape[0] // LANES, LANES)
        piece = jax.lax.slice_in_dim(
            rows, off // LANES, (off + ln) // LANES, axis=0
        )
        return piece.reshape(ln)
    return jax.lax.slice_in_dim(flat, off, off + ln, axis=0)


def _logical(shape: Tuple[int, ...], dtype: Any) -> jax.ShapeDtypeStruct:
    # ledger stand-in for "what this payload would cost uncompressed" — a
    # ShapeDtypeStruct so no dead cast op enters the trace
    return jax.ShapeDtypeStruct(shape, dtype)


def _compressed_allreduce(x, axis_name, *, site: str, wire_dtype):
    """2-shot compressed all-reduce of a 1D bucket with fp32 accumulation.

    Phase 1 is a reduce-scatter spelled as ``all_to_all`` over a rank-major
    (world, chunk) view — spelling it that way is what lets each rank do the
    accumulation itself in fp32 (a compressed ``psum_scatter`` would round in
    the wire dtype at every reduction hop). Phase 2 re-shares the reduced
    chunks with one more wire cast. Returns fp32."""
    world = static_axis_size(axis_name)
    n = x.shape[0]
    chunk = -(-n // world)
    pad = chunk * world - n
    xp = jnp.pad(x, (0, pad)) if pad else x
    wire = xp.reshape(world, chunk).astype(wire_dtype)
    recv = comms.all_to_all(
        wire, axis_name, 0, 0, site=site,
        logical=_logical(wire.shape, x.dtype),
    )
    acc = jnp.sum(recv.astype(jnp.float32), axis=0)
    back = comms.all_gather(
        acc.astype(wire_dtype), axis_name, axis=0, tiled=True, site=site,
        logical=_logical(acc.shape, jnp.float32),
    )
    out = back.astype(jnp.float32)
    return out[:n] if pad else out


# ------------------------------------------------- two-level (slice x intra)
# The multi-slice decomposition: intra-slice reduce-scatter -> inter-slice
# (DCN) psum on 1/slice_size of the data -> intra-slice all-gather, the same
# hierarchy Apex's ``allreduce_communicators`` / NCCL trees exploit. Two
# contracts make the flat and hierarchical paths comparable:
#
# * **Deterministic flat spelling.** On a two-level axis spec the FLAT
#   uncompressed reduce is spelled as chained per-axis psums (intra tier
#   first, then slice) rather than one joint-axis collective. A joint
#   AllReduce's reduction order is XLA's choice (linear rank order on the CPU
#   backend) and NO two-level decomposition can reproduce it — partials over
#   the fast tier destroy the information an interleaved order needs. The
#   chained spelling pins the order to intra-linear-then-slice, which is
#   exactly the order the hierarchical path computes in, so hierarchical is
#   bitwise-equal to flat while still moving the FULL payload over the slow
#   tier (the contrast the ledger measures). Single-axis specs are untouched.
# * **Per-tier ledger booking.** Collectives over the slice axis book as
#   "dcn", everything else "ici" (``monitor.comms.infer_tier``), so
#   ``comms_summary()['by_tier']`` proves the hierarchical path's DCN bytes
#   are flat's / slice_size.


def _sized_axes(axes: Tuple[str, str]) -> Tuple[Tuple[str, int], ...]:
    """(axis, size) for the non-degenerate axes of a two-level spec, fast
    tier first (reduction order); size-1 axes drop out so degenerate meshes
    (slice_size=1 or n_slices=1) emit exactly the flat path's collectives."""
    slice_axis, intra_axis = axes
    out = []
    for ax in (intra_axis, slice_axis):
        size = static_axis_size(ax)
        if size > 1:
            out.append((ax, size))
    return tuple(out)


def _chained_psum(x, axes: Tuple[str, str], *, site: str):
    """Deterministic flat all-reduce over a two-level spec: psum the fast
    tier, then the slow one. ``x`` may be a leaf or a tuple of leaves (the
    variadic tree-group form)."""
    for ax, _ in _sized_axes(axes):
        x = comms.psum(x, ax, site=site)
    return x


def hierarchical_psum(
    flat,
    axes: Tuple[str, str],
    *,
    site: str,
    bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES,
    bucket_bytes_dcn: Optional[int] = None,
    compress_intra: bool = False,
    compress_dcn: bool = False,
    wire_dtype: Any = jnp.bfloat16,
):
    """Two-level all-reduce of a flat arena: per bucket, intra-slice
    reduce-scatter -> inter-slice psum on the 1/slice_size chunk -> intra
    all-gather. Only the chunk crosses DCN — the slow tier carries
    flat_bytes / slice_size.

    Uncompressed this is bitwise-equal to the flat chained psum (see the
    section comment). ``compress_intra`` sends the reduce-scatter and
    all-gather legs in ``wire_dtype``; ``compress_dcn`` compresses the
    inter-slice leg; accumulation stays fp32 on every tier and the
    composed error is within ``hierarchical_compression_error_bound``.
    Degenerate meshes (either axis size 1) collapse to the single-tier
    bucketed path with that tier's compression knob — no extra collectives.

    ``bucket_bytes_dcn`` sizes the DCN leg's collectives INDEPENDENTLY of
    the ICI leg's (``None`` = DCN follows the ICI buckets, one psum per
    bucket chunk — the historical behavior). DCN round-trip latency is
    orders of magnitude above ICI, so the slow tier wants FEWER, BIGGER
    collectives than the fast tier: the reduced 1/intra chunks of all ICI
    buckets are re-bucketed at ``bucket_bytes_dcn`` granularity (consecutive
    chunks concatenated, oversized runs split) and each re-bucket crosses
    DCN as one collective. The per-element reduction is unchanged —
    psum and the compressed exchange are both elementwise, so regrouping is
    bitwise-invisible; only the ledger's per-tier ``calls`` count moves."""
    if flat.ndim != 1:
        raise ValueError(
            f"hierarchical_psum wants a flat arena, got {flat.shape}"
        )
    slice_axis, intra_axis = axes
    sized = _sized_axes(axes)
    if len(sized) < 2:
        # one (or zero) real tiers: the flat bucketed path IS the
        # hierarchical one; keep the surviving tier's compression AND bucket
        # size knobs (a slice-only mesh's collectives all cross DCN)
        if not sized:
            return flat
        ax, _ = sized[0]
        on_dcn = ax == slice_axis
        return bucketed_psum(
            flat, ax, site=site,
            bucket_bytes=(
                bucket_bytes_dcn
                if on_dcn and bucket_bytes_dcn is not None else bucket_bytes
            ),
            compress=(compress_dcn if on_dcn else compress_intra),
            wire_dtype=wire_dtype,
        )
    intra = static_axis_size(intra_axis)
    slices = bucket_slices(flat.shape[0], flat.dtype.itemsize, bucket_bytes)

    def _dcn_reduce(x):
        if compress_dcn:
            return _compressed_allreduce(
                x, slice_axis, site=site, wire_dtype=wire_dtype
            )
        return comms.psum(x, slice_axis, site=site)

    # leg 1 (ICI): per-bucket reduce-scatter down to the 1/intra chunk
    reds = []
    pads = []
    for off, ln in slices:
        piece = _slice_flat(flat, off, ln)
        chunk = -(-ln // intra)
        pad = chunk * intra - ln
        xp = jnp.pad(piece, (0, pad)) if pad else piece
        if compress_intra:
            wire = xp.reshape(intra, chunk).astype(wire_dtype)
            recv = comms.all_to_all(
                wire, intra_axis, 0, 0, site=site,
                logical=_logical(wire.shape, piece.dtype),
            )
            red = jnp.sum(recv.astype(jnp.float32), axis=0)
        else:
            red = comms.psum_scatter(
                xp, intra_axis, scatter_dimension=0, tiled=True, site=site
            )
        reds.append(red)
        pads.append(pad)
    # leg 2 (DCN): reduce the chunks across slices, regrouped to the DCN
    # bucket size when one is set (elementwise -> bitwise-invariant)
    if bucket_bytes_dcn is None:
        reds = [_dcn_reduce(r) for r in reds]
    else:
        cat = reds[0] if len(reds) == 1 else jnp.concatenate(reds)
        parts = [
            _dcn_reduce(_slice_flat(cat, doff, dln))
            for doff, dln in bucket_slices(
                cat.shape[0], cat.dtype.itemsize, bucket_bytes_dcn
            )
        ]
        cat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        lens = [r.shape[0] for r in reds]
        reds, o = [], 0
        for ln in lens:
            reds.append(jax.lax.slice_in_dim(cat, o, o + ln, axis=0))
            o += ln
    # leg 3 (ICI): per-bucket all-gather back to full bucket width
    pieces = []
    for (off, ln), red, pad in zip(slices, reds, pads):
        if compress_intra:
            g = comms.all_gather(
                red.astype(wire_dtype), intra_axis, axis=0, tiled=True,
                site=site, logical=_logical(red.shape, jnp.float32),
            )
        else:
            g = comms.all_gather(
                red, intra_axis, axis=0, tiled=True, site=site
            )
        out = (
            g.astype(flat.dtype)
            if (compress_intra or compress_dcn) else g
        )
        pieces.append(out[:ln] if pad else out)
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def hierarchical_psum_scatter(
    flat,
    axes: Tuple[str, str],
    *,
    site: str,
    bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES,
    compress_intra: bool = False,
    compress_dcn: bool = False,
    wire_dtype: Any = jnp.bfloat16,
    concat: bool = True,
):
    """Two-level reduce-scatter of a (world*shard,) arena into this rank's
    (shard,) piece, shard ownership identical to the flat path (rank
    ``slice * slice_size + intra`` owns shard ``r`` — the slice-major mesh
    order). Per shard-column bucket: reorder the rank-major view
    intra-major, reduce-scatter over the intra tier (each intra rank is left
    holding the per-slice partials of its slice_size-th of the column), then
    reduce-scatter the 1/slice_size remainder over DCN. Bucketing and
    ``concat=False`` semantics match ``bucketed_psum_scatter``."""
    world = static_axis_size(axes)
    total = flat.shape[0]
    if flat.ndim != 1 or total % world:
        raise ValueError(
            f"hierarchical_psum_scatter wants a flat arena divisible by the "
            f"world size, got shape {flat.shape} over world={world}"
        )
    slice_axis, intra_axis = axes
    sized = _sized_axes(axes)
    if len(sized) < 2:
        if not sized:
            return flat if concat else [flat]
        ax, _ = sized[0]
        return bucketed_psum_scatter(
            flat, ax, site=site, bucket_bytes=bucket_bytes,
            compress=(compress_dcn if ax == slice_axis else compress_intra),
            wire_dtype=wire_dtype, concat=concat,
        )
    n_slices = static_axis_size(slice_axis)
    intra = static_axis_size(intra_axis)
    shard = total // world
    mat = flat.reshape(world, shard)
    slices = bucket_slices(shard, flat.dtype.itemsize * world, bucket_bytes)
    pieces = []
    for off, ln in slices:
        col = jax.lax.slice_in_dim(mat, off, off + ln, axis=1)
        # (world, ln) rank-major -> (intra, n_slices, ln): intra rank i's
        # scatter chunk is the per-slice stack of destination rows
        # (s*intra + i for every s), so the second-stage DCN scatter lands
        # each rank exactly its flat-path shard
        im = jnp.transpose(col.reshape(n_slices, intra, ln), (1, 0, 2))
        if compress_intra:
            wire = im.reshape(intra, n_slices * ln).astype(wire_dtype)
            recv = comms.all_to_all(
                wire, intra_axis, 0, 0, site=site,
                logical=_logical(wire.shape, flat.dtype),
            )
            red = jnp.sum(recv.astype(jnp.float32), axis=0)
        else:
            red = comms.psum_scatter(
                im.reshape(intra * n_slices * ln), intra_axis,
                scatter_dimension=0, tiled=True, site=site,
            )
        if compress_dcn:
            wire = red.reshape(n_slices, ln).astype(wire_dtype)
            recv = comms.all_to_all(
                wire, slice_axis, 0, 0, site=site,
                logical=_logical(wire.shape, flat.dtype),
            )
            piece = jnp.sum(recv.astype(jnp.float32), axis=0)
        else:
            piece = comms.psum_scatter(
                red, slice_axis, scatter_dimension=0, tiled=True, site=site
            )
        if compress_intra or compress_dcn:
            piece = piece.astype(flat.dtype)
        pieces.append(piece)
    if not concat:
        return pieces
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def hierarchical_all_gather(
    shard,
    axes: Tuple[str, str],
    *,
    site: str,
    bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES,
    logical_dtype: Any = None,
):
    """Two-level all-gather of per-rank (shard,) pieces into the rank-major
    (world*shard,) arena: gather over the slice (DCN) tier first — each rank
    ships only its own shard across the slow link — then over the intra tier,
    and un-interleave back to slice-major rank order. Bitwise-identical to
    the flat joint-axis gather (gathers move data, no arithmetic)."""
    world = static_axis_size(axes)
    if shard.ndim != 1:
        raise ValueError(
            f"hierarchical_all_gather wants a flat shard, got {shard.shape}"
        )
    slice_axis, intra_axis = axes
    sized = _sized_axes(axes)
    if len(sized) < 2:
        if not sized:
            return shard
        return bucketed_all_gather(
            shard, sized[0][0], site=site, bucket_bytes=bucket_bytes,
            logical_dtype=logical_dtype,
        )
    n_slices = static_axis_size(slice_axis)
    intra = static_axis_size(intra_axis)
    n = shard.shape[0]
    slices = bucket_slices(n, shard.dtype.itemsize, bucket_bytes)
    parts = []
    for off, ln in slices:
        piece = _slice_flat(shard, off, ln)
        logical = (
            None if logical_dtype is None
            else _logical(piece.shape, logical_dtype)
        )
        ga = comms.all_gather(
            piece, slice_axis, axis=0, tiled=True, site=site, logical=logical
        )
        gb = comms.all_gather(
            ga, intra_axis, axis=0, tiled=True, site=site,
            logical=None if logical_dtype is None
            else _logical(ga.shape, logical_dtype),
        )
        # (intra, n_slices, ln) -> slice-major (world, ln) rank order
        parts.append(
            jnp.transpose(gb.reshape(intra, n_slices, ln), (1, 0, 2)).reshape(
                world, ln
            )
        )
    if len(parts) == 1:
        return parts[0].reshape(world * n)
    return jnp.concatenate(parts, axis=1).reshape(world * n)


def bucketed_psum(
    flat,
    axis_name: Any,
    *,
    site: str,
    bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES,
    compress: bool = False,
    wire_dtype: Any = jnp.bfloat16,
):
    """All-reduce a flat (1D) arena in independent per-bucket collectives.

    Uncompressed buckets are plain ``psum`` slices — bitwise identical to the
    monolithic ``psum`` regardless of bucket size. ``compress=True`` sends
    each bucket over the wire in ``wire_dtype`` with fp32 accumulation (see
    module docstring for the error bound) and returns in the input dtype.

    On a two-level ``(slice, intra)`` spec the uncompressed reduce is spelled
    as chained per-axis psums — full payload on BOTH tiers, deterministic
    intra-then-slice order (see the two-level section comment) — making this
    the flat baseline ``hierarchical_psum`` is bitwise-equal to."""
    if flat.ndim != 1:
        raise ValueError(f"bucketed_psum wants a flat arena, got {flat.shape}")
    axes = hierarchical_axes(axis_name)
    if not compress and bucket_bytes is None:
        if axes is not None:
            return _chained_psum(flat, axes, site=site)
        return comms.psum(flat, axis_name, site=site)
    slices = bucket_slices(flat.shape[0], flat.dtype.itemsize, bucket_bytes)
    pieces = []
    for off, ln in slices:
        piece = _slice_flat(flat, off, ln)
        if compress:
            piece = _compressed_allreduce(
                piece, axis_name, site=site, wire_dtype=wire_dtype
            ).astype(flat.dtype)
        elif axes is not None:
            piece = _chained_psum(piece, axes, site=site)
        else:
            piece = comms.psum(piece, axis_name, site=site)
        pieces.append(piece)
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def bucketed_psum_scatter(
    flat,
    axis_name: Any,
    *,
    site: str,
    bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES,
    compress: bool = False,
    wire_dtype: Any = jnp.bfloat16,
    concat: bool = True,
):
    """Reduce-scatter a (world*shard,) arena into this rank's (shard,) piece.

    Bucketing runs along SHARD columns of the rank-major (world, shard) view,
    so concatenating per-bucket results reconstructs the rank's contiguous
    shard — per-bucket collectives stay independent AND shard ownership stays
    contiguous (what the ZeRO-2 optimizer step indexes into). Compressed
    buckets do the all_to_all + local-fp32-sum exchange and never leave fp32
    on the reduction path (output cast back to the input dtype, a no-op for
    fp32 arenas).

    ``concat=False`` returns the per-bucket pieces as a list (in shard
    order, geometry ``bucket_slices(shard, itemsize * world, bucket_bytes)``)
    instead of concatenating — the optimizer-in-backward path consumes each
    bucket as it lands, and the concat at the end of *its* consumers would
    otherwise serialize every bucket behind the slowest one.

    On a two-level ``(slice, intra)`` spec the uncompressed form is spelled
    as the chained all-reduce plus a local shard slice — the deterministic
    full-DCN-payload flat baseline ``hierarchical_psum_scatter`` is
    bitwise-equal to (a joint-axis reduce-scatter's order is XLA's choice;
    see the two-level section comment)."""
    world = static_axis_size(axis_name)
    total = flat.shape[0]
    if flat.ndim != 1 or total % world:
        raise ValueError(
            f"bucketed_psum_scatter wants a flat arena divisible by the axis "
            f"size, got shape {flat.shape} over world={world}"
        )
    axes = hierarchical_axes(axis_name)
    if not compress and bucket_bytes is None and axes is None:
        whole = comms.psum_scatter(
            flat, axis_name, scatter_dimension=0, tiled=True, site=site
        )
        return whole if concat else [whole]
    shard = total // world
    mat = flat.reshape(world, shard)
    # a shard column costs world*itemsize wire bytes, so budget per column
    slices = bucket_slices(shard, flat.dtype.itemsize * world, bucket_bytes)
    pieces = []
    for off, ln in slices:
        col = jax.lax.slice_in_dim(mat, off, off + ln, axis=1)
        if compress:
            wire = col.astype(wire_dtype)
            recv = comms.all_to_all(
                wire, axis_name, 0, 0, site=site,
                logical=_logical(wire.shape, flat.dtype),
            )
            piece = jnp.sum(recv.astype(jnp.float32), axis=0).astype(
                flat.dtype
            )
        elif axes is not None:
            full = _chained_psum(col.reshape(world * ln), axes, site=site)
            rank = jax.lax.axis_index(tuple(axes))
            piece = jax.lax.dynamic_slice_in_dim(full, rank * ln, ln)
        else:
            piece = comms.psum_scatter(
                col.reshape(world * ln), axis_name, scatter_dimension=0,
                tiled=True, site=site,
            )
        pieces.append(piece)
    if not concat:
        return pieces
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def bucketed_all_gather(
    shard,
    axis_name: Any,
    *,
    site: str,
    bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES,
    logical_dtype: Any = None,
):
    """All-gather per-rank (shard,) pieces into the rank-major (world*shard,).

    Issued as independent per-bucket gathers (the double-buffering the ZeRO
    param re-materialization wants: XLA can overlap bucket k's gather with
    bucket k-1's consumer). The caller owns any wire cast — pass
    ``logical_dtype`` so the ledger still knows the uncompressed cost."""
    world = static_axis_size(axis_name)
    n = shard.shape[0]
    logical = (
        None if logical_dtype is None
        else _logical(shard.shape, logical_dtype)
    )
    if shard.ndim != 1:
        raise ValueError(
            f"bucketed_all_gather wants a flat shard, got {shard.shape}"
        )
    slices = bucket_slices(n, shard.dtype.itemsize, bucket_bytes)
    if len(slices) == 1:
        return comms.all_gather(
            shard, axis_name, axis=0, tiled=True, site=site, logical=logical
        )
    parts = []
    for off, ln in slices:
        piece = _slice_flat(shard, off, ln)
        g = comms.all_gather(
            piece, axis_name, axis=0, tiled=True, site=site,
            logical=None if logical_dtype is None
            else _logical(piece.shape, logical_dtype),
        )
        parts.append(g.reshape(world, ln))
    # concatenating along the chunk axis of the (world, ln) views restores
    # rank-major order, exactly matching the monolithic tiled gather
    return jnp.concatenate(parts, axis=1).reshape(world * n)


# --------------------------------------------------------- ND chunked forms
# For the tensor-parallel mappings: same independence argument, but over an
# arbitrary gather/scatter dimension of an activation tensor instead of a
# flat arena. Both are bitwise-equal to their monolithic counterparts.


def chunked_all_gather(
    x,
    axis_name: Any,
    *,
    site: str,
    dim: int = 0,
    chunk_bytes: int = DEFAULT_BUCKET_BYTES,
):
    """Tiled ``all_gather`` along ``dim``, issued as independent chunks."""
    world = static_axis_size(axis_name)
    dim = dim % x.ndim
    n = x.shape[dim]
    row_bytes = (x.size // n) * x.dtype.itemsize
    slices = bucket_slices(n, row_bytes, chunk_bytes, align=1)
    if len(slices) == 1:
        return comms.all_gather(x, axis_name, axis=dim, tiled=True, site=site)
    parts = []
    for off, ln in slices:
        piece = jax.lax.slice_in_dim(x, off, off + ln, axis=dim)
        g = comms.all_gather(piece, axis_name, axis=dim, tiled=True, site=site)
        parts.append(
            g.reshape(g.shape[:dim] + (world, ln) + g.shape[dim + 1:])
        )
    cat = jnp.concatenate(parts, axis=dim + 1)
    return cat.reshape(
        cat.shape[:dim] + (world * n,) + cat.shape[dim + 2:]
    )


def chunked_reduce_scatter(
    x,
    axis_name: Any,
    *,
    site: str,
    dim: int = 0,
    chunk_bytes: int = DEFAULT_BUCKET_BYTES,
):
    """Tiled ``psum_scatter`` along ``dim``, issued as independent chunks."""
    world = static_axis_size(axis_name)
    dim = dim % x.ndim
    total = x.shape[dim]
    if total % world:
        raise ValueError(
            f"scatter dim {dim} (size {total}) not divisible by "
            f"world={world}"
        )
    n = total // world
    row_bytes = (x.size // total) * x.dtype.itemsize * world
    slices = bucket_slices(n, row_bytes, chunk_bytes, align=1)
    if len(slices) == 1:
        return comms.psum_scatter(
            x, axis_name, scatter_dimension=dim, tiled=True, site=site
        )
    x2 = x.reshape(x.shape[:dim] + (world, n) + x.shape[dim + 1:])
    parts = []
    for off, ln in slices:
        piece = jax.lax.slice_in_dim(x2, off, off + ln, axis=dim + 1)
        flatp = piece.reshape(
            piece.shape[:dim] + (world * ln,) + piece.shape[dim + 2:]
        )
        parts.append(
            comms.psum_scatter(
                flatp, axis_name, scatter_dimension=dim, tiled=True,
                site=site,
            )
        )
    return jnp.concatenate(parts, axis=dim)


# -------------------------------------------------------------- tree grads
# The DDP path for grads that are still a pytree (not an arena): group leaves
# into ~bucket_bytes chunks and reduce each group with ONE collective — a
# variadic psum (single multi-operand AllReduce) when uncompressed, a packed
# compressed exchange otherwise.


def partition_leaves(
    leaves: Sequence[Any],
    bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES,
) -> List[List[int]]:
    """Greedy dtype-uniform partition of leaf indices into byte-budgeted
    groups (a leaf larger than the budget gets its own group; order within a
    dtype is preserved). ``bucket_bytes=None`` -> one group per dtype."""
    order = sorted(
        range(len(leaves)),
        key=lambda i: str(np.dtype(jnp.result_type(leaves[i]))),
    )
    groups: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_dt = None
    for i in order:
        dt = np.dtype(jnp.result_type(leaves[i]))
        nb = int(np.prod(jnp.shape(leaves[i]))) * dt.itemsize
        if cur and (
            dt != cur_dt
            or (bucket_bytes is not None and cur_bytes + nb > bucket_bytes)
        ):
            groups.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
        cur_dt = dt
    if cur:
        groups.append(cur)
    return groups


def bucketed_tree_psum(
    leaves: Sequence[Any],
    axis_name: Any,
    *,
    site: str,
    bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES,
    bucket_bytes_dcn: Optional[int] = None,
    compress: bool = False,
    wire_dtype: Any = jnp.bfloat16,
    hierarchical: bool = False,
    compress_intra: bool = False,
    compress_dcn: bool = False,
) -> List[Any]:
    """All-reduce a leaf list group-by-group; returns reduced leaves in the
    original order/dtypes. Non-float groups always go uncompressed. On a
    two-level axis spec the uncompressed groups reduce via the chained
    per-axis psum (the deterministic flat spelling); ``hierarchical=True``
    concatenates each float group and routes it through
    ``hierarchical_psum`` instead, with per-tier compression knobs (and the
    per-tier ``bucket_bytes_dcn`` DCN collective size)."""
    axes = hierarchical_axes(axis_name)
    if hierarchical and axes is None:
        raise ValueError(
            "hierarchical=True needs a (slice, intra) axis spec; got "
            f"{axis_name!r}"
        )
    out: List[Any] = [None] * len(leaves)
    for group in partition_leaves(leaves, bucket_bytes):
        sub = [leaves[i] for i in group]
        dt = np.dtype(jnp.result_type(sub[0]))
        # jnp.issubdtype, not np: ml_dtypes (bfloat16) sit outside numpy's
        # type lattice — a bf16 grad group still wants fp32 accumulation
        is_float = jnp.issubdtype(dt, jnp.floating)
        if (compress or hierarchical) and is_float:
            flat = (
                sub[0].reshape(-1) if len(sub) == 1
                else jnp.concatenate([x.reshape(-1) for x in sub])
            )
            if hierarchical:
                red = hierarchical_psum(
                    flat, axes, site=site, bucket_bytes=None,
                    bucket_bytes_dcn=bucket_bytes_dcn,
                    compress_intra=compress_intra, compress_dcn=compress_dcn,
                    wire_dtype=wire_dtype,
                )
            else:
                red = _compressed_allreduce(
                    flat, axis_name, site=site, wire_dtype=wire_dtype
                )
            off = 0
            for i, x in zip(group, sub):
                sz = int(np.prod(jnp.shape(x))) or 1
                piece = jax.lax.slice_in_dim(red, off, off + sz)
                out[i] = piece.reshape(jnp.shape(x)).astype(dt)
                off += sz
        elif axes is not None:
            red = _chained_psum(tuple(sub), axes, site=site)
            for i, r in zip(group, red):
                out[i] = r
        else:
            red = comms.psum(tuple(sub), axis_name, site=site)
            for i, r in zip(group, red):
                out[i] = r
    return out


@dataclasses.dataclass(frozen=True)
class BucketedReduce:
    """Bundled bucketing policy — the knob object DDP/ZeRO layers carry.

    ``bucket_bytes=None`` disables splitting (monolithic collectives);
    ``compress=True`` turns on wire-dtype compression with fp32
    accumulation. ``hierarchical=True`` (needs a two-level
    ``(slice, intra)`` ``axis_name``) routes reduces through the two-level
    engines — ``compress_intra``/``compress_dcn`` then compress each tier
    independently (both default to ``compress`` when left ``None``), and
    ``bucket_bytes_dcn`` sizes the DCN leg's collectives independently of
    the ICI leg's (DCN wants bigger buckets — see ``hierarchical_psum``;
    ``None`` keeps the one-DCN-psum-per-ICI-bucket behavior)."""

    axis_name: Any = DATA_AXIS
    bucket_bytes: Optional[int] = DEFAULT_BUCKET_BYTES
    bucket_bytes_dcn: Optional[int] = None
    compress: bool = False
    wire_dtype: Any = jnp.bfloat16
    hierarchical: bool = False
    compress_intra: Optional[bool] = None
    compress_dcn: Optional[bool] = None

    def __post_init__(self):
        if self.hierarchical and hierarchical_axes(self.axis_name) is None:
            raise ValueError(
                "hierarchical=True needs a (slice, intra) axis spec; got "
                f"{self.axis_name!r}"
            )
        if self.bucket_bytes_dcn is not None and not self.hierarchical:
            raise ValueError(
                "bucket_bytes_dcn is a two-level knob; set hierarchical=True"
            )

    def _tier_compress(self) -> Tuple[bool, bool]:
        ci = self.compress if self.compress_intra is None else (
            self.compress_intra
        )
        cd = self.compress if self.compress_dcn is None else self.compress_dcn
        return ci, cd

    def psum(self, flat, *, site: str = "bucketed.psum"):
        if self.hierarchical:
            ci, cd = self._tier_compress()
            return hierarchical_psum(
                flat, hierarchical_axes(self.axis_name), site=site,
                bucket_bytes=self.bucket_bytes,
                bucket_bytes_dcn=self.bucket_bytes_dcn, compress_intra=ci,
                compress_dcn=cd, wire_dtype=self.wire_dtype,
            )
        return bucketed_psum(
            flat, self.axis_name, site=site, bucket_bytes=self.bucket_bytes,
            compress=self.compress, wire_dtype=self.wire_dtype,
        )

    def psum_scatter(self, flat, *, site: str = "bucketed.psum_scatter"):
        if self.hierarchical:
            ci, cd = self._tier_compress()
            return hierarchical_psum_scatter(
                flat, hierarchical_axes(self.axis_name), site=site,
                bucket_bytes=self.bucket_bytes, compress_intra=ci,
                compress_dcn=cd, wire_dtype=self.wire_dtype,
            )
        return bucketed_psum_scatter(
            flat, self.axis_name, site=site, bucket_bytes=self.bucket_bytes,
            compress=self.compress, wire_dtype=self.wire_dtype,
        )

    def all_gather(
        self, shard, *, site: str = "bucketed.all_gather",
        logical_dtype: Any = None,
    ):
        if self.hierarchical:
            return hierarchical_all_gather(
                shard, hierarchical_axes(self.axis_name), site=site,
                bucket_bytes=self.bucket_bytes, logical_dtype=logical_dtype,
            )
        return bucketed_all_gather(
            shard, self.axis_name, site=site,
            bucket_bytes=self.bucket_bytes, logical_dtype=logical_dtype,
        )

    def tree_psum(self, leaves, *, site: str = "bucketed.tree_psum"):
        ci, cd = self._tier_compress()
        return bucketed_tree_psum(
            leaves, self.axis_name, site=site,
            bucket_bytes=self.bucket_bytes,
            bucket_bytes_dcn=self.bucket_bytes_dcn, compress=self.compress,
            wire_dtype=self.wire_dtype, hierarchical=self.hierarchical,
            compress_intra=ci, compress_dcn=cd,
        )

    def n_buckets(self, n_elements: int, itemsize: int) -> int:
        return n_buckets(n_elements, itemsize, self.bucket_bytes)
