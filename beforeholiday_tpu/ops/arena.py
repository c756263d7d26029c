"""Tensor-list arena — the TPU equivalent of ``apex_C`` flatten/unflatten.

The reference packs up to 110 raw CUDA pointers per kernel launch
(ref: csrc/multi_tensor_apply.cuh:16-26, ``TensorListMetadata``) and exposes
``apex_C.flatten``/``unflatten`` (ref: csrc/flatten_unflatten.cpp:1-18) for DDP
bucketing. Pointer lists do not exist under XLA; the TPU-native design (SURVEY.md
§7 "hard parts") is a *flat HBM arena*: every tensor list is flattened once into a
single 1D buffer padded to the TPU lane/sublane tiling, and every multi-tensor
kernel runs over the arena with one grid. Per-tensor boundaries are kept as a
*static* offset table (shapes are static under jit), so unflattening is a set of
slices XLA fuses into consumers.

Views of one flat buffer also make ZeRO-style sharding trivial: shard the arena
itself over the ``data`` axis (ref: apex/contrib/optimizers/distributed_fused_adam.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# TPU native tiling: last dim is always 128 lanes; fp32 sublane is 8.
# Pad every arena to a multiple of the multi-tensor kernel block (256 rows x 128
# lanes = 32768 elements) so the Pallas grid needs no remainder handling — the
# reference's chunk size 2048*32 plays the same role
# (csrc/multi_tensor_apply.cuh:44-58). Worst-case waste is 128 KiB fp32.
LANES = 128
SUBLANES = 8
TILE = 256 * LANES  # one kernel block


@dataclasses.dataclass(frozen=True)
class ArenaSpec:
    """Static metadata describing how a tensor list is packed into a flat buffer."""

    shapes: Tuple[Tuple[int, ...], ...]
    offsets: Tuple[int, ...]  # start offset of each tensor in the flat buffer
    total: int  # sum of tensor sizes (unpadded)
    padded_total: int  # total rounded up to a TILE multiple

    @property
    def num_tensors(self) -> int:
        return len(self.shapes)

    def segment_ids(self) -> np.ndarray:
        """int32[padded_total] mapping every arena element to its tensor index.

        Padding elements map to ``num_tensors`` (an extra, discarded segment) so
        per-tensor reductions (LAMB/LARS/NovoGrad trust ratios, per-tensor
        l2norm — ref: csrc/multi_tensor_l2norm_kernel.cu per-tensor outputs) are
        one ``segment_sum`` over the arena. Cached per spec — LAMB queries it
        three times per eager step and the table is O(arena).

        Under jit prefer static slicing (multi_tensor.per_tensor_sumsq) or
        :func:`segment_ids_of` (ZeRO shards): this host table becomes an
        O(arena)-byte CONSTANT baked into the compiled program (a 46M-param
        LAMB step ships ~186 MB of table per use — the cause of the r03
        compile-payload blowup on mid-size BERT).
        """
        return _segment_ids_cached(self)


@functools.lru_cache(maxsize=8)  # entries are O(arena) bytes — keep the cache tiny
def _segment_ids_cached(spec: "ArenaSpec") -> np.ndarray:
    ids = np.full((spec.padded_total,), spec.num_tensors, dtype=np.int32)
    for i, (off, shape) in enumerate(zip(spec.offsets, spec.shapes)):
        n = int(np.prod(shape)) if shape else 1
        ids[off : off + n] = i
    ids.setflags(write=False)  # shared across callers
    return ids


def segment_ids_of(spec: ArenaSpec, idx: jax.Array) -> jax.Array:
    """Owning-tensor index for each (possibly dynamic) arena position in
    ``idx``; positions >= spec.total map to ``num_tensors`` (padding segment).

    Implemented as a broadcast compare-and-sum against the static boundary
    list — ``seg[i] = #{j : boundary_j <= idx[i]}`` — which XLA fuses into one
    pass. NOT ``jnp.searchsorted``: its scan carry is an (N, 2) array whose
    size-2 trailing dim TPU tiling pads to 128 lanes (64x memory, 21 GB on a
    42M arena — the compile-time OOM this replaced).
    """
    sizes = [int(np.prod(s)) if s else 1 for s in spec.shapes]
    cum = np.cumsum(sizes, dtype=np.int64)
    if spec.padded_total >= 2**31:
        # int32 boundaries (and int32 idx positions, which legitimately span
        # the PADDED arena — the ZeRO shard path indexes up to padded_total-1)
        # silently wrap past 2^31 elements
        raise ValueError(
            f"arena spans {spec.padded_total} padded elements, >= 2**31 — "
            "segment_ids_of's int32 positions would overflow; split into "
            "smaller arenas"
        )
    boundaries = jnp.asarray(cum, dtype=jnp.int32)
    return jnp.sum(
        idx[:, None] >= boundaries[None, :], axis=1, dtype=jnp.int32
    )


@functools.lru_cache(maxsize=4096)
def _spec_of_shapes(shapes: Tuple[Tuple[int, ...], ...]) -> ArenaSpec:
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    offsets = tuple(int(x) for x in np.cumsum([0] + sizes[:-1]))
    total = int(sum(sizes))
    padded_total = ((total + TILE - 1) // TILE) * TILE if total else TILE
    return ArenaSpec(shapes=shapes, offsets=offsets, total=total, padded_total=padded_total)


def make_spec(tensors: Sequence[jax.Array]) -> ArenaSpec:
    """Spec for a tensor list. Memoized on the shape tuple, so every caller
    with the same layout shares ONE ArenaSpec object — repeated steps never
    re-run the cumsum, and per-spec caches downstream (``_segment_ids_cached``,
    the per-tensor-norm machinery) hit on identity, not just equality."""
    return _spec_of_shapes(tuple(tuple(t.shape) for t in tensors))


def flatten(tensors: Sequence[jax.Array], dtype=None) -> Tuple[jax.Array, ArenaSpec]:
    """Pack a tensor list into one flat padded 1D buffer.

    TPU analogue of ``apex_C.flatten`` (ref: csrc/flatten_unflatten.cpp:6-9).
    All tensors must share a dtype unless ``dtype`` forces a cast — the reference
    likewise buckets by dtype before flattening (apex/parallel/distributed.py:241-244).
    """
    if not tensors:
        raise ValueError("flatten() requires a non-empty tensor list")
    spec = make_spec(tensors)
    if dtype is None:
        dtype = tensors[0].dtype
        for t in tensors:
            if t.dtype != dtype:
                raise ValueError(
                    f"mixed dtypes in arena ({t.dtype} vs {dtype}); bucket by dtype "
                    "first (ref: apex/parallel/distributed.py:241-244) or pass dtype="
                )
    flat = jnp.concatenate([jnp.ravel(t).astype(dtype) for t in tensors])
    pad = spec.padded_total - spec.total
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), dtype=dtype)])
    return flat, spec


def unflatten(flat: jax.Array, spec: ArenaSpec, dtype=None) -> List[jax.Array]:
    """Slice a flat arena back into the original tensor list.

    TPU analogue of ``apex_C.unflatten`` (ref: csrc/flatten_unflatten.cpp:11-14).
    Slices are static, so XLA fuses them into consumers — no materialized copy.

    Slicing happens through a (rows, 128) 2D view, NOT directly on the 1D
    array: the TPU compiler rewrites large-1D-array slicing into an
    (N/2, 2)-shaped intermediate whose size-2 trailing dim tiling pads 64x —
    a silent 11.7 GB hidden buffer at 46M params and a compile-time HBM OOM
    at 84M (BERT-large). Row-sliced 2D views lower cleanly; only the final
    tensor-sized trim is a 1D op.
    """
    out = []
    use_2d = flat.shape[0] % LANES == 0
    rows2d = flat.reshape(-1, LANES) if use_2d else None
    for off, shape in zip(spec.offsets, spec.shapes):
        n = int(np.prod(shape)) if shape else 1
        if use_2d:
            r0, r1 = off // LANES, (off + n + LANES - 1) // LANES
            piece = jax.lax.dynamic_slice_in_dim(rows2d, r0, r1 - r0).reshape(-1)
            piece = jax.lax.dynamic_slice_in_dim(piece, off - r0 * LANES, n)
            piece = piece.reshape(shape)
        else:
            piece = jax.lax.dynamic_slice_in_dim(flat, off, n).reshape(shape)
        if dtype is not None:
            piece = piece.astype(dtype)
        out.append(piece)
    return out


@functools.lru_cache(maxsize=256)
def _packer(shapes, dtype_names, out_dtype_name):
    """Jitted pack executable, memoized on (shapes, dtypes, out dtype).

    Eager callers of :func:`tree_flatten_arena` hit a compiled concat+pad
    instead of dispatching O(leaves) ops per step; under an outer jit the
    nested call is a cached sub-jaxpr XLA inlines. This is the "never
    re-trace the pack" half of the treeapi fix (the other half is the
    view-path optimizer step that skips packing entirely)."""
    spec = _spec_of_shapes(shapes)
    dtype = jnp.dtype(out_dtype_name or dtype_names[0])

    @jax.jit
    def pack(leaves):
        flat = (
            jnp.ravel(leaves[0]).astype(dtype) if len(leaves) == 1
            else jnp.concatenate([jnp.ravel(t).astype(dtype) for t in leaves])
        )
        pad = spec.padded_total - spec.total
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), dtype=dtype)])
        return flat

    return pack, spec


def tree_flatten_arena(tree: Any, dtype=None):
    """Flatten an arbitrary pytree of arrays into (arena, spec, treedef).

    The pack executable and the spec are memoized on (shapes, dtypes) —
    repeated steps over the same model never re-derive offsets or re-trace
    the concatenation."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        raise ValueError("tree_flatten_arena() requires a non-empty tree")
    dtype_names = tuple(jnp.dtype(t.dtype).name for t in leaves)
    if dtype is None and len(set(dtype_names)) > 1:
        raise ValueError(
            f"mixed dtypes in arena ({sorted(set(dtype_names))}); bucket by "
            "dtype first (ref: apex/parallel/distributed.py:241-244) or "
            "pass dtype="
        )
    pack, spec = _packer(
        tuple(tuple(t.shape) for t in leaves),
        dtype_names,
        jnp.dtype(dtype).name if dtype is not None else None,
    )
    return pack(leaves), spec, treedef


def tree_unflatten_arena(flat: jax.Array, spec: ArenaSpec, treedef, dtype=None):
    return jax.tree_util.tree_unflatten(treedef, unflatten(flat, spec, dtype=dtype))


def views_to_arena(pieces: Sequence[jax.Array], spec: ArenaSpec, dtype=None) -> jax.Array:
    """Reassemble per-tensor pieces into a flat padded arena — the inverse of
    :func:`unflatten` and the write half of the pack-free "view path": the
    optimizer computes each leaf's update against an arena VIEW, and one
    fused concatenate writes the new arena in a single pass (XLA fuses the
    elementwise producers into the concat; nothing materializes per leaf)."""
    if len(pieces) != len(spec.shapes):
        raise ValueError(
            f"{len(pieces)} pieces for a {len(spec.shapes)}-tensor spec"
        )
    if dtype is None:
        dtype = pieces[0].dtype
    parts = [jnp.ravel(p).astype(dtype) for p in pieces]
    pad = spec.padded_total - spec.total
    if pad:
        parts.append(jnp.zeros((pad,), dtype=dtype))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def as_rows(flat: jax.Array) -> jax.Array:
    """View a padded flat arena as (rows, LANES) for lane-aligned kernels."""
    assert flat.shape[0] % LANES == 0, "arena must be padded to LANES"
    return flat.reshape(-1, LANES)


# ---------------------------------------------------------------------------------
# PackedParams — arena-NATIVE parameter storage (grads born flat)
# ---------------------------------------------------------------------------------


def bucket_by_dtype(leaves: Sequence[jax.Array]):
    """Partition leaf indices into per-dtype buckets, sorted by dtype name —
    THE bucketing contract shared by :class:`PackedParams` and
    ``MasterWeights``'s arena mode (gradient arenas must align
    bucket-for-bucket with master/optimizer-state arenas, so both sides call
    this one function). Rejects non-floating leaves: an int leaf flattened
    into an fp32 arena would be optimizer-updated and written back truncated
    — silent corruption (the tree path skips non-floats via cast_floats)."""
    buckets: dict = {}
    for i, p in enumerate(leaves):
        if not jnp.issubdtype(p.dtype, jnp.floating):
            raise ValueError(
                f"cannot pack non-floating leaf #{i} (dtype {p.dtype}) into "
                "a parameter arena; keep integer leaves out of the optimized "
                "tree"
            )
        buckets.setdefault(jnp.dtype(p.dtype), []).append(i)
    return sorted(buckets.items(), key=lambda kv: kv[0].name)


@dataclasses.dataclass(frozen=True)
class PackedLayout:
    """Static layout of a params pytree packed into per-dtype arenas.

    Hashable (all-static) so it can ride a pytree aux_data / jit static arg.
    Buckets are sorted by dtype name — the same order ``MasterWeights``'s
    arena mode uses, so packed grads align bucket-for-bucket with the
    optimizer's master/state arenas.
    """

    treedef: Any
    dtypes: Tuple[Any, ...]  # one jnp.dtype per bucket
    indices: Tuple[Tuple[int, ...], ...]  # leaf indices per bucket
    specs: Tuple[ArenaSpec, ...]  # arena spec per bucket
    n_leaves: int


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _leaf_views(arena_buf: jax.Array, spec: ArenaSpec) -> Tuple[jax.Array, ...]:
    """``unflatten`` whose transpose is the pack (``PackedParams.unpack``)."""
    return tuple(unflatten(arena_buf, spec))


def _leaf_views_fwd(arena_buf, spec):
    # the custom_vjp itself, not unflatten: higher orders pack once too
    return _leaf_views(arena_buf, spec), None


def _leaf_views_bwd(spec, _, cotangents):
    return (views_to_arena(cotangents, spec),)


_leaf_views.defvjp(_leaf_views_fwd, _leaf_views_bwd)


@jax.tree_util.register_pytree_node_class
class PackedParams:
    """A params pytree stored as per-dtype flat HBM arenas.

    The arena-native answer to the reference's aliased tensor lists
    (ref: csrc/multi_tensor_apply.cuh:19-147 — CUDA kernels walk raw pointers
    into the ORIGINAL storage, so the optimizer never repacks). Under XLA
    there is no aliasing, so the equivalent is to make the flat arena the
    source of truth: the model's parameters ARE the arenas, ``unpack()``
    produces the leaf views (static slices XLA fuses into consumers), and
    ``jax.grad`` of a loss taken at a ``PackedParams`` argument returns the
    gradient ARENAS directly.

    What "born flat" costs: the transpose of ``unpack()`` is ONE pack per
    dtype bucket — a ``concatenate`` of the leaf cotangents and the zero tail,
    the buffer ``flatten`` builds — which the chip runs as in-place copies
    into one allocation (3.8 ms a step for gpt2-medium's 0.71 GB). The
    overflow check and the fused optimizers' ``step_flat`` then stream that
    buffer. The slices' own transpose is ``add_any(pad(g0), …, pad(gn))``:
    on one chip XLA never materialised that sum and re-evaluated it inside
    each of its five arena-wide consumers, 35 ms a step (PR 25).

    Registered as a pytree: arenas are the children (traced), the layout is
    static aux data. Works as a jit/grad argument transparently.
    """

    __slots__ = ("arenas", "layout")

    def __init__(self, arenas: Sequence[jax.Array], layout: PackedLayout):
        self.arenas = tuple(arenas)
        self.layout = layout

    def tree_flatten(self):
        return self.arenas, self.layout

    @classmethod
    def tree_unflatten(cls, layout, arenas):
        return cls(arenas, layout)

    @classmethod
    def pack(cls, tree: Any) -> "PackedParams":
        """One-time pack (init/checkpoint-load boundary, never per-step)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        arenas, dtypes, indices, specs = [], [], [], []
        for dtype, idx in bucket_by_dtype(leaves):
            flat, spec = flatten([leaves[i] for i in idx])
            arenas.append(flat)
            dtypes.append(dtype)
            indices.append(tuple(idx))
            specs.append(spec)
        layout = PackedLayout(
            treedef=treedef, dtypes=tuple(dtypes), indices=tuple(indices),
            specs=tuple(specs), n_leaves=len(leaves),
        )
        return cls(arenas, layout)

    def unpack(self) -> Any:
        """Rebuild the leaf pytree as static slices of the arenas.

        The values are ``unflatten``'s slices through the ``(rows, 128)``
        view, which fuse into their consumers. The transpose is not the
        slices' own (see the class docstring) but a ``jax.custom_vjp``: the
        cotangent of an arena is one pack of the leaf cotangents, zeros for
        an unused leaf and for the tail. Reverse mode of any order works;
        ``jax.jvp`` through ``unpack`` raises JAX's "can't apply forward-mode
        autodiff (jvp) to a custom_vjp function". (``lax.split``, whose
        built-in transpose is the same ``concatenate``, keeps forward mode
        but costs 4.3 ms a step on the chip: XLA lays the whole arena out
        twice more, as rows of two leaf widths, to cut it — PR 25.)
        """
        lay = self.layout
        leaves: List[Any] = [None] * lay.n_leaves
        for arena_buf, idx, spec in zip(self.arenas, lay.indices, lay.specs):
            for i, piece in zip(idx, _leaf_views(arena_buf, spec)):
                leaves[i] = piece
        return jax.tree_util.tree_unflatten(lay.treedef, leaves)

    def replace_arenas(self, arenas: Sequence[jax.Array]) -> "PackedParams":
        if len(arenas) != len(self.arenas):
            raise ValueError(
                f"expected {len(self.arenas)} arenas, got {len(arenas)}"
            )
        return PackedParams(arenas, self.layout)
