"""ZeRO-3 fully-sharded engine: the params arena itself is sharded.

ZeRO stage 3 (Rajbhandari et al., 2020; FSDP; the pipelined param gather of
apex/contrib/optimizers/distributed_fused_adam.py:1071-1076 taken to its
conclusion): ZeRO-2 (``distributed_fused.py``) shards the optimizer state but
still replicates the params — so after PR 5 cut activation temps, the
params+masters arena dominates peak memory. Here each rank holds ONLY its
1/world TILE-aligned slice of the flat fp32 master arena; that shard is the
single persistent copy of the model. Forward materializes params transiently:

    params   = gather_params(master_shard)      # bucketed all-gather,
                                                #   one-bucket-ahead prefetch
    grads    = (gather_params' custom_vjp)      # bucketed psum_scatter of the
                                                #   cotangent INTO the shard
    state'   = step(grad_shard, state)          # fused Adam on the shard only

``gather_params`` is a ``jax.custom_vjp`` (the PR-7 hook idiom): its forward
issues one independent all-gather per ~``bucket_bytes`` bucket of the shard
and rebuilds each param leaf from ONLY the bucket stripes that cover it — so
a leaf's consumers are dataflow-ready the moment its buckets land, and XLA's
latency-hiding scheduler runs bucket k+1's gather under bucket k's layer
(``prefetch`` bounds how many gathers may be in flight via an
``optimization_barrier`` chain; ``prefetch=0`` degrades to the blocking
concat-join form, where every consumer waits for the whole arena). Its
backward flattens the param cotangents and ``bucketed_psum_scatter``s them
straight into this rank's fp32 grad shard — no full-size grad arena ever
exists. Uncompressed, the whole pipeline is bitwise-equal to ZeRO-2 on the
same inputs: gathers move bits, the scatter shares ZeRO-2's exact bucket
geometry and fp32 flatten, and the fused update is the same kernel on the
same shard.

Param residency: gathered leaves are tagged ``zero3_gathered``
(``remat.policies.ZERO3_GATHERED_TAG``). Under the ``"zero3_regather"``
policy (``param_residency="regather"`` + wrapping the loss in
``wrap_residency``/``remat.apply``) the gathered arena is non-saveable:
backward re-runs the bucketed gather instead of holding a full param copy
across forward+backward — FSDP's ``reshard_after_forward``.
``param_residency="keep"`` skips the wrap; autodiff keeps the gathered
leaves resident (more memory, half the gather traffic).

Sharded checkpointing: ``state_dict(layout, state, gather_on_root=False)``
returns the raw shard; ``shard_manifest``/``save_shard_files`` persist one
``.npz`` per rank plus a JSON layout manifest of
``(arena_len, world, shard_len, pad)``. ``reshard_state`` restores at a
DIFFERENT world size by concatenating the saved shards back into the flat
arena and re-slicing — save at world=8, restore at 4/2/1, bitwise. All
host I/O here runs between steps; the traced paths never read back to the
host (``tests/test_no_host_sync.py`` scans this file).

Ledger sites are ``zero3.*`` (``gather_params``, ``reduce_scatter_grads``,
``found_inf``, ``gather_state``) — ``monitor.comms.comms_summary`` rolls
them up as their own subsystem.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from beforeholiday_tpu.monitor import comms
from beforeholiday_tpu.ops import multi_tensor as mt
from beforeholiday_tpu.ops.arena import (
    TILE, ArenaSpec, _spec_of_shapes, flatten, unflatten,
)
from beforeholiday_tpu.optimizers.distributed_fused import (
    DistributedFusedAdam, _pad_to, _shard_len,
)
from beforeholiday_tpu.parallel import bucketing
from beforeholiday_tpu.parallel.parallel_state import (
    DATA_AXIS,
    hierarchical_axes,
)
from beforeholiday_tpu.remat.policies import ZERO3_GATHERED_TAG

__all__ = [
    "ZeRO3FusedAdam",
    "ZeRO3FusedLAMB",
    "Zero3Layout",
    "layout_of",
    "shard_manifest",
    "shards_from_stacked",
    "save_shard_files",
    "load_shard_files",
    "reshard_state",
    "manifest_hosts",
    "host_rank_range",
    "host_manifest_path",
    "effective_hosts",
]

_MANIFEST_NAME = "manifest.json"
_MANIFEST_FORMAT = "zero3-shard-v1"
_STATE_KEYS = ("master", "exp_avg", "exp_avg_sq")


@dataclasses.dataclass(frozen=True)
class Zero3Layout:
    """Static description of the sharded model: tree structure + leaf
    shapes/dtypes. Hashable, so the gather's ``custom_vjp`` closure is built
    once per layout (no recompile churn — same contract as the PR-7 hooks)."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]

    @property
    def spec(self) -> ArenaSpec:
        return _spec_of_shapes(self.shapes)


def layout_of(params) -> Zero3Layout:
    """Layout from a params pytree (arrays or ``jax.ShapeDtypeStruct``s —
    only shapes/dtypes/structure are read, never values)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return Zero3Layout(
        treedef=treedef,
        shapes=tuple(tuple(l.shape) for l in leaves),
        dtypes=tuple(np.dtype(l.dtype).name for l in leaves),
    )


def _bucket_of(slices: Tuple[Tuple[int, int], ...], q: int) -> int:
    for k, (off, ln) in enumerate(slices):
        if off <= q < off + ln:
            return k
    raise AssertionError(f"shard offset {q} outside bucket cover {slices}")


@functools.lru_cache(maxsize=4096)
def _stripe_plan(
    layout: Zero3Layout, shard: int, slices: Tuple[Tuple[int, int], ...],
) -> Tuple[Tuple[Tuple[int, int, int, int], ...], ...]:
    """Per-leaf static segment plan over the gathered bucket stripes.

    Bucket k's gather lands as a (world, ln_k) block: row r holds arena
    positions ``[r*shard + off_k, r*shard + off_k + ln_k)``. A leaf spanning
    arena ``[o, o+n)`` is the ordered concatenation of ``(k, r, start, len)``
    segments — split at rank-stripe and bucket boundaries. Pure host
    arithmetic on the static geometry."""
    spec = layout.spec
    plans = []
    for off_leaf, shape in zip(spec.offsets, layout.shapes):
        n = int(np.prod(shape)) if shape else 1
        segs = []
        pos, end = off_leaf, off_leaf + n
        while pos < end:
            r, q = divmod(pos, shard)
            k = _bucket_of(slices, q)
            off_k, ln_k = slices[k]
            take = min(end - pos, (r + 1) * shard - pos, off_k + ln_k - q)
            segs.append((k, r, q - off_k, take))
            pos += take
        plans.append(tuple(segs))
    return tuple(plans)


@functools.lru_cache(maxsize=256)
def _gather_fn(
    axis_name: Any,
    layout: Zero3Layout,
    bucket_bytes: Optional[int],
    prefetch: int,
    gather_wire: str,
    compress: bool,
    scatter_wire: str,
    site_prefix: str,
    hierarchical: bool = False,
    compress_intra: bool = False,
    compress_dcn: bool = False,
):
    """Build the (cached) custom_vjp param gather for one static config.

    Forward: prefetched bucketed all-gather of the master shard, leaves
    rebuilt per-bucket-stripe (or the blocking concat form for prefetch=0).
    Backward: flatten the param cotangents to the fp32 arena and
    ``bucketed_psum_scatter`` into this rank's grad shard — ZeRO-2's exact
    ``_reduce_scatter_grads`` op sequence, so grads match it bitwise.
    ``hierarchical`` swaps both directions for the two-level engines
    (slice-tier gather first / two-level scatter), so only 1/slice_size of
    the arena crosses DCN each way."""
    spec = layout.spec
    gather_site = f"{site_prefix}.gather_params"
    grad_site = f"{site_prefix}.reduce_scatter_grads"
    wire_dt = jnp.dtype(gather_wire)
    axes = hierarchical_axes(axis_name) if hierarchical else None

    def _impl(master_shard):
        world = bucketing.static_axis_size(axis_name)
        shard = master_shard.shape[0]
        wire = (
            master_shard if master_shard.dtype == wire_dt
            else master_shard.astype(wire_dt)
        )
        # ledger: account the uncompressed (master-dtype) cost when a
        # narrower dtype rides the wire
        logical = (
            None if wire.dtype == master_shard.dtype else master_shard.dtype
        )
        slices = bucketing.bucket_slices(
            shard, wire.dtype.itemsize, bucket_bytes
        )
        if prefetch <= 0 or len(slices) == 1:
            # blocking form: the concat joins every bucket, so no consumer
            # starts before the whole arena has landed
            if hierarchical:
                full = bucketing.hierarchical_all_gather(
                    wire, axes, site=gather_site,
                    bucket_bytes=bucket_bytes, logical_dtype=logical,
                )
            else:
                full = bucketing.bucketed_all_gather(
                    wire, axis_name, site=gather_site,
                    bucket_bytes=bucket_bytes, logical_dtype=logical,
                )
            pieces = unflatten(full[: spec.padded_total], spec)
            return tuple(
                p.astype(dt) for p, dt in zip(pieces, layout.dtypes)
            )
        # slice every bucket's wire piece up front: the slices depend only
        # on the shard, so no gather's INPUT ever sits in program order
        # behind another gather's output (that false dependency would
        # serialize the gather queue)
        pieces = [bucketing._slice_flat(wire, o, n) for o, n in slices]
        gathered = []
        for k, piece in enumerate(pieces):
            if k > prefetch:
                # depth chain: bucket k's gather may not launch until bucket
                # k-prefetch-1's has landed — at most prefetch+1 gathered
                # buckets in flight, bounding transient residency
                piece, _ = jax.lax.optimization_barrier(
                    (piece, gathered[k - prefetch - 1])
                )
            # kept flat (world*ln,): stripes are indexed directly, so the
            # only op between a bucket landing and its consumers is the
            # per-segment slice
            if hierarchical:
                gathered.append(bucketing.hierarchical_all_gather(
                    piece, axes, site=gather_site, bucket_bytes=None,
                    logical_dtype=logical,
                ))
            else:
                gathered.append(comms.all_gather(
                    piece, axis_name, axis=0, tiled=True, site=gather_site,
                    logical=None if logical is None
                    else jax.ShapeDtypeStruct(piece.shape, logical),
                ))
        plans = _stripe_plan(layout, shard, slices)
        leaves = []
        for segs, shape, dt in zip(plans, layout.shapes, layout.dtypes):
            parts = [
                jax.lax.slice(
                    gathered[k],
                    (r * slices[k][1] + s,),
                    (r * slices[k][1] + s + ln,),
                )
                for k, r, s, ln in segs
            ]
            flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            leaves.append(flat.reshape(shape).astype(dt))
        return tuple(leaves)

    @jax.custom_vjp
    def gather(master_shard):
        return _impl(master_shard)

    def _fwd(master_shard):
        return _impl(master_shard), None

    def _bwd(_, cts):
        world = bucketing.static_axis_size(axis_name)
        shard = _shard_len(spec.padded_total, world)
        gflat, _ = flatten([jnp.asarray(c) for c in cts], dtype=jnp.float32)
        gflat = _pad_to(gflat, shard * world)
        if hierarchical:
            g = bucketing.hierarchical_psum_scatter(
                gflat, axes, site=grad_site, bucket_bytes=bucket_bytes,
                compress_intra=compress_intra, compress_dcn=compress_dcn,
                wire_dtype=jnp.dtype(scatter_wire),
            )
        else:
            g = bucketing.bucketed_psum_scatter(
                gflat, axis_name, site=grad_site, bucket_bytes=bucket_bytes,
                compress=compress, wire_dtype=jnp.dtype(scatter_wire),
            )
        return (g,)

    gather.defvjp(_fwd, _bwd)
    return gather


class ZeRO3FusedAdam(DistributedFusedAdam):
    """Fully-sharded AdamW: the fp32 master shard is the only param copy.

    Train-step shape (inside ``shard_map`` with the data axis bound)::

        layout = zero3.layout_of(params_template)
        state  = opt.init(params)                  # once, from full params

        def loss_fn(master_shard):
            params = opt.gather_params(master_shard, layout)
            return loss(params, batch)

        loss_fn = opt.wrap_residency(loss_fn)      # "regather" residency
        loss, g = jax.value_and_grad(loss_fn)(state["master"])
        state   = opt.step(g, state)               # g is already the shard

    ``g`` arrives as the fp32 reduce-scattered SUM over ranks (the gather's
    custom_vjp did the collective); ``step`` applies grad averaging/scaling
    and the fused kernel exactly as ZeRO-2's sharded step does."""

    _site_prefix = "zero3"

    def __init__(
        self,
        lr: float = 1e-3,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-8,
        *,
        adam_w_mode: bool = True,
        weight_decay: float = 0.0,
        bias_correction: bool = True,
        axis_name: Any = DATA_AXIS,
        grad_average: bool = True,
        bucket_bytes: Optional[int] = bucketing.DEFAULT_BUCKET_BYTES,
        compress: bool = False,
        wire_dtype: Any = jnp.bfloat16,
        overlap_backward: bool = False,
        hierarchical: bool = False,
        compress_intra: Optional[bool] = None,
        compress_dcn: Optional[bool] = None,
        impl: Optional[str] = None,
        prefetch: int = 1,
        param_residency: str = "regather",
    ):
        super().__init__(
            lr, betas, eps, adam_w_mode=adam_w_mode,
            weight_decay=weight_decay, bias_correction=bias_correction,
            axis_name=axis_name, grad_average=grad_average,
            bucket_bytes=bucket_bytes, compress=compress,
            wire_dtype=wire_dtype, overlap_backward=overlap_backward,
            hierarchical=hierarchical, compress_intra=compress_intra,
            compress_dcn=compress_dcn, impl=impl,
        )
        if prefetch < 0:
            raise ValueError(f"prefetch must be >= 0, got {prefetch}")
        if param_residency not in ("regather", "keep"):
            raise ValueError(
                f"param_residency must be 'regather' or 'keep', "
                f"got {param_residency!r}"
            )
        self.prefetch = prefetch
        self.param_residency = param_residency

    # ---- forward-side param materialization --------------------------------

    def _gather_wire(self, layout: Zero3Layout) -> str:
        """Wire dtype for the param gather: the common leaf dtype when the
        model is dtype-uniform (a bf16 model gathers bf16 — casting the fp32
        master before vs after the gather is bitwise the same cast, so
        ZeRO-2 parity survives), otherwise fp32; ``compress`` forces
        ``wire_dtype``."""
        if self.compress or (self.hierarchical and any(self._tier_compress())):
            return np.dtype(self.wire_dtype).name
        if len(set(layout.dtypes)) == 1:
            return layout.dtypes[0]
        return "float32"

    def gather_params(self, master_shard, layout: Zero3Layout):
        """Transient full-precision params from this rank's master shard.

        Differentiable: the custom VJP reduce-scatters the param cotangents
        into the fp32 grad shard (``zero3.reduce_scatter_grads``)."""
        ci, cd = self._tier_compress()
        fn = _gather_fn(
            self.axis_name
            if hierarchical_axes(self.axis_name) is None
            else hierarchical_axes(self.axis_name),
            layout, self.bucket_bytes, self.prefetch,
            self._gather_wire(layout), self.compress,
            np.dtype(self.wire_dtype).name, self._site_prefix,
            bool(self.hierarchical), ci, cd,
        )
        leaves = fn(master_shard)
        if self.param_residency == "regather":
            leaves = tuple(
                checkpoint_name(l, ZERO3_GATHERED_TAG) for l in leaves
            )
        return jax.tree_util.tree_unflatten(layout.treedef, list(leaves))

    def residency_policy(self) -> str:
        """Remat-policy name matching ``param_residency`` ("none" = keep)."""
        return "zero3_regather" if self.param_residency == "regather" else "none"

    def wrap_residency(self, fn):
        """Wrap a loss function so ``param_residency`` takes effect: under
        "regather" the gathered arena is non-saveable and backward re-runs
        the bucketed gather; under "keep" this is the identity."""
        from beforeholiday_tpu.remat import policies as remat_policies

        return remat_policies.apply(fn, self.residency_policy())

    # ---- sharded update ----------------------------------------------------

    def step(self, grads, state, *, found_inf=None, grad_scale=1.0, lr=None):
        """Fused AdamW on the shard. ``grads`` is the fp32 (shard,) SUM over
        ranks — the cotangent ``jax.grad`` returns for ``gather_params``'
        master input. No full params are built here: the next forward's
        gather reads the updated master."""
        lr = self.lr if lr is None else lr
        g = jnp.asarray(grads)
        if g.ndim != 1 or g.shape[0] != state["master"].shape[0]:
            raise ValueError(
                f"ZeRO3FusedAdam.step wants the reduce-scattered grad shard "
                f"(shape {state['master'].shape}), got {g.shape}; pass the "
                "gradient w.r.t. gather_params' master_shard input"
            )
        # same order as ZeRO-2: scatter (already done in the VJP) -> /world
        # -> *grad_scale -> global overflow flag
        if self.grad_average:
            g = g / self._world()
        g = g * grad_scale
        flag = self._global_found_inf(g, found_inf)
        step_no = jnp.where(flag, state["step"], state["step"] + 1)

        if self.overlap_backward and self.bucket_bytes is not None:
            # per-chunk update, ZeRO-2's _step_overlap geometry: slicing
            # commutes with the elementwise kernel, so this stays bitwise
            # equal to the phased form
            slices = bucketing.bucket_slices(
                g.shape[0], 4 * self._world(), self.bucket_bytes,
            )
            chunks = [bucketing._slice_flat(g, o, n) for o, n in slices]
            masters = [
                bucketing._slice_flat(state["master"], o, n)
                for o, n in slices
            ]
            ms = [
                bucketing._slice_flat(state["exp_avg"], o, n)
                for o, n in slices
            ]
            vs = [
                bucketing._slice_flat(state["exp_avg_sq"], o, n)
                for o, n in slices
            ]
            p2, m2, v2 = mt.multi_tensor_adam(
                chunks, masters, ms, vs,
                lr=lr, beta1=self.betas[0], beta2=self.betas[1],
                eps=self.eps, step=step_no, adam_w_mode=self.adam_w_mode,
                bias_correction=self.bias_correction,
                weight_decay=self.weight_decay, found_inf=flag,
                impl=self.impl,
            )
            master2 = p2[0] if len(p2) == 1 else jnp.concatenate(p2)
            exp_avg2 = m2[0] if len(m2) == 1 else jnp.concatenate(m2)
            exp_avg_sq2 = v2[0] if len(v2) == 1 else jnp.concatenate(v2)
        else:
            [master2], [exp_avg2], [exp_avg_sq2] = mt.multi_tensor_adam(
                [g], [state["master"]], [state["exp_avg"]],
                [state["exp_avg_sq"]],
                lr=lr, beta1=self.betas[0], beta2=self.betas[1],
                eps=self.eps, step=step_no, adam_w_mode=self.adam_w_mode,
                bias_correction=self.bias_correction,
                weight_decay=self.weight_decay, found_inf=flag,
                impl=self.impl,
            )
        return {
            "master": master2, "exp_avg": exp_avg2,
            "exp_avg_sq": exp_avg_sq2, "step": step_no,
        }

    # ---- checkpointing -----------------------------------------------------

    def state_dict(self, layout: Zero3Layout, state, *,
                   gather_on_root: bool = True):
        """Checkpointable state. Runs INSIDE shard_map.

        ``gather_on_root=True`` all-gathers each shard into full per-tensor
        pytrees (identical on every rank under SPMD). ``False`` returns the
        local shard verbatim — pair with ``shard_manifest`` +
        ``save_shard_files`` for the per-rank sharded checkpoint."""
        if not gather_on_root:
            return dict(state)
        spec = layout.spec
        out = {"step": state["step"]}
        for key in ("master",) + self._state_keys():
            out[key] = jax.tree_util.tree_unflatten(
                layout.treedef, [
                    p.astype(jnp.float32)
                    for p in self._gather_full(state[key], spec)
                ]
            )
        return out

    def load_state_dict(self, layout: Zero3Layout, state_dict):
        """Inverse of ``state_dict``: accepts either the gathered full
        per-tensor trees (re-sharded onto this rank) or flat (shard,) arrays
        as produced by ``gather_on_root=False`` / ``reshard_state``."""
        shard = _shard_len(layout.spec.padded_total, self._world())
        state = {"step": jnp.asarray(state_dict["step"], jnp.int32)}
        for key in ("master",) + self._state_keys():
            val = state_dict[key]
            leaves = jax.tree_util.tree_leaves(val)
            structure = jax.tree_util.tree_structure(val)
            if (
                structure == layout.treedef
                and tuple(tuple(l.shape) for l in leaves) == layout.shapes
            ):
                state[key] = self._shard_of(leaves, shard)
            else:
                arr = jnp.asarray(val, jnp.float32)
                if arr.shape != (shard,):
                    raise ValueError(
                        f"state_dict[{key!r}] is neither a full param tree "
                        f"nor a (shard,) array for this topology: got shape "
                        f"{arr.shape}, want ({shard},) — reshard with "
                        "zero3.reshard_state first"
                    )
                state[key] = arr
        return state


class ZeRO3FusedLAMB:
    """Not implemented — fail loudly instead of silently serializing.

    LAMB's per-tensor trust ratios need full per-tensor norms (segment
    partial sums + cross-shard psum over the WHOLE arena) between the grad
    reduce-scatter and ANY slice's update — a full-shard barrier that
    defeats the prefetched-gather pipeline this engine exists for."""

    def __init__(self, *args, **kwargs):
        raise NotImplementedError(
            "ZeRO3FusedLAMB is not implemented: LAMB's per-tensor trust "
            "ratios are a whole-arena barrier between the grad "
            "reduce-scatter and the sharded update, which defeats the "
            "ZeRO-3 prefetched-gather pipeline; use ZeRO3FusedAdam, or "
            "DistributedFusedLAMB (ZeRO-2, phased step) for sharded LAMB"
        )


# ---- host-side sharded checkpoint I/O (between steps, never traced) --------


def shard_manifest(
    layout: Zero3Layout,
    world: int,
    *,
    state_keys: Sequence[str] = _STATE_KEYS,
    hosts: int = 1,
) -> Dict[str, Any]:
    """Layout manifest persisted next to the shard files: everything needed
    to validate and reshard the flat arena at a different world size.

    ``manifest_version`` 2 adds the multi-host partition (``hosts``): ranks
    are split contiguously across ``hosts`` simulated hosts, each of which
    writes only its own shard subset plus a per-host manifest. Version-1
    manifests (no ``hosts``/``manifest_version`` keys) load with
    ``hosts=1`` defaults — the single-host layout is byte-identical to
    PR 12's."""
    spec = layout.spec
    shard = _shard_len(spec.padded_total, world)
    if hosts < 1:
        raise ValueError(f"hosts must be >= 1, got {hosts}")
    if world % hosts:
        raise ValueError(
            f"hosts={hosts} must divide world={world} (contiguous rank "
            "partition)"
        )
    return {
        "format": _MANIFEST_FORMAT,
        "manifest_version": 2,
        "arena_len": spec.padded_total,
        "total": spec.total,
        "world": world,
        "shard_len": shard,
        "pad": shard * world - spec.padded_total,
        "tile": TILE,
        "state_keys": list(state_keys),
        "hosts": hosts,
    }


def manifest_hosts(manifest: Dict[str, Any]) -> int:
    """Host count declared by a manifest; version-1 manifests (PR 12) carry
    no ``hosts`` key and default to 1."""
    return int(manifest.get("hosts", 1))


def host_rank_range(world: int, hosts: int, host: int) -> range:
    """Contiguous rank subset owned by ``host``: with ``world=8, hosts=2``,
    host 0 writes ranks 0..3 and host 1 writes ranks 4..7 (mirrors how a
    real multi-host slice pins ranks to hosts)."""
    if not 0 <= host < hosts:
        raise ValueError(f"host {host} out of range for hosts={hosts}")
    if world % hosts:
        raise ValueError(f"hosts={hosts} must divide world={world}")
    per = world // hosts
    return range(host * per, (host + 1) * per)


def effective_hosts(world: int, hosts: int) -> int:
    """Largest host count ≤ ``hosts`` that divides ``world`` — the partition
    a resized world keeps writing with (a shrink 8→4 under ``hosts=2``
    stays 2-host; a shrink to world=1 degrades to 1 host, never fails)."""
    if hosts < 1:
        raise ValueError(f"hosts must be >= 1, got {hosts}")
    for h in range(min(hosts, world), 0, -1):
        if world % h == 0:
            return h
    return 1  # pragma: no cover — h=1 always divides


def host_manifest_path(directory: str, host: int) -> str:
    """Per-host durability stamp: ``host_<h>.manifest.json``. Presence means
    this host's shard subset landed completely (each host stamps AFTER its
    shards, mirroring the top-level manifest-last rule)."""
    return os.path.join(directory, f"host_{host:03d}.manifest.json")


def shards_from_stacked(stacked, world: int) -> List[Dict[str, np.ndarray]]:
    """Split a rank-stacked state dict (arrays of shape (world, shard), e.g.
    from running ``state_dict(gather_on_root=False)`` with
    ``out_specs=P(axis)``) into per-rank host dicts for
    ``save_shard_files``."""
    out = []
    for r in range(world):
        d = {}
        for k, v in stacked.items():
            a = np.asarray(v)
            d[k] = a if k == "step" and a.ndim == 0 else a[r]
        out.append(d)
    return out


def _shard_path(directory: str, rank: int) -> str:
    return os.path.join(directory, f"shard_{rank:05d}.npz")


# rename seam: the kill-mid-save drill (tests/test_elastic.py) hooks this to
# SIGKILL the writer between file landings and prove the previous checkpoint
# generation still loads
_rename = os.replace


def _atomic_write(path: str, write_fn) -> None:
    """Write via temp file + fsync + atomic rename: ``path`` either holds
    the COMPLETE new contents or does not exist — never a torn file."""
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        write_fn(f)
        f.flush()
        os.fsync(f.fileno())
    _rename(tmp, path)


def _save_rank_shard(directory, rank, sd, manifest) -> None:
    for key in manifest["state_keys"]:
        arr = np.asarray(sd[key])
        if arr.shape != (manifest["shard_len"],):
            raise ValueError(
                f"shard {rank} key {key!r} has shape {arr.shape}, manifest "
                f"says ({manifest['shard_len']},)"
            )
    payload = {k: np.asarray(v) for k, v in sd.items()}
    _atomic_write(
        _shard_path(directory, rank),
        lambda f, p=payload: np.savez(f, **p),
    )


def save_shard_files(directory, shard_states, manifest) -> None:
    """Write one ``shard_{rank}.npz`` per rank, then ``manifest.json``.

    Crash-safe by construction: every file lands through
    :func:`_atomic_write` (temp file + fsync + atomic rename), and the
    manifest is stamped LAST — so a writer killed mid-save leaves stray
    ``*.tmp`` files and a manifest-less directory, never a loadable torn
    checkpoint. ``load_shard_files`` refuses a manifest-less directory and
    ``elastic.latest_generation`` falls back to the previous durable
    generation; manifest presence IS durability.

    With ``manifest["hosts"] > 1`` the write is partitioned like a real
    multi-host job: each simulated host writes ONLY its contiguous rank
    subset (:func:`host_rank_range`) and then stamps its own
    ``host_<h>.manifest.json``; the top-level manifest still lands last,
    after every host. Durability becomes two-level — a generation is
    restorable only when the top manifest AND every declared host manifest
    are present, so losing any single host's stamp (torn host) demotes the
    whole generation and restore falls back to the last generation durable
    on ALL hosts. ``hosts=1`` writes no host manifests: the on-disk layout
    is exactly the version-1 single-writer one."""
    if len(shard_states) != manifest["world"]:
        raise ValueError(
            f"got {len(shard_states)} shard states for manifest "
            f"world={manifest['world']}"
        )
    hosts = manifest_hosts(manifest)
    os.makedirs(directory, exist_ok=True)
    if hosts == 1:
        for r, sd in enumerate(shard_states):
            _save_rank_shard(directory, r, sd, manifest)
    else:
        for host in range(hosts):
            ranks = host_rank_range(manifest["world"], hosts, host)
            for r in ranks:
                _save_rank_shard(directory, r, shard_states[r], manifest)
            host_manifest = {
                "format": _MANIFEST_FORMAT,
                "manifest_version": manifest.get("manifest_version", 2),
                "host": host,
                "hosts": hosts,
                "world": manifest["world"],
                "ranks": list(ranks),
            }
            _atomic_write(
                host_manifest_path(directory, host),
                lambda f, m=host_manifest: f.write(
                    json.dumps(m, indent=1).encode("utf-8")
                ),
            )
    _atomic_write(
        os.path.join(directory, _MANIFEST_NAME),
        lambda f: f.write(json.dumps(manifest, indent=1).encode("utf-8")),
    )


def load_shard_files(directory):
    """Read back ``(manifest, [per-rank shard dicts])``, validating shard
    count, keys, and lengths — a missing or truncated shard file fails
    loudly instead of resharding garbage. Multi-host generations
    (``hosts > 1``) must additionally hold every declared host manifest:
    a torn host raises here and demotes the generation for
    ``elastic.latest_generation``'s fallback scan."""
    mpath = os.path.join(directory, _MANIFEST_NAME)
    if not os.path.exists(mpath):
        raise FileNotFoundError(
            f"no {_MANIFEST_NAME} in {directory!r} — not a ZeRO-3 sharded "
            "checkpoint"
        )
    with open(mpath) as f:
        manifest = json.load(f)
    if manifest.get("format") != _MANIFEST_FORMAT:
        raise ValueError(
            f"unknown manifest format {manifest.get('format')!r} "
            f"(want {_MANIFEST_FORMAT!r})"
        )
    hosts = manifest_hosts(manifest)
    if hosts > 1:
        missing = [
            h for h in range(hosts)
            if not os.path.exists(host_manifest_path(directory, h))
        ]
        if missing:
            raise FileNotFoundError(
                f"generation {directory!r} is torn: top-level manifest "
                f"declares hosts={hosts} but host manifest(s) "
                f"{missing} are missing — this generation is not durable "
                "on all hosts; restore from the previous fully-durable one"
            )
    shards = []
    for r in range(manifest["world"]):
        p = _shard_path(directory, r)
        if not os.path.exists(p):
            raise FileNotFoundError(
                f"missing shard file {p}: manifest declares "
                f"world={manifest['world']}"
            )
        with np.load(p) as z:
            d = {k: z[k] for k in z.files}
        for key in manifest["state_keys"]:
            if key not in d:
                raise ValueError(f"shard file {p} is missing key {key!r}")
            if d[key].shape != (manifest["shard_len"],):
                raise ValueError(
                    f"shard file {p} key {key!r} has shape {d[key].shape}, "
                    f"manifest says ({manifest['shard_len']},) — corrupted "
                    "or mismatched checkpoint"
                )
        shards.append(d)
    return manifest, shards


def reshard_state(
    shard_states, manifest, new_world: int,
) -> List[Dict[str, np.ndarray]]:
    """Re-slice saved shards for a different topology.

    Concatenate the per-rank shards back into the flat arena, truncate the
    old world's padding at ``arena_len``, re-pad for ``new_world``'s
    TILE-aligned shard, and slice per new rank. Padding regions are zeros on
    both sides (init zero-pads, and a zero-grad zero-master Adam update
    stays zero), so save-at-8/load-at-{4,2,1} round-trips bitwise."""
    arena_len = manifest["arena_len"]
    new_shard = _shard_len(arena_len, new_world)
    out: List[Dict[str, np.ndarray]] = [dict() for _ in range(new_world)]
    for key in manifest["state_keys"]:
        full = np.concatenate(
            [np.asarray(s[key]) for s in shard_states]
        )[:arena_len]
        pad = new_shard * new_world - arena_len
        if pad:
            full = np.concatenate(
                [full, np.zeros((pad,), full.dtype)]
            )
        for r in range(new_world):
            out[r][key] = full[r * new_shard:(r + 1) * new_shard]
    for r in range(new_world):
        out[r]["step"] = np.asarray(shard_states[0]["step"])
    return out
