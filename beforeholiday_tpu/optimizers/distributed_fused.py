"""ZeRO-2 sharded fused optimizers
(ref: apex/contrib/optimizers/distributed_fused_adam.py:19-35, distributed_fused_lamb.py).

The reference reduce-scatters flat grad buckets over the data-parallel group,
keeps fp32 optimizer state (master params, moments) only for the local shard,
runs the fused update on the shard, and all-gathers the updated params
(:691-724 reduce-scatter, :914 sharded step, :1071-1076 all-gather), with
communication overlapped on pipelined streams (:302).

TPU design over the flat arena: params flatten into one buffer padded so every
data-parallel rank owns an equal, TILE-aligned shard —

    g_shard  = psum_scatter(grad_arena)/world     (one ICI reduce-scatter)
    state    = {master, m, v} fp32, shard-sized   (1/world of the memory)
    update   = the same multi-tensor Adam/LAMB kernel, on the shard
    params   = all_gather(master_shard.astype(param_dtype))

XLA's latency-hiding scheduler overlaps the collectives with surrounding
compute — the stream pipelining the reference hand-builds. All functions run
inside ``shard_map`` with the data axis bound (``check_vma=False``), taking
*local unreduced* grads exactly like ``reduce_gradients``.

``bucket_bytes``/``compress`` split both transfers into independent
~bucket_bytes collectives (``parallel.bucketing``) — the XLA analogue of the
reference's pipelined reduce-scatter/all-gather streams (:302) — optionally
with a ``wire_dtype`` (bf16) on the wire and fp32 accumulation. Grads may
arrive as a ``PackedParams`` whose arena layout matches the params: then the
reduce-scatter consumes the flat arena directly, no per-step tree flatten.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from beforeholiday_tpu.monitor import comms
from beforeholiday_tpu.ops import multi_tensor as mt
from beforeholiday_tpu.ops.arena import (
    TILE, PackedParams, flatten, make_spec, unflatten,
)
from beforeholiday_tpu.parallel import bucketing
from beforeholiday_tpu.parallel.parallel_state import (
    DATA_AXIS,
    hierarchical_axes,
)


def _shard_len(total_padded: int, world: int) -> int:
    """Per-rank arena shard, TILE-aligned so the pallas kernels tile cleanly."""
    per = -(-total_padded // world)  # ceil
    return -(-per // TILE) * TILE


def _pad_to(flat: jax.Array, n: int) -> jax.Array:
    if flat.shape[0] == n:
        return flat
    return jnp.concatenate([flat, jnp.zeros((n - flat.shape[0],), flat.dtype)])


class _DistributedFused:
    """Shared arena/collective machinery for the sharded optimizers."""

    # comms-ledger site prefix; ``comms_summary`` rolls sites up by this, so
    # the ZeRO-3 subclass reports under ``zero3.*`` with the same machinery
    _site_prefix = "zero2"

    def __init__(
        self,
        *,
        axis_name: Any = DATA_AXIS,
        grad_average: bool = True,
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
        self.grad_average = grad_average
        self.bucket_bytes = bucket_bytes
        self.compress = compress
        self.wire_dtype = wire_dtype
        self.overlap_backward = overlap_backward
        self.hierarchical = hierarchical
        self.compress_intra = compress_intra
        self.compress_dcn = compress_dcn

    def _tier_compress(self) -> Tuple[bool, bool]:
        ci = self.compress if self.compress_intra is None else (
            self.compress_intra
        )
        cd = self.compress if self.compress_dcn is None else self.compress_dcn
        return bool(ci), bool(cd)

    def _world(self):
        return bucketing.static_axis_size(self.axis_name)

    def _arena_layout(self, params) -> Tuple[Any, Any, int, int]:
        leaves, treedef = jax.tree_util.tree_flatten(params)
        spec = make_spec(leaves)
        world = self._world()
        shard = _shard_len(spec.padded_total, world)
        return leaves, treedef, spec, shard

    def _shard_of(self, leaves, shard):
        """Flatten per-tensor leaves into the fp32 arena and slice THIS rank's
        TILE-aligned shard — the one layout used by init/load_state_dict."""
        flat, _ = flatten(leaves, dtype=jnp.float32)
        flat = _pad_to(flat, shard * self._world())
        rank = jax.lax.axis_index(self.axis_name)
        return jax.lax.dynamic_slice_in_dim(flat, rank * shard, shard)

    def _gather_full(self, shard_arr, spec):
        """all_gather a state shard back into full per-tensor pieces — the one
        inverse used by _gather_params/state_dict."""
        full = comms.all_gather(shard_arr, self.axis_name,
                                site=f"{self._site_prefix}.gather_state",
                                axis=0, tiled=True)
        return unflatten(full[: spec.padded_total], spec)

    def init(self, params):
        """Local fp32 state shard. Must run inside shard_map (data axis bound)."""
        leaves, treedef, spec, shard = self._arena_layout(params)
        state = {
            "master": self._shard_of(leaves, shard),
            "step": jnp.zeros((), jnp.int32),
        }
        for key in self._state_keys():
            state[key] = jnp.zeros((shard,), jnp.float32)
        return state

    def _reduce_scatter_grads(self, grads, spec, shard, *, concat=True):
        if isinstance(grads, PackedParams):
            lay = grads.layout
            if len(grads.arenas) == 1 and lay.specs[0].shapes == spec.shapes:
                # arena-native grads with the optimizer's own layout: the flat
                # buffer IS the reduce-scatter operand, zero per-step packing
                gflat = grads.arenas[0].astype(jnp.float32)
            else:
                # mixed-dtype packing orders leaves per dtype bucket — fall
                # back through the leaf views to restore params order
                gleaves = jax.tree_util.tree_leaves(grads.unpack())
                gflat, _ = flatten(gleaves, dtype=jnp.float32)
        else:
            gleaves = jax.tree_util.tree_leaves(grads)
            gflat, _ = flatten(gleaves, dtype=jnp.float32)
        gflat = _pad_to(gflat, shard * self._world())
        site = f"{self._site_prefix}.reduce_scatter_grads"
        if self.hierarchical:
            ci, cd = self._tier_compress()

            def _scatter(concat):
                return bucketing.hierarchical_psum_scatter(
                    gflat, hierarchical_axes(self.axis_name), site=site,
                    bucket_bytes=self.bucket_bytes, compress_intra=ci,
                    compress_dcn=cd, wire_dtype=self.wire_dtype,
                    concat=concat,
                )
        else:

            def _scatter(concat):
                return bucketing.bucketed_psum_scatter(
                    gflat, self.axis_name, site=site,
                    bucket_bytes=self.bucket_bytes, compress=self.compress,
                    wire_dtype=self.wire_dtype, concat=concat,
                )
        if not concat:
            # overlap path: keep the per-bucket pieces separate so each
            # bucket's consumer (its slice of the fused update) can start
            # the moment that bucket's reduce-scatter lands — the geometry
            # is bucket_slices(shard, 4 * world, bucket_bytes), fp32 arena
            chunks = _scatter(False)
            if self.grad_average:
                chunks = [c / self._world() for c in chunks]
            return chunks
        g_shard = _scatter(True)
        if self.grad_average:
            g_shard = g_shard / self._world()
        return g_shard

    def _gather_params(self, master_shard, params, spec):
        leaves = jax.tree_util.tree_leaves(params)
        if self.hierarchical:
            # two-level re-materialization: each rank ships only its own
            # shard over the slice (DCN) tier, then the intra gather fans the
            # slice-local copies out — DCN carries 1/slice_size of the flat
            # gather's bytes. Any tier compression puts wire_dtype on both
            # legs (masters stay fp32, same contract as the flat path).
            ci, cd = self._tier_compress()
            wire = master_shard
            logical_dtype = None
            if ci or cd:
                wire = master_shard.astype(self.wire_dtype)
                logical_dtype = master_shard.dtype
            full = bucketing.hierarchical_all_gather(
                wire, hierarchical_axes(self.axis_name),
                site=f"{self._site_prefix}.gather_params",
                bucket_bytes=self.bucket_bytes, logical_dtype=logical_dtype,
            )
            pieces = unflatten(full[: spec.padded_total], spec)
        elif self.bucket_bytes is None and not self.compress:
            pieces = self._gather_full(master_shard, spec)
        else:
            # bucketed re-materialization: independent per-bucket gathers XLA
            # double-buffers against the consumers of already-landed buckets
            # (ref: distributed_fused_adam.py:1071-1076 pipelined all-gather).
            # compress puts wire_dtype on the wire; the masters stay fp32, so
            # the rounding hits only the model copy — same contract as
            # MasterWeights' low-precision model params.
            wire = master_shard
            logical_dtype = None
            if self.compress:
                wire = master_shard.astype(self.wire_dtype)
                logical_dtype = master_shard.dtype
            full = bucketing.bucketed_all_gather(
                wire, self.axis_name,
                site=f"{self._site_prefix}.gather_params",
                bucket_bytes=self.bucket_bytes, logical_dtype=logical_dtype,
            )
            pieces = unflatten(full[: spec.padded_total], spec)
        new_leaves = [
            piece.astype(leaf.dtype)
            for piece, leaf in zip(pieces, leaves)
        ]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(params), new_leaves
        )

    def _global_found_inf(self, g_shard, found_inf):
        local_bad = jnp.any(~jnp.isfinite(g_shard))
        flag = local_bad if found_inf is None else (
            local_bad | (jnp.asarray(found_inf) != 0)
        )
        return comms.pmax(flag.astype(jnp.float32), self.axis_name,
                          site=f"{self._site_prefix}.found_inf") != 0

    # -- checkpointing (ref: distributed_fused_adam.py:1123-1150
    # ``state_dict(gather_on_root=True)`` + ``load_state_dict``) --------------

    def state_dict(self, params, state, *, gather_on_root: bool = True):
        """Checkpointable optimizer state. Runs INSIDE shard_map.

        ``gather_on_root=True`` all-gathers each state shard into full
        per-tensor pytrees (fp32, shaped like ``params``) — the reference
        gathers to rank 0 for ``torch.save``; under SPMD the gathered copy is
        identical on every rank, which is strictly more convenient (any host
        can save). ``False`` returns the local shard verbatim (the
        reference's shard-local checkpoint mode)."""
        if not gather_on_root:
            return dict(state)
        _, treedef, spec, _ = self._arena_layout(params)
        out = {"step": state["step"]}
        for key in ("master",) + self._state_keys():
            out[key] = jax.tree_util.tree_unflatten(
                treedef, self._gather_full(state[key], spec)
            )
        return out

    def load_state_dict(self, params, state_dict):
        """Inverse of ``state_dict(gather_on_root=True)``: re-shard the full
        per-tensor state onto this rank. Runs INSIDE shard_map."""
        _, _, _, shard = self._arena_layout(params)
        state = {"step": jnp.asarray(state_dict["step"], jnp.int32)}
        for key in ("master",) + self._state_keys():
            kleaves = jax.tree_util.tree_leaves(state_dict[key])
            state[key] = self._shard_of(kleaves, shard)
        return state


class DistributedFusedAdam(_DistributedFused):
    """ZeRO-2 AdamW (ref: apex/contrib/optimizers/distributed_fused_adam.py:19)."""

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
        bucket_bytes: Optional[int] = None,
        compress: bool = False,
        wire_dtype: Any = jnp.bfloat16,
        overlap_backward: bool = False,
        hierarchical: bool = False,
        compress_intra: Optional[bool] = None,
        compress_dcn: Optional[bool] = None,
        impl: Optional[str] = None,
    ):
        super().__init__(
            axis_name=axis_name, grad_average=grad_average,
            bucket_bytes=bucket_bytes, compress=compress,
            wire_dtype=wire_dtype, overlap_backward=overlap_backward,
            hierarchical=hierarchical, compress_intra=compress_intra,
            compress_dcn=compress_dcn,
        )
        self.lr, self.betas, self.eps = lr, betas, eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.bias_correction = bias_correction
        self.impl = impl

    def _state_keys(self):
        return ("exp_avg", "exp_avg_sq")

    def step(self, params, grads, state, *, found_inf=None, grad_scale=1.0, lr=None):
        lr = self.lr if lr is None else lr
        leaves, treedef, spec, shard = self._arena_layout(params)
        if self.overlap_backward:
            return self._step_overlap(
                params, grads, state, spec=spec, shard=shard,
                found_inf=found_inf, grad_scale=grad_scale, lr=lr,
            )
        g_shard = self._reduce_scatter_grads(grads, spec, shard) * grad_scale
        flag = self._global_found_inf(g_shard, found_inf)
        step_no = jnp.where(flag, state["step"], state["step"] + 1)

        [p2], [m2], [v2] = mt.multi_tensor_adam(
            [g_shard], [state["master"]], [state["exp_avg"]], [state["exp_avg_sq"]],
            lr=lr, beta1=self.betas[0], beta2=self.betas[1], eps=self.eps,
            step=step_no, adam_w_mode=self.adam_w_mode,
            bias_correction=self.bias_correction, weight_decay=self.weight_decay,
            found_inf=flag, impl=self.impl,
        )
        new_params = self._gather_params(p2, params, spec)
        return new_params, {
            "master": p2, "exp_avg": m2, "exp_avg_sq": v2, "step": step_no,
        }

    def _step_overlap(self, params, grads, state, *, spec, shard,
                      found_inf, grad_scale, lr):
        """Reduce-scatter-then-update PER BUCKET (the overlap_backward rung).

        Each ~bucket_bytes column of the grad arena goes out as its own
        reduce-scatter, and the fused Adam kernel consumes the matching
        slice of the master/moment shards as a separate multi-tensor entry —
        so bucket k's update math is dataflow-ready the moment bucket k's
        collective lands, while later buckets are still on the wire (ref:
        distributed_fused_adam.py:302 pipelined streams). Bitwise-identical
        to the phased step: the kernel is elementwise over the arena, so
        slicing commutes with it, and the overflow flag is the same global
        any-bucket OR the phased path computes — one overflowing bucket
        still skips the whole step on every rank."""
        chunks = self._reduce_scatter_grads(grads, spec, shard, concat=False)
        chunks = [c * grad_scale for c in chunks]
        local_bad = jnp.zeros((), jnp.bool_)
        for c in chunks:
            # per-bucket flag, available as each bucket lands; the fold to
            # ONE pmax'd scalar preserves whole-step skip semantics
            local_bad = local_bad | jnp.any(~jnp.isfinite(c))
        if found_inf is not None:
            local_bad = local_bad | (jnp.asarray(found_inf) != 0)
        flag = comms.pmax(local_bad.astype(jnp.float32), self.axis_name,
                          site=f"{self._site_prefix}.found_inf") != 0
        step_no = jnp.where(flag, state["step"], state["step"] + 1)

        # state slices share the grad chunks' geometry: the fp32 (shard,)
        # arena bucketed by wire cost (itemsize * world per column)
        slices = bucketing.bucket_slices(
            shard, 4 * self._world(), self.bucket_bytes,
        )
        assert len(slices) == len(chunks)
        masters = [bucketing._slice_flat(state["master"], o, n) for o, n in slices]
        ms = [bucketing._slice_flat(state["exp_avg"], o, n) for o, n in slices]
        vs = [bucketing._slice_flat(state["exp_avg_sq"], o, n) for o, n in slices]

        p2, m2, v2 = mt.multi_tensor_adam(
            chunks, masters, ms, vs,
            lr=lr, beta1=self.betas[0], beta2=self.betas[1], eps=self.eps,
            step=step_no, adam_w_mode=self.adam_w_mode,
            bias_correction=self.bias_correction, weight_decay=self.weight_decay,
            found_inf=flag, impl=self.impl,
        )
        master2 = p2[0] if len(p2) == 1 else jnp.concatenate(p2)
        exp_avg2 = m2[0] if len(m2) == 1 else jnp.concatenate(m2)
        exp_avg_sq2 = v2[0] if len(v2) == 1 else jnp.concatenate(v2)
        new_params = self._gather_params(master2, params, spec)
        return new_params, {
            "master": master2, "exp_avg": exp_avg2,
            "exp_avg_sq": exp_avg_sq2, "step": step_no,
        }


class DistributedFusedLAMB(_DistributedFused):
    """ZeRO-sharded LAMB (ref: apex/contrib/optimizers/distributed_fused_lamb.py).

    Per-tensor trust ratios need cross-shard norms: the shard's per-tensor
    partial sums (via a rank-sliced segment table) are psum'd over the data
    axis, reproducing the reference's L2-norm allreduce before stage 2.
    """

    def __init__(
        self,
        lr: float = 1e-3,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-6,
        *,
        weight_decay: float = 0.01,
        bias_correction: bool = True,
        grad_averaging: bool = True,
        adam_w_mode: bool = True,
        max_grad_norm: float = 1.0,
        use_nvlamb: bool = False,
        axis_name: Any = DATA_AXIS,
        grad_average: bool = True,
        bucket_bytes: Optional[int] = None,
        compress: bool = False,
        wire_dtype: Any = jnp.bfloat16,
        overlap_backward: bool = False,
        hierarchical: bool = False,
        compress_intra: Optional[bool] = None,
        compress_dcn: Optional[bool] = None,
        impl: Optional[str] = None,
    ):
        if overlap_backward:
            # LAMB's trust ratios need per-tensor norms over the WHOLE shard
            # (segment-id partial sums + cross-shard psum) before any slice
            # can update — per-bucket updates would commit a bucket before
            # the global norms exist. Fail loudly instead of silently
            # serializing.
            raise NotImplementedError(
                "DistributedFusedLAMB does not support overlap_backward: "
                "the sharded-norm reduction is a whole-shard barrier; use "
                "DistributedFusedAdam or the phased LAMB step"
            )
        super().__init__(
            axis_name=axis_name, grad_average=grad_average,
            bucket_bytes=bucket_bytes, compress=compress,
            wire_dtype=wire_dtype, hierarchical=hierarchical,
            compress_intra=compress_intra, compress_dcn=compress_dcn,
        )
        self.lr, self.betas, self.eps = lr, betas, eps
        self.weight_decay = weight_decay
        self.bias_correction = bias_correction
        self.grad_averaging = grad_averaging
        self.adam_w_mode = adam_w_mode
        self.max_grad_norm = max_grad_norm
        self.use_nvlamb = use_nvlamb
        self.impl = impl

    def _state_keys(self):
        return ("exp_avg", "exp_avg_sq")

    def _local_segment_ids(self, spec, shard):
        """This rank's arena→tensor segment ids, computed O(shard * t): the
        static boundary table recovers the owning tensor of each global index
        without materializing the full-arena table (an O(model) replicated
        buffer defeating the sharding). Uses the fused compare-sum from
        ``arena.segment_ids_of`` — searchsorted's (N, 2) scan carry blows up
        64x under TPU tiling."""
        from beforeholiday_tpu.ops.arena import segment_ids_of

        rank = jax.lax.axis_index(self.axis_name)
        idx = rank * shard + jnp.arange(shard)
        return segment_ids_of(spec, idx)

    def step(self, params, grads, state, *, found_inf=None, grad_scale=1.0, lr=None):
        lr = self.lr if lr is None else lr
        leaves, treedef, spec, shard = self._arena_layout(params)
        seg_local = self._local_segment_ids(spec, shard)
        g_shard = self._reduce_scatter_grads(grads, spec, shard) * grad_scale
        flag = self._global_found_inf(g_shard, found_inf)
        step_no = jnp.where(flag, state["step"], state["step"] + 1)

        # global grad norm for clipping (ref: fused_lamb step's l2norm)
        gnorm = jnp.sqrt(
            comms.psum(jnp.sum(g_shard.astype(jnp.float32) ** 2),
                       self.axis_name, site="zero2.lamb_gnorm")
        )
        [p2], [m2], [v2] = mt.multi_tensor_lamb(
            [g_shard], [state["master"]], [state["exp_avg"]], [state["exp_avg_sq"]],
            lr=lr, beta1=self.betas[0], beta2=self.betas[1], eps=self.eps,
            step=step_no, bias_correction=self.bias_correction,
            weight_decay=self.weight_decay, grad_averaging=self.grad_averaging,
            mode=1 if self.adam_w_mode else 0, global_grad_norm=gnorm,
            max_grad_norm=self.max_grad_norm, use_nvlamb=self.use_nvlamb,
            found_inf=flag, impl=self.impl,
            _sharded_norms=(seg_local, spec.num_tensors, self.axis_name),
        )
        new_params = self._gather_params(p2, params, spec)
        return new_params, {
            "master": p2, "exp_avg": m2, "exp_avg_sq": v2, "step": step_no,
        }
