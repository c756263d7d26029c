"""amp frontend — opt levels O0–O5 and ``initialize`` for functional models.

The reference's ``amp.initialize`` rewires a torch model in place: casts
weights, patches ``forward`` to cast inputs, builds fp32 masters, patches
``optimizer.step`` (ref: apex/amp/frontend.py:259-431, _initialize.py:147-267).
A functional framework cannot (and should not) monkey-patch; the same policy
becomes explicit dataflow:

* weight casting    → ``initialize`` returns a cast params pytree
  (norm/batchnorm leaves kept fp32 per ``keep_batchnorm_fp32``, the
  ``convert_network`` rule);
* forward patching  → the returned ``apply`` wrapper casts array inputs to the
  compute dtype and outputs back to fp32 (``cast_model_outputs``);
* O1's function patching → under jit every cast is traced and fused, so the
  "patch + cast cache" machinery (apex/amp/amp.py:75-198, utils.py:101-123)
  reduces to casting at the apply boundary with fp32 storage;
* optimizer patching → a master-weights wrapper with the scaler's
  ``found_inf``/``grad_scale`` threaded through (skip-step with no host sync).

Deliberately not ported: the legacy ``AmpHandle``/``OptimWrapper`` API
(ref: apex/amp/handle.py:170-282) — deprecated in the reference itself, its
contract is eager in-place mutation (``with handle.scale_loss(...) as s:
s.backward()``), which has no meaning for traced functional code. Its
capability surface survives in full: per-loss scalers = ``num_losses`` +
``scalers``; ``scale_loss`` = ``scaled_value_and_grad``; the deprecated
``half_function`` registrations = ``amp.functional``'s tagged ops; the even
older explicit-master vintage = ``beforeholiday_tpu.fp16_utils``.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from beforeholiday_tpu.amp.scaler import LossScaler
from beforeholiday_tpu.monitor.spans import span
from beforeholiday_tpu.ops._autocast import (
    autocast,
    cast_floats as _cast_floats,
    quantized_compute,
)
from beforeholiday_tpu.ops.arena import PackedParams
from beforeholiday_tpu.optimizers.fused import MasterWeights
from beforeholiday_tpu.utils.logging import get_logger

logger = get_logger(__name__)


@dataclasses.dataclass(frozen=True)
class Properties:
    """Opt-level property set (ref: apex/amp/frontend.py:8-52 ``Properties``)."""

    enabled: bool = True
    opt_level: str = "O0"
    cast_model_type: Optional[Any] = None  # storage dtype for params
    patch_torch_functions: bool = False  # compute-dtype casting w/ fp32 storage
    patch_torch_functions_type: Optional[Any] = None
    keep_batchnorm_fp32: Optional[bool] = None
    master_weights: Optional[bool] = None
    loss_scale: Any = 1.0  # "dynamic" | float
    quantized: bool = False  # O6: fp8-quantized matmuls under delayed scaling

    @property
    def compute_dtype(self):
        """dtype arithmetic runs in: patched-functions type, else storage type."""
        if self.patch_torch_functions and self.patch_torch_functions_type is not None:
            return self.patch_torch_functions_type
        return self.cast_model_type or jnp.float32


# ref: apex/amp/frontend.py:70-247 O0..O5 classes. O4/O5 (bf16) are the
# ROCm-fork additions and the natural TPU defaults.
opt_levels: Dict[str, Properties] = {
    "O0": Properties(opt_level="O0", cast_model_type=jnp.float32,
                     master_weights=False, loss_scale=1.0),
    "O1": Properties(opt_level="O1", patch_torch_functions=True,
                     patch_torch_functions_type=jnp.float16, loss_scale="dynamic"),
    "O2": Properties(opt_level="O2", cast_model_type=jnp.float16,
                     keep_batchnorm_fp32=True, master_weights=True,
                     loss_scale="dynamic"),
    "O3": Properties(opt_level="O3", cast_model_type=jnp.float16,
                     keep_batchnorm_fp32=False, master_weights=False, loss_scale=1.0),
    "O4": Properties(opt_level="O4", patch_torch_functions=True,
                     patch_torch_functions_type=jnp.bfloat16, loss_scale=1.0),
    "O5": Properties(opt_level="O5", cast_model_type=jnp.bfloat16,
                     keep_batchnorm_fp32=True, master_weights=True, loss_scale=1.0),
    # O6 = O5's storage policy + fp8-quantized GEMMs (ops.quantized). The loss
    # scale is dynamic: e5m2 grad quantization signals overflow by saturating
    # to inf, and the dynamic scaler's skip/halve loop is the recovery path —
    # the amax history for the delayed scales rides inside the scaler state.
    "O6": Properties(opt_level="O6", cast_model_type=jnp.bfloat16,
                     keep_batchnorm_fp32=True, master_weights=True,
                     loss_scale="dynamic", quantized=True),
}


def _default_keep_fp32(path: Tuple[Any, ...]) -> bool:
    """Heuristic for ``keep_batchnorm_fp32``: norm-layer parameters stay fp32.

    The reference excludes BatchNorm modules from casting by module class
    (``convert_network``, apex/fp16_utils/fp16util.py); a params pytree carries
    names, not classes, so match norm-ish path components.
    """
    for part in path:
        name = getattr(part, "key", None) or getattr(part, "name", None) or str(part)
        low = str(name).lower()
        if (
            "norm" in low  # layernorm, rmsnorm, groupnorm, norm
            or low.startswith("bn") or low.endswith("bn")  # bn1, sync_bn
            or low.startswith("ln")  # ln1_scale, lnf_bias
        ):
            return True
    return False


def _cast_params(params, policy: Properties, keep_fp32_mask):
    if policy.cast_model_type is None:
        return params
    target = policy.cast_model_type
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keep = keep_fp32_mask if keep_fp32_mask is not None else _default_keep_fp32
    out = []
    for path, leaf in flat:
        if (
            policy.keep_batchnorm_fp32
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and keep(path)
        ):
            out.append(leaf.astype(jnp.float32))
        elif jnp.issubdtype(leaf.dtype, jnp.floating):
            out.append(leaf.astype(target))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


@dataclasses.dataclass
class AmpModel:
    """Bundle returned by ``initialize`` — the functional analogue of the
    (patched model, patched optimizer) pair."""

    policy: Properties
    apply: Callable  # wrapped apply: casts inputs/outputs per policy
    params: Any  # storage-dtype params
    optimizer: Any  # possibly MasterWeights-wrapped
    scaler: LossScaler  # scalers[0], kept as a field for the common case
    scalers: Tuple[LossScaler, ...] = ()  # one per loss (ref: num_losses)

    def __post_init__(self):
        if not self.scalers:
            self.scalers = (self.scaler,)

    def state_dict(self, scaler_state, metrics=None,
                   optimizer_state=None) -> Dict[str, Any]:
        """Scaler checkpoint (ref: apex/amp/frontend.py:434-452 amp.state_dict
        — one ``loss_scaler{i}`` entry per loss). ``scaler_state`` is the
        single state, or a sequence of per-loss states when num_losses > 1.

        A :class:`~beforeholiday_tpu.guard.StepGuard` state (recognized by its
        ``health`` key) may be passed in place of a bare scaler state: its
        embedded scaler serializes as ``loss_scaler{i}`` as before, and the
        health counters ride along as ``health{i}``. The rollback snapshot is
        deliberately NOT serialized (it is model-sized and re-seeded from the
        checkpointed params via :meth:`StepGuard.load_state_dict`).

        ``metrics`` optionally takes the :mod:`beforeholiday_tpu.monitor`
        ``Metrics`` pytree; it serializes under a single ``"monitor"`` entry
        (EMAs and counters survive restarts). Old loaders ignore the extra
        key, so checkpoints stay readable both ways.

        ``optimizer_state`` optionally rides along under a single
        ``"optimizer"`` entry, stored verbatim — pass the distributed
        optimizer's own ``state_dict(...)`` result (e.g. ``ZeRO3FusedAdam``'s
        gathered trees, or its ``gather_on_root=False`` shard next to a
        ``zero3.shard_manifest``). Recover it with
        :meth:`load_optimizer_state`; scaler-only loaders ignore the key."""
        states = (
            list(scaler_state)
            if isinstance(scaler_state, (list, tuple))
            else [scaler_state]
        )
        if len(states) != len(self.scalers):
            raise ValueError(
                f"expected {len(self.scalers)} scaler states, got {len(states)}"
            )
        out: Dict[str, Any] = {}
        for i, (s, st) in enumerate(zip(self.scalers, states)):
            if isinstance(st, dict) and "health" in st:
                out[f"loss_scaler{i}"] = s.state_dict(st["scaler"])
                out[f"health{i}"] = {k: int(v) for k, v in st["health"].items()}
            else:
                out[f"loss_scaler{i}"] = s.state_dict(st)
        if metrics is not None:
            out["monitor"] = {
                k: (int(v) if jnp.issubdtype(jnp.asarray(v).dtype, jnp.integer)
                    else float(v))
                for k, v in metrics.items()
            }
        if optimizer_state is not None:
            out["optimizer"] = optimizer_state
        return out

    def load_state_dict(self, state_dict):
        """Inverse of ``state_dict`` (ref: frontend.py:454-473). Returns the
        single scaler state, or the list of per-loss states. Entries saved
        with a ``health{i}`` sibling come back as guard-shaped states
        (``{"scaler": ..., "health": ...}``, no snapshot — re-seed it through
        :meth:`StepGuard.load_state_dict` when rollback is armed)."""
        out = []
        for i, s in enumerate(self.scalers):
            sstate = s.load_state_dict(state_dict[f"loss_scaler{i}"])
            if f"health{i}" in state_dict:
                health = {
                    k: jnp.int32(v)
                    for k, v in state_dict[f"health{i}"].items()
                }
                out.append({"scaler": sstate, "health": health})
            else:
                out.append(sstate)
        return out[0] if len(out) == 1 else out

    def load_optimizer_state(self, state_dict):
        """Recover the ``"optimizer"`` entry saved by
        ``state_dict(..., optimizer_state=...)``, or None for checkpoints
        without one. The value is whatever the optimizer's own
        ``state_dict`` produced — feed it back through that optimizer's
        ``load_state_dict`` (resharding first via ``zero3.reshard_state``
        when the topology changed)."""
        return state_dict.get("optimizer")

    def load_metrics(self, state_dict, monitor=None):
        """Restore the monitor ``Metrics`` pytree saved by
        ``state_dict(..., metrics=...)``. Returns None for pre-monitor
        checkpoints (no ``"monitor"`` entry) — callers fall back to
        ``monitor.init()``. ``monitor`` defaults to a fresh
        :class:`~beforeholiday_tpu.monitor.TrainMonitor`, whose
        ``load_state_dict`` zero-fills missing keys and drops unknown ones,
        so spec drift in either direction stays loadable."""
        if "monitor" not in state_dict:
            return None
        if monitor is None:
            from beforeholiday_tpu.monitor import TrainMonitor

            monitor = TrainMonitor()
        return monitor.load_state_dict(state_dict["monitor"])


def initialize(
    apply_fn: Callable,
    params: Any,
    optimizer: Any = None,
    opt_level: Optional[str] = None,
    *,
    cast_model_outputs: Optional[Any] = jnp.float32,
    keep_batchnorm_fp32: Optional[bool] = None,
    master_weights: Optional[bool] = None,
    loss_scale: Optional[Any] = None,
    keep_fp32_mask: Optional[Callable] = None,
    has_state: bool = False,
    num_losses: int = 1,
    arena_masters: bool = False,
    arena_native: bool = False,
) -> AmpModel:
    """Apply an opt-level policy to (apply_fn, params, optimizer).

    Ref: apex/amp/frontend.py:259-431 — including the explicit-override rule:
    ``keep_batchnorm_fp32``/``master_weights``/``loss_scale`` kwargs override
    the opt-level defaults (:347-390). The TPU-native default is O5 (bf16 +
    fp32 masters, no loss scaling).

    ``apply_fn(params, *inputs)`` is the model forward. The returned
    ``AmpModel.apply`` casts floating inputs (and, per O1/O4 semantics, the
    fp32-stored params) to the compute dtype and the outputs to
    ``cast_model_outputs``.

    ``has_state=True`` declares ``apply_fn(params, model_state, *inputs) ->
    (out, new_model_state)`` — model buffers like BN running stats. The state
    is passed through UNCAST in both directions: the reference's
    ``convert_network`` never casts BN buffers (apex/fp16_utils/fp16util.py),
    and low-precision round-trips would erode the running averages.

    ``num_losses`` creates one independent LossScaler per loss (ref:
    _initialize.py:229-233) — GAN-style multi-loss training scales each loss
    with its own dynamic state; all land in ``state_dict`` as loss_scaler{i}.

    ``arena_native=True`` (implies ``arena_masters``) stores the cast params
    as :class:`PackedParams` — per-dtype flat HBM arenas. ``AmpModel.apply``
    unpacks transparently (static slices XLA fuses into consumers), so
    ``jax.grad`` taken at the packed argument returns gradient ARENAS, packed
    once per step by ``unpack``'s ``custom_vjp`` (one ``concatenate`` per
    dtype bucket: what "born flat" costs), and the master-weight optimizer
    step streams them with no packing of its own — the TPU
    equivalent of the reference's pointer-aliased tensor lists
    (csrc/multi_tensor_apply.cuh never repacks either). Single-device /
    manual-shard_map fast path, like ``arena_masters``.
    """
    if opt_level is None:
        opt_level = "O5"
    if opt_level not in opt_levels:
        raise RuntimeError(
            f"Unexpected optimization level {opt_level}. Options are 'O0', 'O1', "
            "'O2', 'O3', 'O4', 'O5', 'O6'."
        )
    policy = opt_levels[opt_level]
    overrides = {}
    if keep_batchnorm_fp32 is not None:
        overrides["keep_batchnorm_fp32"] = keep_batchnorm_fp32
    if master_weights is not None:
        overrides["master_weights"] = master_weights
    if loss_scale is not None:
        overrides["loss_scale"] = loss_scale
    if overrides:
        policy = dataclasses.replace(policy, **overrides)
    logger.info("amp.initialize: %s", policy)

    cast_params = _cast_params(params, policy, keep_fp32_mask)
    if arena_native:
        if policy.patch_torch_functions or (
            optimizer is not None and not policy.master_weights
        ):
            # without the MasterWeights wrap a raw optimizer would consume the
            # PackedParams pytree as 1-2 arena "leaves" — LAMB/LARS/NovoGrad
            # per-TENSOR norms and weight-decay masks would silently apply
            # per-ARENA; only the master-weight levels route the packed step
            raise ValueError(
                "arena_native requires a master-weights opt level (O2/O5, or "
                f"master_weights=True); {policy.opt_level} with "
                f"master_weights={policy.master_weights} would hand "
                "PackedParams to the raw optimizer"
            )
        cast_params = PackedParams.pack(cast_params)
    amp_apply = make_apply(
        policy, apply_fn, cast_model_outputs=cast_model_outputs,
        has_state=has_state, keep_fp32_mask=keep_fp32_mask,
    )

    opt = optimizer
    if opt is not None and policy.master_weights:
        # arena_masters keeps fp32 masters + optimizer state packed flat and
        # fuses the master->model cast into the optimizer kernel (single-device
        # / manual-shard_map fast path; see MasterWeights docstring);
        # MasterWeights.step dispatches on PackedParams for the arena-native
        # zero-packing path
        opt = MasterWeights(opt, arena=arena_masters or arena_native)

    if num_losses < 1:
        raise ValueError(f"num_losses must be >= 1, got {num_losses}")
    scalers = tuple(
        LossScaler(loss_scale=policy.loss_scale, quantized=policy.quantized)
        for _ in range(num_losses)
    )
    return AmpModel(
        policy=policy, apply=amp_apply, params=cast_params,
        optimizer=opt, scaler=scalers[0], scalers=scalers,
    )


def make_apply(
    policy: Properties,
    apply_fn: Callable,
    *,
    cast_model_outputs: Optional[Any] = jnp.float32,
    has_state: bool = False,
    keep_fp32_mask: Optional[Callable] = None,
) -> Callable:
    """Wrap ``apply_fn`` with a policy's input/param/output casts WITHOUT
    re-casting a params copy — for building extra apply variants (e.g. an
    eval-mode forward) that share an existing ``AmpModel``'s params."""
    compute_dtype = policy.compute_dtype
    keep = keep_fp32_mask if keep_fp32_mask is not None else _default_keep_fp32

    def _cast_params_keep_norms(p):
        """O1/O4 boundary cast that leaves norm-ish params at full precision:
        the reference's O1 keeps model weights fp32 and FP32_FUNCS consume
        them uncast — bulk-down-casting gamma/beta would quantize them before
        float_function re-promotes (a value-level divergence, not just dtype)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(p)
        out = [
            leaf
            if (hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating)
                and keep(path))
            else _cast_floats(leaf, compute_dtype)
            for path, leaf in flat
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def amp_apply(p, *inputs, **kwinputs):
        if isinstance(p, PackedParams):
            # static slices, fused into consumers under jit; transposed as
            # ONE pack per arena, not as the slices' own add_any(pad(g0), …),
            # which one chip recomputed in every consumer of the gradients
            # (PR 25; PackedParams.unpack)
            p = p.unpack()
        if has_state:
            model_state, *inputs = inputs
        if policy.patch_torch_functions:
            # O1/O4: fp32 storage, low-precision compute — the cast happens at
            # the trace boundary and XLA fuses it (the "cast cache" for free),
            # AND the per-op policy activates: ops tagged float_function
            # (norms/losses) re-promote their inputs to fp32, half ops
            # (dense/mlp/attention) stay low-precision — the reference's
            # FP32_FUNCS / FP16_FUNCS split (functional_overrides.py:17-91)
            p = _cast_params_keep_norms(p)
            scope = autocast(compute_dtype, quantized=policy.quantized)
        elif policy.quantized:
            # O6: O5's storage-cast semantics, but every ops.dense matmul
            # routes through the fp8 tier — no per-op cast policy, the scope
            # only flips the quantized-routing predicate (jit-cache-keyed)
            scope = quantized_compute()
        else:
            scope = contextlib.nullcontext()
        inputs = _cast_floats(inputs, compute_dtype)
        kwinputs = _cast_floats(kwinputs, compute_dtype)
        with scope:
            if has_state:
                out, new_state = apply_fn(p, model_state, *inputs, **kwinputs)
                if cast_model_outputs is not None:
                    out = _cast_floats(out, cast_model_outputs)
                return out, new_state
            out = apply_fn(p, *inputs, **kwinputs)
        if cast_model_outputs is not None:
            out = _cast_floats(out, cast_model_outputs)
        return out

    return amp_apply


def scaled_value_and_grad(
    loss_fn: Callable, scaler: LossScaler, *, has_aux: bool = False, impl=None,
    reduce_grads: Optional[Callable] = None,
):
    """The functional ``amp.scale_loss`` (ref: apex/amp/handle.py:17-158).

    Returns ``f(params, scaler_state, *args) -> (loss, grads, found_inf,
    new_scaler_state)``: grads of ``scale*loss`` are unscaled to fp32, overflow
    is detected in the fused unscale kernel, and the scaler state advances —
    the context manager's enter/exit collapsed into one jittable call. Thread
    ``found_inf`` into ``optimizer.step`` for the skip-step.

    ``reduce_grads`` (e.g. ``DistributedDataParallel.reduce``) runs on the
    still-scaled low-precision grads BEFORE unscale — the reference's hot-loop
    order (NCCL allreduce of scaled fp16 grads during backward, fused unscale
    on exit, apex/parallel/distributed.py:352-409 + amp/scaler.py:114-126) —
    so overflow detection sees the reduced grads and every rank takes the same
    skip-step decision.
    """

    def wrapped(params, scaler_state, *args, **kw):
        def scaled_loss_fn(p):
            res = loss_fn(p, *args, **kw)
            loss, aux = res if has_aux else (res, None)
            return scaler.scale_loss(loss, scaler_state), (loss, aux)

        # O6: derive this step's delayed fp8 scales from the amax history in
        # the scaler state and expose them to every quantized_matmul in the
        # trace (scope values are step-level tracers; closures inside
        # scan/grad capture them legally — nothing escapes a trace)
        scale_w, scale_g = scaler.quantized_scales(scaler_state)
        if scale_w is not None:
            from beforeholiday_tpu.ops.quantized import quantized_scope

            q_scope = quantized_scope(scale_w, scale_g)
        else:
            q_scope = contextlib.nullcontext()
        # jax.grad, split at the layer boundary the device trace is read by:
        # the primal pass (and the residuals it saves) under ``amp_forward``,
        # the pull-back with jax.grad's own ones cotangent under
        # ``amp_backward`` (a remat policy's recomputed forward lands there,
        # where its time is spent). Same program, named.
        with q_scope:
            with span("amp_forward"):
                scaled, pull, (loss, aux) = jax.vjp(
                    scaled_loss_fn, params, has_aux=True
                )
            with span("amp_backward"):
                (grads,) = pull(jnp.ones_like(scaled))
        if reduce_grads is not None:
            grads = reduce_grads(grads)
        amax = None
        if scale_w is not None:
            from beforeholiday_tpu.ops.quantized import amax_of_tree

            # weight row: params ARE the tensors the forward quantized
            # (exact); grad row: the still-scaled grads live in the same
            # scaling regime the backward quantized its cotangents in — a
            # conservative per-step proxy for the dy amax
            amax = (amax_of_tree(params), amax_of_tree(grads))
        grads, found_inf = scaler.unscale(grads, scaler_state, impl=impl)
        new_state = scaler.update(scaler_state, found_inf, amax=amax)
        if has_aux:
            return loss, aux, grads, found_inf, new_state
        return loss, grads, found_inf, new_state

    return wrapped
