"""Named, registrable rematerialization policies.

Activation checkpointing is the Apex/Megatron heritage feature
(``apex.transformer`` checkpointed layers; Chen et al. 2016, "Training Deep
Nets with Sublinear Memory Cost"; Korthikanti et al. 2022, "Reducing
Activation Recomputation in Large Transformer Models"): trade backward-pass
recompute for peak activation memory. JAX already ships the machinery
(``jax.checkpoint`` + ``jax.checkpoint_policies``); what this module adds is
the *naming layer* so a policy travels as a plain string through configs,
pipeline schedules and result files — no callables smuggled through
dataclasses, no jit-cache misses from anonymous lambdas.

Built-in policies:

* ``"none"``           — no remat: every intermediate is saved (jax default).
* ``"full"``           — ``jax.checkpoint`` with nothing saveable: only the
                         wrapped function's inputs survive; the whole body is
                         recomputed in backward (Chen et al.'s sqrt schedule
                         degenerate case — min memory, max recompute).
* ``"dots_saveable"``  — save matmul outputs, recompute elementwise ops (the
                         classic TPU policy: matmuls are the expensive thing
                         to redo, pointwise ops are nearly free).
* ``"save_boundaries"``— tag-based selective checkpointing: save ONLY the
                         values named with ``jax.ad_checkpoint.checkpoint_name``
                         at the repo's planted boundary tags (block outputs,
                         fused-norm outputs, attention context, flash ``lse``)
                         and recompute everything between them. This is the
                         Korthikanti "selective activation recomputation"
                         shape: the big per-layer residuals (attention scores/
                         probs, gelu inputs) are recomputed from cheap saved
                         boundaries.

* ``"zero3_regather"`` — param-residency knob for the ZeRO-3 engine: save
                         everything EXCEPT values tagged ``zero3_gathered``
                         (the all-gathered param leaves), so backward
                         re-gathers params instead of keeping the full
                         arena resident between forward and backward.

``register_policy`` adds new named policies (e.g. a model-specific tag set);
``apply(fn, policy)`` wraps a function for use under ``lax.scan`` or a
pipeline stage slot.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint

__all__ = [
    "BOUNDARY_TAGS",
    "TAG_ATTN_OUT",
    "TAG_BLOCK",
    "TAG_FLASH_LSE",
    "TAG_MOE_COMBINE",
    "TAG_MOE_DISPATCH",
    "TAG_NORM_OUT",
    "ZERO3_GATHERED_TAG",
    "apply",
    "available_policies",
    "register_policy",
    "resolve",
]

# checkpoint_name tags planted in the library / testing models. Planting is
# unconditional (the name primitive is identity outside jax.checkpoint) so a
# tag-based policy sees them whenever the caller opts in.
TAG_BLOCK = "remat.block"          # transformer block output (testing/gpt, bert)
TAG_NORM_OUT = "remat.norm_out"    # fused_layer_norm / fused_rms_norm output
TAG_ATTN_OUT = "remat.attn_out"    # attention context (post-kernel, pre-proj)
TAG_FLASH_LSE = "remat.flash_lse"  # flash-attention log-sum-exp residual
# MoE all_to_all boundaries (moe/dispatch.py): saving the dispatched and
# combined activations means backward re-runs the cheap expert einsums, not
# the expert-parallel collectives
TAG_MOE_DISPATCH = "remat.moe_dispatch"  # post-dispatch (E, C, D) activations
TAG_MOE_COMBINE = "remat.moe_combine"    # post-combine expert outputs

BOUNDARY_TAGS: Tuple[str, ...] = (
    TAG_BLOCK, TAG_NORM_OUT, TAG_ATTN_OUT, TAG_FLASH_LSE,
    TAG_MOE_DISPATCH, TAG_MOE_COMBINE,
)

# ZeRO-3 param residency: ``optimizers.zero3`` tags every all-gathered param
# leaf with this name, so the ``"zero3_regather"`` policy below can make
# gathered params NON-saveable — backward re-runs the bucketed all-gather
# instead of holding the full-precision param copy across forward+backward
ZERO3_GATHERED_TAG = "zero3_gathered"

# sentinel for "do not wrap at all" — distinct from jax.checkpoint(policy=None)
# which means "save nothing"
_NO_REMAT = object()

_LOCK = threading.Lock()
# name -> jax saveable-policy callable, None (save nothing), or _NO_REMAT
_POLICIES: Dict[str, Any] = {}


def register_policy(name: str, policy: Any, *, overwrite: bool = False) -> None:
    """Register a named policy.

    ``policy`` is a jax saveable-policy callable (anything accepted by
    ``jax.checkpoint(policy=...)``, e.g. the ``jax.checkpoint_policies``
    combinators), or ``None`` for "save nothing" (full remat)."""
    with _LOCK:
        if name in _POLICIES and not overwrite:
            raise ValueError(
                f"remat policy {name!r} already registered "
                "(pass overwrite=True to replace)"
            )
        _POLICIES[name] = policy


def available_policies() -> Tuple[str, ...]:
    """Sorted names of all registered policies."""
    with _LOCK:
        return tuple(sorted(_POLICIES))


def resolve(policy: Optional[str]) -> Any:
    """Name -> saveable-policy callable / None / no-remat sentinel.

    ``None`` and ``"none"`` both mean "no remat". A non-string is assumed to
    already be a saveable-policy callable and passes through (escape hatch
    for one-off experiments)."""
    if policy is None:
        return _NO_REMAT
    if not isinstance(policy, str):
        return policy
    with _LOCK:
        try:
            return _POLICIES[policy]
        except KeyError:
            known = ", ".join(sorted(_POLICIES))
            raise ValueError(
                f"unknown remat policy {policy!r}; registered: {known}"
            ) from None


def apply(
    fn: Callable,
    policy: Optional[str] = None,
    *,
    prevent_cse: bool = True,
    static_argnums: Tuple[int, ...] = (),
) -> Callable:
    """Wrap ``fn`` with the named remat policy.

    ``"none"``/``None`` returns ``fn`` unchanged (no ``jax.checkpoint`` wrap,
    so no prevent-CSE pessimization on the no-remat path). Everything else
    returns ``jax.checkpoint(fn, policy=...)`` — suitable as a ``lax.scan``
    body or a pipeline-stage function."""
    resolved = resolve(policy)
    if resolved is _NO_REMAT:
        return fn
    return jax.checkpoint(
        fn, policy=resolved, prevent_cse=prevent_cse,
        static_argnums=static_argnums,
    )


# ---- built-ins -------------------------------------------------------------

register_policy("none", _NO_REMAT)
register_policy("full", None)  # jax.checkpoint default: save nothing
register_policy("dots_saveable", jax.checkpoint_policies.dots_saveable)
register_policy(
    "save_boundaries",
    jax.checkpoint_policies.save_only_these_names(*BOUNDARY_TAGS),
)
register_policy(
    # everything EXCEPT the gathered param arena is saveable: normal
    # activation residency, but params are re-gathered in backward — the
    # FSDP ``reshard_after_forward`` residency knob as a remat policy
    "zero3_regather",
    jax.checkpoint_policies.save_any_names_but_these(ZERO3_GATHERED_TAG),
)
