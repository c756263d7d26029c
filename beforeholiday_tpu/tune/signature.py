"""Stable tuning keys: (model abstract signature, mesh, ChipSpec) → digest.

A tuned configuration is only transferable between runs that compile the
SAME program on the SAME machine shape — the autotuner therefore keys its
manifest on exactly what determines the compiled program: the model's
abstract signature (pytree structure + leaf shapes/dtypes, via
``jax.eval_shape`` so no device executes anything), the mesh geometry
(axis names + sizes), and the chip's roofline spec from the
:mod:`beforeholiday_tpu.monitor.roofline` registry. Two processes that
agree on those three agree on the digest, and a re-run becomes a manifest
cache hit with zero trials.

Everything here is host-side metadata; the one jax API used is
``eval_shape`` (and ``jnp.shape``/``result_type`` on leaves), which traces
abstractly and never touches a device.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

__all__ = ["TuningKey", "tuning_key"]


@dataclasses.dataclass(frozen=True)
class TuningKey:
    """One (model, mesh, chip) point in tuning space.

    ``model`` is the canonical abstract-signature string; ``mesh`` is
    ``((axis_name, size), ...)``; ``chip`` is ``(name, peak_tflops,
    hbm_gbs, fp8_peak_tflops)``. ``digest`` is the manifest key."""

    model: str
    mesh: Tuple[Tuple[str, int], ...]
    chip: Tuple[Any, ...]

    @property
    def digest(self) -> str:
        payload = json.dumps(
            {"model": self.model, "mesh": list(map(list, self.mesh)),
             "chip": list(self.chip)},
            sort_keys=True,
        )
        return hashlib.sha256(payload.encode()).hexdigest()[:16]

    def describe(self) -> Dict[str, Any]:
        """Human-readable manifest payload (the digest alone would make the
        manifest opaque to review)."""
        return {
            "model": self.model,
            "mesh": [[name, size] for name, size in self.mesh],
            "chip": list(self.chip),
            "digest": self.digest,
        }


def _leaf_sig(leaf: Any) -> str:
    import jax.numpy as jnp
    import numpy as np

    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        # jax.Array / ShapeDtypeStruct / np.ndarray — the common leaves
        return (
            f"{np.dtype(leaf.dtype).name}"
            f"[{','.join(str(d) for d in leaf.shape)}]"
        )
    if hasattr(leaf, "shape"):
        return (
            f"{np.dtype(jnp.result_type(leaf)).name}"
            f"[{','.join(str(d) for d in jnp.shape(leaf))}]"
        )
    return f"{type(leaf).__name__}:{leaf!r}"


def _abstract_signature(
    model: Any,
    example_args: Optional[Sequence[Any]],
    example_kwargs: Optional[Mapping[str, Any]],
) -> str:
    """Canonical string for the model's abstract signature.

    A callable with ``example_args`` goes through ``jax.eval_shape`` —
    inputs AND abstract outputs both land in the signature (two models with
    identical params but different heads tune separately). A pytree (the
    params, the common trainer-side handle) contributes its treedef and
    leaf shapes/dtypes."""
    import jax

    if callable(model) and example_args is not None:
        kwargs = dict(example_kwargs or {})
        out = jax.eval_shape(model, *example_args, **kwargs)
        parts = [
            "in:" + _tree_sig((tuple(example_args), kwargs)),
            "out:" + _tree_sig(out),
        ]
        return "|".join(parts)
    if callable(model):
        raise TypeError(
            "a callable model needs example_args (shapes drive the "
            "signature); pass the params pytree instead to key on "
            "parameters alone"
        )
    return _tree_sig(model)


def _tree_sig(tree: Any) -> str:
    import jax

    treedef = jax.tree_util.tree_structure(tree)
    leaves = jax.tree_util.tree_leaves(tree)
    return f"{treedef}{{{';'.join(_leaf_sig(x) for x in leaves)}}}"


def _canon_mesh(mesh: Any) -> Tuple[Tuple[str, int], ...]:
    import jax

    if mesh is None:
        return (("device", jax.device_count()),)
    if hasattr(mesh, "axis_names") and hasattr(mesh, "devices"):
        # jax.sharding.Mesh
        return tuple(
            (str(name), int(size))
            for name, size in zip(mesh.axis_names, mesh.devices.shape)
        )
    if isinstance(mesh, Mapping):
        return tuple((str(k), int(v)) for k, v in mesh.items())
    # sequence of (axis_name, size) pairs
    return tuple((str(k), int(v)) for k, v in mesh)


def _canon_chip(chip: Any) -> Tuple[Any, ...]:
    from beforeholiday_tpu.monitor import roofline as _roofline

    spec = _roofline._resolve_chip(chip)
    return (
        spec.name,
        float(spec.peak_tflops),
        float(spec.hbm_gbs),
        spec.fp8_peak_tflops,
    )


def tuning_key(
    model: Any,
    example_args: Optional[Sequence[Any]] = None,
    *,
    example_kwargs: Optional[Mapping[str, Any]] = None,
    mesh: Any = None,
    chip: Any = None,
) -> TuningKey:
    """Build the stable tuning key for ``(model, mesh, chip)``.

    ``model`` is either a pytree (typically the params — keyed on structure
    + leaf shapes/dtypes) or a callable plus ``example_args``, in which case
    ``jax.eval_shape`` contributes the abstract inputs AND outputs.
    ``mesh`` accepts a ``jax.sharding.Mesh``, a ``{axis: size}`` mapping, a
    sequence of ``(axis, size)`` pairs, or None (single flat device axis).
    ``chip`` accepts a :class:`~beforeholiday_tpu.monitor.roofline.ChipSpec`,
    a registered spec name, or None (the backend-resolved default — TPU
    roofline on TPU, CPU proxy elsewhere)."""
    return TuningKey(
        model=_abstract_signature(model, example_args, example_kwargs),
        mesh=_canon_mesh(mesh),
        chip=_canon_chip(chip),
    )
