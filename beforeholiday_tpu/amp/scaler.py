"""Dynamic loss scaling — functional port of ``apex.amp.scaler.LossScaler``.

The reference keeps a device-side ``_overflow_buf``, unscales through
``multi_tensor_scale``, and defers ``.item()`` to scale-update time
(ref: apex/amp/scaler.py:42-226). Under XLA any host readback would stall the
pipeline, so here the whole scaler lives in device state: ``scale`` and the
unskipped-step counter are traced arrays, overflow detection rides the fused
unscale kernel's flag, and the skip-step is a ``where`` select threaded into the
optimizer (the carried-boolean design from SURVEY.md §7 "hard parts").
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from beforeholiday_tpu.monitor.spans import span
from beforeholiday_tpu.ops import multi_tensor as mt


@dataclasses.dataclass(frozen=True)
class LossScaler:
    """Static scaler config; all dynamics live in the state pytree.

    Defaults match the reference: dynamic scaling starts at 2**16, doubles
    every 2000 clean steps, halves on overflow
    (ref: apex/amp/scaler.py:47-63,206-226).
    """

    loss_scale: Any = "dynamic"  # "dynamic" | float
    init_scale: float = 2.0**16
    scale_factor: float = 2.0
    scale_window: int = 2000
    min_loss_scale: Optional[float] = None
    max_loss_scale: float = 2.0**24
    # O6: carry the fp8 delayed-scaling amax history (ops.quantized) inside
    # this state pytree — one rolling row per HISTORY_ROLES entry — so the
    # quantization scales ride the exact same skip/rollback/checkpoint
    # machinery (StepGuard snapshots, state_dict) as the loss scale itself.
    quantized: bool = False
    amax_history_len: int = 16
    amax_margin: float = 2.0

    @property
    def dynamic(self) -> bool:
        return self.loss_scale == "dynamic"

    def init(self) -> Dict[str, jax.Array]:
        scale = self.init_scale if self.dynamic else float(self.loss_scale)
        state = {
            "scale": jnp.float32(scale),
            "unskipped": jnp.int32(0),
            "consecutive_overflows": jnp.int32(0),
        }
        if self.quantized:
            from beforeholiday_tpu.ops.quantized import init_amax_history

            state["amax_history"] = init_amax_history(self.amax_history_len)
        return state

    def at_min_scale(self, state) -> jax.Array:
        """True when the scale cannot shrink further — the reference halves
        silently into the ``min_loss_scale`` clamp forever (scaler.py:210-214);
        exposing the floor lets the step guard's rollback key off
        "still overflowing AND shrinking is exhausted". A static scale can
        never shrink; a dynamic scaler without a floor always can."""
        if not self.dynamic:
            return jnp.bool_(True)
        if self.min_loss_scale is None:
            return jnp.bool_(False)
        return state["scale"] <= self.min_loss_scale

    def scale_loss(self, loss: jax.Array, state) -> jax.Array:
        """loss.float() * loss_scale (ref: apex/amp/handle.py:113)."""
        return loss.astype(jnp.float32) * state["scale"]

    def unscale(self, grads, state, *, impl=None) -> Tuple[Any, jax.Array]:
        """Unscale a grad pytree by 1/scale; returns (fp32 grads, found_inf).

        Overflow detection is the fused scale kernel's non-finite flag, exactly
        the reference's ``multi_tensor_scale`` + ``_overflow_buf`` path
        (apex/amp/scaler.py:114-126). Gradients come back fp32 (master-grad
        dtype), like unscale-into-master-grads.
        """
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        out = list(leaves)
        by_dtype: Dict[Any, list] = {}
        for i, g in enumerate(leaves):
            by_dtype.setdefault(g.dtype, []).append(i)
        # one scope for every caller: the multiply and the non-finite check
        with span("amp_unscale"):
            inv = 1.0 / state["scale"]
            found = jnp.bool_(False)
            for dt, idx in by_dtype.items():
                scaled, flag = mt.multi_tensor_scale(
                    [leaves[i] for i in idx], inv, out_dtype=jnp.float32, impl=impl
                )
                for i, s in zip(idx, scaled):
                    out[i] = s
                found = found | flag
        return jax.tree_util.tree_unflatten(treedef, out), found

    def quantized_scales(self, state):
        """(scale_w, scale_g) for this step's :func:`ops.quantized
        .quantized_scope`, derived from the state's amax history. States
        without the key (or a non-quantized scaler) get (None, None)."""
        if not (isinstance(state, dict) and "amax_history" in state):
            return None, None
        from beforeholiday_tpu.ops.quantized import scales_from_history

        return scales_from_history(
            state["amax_history"], margin=self.amax_margin
        )

    def update(self, state, found_inf, *, amax=None) -> Dict[str, jax.Array]:
        """Post-step scale update (ref: apex/amp/scaler.py:206-226).

        overflow → scale /= factor, counter reset; scale_window clean steps →
        scale *= factor. Pure ``where`` arithmetic — no host sync, jittable.

        ``consecutive_overflows`` counts back-to-back skipped steps (reset on
        any clean step) for BOTH dynamic and static scales: once the dynamic
        scale is clamped at ``min_loss_scale`` the shrink is a silent no-op,
        and this counter is the visible evidence — the step guard's rollback
        keys off it together with :meth:`at_min_scale`. Old states without the
        key are tolerated (pre-guard checkpoints).

        ``amax`` optionally rolls this step's (weight, grad) amax
        observations into the fp8 delayed-scaling history (states carrying
        ``"amax_history"`` only; non-finite observations are dropped inside
        ``update_amax_history``, so an overflow step never poisons the
        scales — it only trips the skip above).
        """
        skip = jnp.asarray(found_inf) != 0
        consec = jnp.where(
            skip,
            state.get("consecutive_overflows", jnp.int32(0)) + 1,
            0,
        ).astype(jnp.int32)
        extra = {}
        if amax is not None and isinstance(state, dict) and "amax_history" in state:
            from beforeholiday_tpu.ops.quantized import update_amax_history

            extra["amax_history"] = update_amax_history(
                state["amax_history"], amax[0], amax[1]
            )
        if not self.dynamic:
            return {**state, "consecutive_overflows": consec, **extra}
        scale, unskipped = state["scale"], state["unskipped"]

        shrunk = scale / self.scale_factor
        if self.min_loss_scale is not None:
            shrunk = jnp.maximum(shrunk, self.min_loss_scale)
        unskipped_next = jnp.where(skip, 0, unskipped + 1)
        grow = unskipped_next >= self.scale_window
        grown = jnp.minimum(scale * self.scale_factor, self.max_loss_scale)

        new_scale = jnp.where(skip, shrunk, jnp.where(grow, grown, scale))
        new_unskipped = jnp.where(grow, 0, unskipped_next)
        return {
            **{k: v for k, v in state.items()},
            "scale": new_scale,
            "unskipped": new_unskipped,
            "consecutive_overflows": consec,
            **extra,
        }

    # --- checkpointing (ref: apex/amp/frontend.py:434-473) ----------------------

    def state_dict(self, state) -> Dict[str, Any]:
        out = {
            "loss_scale": float(state["scale"]),
            "unskipped": int(state["unskipped"]),
            "consecutive_overflows": int(
                state.get("consecutive_overflows", 0)
            ),
        }
        if isinstance(state, dict) and "amax_history" in state:
            # JSON-ready nested lists; pre-O6 loaders ignore the extra key
            import numpy as _np

            out["amax_history"] = _np.asarray(
                state["amax_history"], dtype=_np.float32
            ).tolist()
        return out

    def load_state_dict(self, state_dict) -> Dict[str, jax.Array]:
        # accept pre-guard dicts without the counter — checkpoints round-trip
        # across the schema change in both directions
        out = {
            "scale": jnp.float32(state_dict["loss_scale"]),
            "unskipped": jnp.int32(state_dict["unskipped"]),
            "consecutive_overflows": jnp.int32(
                state_dict.get("consecutive_overflows", 0)
            ),
        }
        if "amax_history" in state_dict:
            out["amax_history"] = jnp.asarray(
                state_dict["amax_history"], jnp.float32
            )
        elif self.quantized:
            # pre-O6 checkpoint into a quantized scaler: fresh history, the
            # delayed scales re-warm from just-in-time fallbacks in one window
            from beforeholiday_tpu.ops.quantized import init_amax_history

            out["amax_history"] = init_amax_history(self.amax_history_len)
        return out
