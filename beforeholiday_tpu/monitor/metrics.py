"""Device-side training metrics as a pytree — observability that rides INSIDE
the jitted step.

The reference introspects eagerly: LAMB reads per-tensor norms off live CUDA
tensors, the scaler ``.item()``s its overflow flag, DDP prints from backward
hooks. Under jit none of that exists — a metric is only observable if it is
*state*, threaded through the step like the scaler's scale or the guard's
health. So ``TrainMonitor`` follows the house pattern (static config class +
state pytree, same as ``LossScaler``/``StepGuard``):

* ``init()``            → a dict of scalar jnp arrays (the ``Metrics`` pytree)
* ``update(...)``       → pure-jnp fold of this step's observations
* ``aggregate(...)``    → ``lax.psum``/``pmax``/``pmin`` cross-rank reduction,
                          riding the same ICI collectives as DDP
* ``pack(...)``         → ONE flat fp32 vector, so the host drains every
                          metric with a single readback (the no-extra-sync
                          contract ``tests/test_no_host_sync.py`` enforces)

Nothing here may read a value back to the host; the only sanctioned readbacks
live in ``monitor/export.py`` (``MetricsLogger.drain``) and the
``state_dict``-family methods below.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

Metrics = Dict[str, jax.Array]

_F32 = jnp.float32
_I32 = jnp.int32


def global_norm(tree: Any) -> jax.Array:
    """fp32 L2 norm over every leaf of a pytree (the multi_tensor_l2norm
    quantity, computed in plain jnp so it composes with any grad/update
    structure)."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((), _F32)
    sq = sum(jnp.sum(jnp.square(g.astype(_F32))) for g in leaves)
    return jnp.sqrt(sq)


class TrainMonitor:
    """Config + pure functions over the ``Metrics`` pytree.

    The metric set is fixed at construction (``_SPEC``): each key carries its
    dtype and its cross-rank reduction (``mean`` → psum/world, ``max`` → pmax,
    ``min`` → pmin). Counters folded in from ``StepGuard.health`` use max:
    ranks run the guard in lockstep, so max is the consensus value and stays
    correct even if a rank ever diverges.
    """

    # (key, dtype, cross-rank reduction) — ORDER IS THE PACK ORDER and is
    # part of the checkpoint/export contract; append only.
    _SPEC: Tuple[Tuple[str, Any, str], ...] = (
        ("steps", _I32, "max"),
        ("loss", _F32, "mean"),
        ("loss_ema", _F32, "mean"),
        ("grad_norm", _F32, "mean"),
        ("grad_norm_ema", _F32, "mean"),
        ("grad_norm_max", _F32, "max"),
        ("param_norm", _F32, "mean"),
        ("update_norm", _F32, "mean"),
        ("update_ratio", _F32, "mean"),
        ("loss_scale", _F32, "min"),
        ("skipped_total", _I32, "max"),
        ("consecutive_overflows", _I32, "max"),
        ("rollbacks_total", _I32, "max"),
        ("last_skip_reason", _I32, "max"),
        ("bn_shift_dominated", _I32, "max"),
        # MoE router observability (beforeholiday_tpu.moe): the load-balance
        # and z losses plus the capacity-drop fraction, mean-reduced across
        # ranks (each rank routes its own token group)
        ("moe_aux_loss", _F32, "mean"),
        ("moe_z_loss", _F32, "mean"),
        ("moe_drop_fraction", _F32, "mean"),
    )

    def __init__(self, *, ema_decay: float = 0.99):
        assert 0.0 <= ema_decay < 1.0, "ema_decay must be in [0, 1)"
        self.ema_decay = float(ema_decay)

    # ------------------------------------------------------------------ keys
    @property
    def keys(self) -> Tuple[str, ...]:
        return tuple(k for k, _, _ in self._SPEC)

    # ------------------------------------------------------------------ init
    def init(self) -> Metrics:
        return {k: jnp.zeros((), dt) for k, dt, _ in self._SPEC}

    # ---------------------------------------------------------------- update
    def update(
        self,
        metrics: Metrics,
        *,
        loss: Optional[jax.Array] = None,
        grads: Any = None,
        params: Any = None,
        new_params: Any = None,
        scaler_state: Optional[Dict[str, jax.Array]] = None,
        health: Optional[Dict[str, jax.Array]] = None,
        moe: Optional[Dict[str, jax.Array]] = None,
    ) -> Metrics:
        """Fold one step's observations into the pytree. Pure jnp — safe under
        jit/shard_map/vmap. Every argument is optional: pass what the step
        has, the rest carries forward.

        ``new_params`` (post-update params) together with ``params`` yields
        the update norm and the update/param-norm ratio — the quantity LAMB
        computes per-layer for its trust ratio, here tracked globally as a
        training-health signal (a ratio drifting toward 1 means steps as
        large as the weights: divergence).
        """
        decay = jnp.asarray(self.ema_decay, _F32)
        first = metrics["steps"] == 0

        def ema(prev, v):
            # seed the EMA with the first observation instead of decaying
            # from zero (which would understate early values by 1/(1-decay))
            return jnp.where(first, v, decay * prev + (1.0 - decay) * v)

        m = dict(metrics)
        if loss is not None:
            v = jnp.asarray(loss, _F32)
            m["loss"] = v
            m["loss_ema"] = ema(metrics["loss_ema"], v)
        if grads is not None:
            g = global_norm(grads)
            m["grad_norm"] = g
            m["grad_norm_ema"] = ema(metrics["grad_norm_ema"], g)
            m["grad_norm_max"] = jnp.maximum(metrics["grad_norm_max"], g)
        if params is not None:
            p = global_norm(params)
            m["param_norm"] = p
            if new_params is not None:
                u = global_norm(
                    jax.tree.map(
                        lambda a, b: a.astype(_F32) - b.astype(_F32),
                        new_params,
                        params,
                    )
                )
                m["update_norm"] = u
                m["update_ratio"] = u / jnp.maximum(p, 1e-12)
        if scaler_state is not None:
            m["loss_scale"] = jnp.asarray(scaler_state["scale"], _F32)
        if moe is not None:
            # the aux dict moe_layer / GPT forward(return_aux=True) returns,
            # keys matching the spec directly
            for k in ("moe_aux_loss", "moe_z_loss", "moe_drop_fraction"):
                if k in moe:
                    m[k] = jnp.asarray(moe[k], _F32)
        if health is not None:
            for k in (
                "skipped_total",
                "consecutive_overflows",
                "rollbacks_total",
                "last_skip_reason",
                "bn_shift_dominated",
            ):
                if k in health:
                    m[k] = jnp.asarray(health[k], _I32)
        m["steps"] = metrics["steps"] + jnp.ones((), _I32)
        return m

    # ------------------------------------------------------------- aggregate
    def aggregate(self, metrics: Metrics, axis_name: str) -> Metrics:
        """Cross-rank reduction per each key's declared semantics. Must run
        inside a binding context for ``axis_name`` (shard_map/pmap) — the
        same place DDP's ``reduce_gradients`` runs, sharing its collectives.
        """
        world = jax.lax.axis_size(axis_name)
        out = dict(metrics)
        for k, dt, red in self._SPEC:
            v = metrics[k]
            if red == "mean":
                out[k] = (jax.lax.psum(v.astype(_F32), axis_name) / world).astype(dt)
            elif red == "max":
                out[k] = jax.lax.pmax(v, axis_name)
            elif red == "min":
                out[k] = jax.lax.pmin(v, axis_name)
            else:  # pragma: no cover - spec is class-internal
                raise ValueError(f"unknown reduction {red!r} for {k!r}")
        return out

    # ------------------------------------------------------------------ pack
    def pack(self, metrics: Metrics) -> jax.Array:
        """Stack every metric into ONE fp32 vector (pack order = ``_SPEC``
        order). Return this from the jitted step and hand it to
        ``MetricsLogger.log`` — draining it costs exactly one readback, the
        same budget as the bare-loss step already spends."""
        return jnp.stack([metrics[k].astype(_F32) for k in self.keys])

    def unpack_host(self, vec) -> Dict[str, float]:
        """Host-side inverse of ``pack`` over an ALREADY-FETCHED vector
        (a numpy array or list — never call this on a traced value). Integer
        metrics come back as Python ints."""
        import numpy as np

        vals = np.asarray(vec).tolist()
        assert len(vals) == len(self._SPEC), (
            f"packed vector has {len(vals)} entries, spec has {len(self._SPEC)}"
        )
        out: Dict[str, float] = {}
        for (k, dt, _), v in zip(self._SPEC, vals):
            out[k] = int(v) if dt == _I32 else float(v)
        return out

    # ------------------------------------------------------------ checkpoint
    def state_dict(self, metrics: Metrics) -> Dict[str, Any]:
        """Host-side snapshot (sanctioned sync point, same contract as the
        scaler/guard ``state_dict`` family)."""
        out: Dict[str, Any] = {}
        for k, dt, _ in self._SPEC:
            out[k] = int(metrics[k]) if dt == _I32 else float(metrics[k])
        return out

    def load_state_dict(self, state: Dict[str, Any]) -> Metrics:
        """Rebuild the device pytree from a snapshot. Unknown keys are
        ignored and missing keys default to zero, so checkpoints survive
        spec growth in either direction."""
        m = self.init()
        for k, dt, _ in self._SPEC:
            if k in state:
                m[k] = jnp.asarray(state[k], dt)
        return m
