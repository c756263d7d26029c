"""The 4D-mesh MoE workload: data x tensor x pipeline x expert, with a
single-device bitwise reference.

A compact two-stage stack exercising every axis of
``parallel_state.make_moe_mesh`` at once:

* **stage 0** — a dense gelu-FFN with Megatron tensor parallelism (column
  ``w1`` / row ``w2``, one ledgered psum over ``tensor``), plus residual;
* **pipe boundary** — stage 0's output crosses the ``pipe`` axis by
  ``ppermute`` (rank 0 -> rank 1), the repo's test-pipeline idiom: every
  pipe rank runs the whole body, non-owning stages compute on zeros, and a
  masked psum replicates the real stage-1 output everywhere (adding exact
  zeros, so the collect is bitwise-free);
* **stage 1** — the MoE layer (``moe.moe_layer``): expert-parallel
  dispatch/combine over ``expert``, tensor parallelism INSIDE the expert
  FFN over ``tensor``, plus residual.

Tokens are sharded over ``(data, expert)`` jointly — each (data, expert)
mesh coordinate routes its own token group, GShard's "group = local batch".

:func:`moe_stack_reference` replays the same math on one device: the tensor
split as ``emulate_tensor`` column/row chunks accumulated in rank order
(CPU psum order), the groups as a Python loop in mesh order. At sufficient
capacity the distributed forward equals the reference BITWISE for any
(data, tensor, pipe, expert) carve — the parity
``tests/test_moe.py::test_4d_mesh_parity`` asserts.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from beforeholiday_tpu.moe import MoEConfig, init_experts, moe_layer
from beforeholiday_tpu.monitor import comms
from beforeholiday_tpu.parallel.parallel_state import (
    DATA_AXIS,
    EXPERT_AXIS,
    PIPE_AXIS,
    TENSOR_AXIS,
)

__all__ = [
    "AUX_KEYS",
    "init_moe_stack",
    "moe_stack_forward",
    "moe_stack_param_specs",
    "moe_stack_reference",
]

AUX_KEYS = ("moe_aux_loss", "moe_z_loss", "moe_drop_fraction")

_F32 = jnp.float32


def init_moe_stack(
    key: jax.Array, cfg: MoEConfig, d_model: int, d_ff: int
) -> dict:
    """fp32 params: stage-0 dense FFN + stage-1 router/experts."""
    k0, k1, k2 = jax.random.split(key, 3)
    std = 1.0 / np.sqrt(d_model)
    return {
        "stage0": {
            "w1": jax.random.normal(k0, (d_model, d_ff), _F32) * std,
            "b1": jnp.zeros((d_ff,), _F32),
            "w2": jax.random.normal(k1, (d_ff, d_model), _F32) * std,
            "b2": jnp.zeros((d_model,), _F32),
        },
        "moe": {
            "w_router": jax.random.normal(
                k2, (d_model, cfg.n_experts), _F32
            ) * std,
            "experts": init_experts(
                jax.random.fold_in(key, 3), cfg.n_experts, d_model, d_ff
            ),
        },
    }


def moe_stack_param_specs(
    *, tensor_axis: Optional[str] = TENSOR_AXIS,
    expert_axis: Optional[str] = EXPERT_AXIS,
) -> dict:
    """shard_map in_specs for the param tree: Megatron column/row over
    ``tensor``, experts over ``expert`` (leading dim), the rest replicated."""
    from beforeholiday_tpu.moe import expert_param_specs

    t, e = tensor_axis, expert_axis
    return {
        "stage0": {
            "w1": P(None, t),
            "b1": P(t),
            "w2": P(t, None),
            "b2": P(None),
        },
        "moe": {
            "w_router": P(None, None),
            "experts": expert_param_specs(expert_axis=e, tensor_axis=t),
        },
    }


def _stage0_ffn(
    sp: dict,
    x: jax.Array,
    *,
    tensor_axis: Optional[str] = None,
    emulate_tensor: int = 1,
) -> jax.Array:
    """Dense gelu-FFN, distributed (``tensor_axis``: local column/row shards
    closed by a ledgered psum) or single-device chunk-emulated
    (``emulate_tensor``: same chunks, partials added in rank order)."""
    if emulate_tensor > 1:
        F = sp["w1"].shape[-1]
        chunk = F // emulate_tensor
        y = None
        for r in range(emulate_tensor):
            sl = slice(r * chunk, (r + 1) * chunk)
            h = jax.nn.gelu(x @ sp["w1"][:, sl] + sp["b1"][sl])
            part = h @ sp["w2"][sl, :]
            y = part if y is None else y + part
        return y + sp["b2"]
    h = jax.nn.gelu(x @ sp["w1"] + sp["b1"])
    y = h @ sp["w2"]
    if tensor_axis is not None:
        y = comms.psum(y, tensor_axis, site="moe_model.stage0.row_parallel")
    return y + sp["b2"]


def moe_stack_forward(
    params: dict,
    x: jax.Array,
    cfg: MoEConfig,
    *,
    pipe_axis: Optional[str] = PIPE_AXIS,
    tensor_axis: Optional[str] = TENSOR_AXIS,
    expert_axis=EXPERT_AXIS,
    hierarchical: bool = False,
    capacity: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The distributed body — call INSIDE shard_map on a
    ``make_moe_mesh`` carve. ``x``: this rank's ``(T_local, D)`` token
    group. Any axis argument may be None when that mesh axis is degenerate
    (carved away by ``make_moe_mesh``).

    Returns ``(y, aux)``: the stage-1 output (replicated over ``pipe`` by
    the masked-psum collect) and this group's ``(1, 3)`` aux row —
    ``AUX_KEYS`` order — for gathering over ``(data, expert)``."""
    y0 = x + _stage0_ffn(params["stage0"], x, tensor_axis=tensor_axis)

    if pipe_axis is not None:
        # stage boundary: rank 0's output crosses to rank 1; rank 0 receives
        # zeros (no inbound edge) and runs stage 1 on them — masked out of
        # the collect below, so the wasted lane never touches the result
        inp1 = comms.ppermute(
            y0, pipe_axis, [(0, 1)], site="moe_model.pipe_boundary"
        )
        owner = jax.lax.axis_index(pipe_axis) == 1
    else:
        inp1 = y0
        owner = None

    y1, aux = moe_layer(
        inp1,
        params["moe"]["w_router"],
        params["moe"]["experts"],
        cfg,
        expert_axis=expert_axis,
        tensor_axis=tensor_axis,
        hierarchical=hierarchical,
        capacity=capacity,
    )
    out = inp1 + y1
    aux_row = jnp.stack([aux[k] for k in AUX_KEYS]).reshape(1, 3)

    if pipe_axis is not None:
        # replicate the owning stage's result to every pipe rank: everything
        # else contributes exact zeros, so the psum is a bitwise no-op on
        # the payload
        zero = jnp.zeros_like(out)
        out = comms.psum(
            jnp.where(owner, out, zero), pipe_axis, site="moe_model.collect"
        )
        aux_row = comms.psum(
            jnp.where(owner, aux_row, jnp.zeros_like(aux_row)),
            pipe_axis, site="moe_model.collect_aux",
        )
    return out, aux_row


def moe_stack_reference(
    params: dict,
    x: jax.Array,
    cfg: MoEConfig,
    *,
    groups: int = 1,
    tensor: int = 1,
    capacity: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Single-device replay of :func:`moe_stack_forward` over the FULL token
    batch: ``groups`` (= data*expert ranks) routing groups in mesh order,
    the tensor split as ``tensor`` emulated chunks. Bitwise-equal to the
    gathered distributed output at sufficient capacity."""
    N, D = x.shape
    if N % groups != 0:
        raise ValueError(f"tokens ({N}) must divide routing groups ({groups})")
    Tl = N // groups
    outs, aux_rows = [], []
    for g in range(groups):
        xg = x[g * Tl:(g + 1) * Tl]
        y0 = xg + _stage0_ffn(params["stage0"], xg, emulate_tensor=tensor)
        y1, aux = moe_layer(
            y0,
            params["moe"]["w_router"],
            params["moe"]["experts"],
            cfg,
            emulate_tensor=tensor,
            capacity=capacity,
        )
        outs.append(y0 + y1)
        aux_rows.append(jnp.stack([aux[k] for k in AUX_KEYS]))
    return jnp.concatenate(outs), jnp.stack(aux_rows)


def data_specs(
    *, data_axis: Optional[str] = DATA_AXIS,
    expert_axis: Optional[str] = EXPERT_AXIS,
) -> Tuple[P, P]:
    """(in_spec for x, out_spec for y): tokens sharded jointly over the
    present group axes, data-major — the same order the reference's group
    loop walks."""
    axes = tuple(a for a in (data_axis, expert_axis) if a is not None)
    spec = P(axes if axes else None, None)
    return spec, spec
