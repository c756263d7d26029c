"""Expert-parallel dispatch/combine over the ledgered ``all_to_all``.

GShard's expert parallelism (Lepikhin et al. 2020 §3.3, see PAPERS.md): each
rank routes its LOCAL tokens among all ``E`` global experts, scatters them
into a ``(E, capacity, D)`` slot tensor, and one ``all_to_all`` over the
``expert`` mesh axis re-shards that tensor from expert-major to rank-major —
every rank ends up holding ``E/ep`` experts' slots from ALL ``ep`` peers
(``(E/ep, ep*capacity, D)``). The grouped FFN runs, and the inverse
``all_to_all`` brings each token's expert outputs home for the weighted
combine. Both hops go through ``monitor.comms.all_to_all``, so the routing
traffic lands in ``comms_summary()`` per site (``moe.dispatch`` /
``moe.combine``) and per interconnect tier like every other collective here.

Two-level routing (``hierarchical=True``): when the expert axis is the
``("slice", "intra")`` pair, the joint all_to_all decomposes into a
slice-stage exchange (booked on the DCN tier) followed by an intra-stage
exchange (ICI tier), with a transpose in between that restores the joint
slice-major chunk order — the decomposition is BITWISE-equal to the joint
collective (it is pure data movement; ``tests/test_moe.py`` pins it), and
the per-tier ledger split shows how much of the dispatch payload actually
crosses the slow tier.

Bitwise-parity contract (the subsystem's keystone, held by
``tests/test_moe.py``): at sufficient capacity —
``route(...).drop_fraction == 0`` — the FORWARD pass of :func:`moe_layer` on
an expert-parallel mesh equals :func:`dense_oracle` bitwise. The chain:
routing is per-group and mesh-independent; the all_to_all pair is a pure
permutation; the grouped FFN is row-stable among slabs the backend gives
the same GEMM kernel (on XLA:CPU the reduction order of a GEMM depends on
its row count: compare at the distributed slab's ``ep * capacity`` rows,
``tests/test_moe.py::test_expert_parallel_bitwise``); the dispatch scatter and combine gather are 0/1 contractions with at
most one nonzero term per output element (exact copies under IEEE, any
grouping); and the final gate-weighted sum is spelled as the SAME
``(T, E) x (E, T, D)`` einsum in both paths, so XLA lowers one kernel shape
over bitwise-identical inputs. Drop
accounting when capacity is NOT sufficient follows the analytic bound
instead: a group that concentrates ``n_e`` first-choice tokens on expert
``e`` keeps exactly ``min(n_e, capacity)`` of them.

Backward is bitwise only where the reduction structure matches: router-weight
and token (input) gradients are per-token contractions with identical shapes
in both paths and come out bitwise at matched granularity. Expert WEIGHT
gradients contract over capacity slots in the MoE path but over tokens in the
dense path — a different reduction grouping, so they agree to f32
reduction-order tolerance (~1e-7 relative), not bitwise; same for any
cross-layout comparison (ep=1 vs ep=4 reduces over ``C`` vs ``ep*C`` slots).
Tests pin the bitwise set exactly and bound the rest.

Remat: the dispatched and combined activations carry ``checkpoint_name``
tags (``remat.moe_dispatch`` / ``remat.moe_combine``, members of
``remat.policies.BOUNDARY_TAGS``), so the ``"save_boundaries"`` policy saves
the two all_to_all boundaries and recomputes the expert FFN between them —
the collectives are the expensive thing to replay, the einsums are not.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from beforeholiday_tpu.moe.experts import expert_ffn
from beforeholiday_tpu.moe.router import (
    MoEConfig,
    dense_gates,
    route,
    router_logits,
)
from beforeholiday_tpu.monitor import comms
from beforeholiday_tpu.parallel.bucketing import static_axis_size
from beforeholiday_tpu.parallel.parallel_state import hierarchical_axes
from beforeholiday_tpu.remat.policies import (
    TAG_MOE_COMBINE,
    TAG_MOE_DISPATCH,
)

__all__ = [
    "dense_oracle",
    "expert_all_to_all",
    "moe_layer",
]

_F32 = jnp.float32


def _tiers(axis_name: Any, hierarchical: bool) -> Optional[Tuple[str, str]]:
    """Resolve the two-stage decomposition: the ``(slow, fast)`` axis pair
    when ``hierarchical`` is on, else None (joint collective)."""
    if not hierarchical:
        return None
    pair = hierarchical_axes(axis_name)
    if pair is None:
        raise ValueError(
            "hierarchical=True needs a (slice, intra) expert-axis pair, "
            f"got {axis_name!r}"
        )
    return pair


def expert_all_to_all(
    x: jax.Array,
    axis_name: Any,
    *,
    site: str,
    inverse: bool = False,
    hierarchical: bool = False,
) -> jax.Array:
    """The expert-parallel reshard: ``(E, C, D) -> (E/ep, ep*C, D)``
    (``inverse=True`` undoes it). Tiled all_to_all splitting the expert dim
    and concatenating received capacity chunks in rank order.

    Hierarchical form: slice-stage then intra-stage, each ``1/tier_size`` of
    the expert dim, with the received-chunk nesting transposed from
    ``(intra, slice, C)`` back to the joint collective's slice-major
    ``(slice, intra, C)`` order — bitwise-equal to the joint all_to_all,
    but the ledger books the slice stage on the DCN tier and the intra
    stage on ICI separately."""
    tiers = _tiers(axis_name, hierarchical)
    if tiers is None:
        return comms.all_to_all(
            x, axis_name, *((1, 0) if inverse else (0, 1)), tiled=True,
            site=site,
        )
    slow, fast = tiers
    S, I = static_axis_size(slow), static_axis_size(fast)
    if not inverse:
        E, C, D = x.shape
        z = comms.all_to_all(x, slow, 0, 1, tiled=True, site=site + ".slice")
        z = comms.all_to_all(z, fast, 0, 1, tiled=True, site=site + ".intra")
        El = E // (S * I)
        return z.reshape(El, I, S, C, D).transpose(0, 2, 1, 3, 4).reshape(
            El, S * I * C, D
        )
    El, PC, D = x.shape
    C = PC // (S * I)
    z = x.reshape(El, S, I, C, D).transpose(0, 2, 1, 3, 4).reshape(
        El, I * S * C, D
    )
    z = comms.all_to_all(z, fast, 1, 0, tiled=True, site=site + ".intra")
    return comms.all_to_all(z, slow, 1, 0, tiled=True, site=site + ".slice")


def moe_layer(
    x: jax.Array,
    w_router: jax.Array,
    expert_params: dict,
    cfg: MoEConfig,
    *,
    expert_axis: Any = None,
    tensor_axis: Optional[str] = None,
    hierarchical: bool = False,
    capacity: Optional[int] = None,
    emulate_tensor: int = 1,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One MoE FFN layer over one routing group.

    ``x``: ``(T, D)`` — the tokens LOCAL to this rank (callers flatten
    ``(B, S, D)`` first). With ``expert_axis`` bound inside shard_map,
    ``expert_params`` leaves are the local ``E/ep`` expert shard and the
    dispatch/combine all_to_all pair runs; with ``expert_axis=None`` the
    full stacked tree computes locally (the single-device form the parity
    oracle compares against). ``tensor_axis`` threads to the expert FFN's
    Megatron column/row split; ``emulate_tensor`` is its single-device
    chunked spelling (for bitwise references — see ``expert_ffn``).

    Returns ``(y, aux)`` — ``y (T, D)`` in x's dtype (dropped tokens get an
    all-zero ``y`` row: the caller's residual add is the pass-through), and
    ``aux`` holding this group's ``moe_aux_loss`` / ``moe_z_loss`` /
    ``moe_drop_fraction`` scalars, keyed to match ``TrainMonitor``'s spec.
    """
    T, D = x.shape
    if capacity is None:
        capacity = cfg.capacity(T)
    if expert_axis is not None:
        ep = static_axis_size(expert_axis)
        if cfg.n_experts % ep != 0:
            raise ValueError(
                f"n_experts ({cfg.n_experts}) must divide evenly over the "
                f"expert-parallel world ({ep})"
            )

    dec = route(router_logits(x, w_router), cfg, capacity)

    # scatter tokens into their (expert, slot) positions; each slot holds at
    # most one token, so the contraction is an exact copy (or an exact zero)
    xd = jnp.einsum(
        "tec,td->ecd", dec.dispatch.astype(x.dtype), x,
        preferred_element_type=_F32,
    ).astype(x.dtype)
    if expert_axis is not None:
        xd = expert_all_to_all(
            xd, expert_axis, site="moe.dispatch", hierarchical=hierarchical
        )
    xd = _checkpoint_name(xd, TAG_MOE_DISPATCH)

    y = expert_ffn(
        expert_params, xd, tensor_axis=tensor_axis,
        emulate_tensor=emulate_tensor,
    )

    if expert_axis is not None:
        y = expert_all_to_all(
            y, expert_axis, site="moe.combine", inverse=True,
            hierarchical=hierarchical,
        )
    y = _checkpoint_name(y, TAG_MOE_COMBINE)

    # combine in two steps so the FINAL contraction has the exact shape the
    # dense oracle uses. Step 1 is a pure 0/1 gather — each (t, e) pair owns
    # at most one slot, so every output element is an exact copy (or exact
    # zero) no matter how XLA groups the reduction. Step 2 is the weighted
    # sum over experts, ``(T, E) x (E, T, D) -> (T, D)`` — the SAME einsum
    # the oracle lowers, on bitwise-identical values at every chosen slot.
    # (A single fused ``tec,ecd->td`` contraction is NOT bitwise-stable
    # against the oracle: the gate products pick up different FMA/lane
    # groupings between a length-E·C and a length-E reduction.)
    y_tok = jnp.einsum(
        "tec,ecd->etd", dec.dispatch, y.astype(_F32),
        preferred_element_type=_F32,
    )
    gates = jnp.sum(dec.combine, axis=-1)  # (T, E) kept gate values
    out = jnp.einsum("te,etd->td", gates, y_tok, preferred_element_type=_F32)
    aux = {
        "moe_aux_loss": dec.aux_loss,
        "moe_z_loss": dec.z_loss,
        "moe_drop_fraction": dec.drop_fraction,
    }
    return out.astype(x.dtype), aux


def dense_oracle(
    x: jax.Array,
    w_router: jax.Array,
    expert_params: dict,
    cfg: MoEConfig,
    *,
    tensor_parallel: int = 1,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The no-drop dense reference: EVERY expert computes EVERY token, then
    each token's top-k gates (no capacity, no dropping) weight the outputs.

    ``tensor_parallel`` spells the expert FFN the way a ``tp``-way Megatron
    split computes it — ``d_ff`` column chunks through gelu, row-chunk
    partial products accumulated IN RANK ORDER — so the oracle matches the
    distributed row-parallel psum bitwise (the CPU backend reduces psum in
    linear rank order; the repo's hierarchical-collective engines pin the
    same contract).

    At sufficient capacity :func:`moe_layer`'s forward output must equal
    this bitwise (see the module docstring for the backward contract);
    ``aux`` reports ``moe_drop_fraction = 0`` by construction."""
    T, D = x.shape
    E = cfg.n_experts
    gates, aux_loss, z_loss = dense_gates(router_logits(x, w_router), cfg)

    wi, bi = expert_params["wi"], expert_params["bi"]
    wo, bo = expert_params["wo"], expert_params["bo"]
    F = wi.shape[-1]
    if F % tensor_parallel != 0:
        raise ValueError(
            f"d_ff ({F}) must divide the emulated tensor world "
            f"({tensor_parallel})"
        )
    chunk = F // tensor_parallel
    xb = jnp.broadcast_to(x[None], (E, T, D))

    y = None
    for r in range(tensor_parallel):
        sl = slice(r * chunk, (r + 1) * chunk)
        h = jnp.einsum(
            "etd,edf->etf", xb, wi[:, :, sl].astype(x.dtype),
            preferred_element_type=_F32,
        ).astype(x.dtype) + bi[:, sl].astype(x.dtype)[:, None, :]
        h = jax.nn.gelu(h)
        part = jnp.einsum(
            "etf,efd->etd", h, wo[:, sl, :].astype(x.dtype),
            preferred_element_type=_F32,
        ).astype(x.dtype)
        y = part if y is None else y + part
    y = y + bo.astype(x.dtype)[:, None, :]

    out = jnp.einsum(
        "te,etd->td", gates, y.astype(_F32), preferred_element_type=_F32
    )
    aux = {
        "moe_aux_loss": aux_loss,
        "moe_z_loss": z_loss,
        "moe_drop_fraction": jnp.zeros((), _F32),
    }
    return out.astype(x.dtype), aux
