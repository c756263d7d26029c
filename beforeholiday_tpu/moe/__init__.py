"""Mixture-of-Experts: GShard/Switch expert parallelism on the mesh.

A departure from the reference framework (which has no MoE story): top-k
routing with static capacity (``moe.router``), the grouped expert FFN as one
batched einsum over a stacked arena-friendly tree (``moe.experts``), and
expert-parallel dispatch/combine over the ledgered ``all_to_all`` on the
``expert`` mesh axis (``moe.dispatch``) — composing with DP/TP/PP/CP on a 4D
``make_moe_mesh(data, tensor, pipeline, expert)`` carve, and with the
``("slice", "intra")`` hierarchy for multi-slice routing. See PAPERS.md
(GShard, Switch Transformer) and the README's **Mixture-of-Experts**
section. ``moe.dropless`` is the other recipe: top-k of many narrow experts,
no capacity and no dropped token, rows sorted by expert into a grouped
matmul, the layer told which experts it holds.
"""

from beforeholiday_tpu.moe.dispatch import (
    dense_oracle,
    expert_all_to_all,
    moe_layer,
)
from beforeholiday_tpu.moe.dropless import (
    dropless_experts,
    dropless_moe,
    route_topk,
)
from beforeholiday_tpu.moe.experts import (
    expert_ffn,
    expert_param_specs,
    init_experts,
)
from beforeholiday_tpu.moe.router import (
    MoEConfig,
    RouterDecision,
    dense_gates,
    route,
    router_logits,
)

__all__ = [
    "MoEConfig",
    "RouterDecision",
    "dense_gates",
    "dense_oracle",
    "dropless_experts",
    "dropless_moe",
    "expert_all_to_all",
    "expert_ffn",
    "expert_param_specs",
    "init_experts",
    "moe_layer",
    "route",
    "route_topk",
    "router_logits",
]
