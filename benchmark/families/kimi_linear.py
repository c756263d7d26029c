"""Family ``kimi_linear``: Kimi Delta Attention (the delta rule under a decay a
key channel, ``ops.kda``) three layers in four, latent attention without rotary
(192-wide queries and keys on 128-wide values, through the flash kernels) the
fourth, a leading dense SwiGLU layer and then top-k sigmoid-routed experts
beside an ungated shared one, an untied head, through the program's training
entry points.

The step is wired exactly as ``families/deepseek_v3.py`` wires its own
(``amp.initialize(.., "O5", arena_native=True)`` + ``FusedAdam`` +
``amp.scaled_value_and_grad`` + ``optimizer.step`` under ``remat.donate_step``),
on ``beforeholiday_tpu.models.kimi_linear``. The program's modules are imported
here at the top, before any reference or compile: a checkout without them fails
at once.

As in that family the state carries a fourth member beside ``(params,
optimizer, scaler)``: the MoE counters of the newest step and their sums, device
scalars written by the step itself (no host sync). ``counters()`` reads them
after the window; a step that dropped a routed row reports it as ``found_inf``,
so the window's ``failed_steps`` counts it.
"""

import jax
import jax.numpy as jnp

from beforeholiday_tpu.models import kimi_linear as model
from beforeholiday_tpu.moe import dropless  # noqa: F401  (must be there: see above)
from beforeholiday_tpu.ops import kda  # noqa: F401

from benchmark.reference import kimi_linear as reference
from benchmark.reference import optim

ITEMS_PER_ROW = "seq_len"
GUARDED_OPS = ("flash_attention", "layer_norm", "kda_rule", "deltanet_qkv", "deltanet_gate",
               "grouped_matmul")
_COUNTERS = model.COUNTERS + ("steps",)
_LAST = {}                           # the newest Program's newest state's counters

# configuration keys handed to the model as they are
_MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "first_layer", "first_k_dense_replace",
    "intermediate_size", "linear_attn_config", "num_attention_heads", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "mla_use_nope",
    "moe_intermediate_size", "num_experts_published", "num_experts", "first_expert",
    "num_shared_experts", "num_experts_per_token", "num_expert_group", "topk_group",
    "moe_renormalize", "moe_router_activation_func", "moe_rows_bound", "rms_norm_eps",
    "initializer_range", "remat_policy", "kda_chunk")


def model_config(cfg):
    return model.KimiLinearConfig(
        **{k: cfg[k] for k in _MODEL_KEYS},
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        dtype=jnp.dtype(cfg["compute_dtype"]))


def param_count(cfg):
    return model.param_count(model_config(cfg))


def weights(cfg, key):
    """Seeded float32 weights, flat, every value exactly a bfloat16: drawn by
    the reference's file, tensor by tensor, not by the program's ``init``."""
    return reference.weights(cfg, key)


def batch(cfg, rows, key):
    """``rows`` seeded sequences, ids from the vocabulary slice, and their
    next-token targets. Traceable."""
    tokens = jax.random.randint(key, (rows, cfg["seq_len"]), 0, cfg["vocab_size"], jnp.int32)
    return tokens, jnp.roll(tokens, -1, axis=-1)


def matmul_params_per_token(cfg):
    """Matmul parameters a token passes in one part of each kind, and in the
    head's slice: ``{"kda", "mla", "dense", "moe", "head"}``. ``kda`` is the mixer's
    four projections, its two low-rank pairs and ``W_b``; ``mla`` the latent mixer's
    four; ``moe`` the router, the shared expert and the held experts at the
    expected number a token reaches. The embedding's lookup is a gather and the
    depthwise convolutions are elementwise: neither is counted."""
    D, H, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    la = cfg["linear_attn_config"]
    Hl, d = la["num_heads"], la["head_dim"]
    Fm = cfg["moe_intermediate_size"]
    expected = cfg["num_experts_per_token"] * cfg["num_experts"] / cfg["num_experts_published"]
    return {
        "kda": 4 * D * Hl * d + 2 * (D * d + d * Hl * d) + D * Hl,
        "mla": D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D,
        "dense": 3 * D * cfg["intermediate_size"],
        "moe": D * cfg["num_experts_published"]
        + (cfg["num_shared_experts"] + expected) * 3 * D * Fm,
        "head": cfg["vocab_size"] * D,
    }


def _mixers(cfg, kind):
    return sum(mixer == kind for mixer, _ in reference.held(cfg))


def attention_flops_per_item(cfg):
    """Required causal attention operations per token, forward and backward, over
    the held latent layers: ``6 * heads * (qk width + v width)`` a kept key, ``(S +
    1) / 2`` keys a query under the mask (as ``families/deepseek_v3.py`` counts)."""
    widths = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return 6 * cfg["num_attention_heads"] * widths * (cfg["seq_len"] + 1) / 2 \
        * _mixers(cfg, "mla")


def kda_flops_per_item(cfg):
    """What the recurrence requires per token over the held KDA layers, whatever
    implements it: per head three ``d_k x d_v`` products (``S^T k``, the rank-one
    update, ``S^T q``) of 2 operations a multiply-add, and three times that for
    forward and backward (as ``families/qwen3_next.py`` counts the scalar rule:
    the decay itself is elementwise and is not counted)."""
    la = cfg["linear_attn_config"]
    per_head = 3 * 2 * la["head_dim"] * la["head_dim"]
    return 3 * per_head * la["num_heads"] * _mixers(cfg, "kda")


def model_flops_per_item(cfg):
    """Operations the forward and backward passes require per token: 6 per
    matmul parameter a token passes (the held experts at their expected rows),
    plus what the causal mask requires of attention and the recurrence of its
    state. Nothing recomputed; norms, convolutions and gates are not counted."""
    per = matmul_params_per_token(cfg)
    matmul = sum(per[mixer] + per[ffn] for mixer, ffn in reference.held(cfg)) + per["head"]
    return 6 * matmul + attention_flops_per_item(cfg) + kda_flops_per_item(cfg)


def reference_optimizer(cfg, cell):
    hyper = dict(lr=cfg["optimizer"]["lr"])
    return optim.adam_init, lambda p, g, s: optim.adam_step(p, g, s, **hyper)


def _to_tree(flat):
    """The program's tree (``"layers"``: a list, one dict a held layer) from the
    flat per-tensor dict: ``layers.<i>/<name>`` is ``tree["layers"][i][name]``."""
    tree, layers = {}, {}
    for key, value in flat.items():
        if "/" in key:
            layer, name = key.split("/")
            layers.setdefault(int(layer[len("layers."):]), {})[name] = value
        else:
            tree[key] = value
    tree["layers"] = [layers[i] for i in range(len(layers))]
    return tree


def _to_flat(tree):
    flat = {k: v for k, v in tree.items() if k != "layers"}
    for i, layer in enumerate(tree["layers"]):
        flat.update({f"layers.{i}/{name}": v for name, v in layer.items()})
    return flat


def counters():
    """``{name: float}`` of the newest state's MoE counters (one device read,
    after the window), or ``{}`` before any step."""
    return {k: float(v) for k, v in jax.device_get(_LAST).items()}


class Program:
    """The compiled step, the program that makes its state, and views of that
    state for the check. Building one does no device work."""

    def __init__(self, cfg, cell, weights_of_seed, devices, mesh):
        from beforeholiday_tpu import amp
        from beforeholiday_tpu.optimizers import FusedAdam
        from beforeholiday_tpu.remat import donate_step

        if mesh is not None:
            raise ValueError("family kimi_linear has one layout: single")
        mcfg = model_config(cfg)
        optimizer = FusedAdam(lr=cfg["optimizer"]["lr"])
        self._beta1, self.mesh = optimizer.betas[0], None
        built = {}

        def make_state(seed):
            m = built["amp"] = amp.initialize(
                lambda p, t: model.forward(p, t, mcfg),
                _to_tree(weights_of_seed(seed)), optimizer, cfg["opt_level"],
                arena_native=True, keep_fp32_mask=model.keep_fp32)
            zeros = {k: jnp.zeros((), jnp.float32) for k in _COUNTERS}
            return m.params, m.optimizer.init(m.params), m.scaler.init(), zeros

        def step(state, batch):
            m = built["amp"]              # made by make_state, which runs first
            svag = amp.scaled_value_and_grad(
                lambda p, tok, tgt: model.loss_fn(p, tok, tgt, mcfg, forward_fn=m.apply),
                m.scaler, has_aux=True)
            p, o, sc, seen = state
            loss, now, g, fi, sc = svag(p, sc, *batch)
            p, o = m.optimizer.step(p, g, o, found_inf=fi)
            seen = {
                "expert_rows": seen["expert_rows"] + now["expert_rows"],
                "expert_load_max_over_mean": jnp.maximum(
                    seen["expert_load_max_over_mean"], now["expert_load_max_over_mean"]),
                "dropped_rows": seen["dropped_rows"] + now["dropped_rows"],
                "steps": seen["steps"] + 1.0,
            }
            return (p, o, sc, seen), loss, fi | (now["dropped_rows"] > 0)

        donated = donate_step(step, donate_argnums=(0,))

        def counted_step(state, batch):
            out = donated(state, batch)
            _LAST.clear()
            _LAST.update(out[0][3])
            return out

        counted_step.jitted = donated.jitted
        self.make_state, self.step = make_state, counted_step
        _LAST.clear()

    def _leaves(self, arenas, state):
        from beforeholiday_tpu.ops.arena import PackedParams

        return _to_flat(PackedParams(arenas, state[0].layout).unpack())

    def masters(self, state):
        """The float32 master weights, as a flat dict of views. Traceable."""
        return self._leaves(state[1]["master"], state)

    def first_gradient(self, state, initial):
        """The gradient the optimizer was given on its first step, from its
        state after that step: Adam's first moment is (1 - beta1) * g."""
        moments = tuple(s["exp_avg"] for s in state[1]["inner"])
        return {k: v / (1.0 - self._beta1) for k, v in self._leaves(moments, state).items()}

    def replicas_disagree(self, state):
        return False
