"""Family ``qwen3_next``: a hybrid linear-attention mixture of experts through
the program's training entry points.

The step is wired exactly as ``families/gpt.py`` wires the flagship
(``amp.initialize(.., "O5", arena_native=True)`` + ``FusedAdam`` +
``amp.scaled_value_and_grad`` + ``optimizer.step`` under ``remat.donate_step``),
on ``beforeholiday_tpu.models.qwen3_next``. The program's modules are imported
here at the top, before any reference or compile: a checkout without them
fails at once.

The state carries a fourth member beside ``(params, optimizer, scaler)``: the
MoE counters of the newest step and their sums, device scalars written by the
step itself (no host sync). ``counters()`` reads them after the window; the
``program_counter`` reduction finds it through this module. A step that
dropped a routed row reports it as ``found_inf``, so the window's
``failed_steps`` counts it.
"""

import jax
import jax.numpy as jnp

from beforeholiday_tpu.models import qwen3_next as model
from beforeholiday_tpu.moe import dropless  # noqa: F401  (must be there: see above)
from beforeholiday_tpu.ops import gated_delta  # noqa: F401

from benchmark.reference import optim
from benchmark.reference import qwen3_next as reference  # noqa: F401
from benchmark.reference.precision import as_bfloat16_values

ITEMS_PER_ROW = "seq_len"
GUARDED_OPS = ("flash_attention", "layer_norm", "gated_delta_rule")
_COUNTERS = model.COUNTERS + ("steps",)
_LAST = {}                           # the newest Program's newest state's counters


def model_config(cfg):
    return model.Qwen3NextConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        num_experts=cfg["num_experts_published"], num_experts_held=cfg["num_experts"],
        first_expert=cfg["first_expert"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["shared_expert_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"], moe_rows_bound=cfg["moe_rows_bound"],
        rms_norm_eps=cfg["rms_norm_eps"], dtype=jnp.dtype(cfg["compute_dtype"]),
        remat_policy=cfg["remat_policy"], gated_delta_chunk=cfg["gated_delta_chunk"])


def weight_shapes(cfg):
    """``{flat name: (shape, init)}``, one entry per tensor of the model: the
    program's stacked leaves taken apart along their leading axis."""
    out = {}
    for group, leaves in model.param_shapes(model_config(cfg)).items():
        for name, (shape, init) in leaves.items():
            if group == "top":
                out[name] = (shape, init)
            else:
                out.update({f"{group}.{i}/{name}": (shape[1:], init) for i in range(shape[0])})
    return out


def param_count(cfg):
    return model.param_count(model_config(cfg))


def weights(cfg, key):
    """Seeded float32 weights as ``model.init`` draws them (matmul weights
    N(0, 0.02), ``A_log = log U(0, 16)``, ``dt_bias`` and the head norm 1, the
    zero-centred norms 0), every value exactly a bfloat16, so that the
    program's bf16 copy and the reference's float32 start equal. Traceable."""
    return {k: as_bfloat16_values(v)
            for k, v in _to_flat(model.init(key, model_config(cfg))).items()}


def batch(cfg, rows, key):
    """``rows`` seeded sequences, ids from the vocabulary slice, and their
    next-token targets. Traceable."""
    tokens = jax.random.randint(key, (rows, cfg["seq_len"]), 0, cfg["vocab_size"], jnp.int32)
    return tokens, jnp.roll(tokens, -1, axis=-1)


def _matmul_params_per_token(cfg):
    """Matmul parameters a token passes in one layer of each kind, and in the head."""
    D = cfg["hidden_size"]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    F, Fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    linear = D * (2 * Hk * dk + 2 * Hv * dv) + D * 2 * Hv + Hv * dv * D
    attn = D * H * 2 * hd + 2 * D * Hkv * hd + H * hd * D
    # the held experts at the expected top_k * held / published experts a token
    expected = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_experts_published"]
    moe = D * cfg["num_experts_published"] + D + 3 * D * Fs + expected * 3 * D * F
    return linear, attn, moe, cfg["vocab_size"] * D


def gated_delta_flops_per_item(cfg):
    """The recurrence's own products per token, forward and backward: per value
    head three ``d_k x d_v`` products forward (the prediction ``S^T k``, the
    rank-one update, the read-out ``S^T q``) and twice that backward, 2 operations
    each; the convolution, the norms and the chunk-local algebra are not counted."""
    layers = cfg["num_hidden_layers"] - cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    per_head = 3 * 2 * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
    return 3 * per_head * cfg["linear_num_value_heads"] * layers


def attention_flops_per_item(cfg):
    """Required causal attention operations per token, forward and backward, of
    the attention layers alone: 2 products forward and 4 backward of
    ``2 * S * heads * head_dim`` each, halved by the mask."""
    layers = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    return 6 * cfg["seq_len"] * cfg["num_attention_heads"] * cfg["head_dim"] * layers


def model_flops_per_item(cfg):
    """Operations the forward and backward passes require per token: 6 per
    matmul parameter a token passes (mixers, router, shared expert, the head's
    slice; the held experts at the expected number a token reaches; the
    embedding is a gather), the causal half of the attention layers, and the
    recurrence's own products. Nothing recomputed."""
    linear, attn, moe, head = _matmul_params_per_token(cfg)
    n_attn = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    n_linear = cfg["num_hidden_layers"] - n_attn
    matmul = n_linear * linear + n_attn * attn + cfg["num_hidden_layers"] * moe + head
    return 6 * matmul + attention_flops_per_item(cfg) + gated_delta_flops_per_item(cfg)


def reference_optimizer(cfg, cell):
    hyper = dict(lr=cfg["optimizer"]["lr"])
    return optim.adam_init, lambda p, g, s: optim.adam_step(p, g, s, **hyper)


_GROUPS = ("layers", "linear", "attn")


def _to_tree(flat):
    """The program's tree (stacked leaves) from the flat per-tensor dict."""
    tree, parts = {}, {}
    for key in sorted(flat):
        if "/" not in key:
            tree[key] = flat[key]
            continue
        where, name = key.split("/")
        group, index = where.split(".")
        parts.setdefault(group, {}).setdefault(name, {})[int(index)] = flat[key]
    for group, leaves in parts.items():
        tree[group] = {name: jnp.stack([by_index[i] for i in range(len(by_index))])
                       for name, by_index in leaves.items()}
    return tree


def _to_flat(tree):
    flat = {k: v for k, v in tree.items() if k not in _GROUPS}
    for group in _GROUPS:
        for name, stacked in tree[group].items():
            flat.update({f"{group}.{i}/{name}": stacked[i] for i in range(stacked.shape[0])})
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
            raise ValueError("family qwen3_next has one layout: single")
        mcfg = model_config(cfg)
        optimizer = FusedAdam(lr=cfg["optimizer"]["lr"])
        self._beta1, self.mesh = optimizer.betas[0], None
        built = {}

        def make_state(seed):
            m = built["amp"] = amp.initialize(
                lambda p, t: model.forward(p, t, mcfg), _to_tree(weights_of_seed(seed)),
                optimizer, cfg["opt_level"], arena_native=True,
                keep_fp32_mask=model.keep_fp32)
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

    @staticmethod
    def _leaves(arenas, state):
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
