"""Family ``lfm2_moe``: gated short-convolution mixers among grouped-query
attention layers, a leading dense SwiGLU layer and then top-k sigmoid-routed
experts chosen under a selection bias, tied embeddings, through the program's
training entry points.

The step is wired exactly as ``families/mellum.py`` and ``families/nemotron_h.py``
wire theirs (``amp.initialize(.., "O5", arena_native=True)`` + ``FusedAdam`` +
``amp.scaled_value_and_grad`` + ``optimizer.step`` under ``remat.donate_step``),
on ``beforeholiday_tpu.models.lfm2_moe``. The program's modules are imported
here at the top, before any reference or compile: a checkout without them fails
at once.

As in those families the state carries a fourth member beside ``(params,
optimizer, scaler)``: the MoE counters of the newest step and their sums, device
scalars written by the step itself (no host sync). ``counters()`` reads them
after the window; a step that dropped a routed row reports it as ``found_inf``,
so the window's ``failed_steps`` counts it.
"""

import jax
import jax.numpy as jnp

from beforeholiday_tpu.models import lfm2_moe as model
from beforeholiday_tpu.moe import dropless  # noqa: F401  (must be there: see above)
from beforeholiday_tpu.ops import short_conv  # noqa: F401

from benchmark.reference import lfm2_moe as reference
from benchmark.reference import optim

ITEMS_PER_ROW = "seq_len"
GUARDED_OPS = ("flash_attention", "layer_norm", "grouped_matmul", "short_conv")
_COUNTERS = model.COUNTERS + ("steps",)
_LAST = {}                           # the newest Program's newest state's counters
_ACTIVATION_BYTES = 2                # the configuration computes in bfloat16


def model_config(cfg):
    return model.Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]), num_hidden_layers=cfg["num_hidden_layers"],
        first_layer=cfg["first_layer"], num_dense_layers=cfg["num_dense_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], conv_L_cache=cfg["conv_L_cache"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts_published=cfg["num_experts_published"], num_experts=cfg["num_experts"],
        first_expert=cfg["first_expert"], num_experts_per_tok=cfg["num_experts_per_tok"],
        use_expert_bias=cfg["use_expert_bias"], norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        moe_rows_bound=cfg["moe_rows_bound"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["norm_eps"], tie_word_embeddings=cfg["tie_word_embeddings"],
        initializer_range=cfg["initializer_range"],
        expert_bias_init_std=cfg["expert_bias_init_std"],
        dtype=jnp.dtype(cfg["compute_dtype"]), remat_policy=cfg["remat_policy"])


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
    head's slice: ``{"conv", "full_attention", "dense", "moe", "head"}``. The held
    experts count at the expected number a token reaches; the embedding's lookup
    is a gather, its use as the head is counted."""
    D, H, Hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = D // H
    expected = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_experts_published"]
    return {
        "conv": D * 3 * D + D * D,
        "full_attention": 2 * D * H * hd + 2 * D * Hkv * hd,
        "dense": 3 * D * cfg["intermediate_size"],
        "moe": D * cfg["num_experts_published"] + expected * 3 * D * cfg["moe_intermediate_size"],
        "head": cfg["vocab_size"] * D,
    }


def attention_flops_per_item(cfg):
    """Required causal attention operations per token, forward and backward, of
    the attention layers alone: 2 products forward and 4 backward of ``2 * heads
    * head_dim`` a kept key, ``(S + 1) / 2`` keys a query under the mask."""
    layers = sum(mixer == "full_attention" for mixer, _ in reference.held(cfg))
    return 12 * cfg["hidden_size"] * (cfg["seq_len"] + 1) / 2 * layers


def short_conv_bytes_per_item(cfg):
    """Bytes the two short-convolution kernels must move per token, over the
    convolution mixers held: forward reads ``[B | C | x~]`` (3 D) and writes ``y``
    (D); backward reads them and ``dy`` (4 D) and writes their cotangent (3 D):
    11 D activations of the compute dtype. The filter, its gradient and the rows
    a tile reads again from its neighbour are not counted."""
    layers = sum(mixer == "conv" for mixer, _ in reference.held(cfg))
    return 11 * cfg["hidden_size"] * _ACTIVATION_BYTES * layers


def model_flops_per_item(cfg):
    """Operations the forward and backward passes require per token: 6 per
    matmul parameter a token passes (the held experts at their expected rows),
    plus what the causal mask requires of attention. Nothing recomputed; the
    convolution's own three taps and the gates are not counted."""
    per = matmul_params_per_token(cfg)
    matmul = sum(per[mixer] + per[ffn] for mixer, ffn in reference.held(cfg)) + per["head"]
    return 6 * matmul + attention_flops_per_item(cfg)


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
            raise ValueError("family lfm2_moe has one layout: single")
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
