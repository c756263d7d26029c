"""Family ``nemotron_h``: blocks that are one mixer each — a Mamba-2 state-space
mixer, a LatentMoE part or a grouped-query attention — by a pattern string,
through the program's training entry points.

The step is wired exactly as ``families/qwen3_next.py`` and ``families/mellum.py``
wire theirs (``amp.initialize(.., "O5", arena_native=True)`` + ``FusedAdam`` +
``amp.scaled_value_and_grad`` + ``optimizer.step`` under ``remat.donate_step``),
on ``beforeholiday_tpu.models.nemotron_h``. The program's modules are imported
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

from beforeholiday_tpu.models import nemotron_h as model
from beforeholiday_tpu.moe import dropless  # noqa: F401  (must be there: see above)
from beforeholiday_tpu.ops import ssd  # noqa: F401

from benchmark.reference import nemotron_h as reference
from benchmark.reference import optim

ITEMS_PER_ROW = "seq_len"
GUARDED_OPS = ("flash_attention", "layer_norm", "ssd", "grouped_matmul")
_COUNTERS = model.COUNTERS + ("steps",)
_LAST = {}                           # the newest Program's newest state's counters
_GROUPS = {"M": "mamba", "E": "moe", "*": "attn"}


def model_config(cfg):
    return model.NemotronHConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        hybrid_override_pattern=reference.pattern(cfg),
        mamba_num_heads=cfg["mamba_num_heads"], mamba_head_dim=cfg["mamba_head_dim"],
        n_groups=cfg["n_groups"], ssm_state_size=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"], time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_routed_experts=cfg["n_routed_experts_published"],
        n_routed_experts_held=cfg["n_routed_experts"], first_expert=cfg["first_expert"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_latent_size=cfg["moe_latent_size"],
        moe_shared_expert_intermediate_size=cfg["moe_shared_expert_columns_held"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"], moe_rows_bound=cfg["moe_rows_bound"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        initializer_range=cfg["initializer_range"],
        rescale_layers=cfg["published"]["num_hidden_layers"],
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
    """Matmul parameters a token passes in one block of each kind, and in the
    head's slice: ``{"M", "*", "E", "head"}``. The held experts count at the
    expected number a token reaches; the embedding is a gather."""
    D = cfg["hidden_size"]
    Hm, P, G, N = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
                   cfg["ssm_state_size"])
    d_in = Hm * P
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    Dl, F, Fs = (cfg["moe_latent_size"], cfg["moe_intermediate_size"],
                 cfg["moe_shared_expert_columns_held"])
    expected = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                / cfg["n_routed_experts_published"])
    return {
        "M": D * (2 * d_in + 2 * G * N + Hm) + d_in * D,
        "*": 2 * D * H * hd + 2 * D * Hkv * hd,
        "E": D * cfg["n_routed_experts_published"] + 2 * D * Dl + 2 * D * Fs
        + expected * 2 * Dl * F,
        "head": cfg["vocab_size"] * D,
    }


def attention_flops_per_item(cfg):
    """Required causal attention operations per token, forward and backward, of
    the attention blocks alone: 2 products forward and 4 backward of
    ``2 * S * heads * head_dim`` each, halved by the mask."""
    blocks = reference.pattern(cfg).count("*")
    return 6 * cfg["seq_len"] * cfg["num_attention_heads"] * cfg["head_dim"] * blocks


def ssd_flops_per_item(cfg):
    """The recurrence's own products per token, forward and backward. Forward,
    per head: into the state ``B x^T`` and out of it ``S^T C``, ``2 N P`` each;
    in the chunk, over the ``(chunk + 1) / 2`` tokens a token sees, ``2 P`` for
    the masked scores times ``dt x`` and the group's ``C . B`` (``2 N``) shared
    by its heads. Twice that backward (each product has two transposes). The
    convolution, the gate, the norm and the decays are not counted, and
    nothing recomputed."""
    H, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    seen = (cfg["chunk_size"] + 1) / 2
    forward = H * (4 * N * P + seen * 2 * P) + cfg["n_groups"] * seen * 2 * N
    return 3 * forward * reference.pattern(cfg).count("M")


def model_flops_per_item(cfg):
    """Operations the forward and backward passes require per token: 6 per
    matmul parameter a token passes, the causal half of the attention blocks,
    and the recurrence's own products. Nothing recomputed."""
    per, kinds = matmul_params_per_token(cfg), reference.pattern(cfg)
    matmul = sum(per[kind] for kind in kinds) + per["head"]
    return 6 * matmul + attention_flops_per_item(cfg) + ssd_flops_per_item(cfg)


def reference_optimizer(cfg, cell):
    hyper = dict(lr=cfg["optimizer"]["lr"])
    return optim.adam_init, lambda p, g, s: optim.adam_step(p, g, s, **hyper)


def _to_tree(flat, kinds):
    """The program's tree (leaves stacked by kind of block) from the flat
    per-tensor dict; ``kinds``: the pattern held."""
    tree = {k: v for k, v in flat.items() if "/" not in k}
    for kind in sorted(set(kinds)):
        blocks = [l for l, k in enumerate(kinds) if k == kind]
        names = [k.split("/")[1] for k in flat if k.startswith(f"layers.{blocks[0]}/")]
        tree[_GROUPS[kind]] = {
            name: jnp.stack([flat[f"layers.{l}/{name}"] for l in blocks]) for name in names}
    return tree


def _to_flat(tree, kinds):
    flat = {k: v for k, v in tree.items() if k not in _GROUPS.values()}
    seen = dict.fromkeys(_GROUPS, 0)
    for l, kind in enumerate(kinds):
        for name, stacked in tree[_GROUPS[kind]].items():
            flat[f"layers.{l}/{name}"] = stacked[seen[kind]]
        seen[kind] += 1
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
            raise ValueError("family nemotron_h has one layout: single")
        mcfg = model_config(cfg)
        optimizer = FusedAdam(lr=cfg["optimizer"]["lr"])
        self._beta1, self._kinds, self.mesh = optimizer.betas[0], reference.pattern(cfg), None
        built = {}

        def make_state(seed):
            m = built["amp"] = amp.initialize(
                lambda p, t: model.forward(p, t, mcfg),
                _to_tree(weights_of_seed(seed), self._kinds), optimizer, cfg["opt_level"],
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

        return _to_flat(PackedParams(arenas, state[0].layout).unpack(), self._kinds)

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
