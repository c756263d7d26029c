"""Family ``mellum``: sliding-window and full attention layers under two rotary
tables, every MLP a mixture of experts without a shared one, through the
program's training entry points.

The step is wired exactly as ``families/gpt.py`` and ``families/qwen3_next.py``
wire theirs (``amp.initialize(.., "O5", arena_native=True)`` + ``FusedAdam`` +
``amp.scaled_value_and_grad`` + ``optimizer.step`` under ``remat.donate_step``),
on ``beforeholiday_tpu.models.mellum``. The program's modules are imported here
at the top, before any reference or compile: a checkout without them fails at
once.

As in family ``qwen3_next`` the state carries a fourth member beside
``(params, optimizer, scaler)``: the MoE counters of the newest step and their
sums, device scalars written by the step itself (no host sync). ``counters()``
reads them after the window; a step that dropped a routed row reports it as
``found_inf``, so the window's ``failed_steps`` counts it.
"""

import jax
import jax.numpy as jnp

from beforeholiday_tpu.models import mellum as model
from beforeholiday_tpu.moe import dropless  # noqa: F401  (must be there: see above)

from benchmark.reference import mellum as reference  # noqa: F401
from benchmark.reference import optim
from benchmark.reference.precision import as_bfloat16_values

ITEMS_PER_ROW = "seq_len"
GUARDED_OPS = ("flash_attention", "layer_norm")
_COUNTERS = model.COUNTERS + ("steps",)
_LAST = {}                           # the newest Program's newest state's counters
_SLIDING, _FULL = "sliding_attention", "full_attention"


def _layer_types(cfg):
    """The kinds of the layers held: the published list's first ``num_hidden_layers``."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def model_config(cfg):
    rope = cfg["rope_parameters"]
    full = rope[_FULL]
    if rope[_SLIDING]["rope_type"] != "default" or full["rope_type"] != "yarn":
        raise ValueError("family mellum: sliding layers plain, full layers under YaRN")
    return model.MellumConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"], layer_types=_layer_types(cfg),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"],
        rope_theta_sliding=float(rope[_SLIDING]["rope_theta"]),
        rope_theta_full=float(full["rope_theta"]),
        rope_yarn_full=model.Yarn(
            float(full["factor"]), full["original_max_position_embeddings"],
            float(full["beta_fast"]), float(full["beta_slow"]), full["attention_factor"]),
        num_experts=cfg["num_experts_published"], num_experts_held=cfg["num_experts"],
        first_expert=cfg["first_expert"], num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"], moe_rows_bound=cfg["moe_rows_bound"],
        rms_norm_eps=cfg["rms_norm_eps"], initializer_range=cfg["initializer_range"],
        embedding_init_std=cfg["embedding_init_std"], dtype=jnp.dtype(cfg["compute_dtype"]),
        remat_policy=cfg["remat_policy"])


def param_count(cfg):
    return model.param_count(model_config(cfg))


def weights(cfg, key):
    """Seeded float32 weights as ``model.init`` draws them (matmul weights
    N(0, ``initializer_range``), the embedding N(0, ``embedding_init_std``), norm
    weights 1), every value exactly a bfloat16, so that the
    program's bf16 copy and the reference's float32 start equal. Traceable."""
    return {k: as_bfloat16_values(v)
            for k, v in _to_flat(model.init(key, model_config(cfg))).items()}


def batch(cfg, rows, key):
    """``rows`` seeded sequences, ids from the vocabulary slice, and their
    next-token targets. Traceable."""
    tokens = jax.random.randint(key, (rows, cfg["seq_len"]), 0, cfg["vocab_size"], jnp.int32)
    return tokens, jnp.roll(tokens, -1, axis=-1)


def keys_per_query(cfg, kind):
    """Mean number of keys the mask of a layer of ``kind`` leaves a query of a
    ``seq_len`` sequence: ``(S + 1) / 2`` under the causal mask, and with a
    window of ``W < S`` keys ``(W (W + 1) / 2 + (S - W) W) / S = W - W (W - 1) / (2 S)``."""
    S = cfg["seq_len"]
    W = min(cfg["sliding_window"], S) if kind == _SLIDING else S
    return W - W * (W - 1) / (2 * S)


def _attention_flops(cfg, kinds):
    """What the masks of the layers of ``kinds`` require per token, forward and
    backward: 2 products forward and 4 backward of ``2 * heads * head_dim`` a
    kept key."""
    per_key = 12 * cfg["num_attention_heads"] * cfg["head_dim"]
    return sum(per_key * keys_per_query(cfg, kind)
               for kind in _layer_types(cfg) if kind in kinds)


def attention_flops_per_item(cfg):
    """Required attention operations per token of every layer, by its mask."""
    return _attention_flops(cfg, (_SLIDING, _FULL))


def window_attention_flops_per_item(cfg):
    """Those of the sliding-window layers alone (the windowed kernels' work)."""
    return _attention_flops(cfg, (_SLIDING,))


def matmul_params_per_token(cfg):
    """Matmul parameters a token passes: ``(a layer's attention projections, its
    router, its held experts at the expected number a token reaches, the head's
    slice)``. The embedding is a gather."""
    D, hd, F = cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    expected = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_experts_published"]
    return (2 * D * H * hd + 2 * D * Hkv * hd, D * cfg["num_experts_published"],
            expected * 3 * D * F, cfg["vocab_size"] * D)


def model_flops_per_item(cfg):
    """Operations the forward and backward passes require per token: 6 per
    matmul parameter a token passes, plus what the attention masks require.
    Nothing recomputed, nothing a mask zeroes."""
    attn, router, experts, head = matmul_params_per_token(cfg)
    return 6 * (cfg["num_hidden_layers"] * (attn + router + experts) + head) \
        + attention_flops_per_item(cfg)


def reference_optimizer(cfg, cell):
    hyper = dict(lr=cfg["optimizer"]["lr"])
    return optim.adam_init, lambda p, g, s: optim.adam_step(p, g, s, **hyper)


def _to_tree(flat):
    """The program's tree (leaves stacked over the layers) from the flat
    per-tensor dict."""
    tree, layers = {}, {}
    for key in sorted(flat):
        if "/" not in key:
            tree[key] = flat[key]
            continue
        where, name = key.split("/")
        layers.setdefault(name, {})[int(where.split(".")[1])] = flat[key]
    tree["layers"] = {name: jnp.stack([by_index[i] for i in range(len(by_index))])
                      for name, by_index in layers.items()}
    return tree


def _to_flat(tree):
    flat = {k: v for k, v in tree.items() if k != "layers"}
    for name, stacked in tree["layers"].items():
        flat.update({f"layers.{i}/{name}": stacked[i] for i in range(stacked.shape[0])})
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
            raise ValueError("family mellum has one layout: single")
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
