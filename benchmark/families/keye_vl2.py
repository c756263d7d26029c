"""Family ``keye_vl2``: grouped-query attention over the keys a learned indexer
selects for each query (``ops.index_select`` in front of
``ops.flash_attention(selected=)``), a three-row rotary table, every MLP top-k
softmax-routed experts without a shared one, an untied head, through the
program's training entry points.

The step is wired exactly as ``families/deepseek_v3.py`` wires its own
(``amp.initialize(.., "O5", arena_native=True)`` + ``FusedAdam`` +
``amp.scaled_value_and_grad`` + ``optimizer.step`` under ``remat.donate_step``),
on ``beforeholiday_tpu.models.keye_vl2``. The program's modules are imported here
at the top, before any reference or compile: a checkout without them fails at
once.

As in that family the state carries a fourth member beside ``(params,
optimizer, scaler)``: the counters of the newest step and their sums, device
scalars written by the step itself (no host sync). ``counters()`` reads them
after the window; a step that dropped a routed row reports it as ``found_inf``,
so the window's ``failed_steps`` counts it. ``selected_pairs`` is the NEWEST
step's count of (query, key) pairs its indexers kept, not a sum (a sum of such
counts leaves float32's integers within a few hundred steps).
"""

import jax
import jax.numpy as jnp

from beforeholiday_tpu.models import keye_vl2 as model
from beforeholiday_tpu.moe import dropless  # noqa: F401  (must be there: see above)
from beforeholiday_tpu.ops import indexer  # noqa: F401  (and this)

from benchmark.reference import keye_vl2 as reference
from benchmark.reference import optim

ITEMS_PER_ROW = "seq_len"
GUARDED_OPS = ("flash_attention", "layer_norm", "grouped_matmul", "index_select")
_COUNTERS = model.COUNTERS + ("steps",)
_LAST = {}                           # the newest Program's newest state's counters

# configuration keys handed to the model as they are
_MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "first_layer", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_experts_published", "num_experts", "first_expert",
    "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob", "moe_rows_bound",
    "rms_norm_eps", "initializer_range", "embedding_init_std", "remat_policy")


def model_config(cfg):
    rope = cfg["rope_scaling"]
    if rope["rope_type"] != "default":
        raise ValueError("family keye_vl2: a plain rotary table under mrope_section")
    return model.KeyeVL2Config(
        **{k: cfg[k] for k in _MODEL_KEYS}, rope_theta=float(cfg["rope_theta"]),
        mrope_section=tuple(rope["mrope_section"]),
        sa_config=model.SparseAttentionConfig(**cfg["sa_config"]),
        dtype=jnp.dtype(cfg["compute_dtype"]))


def param_count(cfg):
    return model.param_count(model_config(cfg))


def weights(cfg, key):
    """Seeded float32 weights, flat, every value exactly a bfloat16: drawn by
    the reference's file, tensor by tensor, not by the program's ``init``."""
    return reference.weights(cfg, key)


def batch(cfg, rows, key):
    """``rows`` seeded sequences of text (a token's three positions are its
    index), ids from the vocabulary slice, and their next-token targets.
    Traceable."""
    tokens = jax.random.randint(key, (rows, cfg["seq_len"]), 0, cfg["vocab_size"], jnp.int32)
    return tokens, jnp.roll(tokens, -1, axis=-1)


def selected_pairs_per_item(cfg):
    """(query, key) pairs an exact selection keeps, per token, in ONE layer:
    ``sum_t min(t + 1, topk) / S``."""
    S = cfg["seq_len"]
    k = min(cfg["sa_config"]["topk"], S)
    return (k * (k + 1) // 2 + (S - k) * k) / S


def matmul_params_per_token(cfg):
    """Matmul parameters a token passes in one layer, and in the head's slice:
    ``{"attention", "indexer", "moe", "head"}``. ``attention`` is the main heads'
    four projections, ``indexer`` the indexer's three (forward only: no gradient
    reaches them), ``moe`` the router and the held experts at the expected number
    a token reaches. The embedding's lookup is a gather and is not counted."""
    D, hd, F = cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"]
    H, Hkv, sa = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["sa_config"]
    expected = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_experts_published"]
    return {
        "attention": 2 * D * H * hd + 2 * D * Hkv * hd,
        "indexer": D * sa["indexer_head_dim"] * (sa["indexer_num_heads"] + 1)
        + D * sa["indexer_num_heads"],
        "moe": D * cfg["num_experts_published"] + expected * 3 * D * F,
        "head": cfg["vocab_size"] * D,
    }


def sparse_attention_flops_per_item(cfg):
    """Required attention operations per token, forward and backward, over the
    held layers: the SELECTED pairs' (whatever the kernels walk), one product of
    ``head_dim`` for the scores and one for the values forward and two of each
    backward, 2 operations a multiply-add: ``6 * heads * 2 * head_dim`` a pair."""
    return 6 * cfg["num_attention_heads"] * 2 * cfg["head_dim"] \
        * selected_pairs_per_item(cfg) * cfg["num_hidden_layers"]


attention_flops_per_item = sparse_attention_flops_per_item     # every layer selects


def index_flops_per_item(cfg):
    """Required operations of the index scores per token, forward only, over the
    held layers: ``indexer_num_heads`` products of ``indexer_head_dim`` a causal
    pair, ``(S + 1) / 2`` causal keys a query."""
    sa = cfg["sa_config"]
    return 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        * (cfg["seq_len"] + 1) / 2 * cfg["num_hidden_layers"]


def model_flops_per_item(cfg):
    """Operations the forward and backward passes require per token: 6 per
    matmul parameter a gradient reaches (the held experts at their expected
    rows), 2 per matmul parameter of the indexer, which runs forward only, what
    the selected pairs require of attention and the causal pairs of the index
    scores. Nothing recomputed, nothing a mask zeroes; norms, rotary embedding,
    the selection itself and gates are not counted."""
    per = matmul_params_per_token(cfg)
    L = cfg["num_hidden_layers"]
    return 6 * (L * (per["attention"] + per["moe"]) + per["head"]) + 2 * L * per["indexer"] \
        + sparse_attention_flops_per_item(cfg) + index_flops_per_item(cfg)

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
    """``{name: float}`` of the newest state's counters (one device read, after
    the window), or ``{}`` before any step."""
    return {k: float(v) for k, v in jax.device_get(_LAST).items()}


class Program:
    """The compiled step, the program that makes its state, and views of that
    state for the check. Building one does no device work."""

    def __init__(self, cfg, cell, weights_of_seed, devices, mesh):
        from beforeholiday_tpu import amp
        from beforeholiday_tpu.optimizers import FusedAdam
        from beforeholiday_tpu.remat import donate_step

        if mesh is not None:
            raise ValueError("family keye_vl2 has one layout: single")
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
                "selected_pairs": now["selected_pairs"],
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
