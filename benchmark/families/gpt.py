"""Family ``gpt``: a dense GPT through the program's training entry points.

The step is wired as ``chip_smoke.build_step`` / ``train_4chip`` wire the
flagship (``amp.initialize(.., "O5", arena_native=True)`` + ``FusedAdam`` +
``amp.scaled_value_and_grad`` + ``optimizer.step`` under ``remat.donate_step``;
data parallel: ``DistributedDataParallel().reduce`` inside
``jax.shard_map(check_vma=False)`` over a ``("data",)`` mesh, state replicated),
on weights and batches the benchmark makes from the seed.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference import gpt as reference  # noqa: F401  (the family's plain reference)
from benchmark.reference import optim
from benchmark.reference.precision import as_bfloat16_values

ITEMS_PER_ROW = "seq_len"           # a row of the batch is one sequence of tokens
GUARDED_OPS = ("flash_attention", "layer_norm")
_LAYER_SHAPES = {                    # per-layer tensors, (shape in D/F, init)
    "ln1_scale": ("D", "one"), "ln1_bias": ("D", "zero"),
    "wqkv": ("D,3D", "std"), "bqkv": ("3D", "zero"),
    "wo": ("D,D", "out"), "bo": ("D", "zero"),
    "ln2_scale": ("D", "one"), "ln2_bias": ("D", "zero"),
    "wi": ("D,F", "std"), "bi": ("F", "zero"),
    "wo2": ("F,D", "out"), "bo2": ("D", "zero"),
}


def _dims(cfg):
    D = cfg["d_model"]
    return {"D": D, "3D": 3 * D, "F": cfg["d_ff"]}


def weight_shapes(cfg):
    """``{name: (shape, init)}`` of every tensor, layers stacked under ``blocks/``."""
    d, L = _dims(cfg), cfg["n_layers"]
    out = {"tok_embed": ((cfg["vocab_size"], d["D"]), "std"),
           "pos_embed": ((cfg["seq_len"], d["D"]), "std"),
           "lnf_scale": ((d["D"],), "one"), "lnf_bias": ((d["D"],), "zero")}
    for name, (dims, init) in _LAYER_SHAPES.items():
        out["blocks/" + name] = ((L, *(d[x] for x in dims.split(","))), init)
    return out


def param_count(cfg):
    return sum(math.prod(shape) for shape, _ in weight_shapes(cfg).values())


def weights(cfg, key):
    """Seeded float32 weights, every value exactly a bfloat16 (the type the
    matmul weights are trained in), initialised as Megatron's GPT: N(0, 0.02),
    output projections N(0, 0.02 / sqrt(2 L)). Traceable."""
    std = {"std": 0.02, "out": 0.02 / math.sqrt(2.0 * cfg["n_layers"])}
    out = {}
    for i, (name, (shape, init)) in enumerate(sorted(weight_shapes(cfg).items())):
        if init in std:
            w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std[init]
            out[name] = as_bfloat16_values(w)
        else:
            out[name] = jnp.full(shape, 1.0 if init == "one" else 0.0, jnp.float32)
    return out


def batch(cfg, rows, key):
    """``rows`` seeded sequences and their next-token targets. Traceable."""
    tokens = jax.random.randint(key, (rows, cfg["seq_len"]), 0, cfg["vocab_size"], jnp.int32)
    return tokens, jnp.roll(tokens, -1, axis=-1)


def model_flops_per_item(cfg):
    """Operations the forward and backward passes require per token: 6 per
    matmul parameter (tied head counted once, position table not) plus the
    causal half of attention (QK^T and PV forward, four products backward)."""
    D, L = cfg["d_model"], cfg["n_layers"]
    matmul_params = L * (4 * D * D + 2 * D * cfg["d_ff"]) + cfg["vocab_size"] * D
    return 6 * matmul_params + attention_flops_per_item(cfg)


def attention_flops_per_item(cfg):
    """Required causal attention operations per token, forward and backward:
    2 products forward and 4 backward of 2*S*D each per layer, halved by the mask."""
    return 6 * cfg["seq_len"] * cfg["d_model"] * cfg["n_layers"]


def reference_optimizer(cfg, cell):
    hyper = dict(lr=cfg["optimizer"]["lr"])
    return optim.adam_init, lambda p, g, s: optim.adam_step(p, g, s, **hyper)


def _to_tree(flat):
    tree = {k: v for k, v in flat.items() if "/" not in k}
    tree["blocks"] = {k.split("/", 1)[1]: v for k, v in flat.items() if "/" in k}
    return tree


def _to_flat(tree):
    flat = {k: v for k, v in tree.items() if k != "blocks"}
    flat.update({"blocks/" + k: v for k, v in tree["blocks"].items()})
    return flat


class Program:
    """The compiled step, the program that makes its state, and views of that
    state for the check. Building one does no device work."""

    def __init__(self, cfg, cell, weights_of_seed, devices, mesh):
        from jax.sharding import PartitionSpec as P

        from beforeholiday_tpu import amp
        from beforeholiday_tpu.optimizers import FusedAdam
        from beforeholiday_tpu.remat import donate_step
        from beforeholiday_tpu.testing import gpt

        gcfg = gpt.GPTConfig(
            vocab_size=cfg["vocab_size"], seq_len=cfg["seq_len"],
            d_model=cfg["d_model"], n_heads=cfg["n_heads"],
            n_layers=cfg["n_layers"], d_ff=cfg["d_ff"],
            dtype=jnp.dtype(cfg["compute_dtype"]),
            use_flash_attention=cfg["use_flash_attention"],
            remat_policy=cfg["remat_policy"])
        optimizer = FusedAdam(lr=cfg["optimizer"]["lr"])
        self._beta1, self.mesh = optimizer.betas[0], mesh
        built = {}

        def make_state(seed):
            """From the seed to the whole training state, in one program (jit
            it, with the state's sharding): the entry points run under its trace."""
            m = built["amp"] = amp.initialize(
                lambda p, t: gpt.forward(p, t, gcfg), _to_tree(weights_of_seed(seed)),
                optimizer, cfg["opt_level"], arena_native=True)
            return m.params, m.optimizer.init(m.params), m.scaler.init()

        reduce = None
        if mesh is not None:
            from beforeholiday_tpu.parallel import DistributedDataParallel
            reduce = DistributedDataParallel().reduce

        def one_chip_step(state, batch):
            m = built["amp"]              # made by make_state, which runs first
            svag = amp.scaled_value_and_grad(
                lambda p, tok, tgt: gpt.loss_fn(p, tok, tgt, gcfg, forward_fn=m.apply),
                m.scaler, reduce_grads=reduce)
            p, o, sc = state
            loss, g, fi, sc = svag(p, sc, *batch)
            p, o = m.optimizer.step(p, g, o, found_inf=fi)
            return (p, o, sc), loss, fi

        step = one_chip_step
        if mesh is not None:
            def dp_step(state, batch):
                state, loss, fi = one_chip_step(state, batch)
                return state, jax.lax.pmean(loss, "data"), fi

            step = jax.shard_map(
                dp_step, mesh=mesh, in_specs=(P(), P("data")),
                out_specs=(P(), P(), P()), check_vma=False)
        self.make_state = make_state
        self.step = donate_step(step, donate_argnums=(0,))

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
        from jax.sharding import PartitionSpec as P

        from beforeholiday_tpu.parallel import check_replicated_consistency

        if self.mesh is None:
            return False
        return bool(jax.jit(jax.shard_map(
            lambda s: check_replicated_consistency(s, "data"), mesh=self.mesh,
            in_specs=(P(),), out_specs=P(), check_vma=False))(state))
