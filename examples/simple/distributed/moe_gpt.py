"""Expert-parallel Mixture-of-Experts GPT on the 4D workload mesh.

The reference framework has no MoE story (Apex trains dense models only);
this is the departure script: a small GPT with every second block's MLP
replaced by a GShard/Switch MoE layer (``GPTConfig(moe_every=2)``), trained
data-parallel x expert-parallel on the ``make_moe_mesh`` carve. Each
(data, expert) mesh coordinate routes its own token group; the dispatch and
combine ``all_to_all`` traffic is booked in the comms ledger, and the router
health scalars (load-balance loss, z-loss, capacity-drop fraction) ride the
packed ``TrainMonitor`` vector — ONE readback per logging interval, never a
per-step host sync.

Run (any machine — 8 virtual CPU devices stand in for a TPU slice):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python moe_gpt.py

Knobs:

* ``--experts N`` / ``--top-k {1,2}`` / ``--capacity-factor F`` — the
  GShard routing triple (capacity is STATIC: derived from shapes, jittable);
* ``--expert-parallel N`` — carve N mesh ranks as the ``expert`` axis
  (the rest become ``data``); the stacked expert tree shards its leading
  axis, dispatch/combine reshard activations via ``all_to_all``;
* ``--steps`` / ``--batch`` — training length and PER-GROUP batch.
"""

import argparse
import functools

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

_shard_map = functools.partial(jax.shard_map, check_vma=False)

from beforeholiday_tpu.monitor import comms_summary
from beforeholiday_tpu.monitor.metrics import TrainMonitor
from beforeholiday_tpu.optimizers import FusedAdam
from beforeholiday_tpu.parallel.parallel_state import (
    DATA_AXIS,
    EXPERT_AXIS,
    make_moe_mesh,
)
from beforeholiday_tpu.testing import gpt


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--experts", type=int, default=4)
    p.add_argument("--top-k", type=int, default=2, choices=(1, 2))
    p.add_argument("--capacity-factor", type=float, default=1.25)
    p.add_argument("--expert-parallel", type=int, default=4,
                   help="mesh ranks on the expert axis (must divide both "
                        "the device count and --experts)")
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--batch", type=int, default=4,
                   help="sequences per routing group (per mesh coordinate)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    world = len(jax.devices())
    ep = args.expert_parallel
    dp = world // ep
    mesh = make_moe_mesh(data=dp, expert=ep)

    cfg = gpt.GPTConfig(
        vocab_size=256, seq_len=64, d_model=64, n_heads=4, n_layers=4,
        use_flash_attention=False,
        moe_every=2,
        moe_experts=args.experts,
        moe_top_k=args.top_k,
        moe_capacity_factor=args.capacity_factor,
        moe_expert_axis=EXPERT_AXIS,
    )
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    opt = FusedAdam(lr=3e-4, impl="jnp")
    mon = TrainMonitor()

    # params replicated except the stacked expert tree, whose LEADING axis
    # shards over the expert ranks — to FusedAdam it is one more dense leaf
    specs = jax.tree.map(lambda _: P(), params)
    specs["moe"]["experts"] = {
        k: P(None, EXPERT_AXIS, *[None] * (v.ndim - 2))
        for k, v in params["moe"]["experts"].items()
    }

    # one fixed synthetic batch, memorized — the loss falling from ~ln(V)
    # shows the experts (sharded) and the router (replicated) both train
    groups = dp * ep
    toks, tgts = gpt.synthetic_batch(
        jax.random.PRNGKey(1), cfg, groups * args.batch)

    group_axes = tuple(a for a in (DATA_AXIS, EXPERT_AXIS)
                       if a in mesh.axis_names)

    # Adam moments mirror the parameter layout leaf-for-leaf (the expert
    # moments live next to the expert shard); the step counter is replicated
    opt_state = opt.init(params)
    opt_specs = {"exp_avg": specs, "exp_avg_sq": specs, "step": P()}

    @functools.partial(
        _shard_map, mesh=mesh,
        in_specs=(specs, opt_specs, P(group_axes), P(group_axes)),
        out_specs=(specs, opt_specs, P()),
    )
    def train_step(p, opt_state, toks, tgts):
        def loss(pp):
            l, aux = gpt.loss_and_aux(pp, toks, tgts, cfg)
            return l, aux

        (l, aux), g = jax.value_and_grad(loss, has_aux=True)(p)
        # every rank routed a different token group, so ALL grads average
        # over the full group product — including the expert shard, whose
        # leading slice each expert rank owns but every group contributed to
        g = jax.tree.map(lambda x: jax.lax.pmean(x, group_axes), g)
        p, opt_state = opt.step(p, g, opt_state)
        m = mon.update(mon.init(), loss=l, moe=aux)
        return p, opt_state, mon.pack(m)

    jit_step = jax.jit(train_step, donate_argnums=(0, 1))
    for t in range(args.steps):
        params, opt_state, packed = jit_step(params, opt_state, toks, tgts)
        if t % 20 == 0 or t == args.steps - 1:
            m = mon.unpack_host(np.asarray(packed))
            print(f"step {t:3d}  loss {m['loss']:.4f}  "
                  f"aux {m['moe_aux_loss']:.4f}  z {m['moe_z_loss']:.4f}  "
                  f"drop {m['moe_drop_fraction']:.3f}")

    for row in comms_summary():
        if row["subsystem"] == "moe":
            print(f"moe a2a traffic: {row['calls']} calls, "
                  f"{row['bytes']} bytes over {row['sites']} sites "
                  f"({', '.join(sorted(row['by_kind']))})")


if __name__ == "__main__":
    main()
