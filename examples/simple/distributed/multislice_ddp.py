"""Multi-slice DDP: the two-level hierarchical all-reduce end to end.

``distributed_data_parallel.py`` trains over one flat ``data`` axis — every
gradient byte crosses the same interconnect. On a multi-slice TPU pod the
interconnect is NOT uniform: ranks inside a slice talk over ICI, slices talk
over the much slower DCN. This example carves the same devices into a
``(slice, intra)`` mesh (``make_two_level_mesh``) and turns on
``hierarchical=True``, which reduces each gradient bucket as intra-slice
reduce-scatter -> inter-slice psum on 1/slice_size of the payload -> intra
all-gather (the apex ``allreduce_communicators`` tree, ref:
apex/parallel/distributed.py:556-587), so DCN carries ``1/slice_size`` of
the flat traffic. Uncompressed this is bitwise-identical to the flat
reduce; the training loop cannot tell the difference except in the ledger,
which this script prints per tier at the end.

Run (any machine — 8 virtual CPU devices stand in for 2 slices x 4 chips):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python multislice_ddp.py --hierarchical

Knobs:

* ``--n-slices K``      — carve the devices into K slices (default 2);
* ``--hierarchical``    — two-level reduce instead of the flat chained one;
* ``--compress-dcn``    — bf16 wire on the slow inter-slice tier only (the
  usual first move: ~2x less DCN traffic, ICI stays exact);
* ``--compress-intra``  — bf16 wire on the intra-slice tier too;
* ``--bucket-bytes N``  — bucket size for the reduction (default 64 KiB).
"""

import argparse
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

_shard_map = functools.partial(jax.shard_map, check_vma=False)

from beforeholiday_tpu.monitor import comms_summary
from beforeholiday_tpu.optimizers import FusedSGD
from beforeholiday_tpu.parallel import DistributedDataParallel
from beforeholiday_tpu.parallel.parallel_state import (
    HIERARCHICAL_AXES,
    make_two_level_mesh,
)
from beforeholiday_tpu.remat import donate_step

N, D_in, D_out = 64, 1024, 16  # per-rank batch, like the reference's fake data


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--n-slices", type=int, default=2,
                   help="carve the devices into this many slices")
    p.add_argument("--hierarchical", action="store_true",
                   help="two-level reduce: intra-slice reduce-scatter, DCN "
                        "psum on 1/slice_size, intra all-gather")
    p.add_argument("--compress-dcn", action="store_true",
                   help="bf16 wire on the inter-slice (DCN) tier only")
    p.add_argument("--compress-intra", action="store_true",
                   help="bf16 wire on the intra-slice (ICI) tier too")
    p.add_argument("--bucket-bytes", type=int, default=64 * 1024,
                   help="gradient bucket size in bytes")
    p.add_argument("--steps", type=int, default=200)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    mesh = make_two_level_mesh(args.n_slices)
    world = mesh.devices.size
    print(f"mesh: {args.n_slices} slices x "
          f"{world // args.n_slices} ranks/slice")

    # each rank gets its own batch of fake data (leading dim = flat rank,
    # slice-major — the same order a flat ("data",) mesh would use)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(world, N, D_in), jnp.float32)
    y = jnp.asarray(rng.randn(world, N, D_out), jnp.float32)

    params = {
        "w": jnp.asarray(rng.randn(D_in, D_out) / np.sqrt(D_in), jnp.float32),
        "b": jnp.zeros((D_out,), jnp.float32),
    }

    ddp = DistributedDataParallel(
        axis_name=HIERARCHICAL_AXES,
        bucket_bytes=args.bucket_bytes,
        hierarchical=args.hierarchical,
        compress_intra=args.compress_intra,
        compress_dcn=args.compress_dcn,
    )
    opt = FusedSGD(lr=1e-3)

    def loss_fn(p, x, y):
        pred = x @ p["w"] + p["b"]
        return jnp.mean((pred - y) ** 2)

    # params/opt state donated: the loop rebinds both every step, so XLA
    # updates their storage in place instead of double-buffering
    @functools.partial(donate_step, donate_argnums=(0,))
    @functools.partial(
        _shard_map, mesh=mesh,
        in_specs=(P(), P(HIERARCHICAL_AXES), P(HIERARCHICAL_AXES)),
        out_specs=(P(), P()),
    )
    def train_step(state, x, y):
        p, opt_state = state
        loss, grads = ddp.value_and_grad(loss_fn)(p, x[0], y[0])
        p, opt_state = opt.step(p, grads, opt_state)
        # loss is rank-local; average it for reporting like the reference
        loss = jax.lax.pmean(loss, HIERARCHICAL_AXES)
        return (p, opt_state), loss

    state = (params, opt.init(params))
    for _ in range(args.steps):
        state, loss = train_step(state, x, y)
    print("final loss = ", float(loss))

    # the ledger's per-tier rollup: with --hierarchical the "dcn" row's
    # bytes are the flat reduce's / slice_size, and with --compress-dcn its
    # compression_ratio reads ~2.0 while "ici" stays 1.0
    for row in comms_summary():
        if row["subsystem"] == "ddp":
            print("ddp comms by tier: " + json.dumps(row["by_tier"]))


if __name__ == "__main__":
    main()
