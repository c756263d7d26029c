"""The smallest data-parallel + amp training script — TPU port of
``examples/simple/distributed/distributed_data_parallel.py`` (a one-layer
regression trained with amp O1 under DDP).

Where the reference launches one process per GPU (torch.distributed.launch,
NCCL ``init_process_group``, ``--local_rank``), the TPU-native program is
single-controller SPMD: build a ``data`` mesh over whatever devices exist,
``shard_map`` the train step across it, and let
``DistributedDataParallel.value_and_grad`` psum the gradients over ICI. The
"gotcha" ordering from the reference README (DDP wraps AFTER amp.initialize)
has no analogue — amp is a dtype policy on a pure function, DDP a gradient
reduction; they compose in any order.

Run (any machine — 8 virtual CPU devices stand in for a TPU slice):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python distributed_data_parallel.py

Knobs (mirroring the reference DDP's allreduce controls):

* ``--bucket-bytes N`` — coalesce the gradient all-reduce into N-byte
  buckets (apex ``allreduce_bucket_cap_mb``);
* ``--compress`` — bf16 wire format with fp32 accumulation;
* ``--overlap-backward`` — launch each group's all-reduce inside the
  backward as its grads are produced (apex ``delay_allreduce=False``)
  instead of one post-backward sweep.
"""

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

_shard_map = functools.partial(jax.shard_map, check_vma=False)

from beforeholiday_tpu import amp
from beforeholiday_tpu.optimizers import FusedSGD
from beforeholiday_tpu.parallel import DistributedDataParallel
from beforeholiday_tpu.remat import donate_step

N, D_in, D_out = 64, 1024, 16  # per-rank batch, like the reference's fake data


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--bucket-bytes", type=int, default=None,
                   help="coalesce gradient all-reduces into buckets of this "
                        "many bytes")
    p.add_argument("--compress", action="store_true",
                   help="all-reduce gradients in bf16 with fp32 accumulation")
    p.add_argument("--overlap-backward", action="store_true",
                   help="reduce each group inside the backward pass instead "
                        "of one post-backward sweep")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    devices = np.asarray(jax.devices())
    world = len(devices)
    mesh = Mesh(devices, ("data",))

    # each rank gets its own batch of fake data (leading dim = data axis)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(world, N, D_in), jnp.float32)
    y = jnp.asarray(rng.randn(world, N, D_out), jnp.float32)

    params = {
        "w": jnp.asarray(rng.randn(D_in, D_out) / np.sqrt(D_in), jnp.float32),
        "b": jnp.zeros((D_out,), jnp.float32),
    }

    # amp O1: cast-list autocast + dynamic loss scaling (the reference's
    # opt_level); the model is a pure function
    model = amp.initialize(
        lambda p, x: x @ p["w"] + p["b"], params, FusedSGD(lr=1e-3), "O1"
    )
    ddp = DistributedDataParallel(
        bucket_bytes=args.bucket_bytes,
        compress=args.compress,
        overlap_backward=args.overlap_backward,
    )

    def loss_fn(p, x, y):
        if ddp.overlap_backward:
            # hooked boundary: each group's grad psum issues inside the
            # backward itself, so no post-backward reduce_grads sweep
            p = ddp.hook(p)
        pred = model.apply(p, x)
        return jnp.mean((pred - y) ** 2)

    svag = amp.scaled_value_and_grad(
        loss_fn, model.scaler,
        reduce_grads=None if ddp.overlap_backward else ddp.reduce,
    )

    # (state, scaler_state) donated: the loop rebinds both every step, so XLA
    # updates params/opt/scaler storage in place instead of double-buffering
    @functools.partial(donate_step, donate_argnums=(0, 1))
    @functools.partial(
        _shard_map, mesh=mesh,
        in_specs=(P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P()),
    )
    def train_step(state, scaler_state, x, y):
        p, opt_state = state
        loss, grads, found_inf, scaler_state = svag(p, scaler_state, x[0], y[0])
        p, opt_state = model.optimizer.step(p, grads, opt_state, found_inf=found_inf)
        # loss is rank-local; average it for reporting like the reference
        loss = jax.lax.pmean(loss, "data")
        return (p, opt_state), scaler_state, loss

    state = (model.params, model.optimizer.init(model.params))
    scaler_state = model.scaler.init()
    for t in range(500):
        state, scaler_state, loss = train_step(state, scaler_state, x, y)
    print("final loss = ", float(loss))


if __name__ == "__main__":
    main()
