"""ZeRO-3 fully-sharded training in ~100 lines — params live ONLY as shards.

Where ``distributed_data_parallel.py`` replicates the model and psums grads,
this script holds 1/world of the flat fp32 master arena per rank
(``ZeRO3FusedAdam``) and materializes params transiently each step:

* forward calls ``gather_params`` — a bucketed all-gather whose buckets
  prefetch under the layers that consume them (``--prefetch`` bounds the
  in-flight depth);
* backward never builds a full gradient: the gather's custom VJP
  reduce-scatters the param cotangents straight into this rank's shard;
* ``--residency regather`` re-runs the gather in backward instead of keeping
  the gathered params alive across forward+backward (FSDP's
  ``reshard_after_forward``);
* the optimizer state (master + Adam moments) is 3 shard-sized arrays —
  nothing in the carried train state is model-sized.

The script finishes with the sharded-checkpoint round trip: save one ``.npz``
per rank plus a layout manifest, then reshard the world=8 checkpoint down to
world=4 and verify the re-sliced arena bit-for-bit — the save-at-one-
topology / restore-at-another move real runs need after a resize.

Run (any machine — 8 virtual CPU devices stand in for a TPU slice):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python zero3_fully_sharded.py
"""

import argparse
import functools
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

_shard_map = functools.partial(jax.shard_map, check_vma=False)

from beforeholiday_tpu.optimizers import ZeRO3FusedAdam, zero3

N, D, LAYERS = 32, 256, 8  # per-rank batch, width, depth


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--bucket-bytes", type=int, default=256 * 1024,
                   help="gather/scatter bucket size (one all-gather per "
                        "bucket of the shard)")
    p.add_argument("--prefetch", type=int, default=1,
                   help="how many bucket gathers may run ahead of their "
                        "consumers (0 = blocking full-arena gather)")
    p.add_argument("--residency", choices=("regather", "keep"),
                   default="regather",
                   help="regather: re-run the gather in backward instead of "
                        "keeping gathered params resident")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    devices = np.asarray(jax.devices())
    world = len(devices)
    mesh = Mesh(devices, ("data",))

    rng = np.random.RandomState(0)
    params = {
        f"w{i}": jnp.asarray(
            rng.randn(D, D) / np.sqrt(D), jnp.float32)
        for i in range(LAYERS)
    }
    layout = zero3.layout_of(params)
    x = jnp.asarray(rng.randn(world * N, D), jnp.float32)
    y = jnp.asarray(rng.randn(world * N, D), jnp.float32)

    opt = ZeRO3FusedAdam(
        lr=1e-3, weight_decay=0.01, impl="jnp",
        bucket_bytes=args.bucket_bytes, prefetch=args.prefetch,
        param_residency=args.residency,
    )

    def apply(p, xb):
        h = xb
        for i in range(LAYERS):
            h = jnp.tanh(h @ p[f"w{i}"])
        return h

    # the carried state is ONLY the shard triplet + step counter; its global
    # view (P("data") on the flat axis) is the fp32 arena itself
    state_specs = {"master": P("data"), "exp_avg": P("data"),
                   "exp_avg_sq": P("data"), "step": P()}

    @jax.jit
    @functools.partial(
        _shard_map, mesh=mesh, in_specs=(P(),), out_specs=state_specs)
    def init(p):
        return opt.init(p)

    @jax.jit
    @functools.partial(
        _shard_map, mesh=mesh,
        in_specs=(state_specs, P("data"), P("data")),
        out_specs=(state_specs, P()),
    )
    def train_step(state, xb, yb):
        def loss_fn(master_shard):
            p = opt.gather_params(master_shard, layout)
            return jnp.mean((apply(p, xb) - yb) ** 2)

        # under "regather" the gathered arena is non-saveable: backward
        # re-gathers instead of holding a second model-sized buffer
        loss_fn = opt.wrap_residency(loss_fn)
        loss, g = jax.value_and_grad(loss_fn)(state["master"])
        state = opt.step(g, state)  # g is already this rank's fp32 shard
        return state, jax.lax.pmean(loss, "data")

    state = init(params)
    shard = state["master"].shape[0] // world
    print(f"world={world}  arena={layout.spec.padded_total}  "
          f"shard={shard}  per-rank state bytes={3 * shard * 4}")
    for t in range(args.steps):
        state, loss = train_step(state, x, y)
    print("final loss =", float(loss))

    # ---- sharded checkpoint + topology-change restore ----------------------
    stacked = {
        k: np.asarray(state[k]).reshape(world, shard)
        for k in ("master", "exp_avg", "exp_avg_sq")
    }
    stacked["step"] = np.asarray(state["step"])
    manifest = zero3.shard_manifest(layout, world)
    with tempfile.TemporaryDirectory() as ckpt:
        zero3.save_shard_files(
            ckpt, zero3.shards_from_stacked(stacked, world), manifest)
        mf, shards = zero3.load_shard_files(ckpt)
        new_world = max(world // 2, 1)
        resharded = zero3.reshard_state(shards, mf, new_world)
        for key in mf["state_keys"]:
            orig = stacked[key].reshape(-1)[: mf["arena_len"]]
            back = np.concatenate(
                [r[key] for r in resharded])[: mf["arena_len"]]
            assert np.array_equal(orig, back), key
        print(f"saved {world} shards, resharded to {new_world}: "
              "arena round-trips bitwise")


if __name__ == "__main__":
    main()
