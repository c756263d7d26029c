"""Bucketed-collective overhead probe — runs on a virtual CPU mesh.

Prices the gradient-arena communication layer (``parallel.bucketing``) on the
same 8-CPU proxy mesh as ``pp_bench``:

* ``ddp_bucketed_vs_monolithic`` — ``reduce_gradients`` with ~bucket_bytes
  buckets vs the single fused psum, same grad tree. Uncompressed bucketing is
  bitwise-identical, so the ratio is pure dispatch/scheduling overhead
  (1.0 = bucketing costs nothing; on TPU the buckets buy backward overlap the
  CPU proxy cannot see).
* ``zero2_compressed_vs_fp32`` — ``DistributedFusedAdam`` full step with bf16
  wire + fp32 accumulation vs the fp32-wire step, both bucketed. The ratio
  prices the cast/unpack tax against the halved wire bytes (on CPU the
  "wire" is memcpy, so this is a LOWER bound on the TPU win).

Both jitted entries are tracked by the recompile sentinel
(``comms_bench.*``); the emitted line carries the per-entry compile counts so
a shape-unstable bucketing path shows up as a sentinel hit, not a silent
slowdown. Run as ``python -m beforeholiday_tpu.testing.comms_bench``
(``--quick`` shrinks sizes for CI) with ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``; prints one JSON line.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _shmap(f, **kw):
    kw.setdefault("check_vma", False)
    return jax.shard_map(f, **kw)


WORLD = 8
BUCKET_BYTES = 256 * 1024


def _grad_tree(dim: int, n_mats: int):
    rng = np.random.RandomState(0)
    tree = {
        f"w{i}": jnp.asarray(rng.randn(dim, dim), jnp.float32)
        for i in range(n_mats)
    }
    tree["bias"] = jnp.asarray(rng.randn(dim + 37), jnp.float32)
    return tree


def _time(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _max_abs_diff(a, b):
    return max(
        float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def main(quick: bool = False):
    from jax.sharding import Mesh, PartitionSpec as P

    from beforeholiday_tpu.monitor import comms, compile_summary, track_compiles
    from beforeholiday_tpu.optimizers.distributed_fused import (
        DistributedFusedAdam,
    )
    from beforeholiday_tpu.parallel import reduce_gradients

    if len(jax.devices()) < WORLD or jax.default_backend() != "cpu":
        # same guard as pp_bench: a silent 1-device "mesh" would report garbage
        raise RuntimeError(
            f"comms_bench needs a >= {WORLD}-device CPU platform, got "
            f"{len(jax.devices())} x {jax.default_backend()}"
        )
    mesh = Mesh(np.array(jax.devices()[:WORLD]), ("data",))
    dim, n_mats, iters = (128, 2, 2) if quick else (512, 6, 10)
    grads = _grad_tree(dim, n_mats)
    n_elems = sum(g.size for g in jax.tree.leaves(grads))

    def _reduce_entry(name, **red_kw):
        def body(g):
            return reduce_gradients(g, axis_name="data", **red_kw)

        fn = jax.jit(_shmap(body, mesh=mesh, in_specs=(P(),), out_specs=P()))
        return track_compiles(f"comms_bench.{name}")(fn)

    comms.reset_comms_ledger()
    mono = _reduce_entry("ddp_monolithic")
    buck = _reduce_entry("ddp_bucketed", bucket_bytes=BUCKET_BYTES)

    r_mono = mono(grads)
    r_buck = buck(grads)  # traces here — the ledger row below counts buckets
    ddp_err = _max_abs_diff(r_mono, r_buck)
    if ddp_err != 0.0:
        raise RuntimeError(
            f"bucketed reduce diverged from monolithic by {ddp_err}"
        )
    n_buckets = sum(
        r["calls"] for r in comms.comms_records()
        if r["site"] == "ddp.bucketed_reduce"
    )

    t_mono = _time(mono, (grads,), iters)
    t_buck = _time(buck, (grads,), iters)

    # --- ZeRO-2: compressed (bf16 wire, fp32 accum) vs fp32 wire ---
    params = _grad_tree(dim, n_mats)

    def _step_entry(name, **opt_kw):
        opt = DistributedFusedAdam(
            axis_name="data", bucket_bytes=BUCKET_BYTES, **opt_kw
        )

        def body(p, g):
            st = opt.init(p)
            p, _ = opt.step(p, g, st)
            return p

        fn = jax.jit(
            _shmap(body, mesh=mesh, in_specs=(P(), P()), out_specs=P())
        )
        return track_compiles(f"comms_bench.{name}")(fn)

    z_fp32 = _step_entry("zero2_fp32")
    z_comp = _step_entry("zero2_compressed", compress=True)
    p_fp32 = z_fp32(params, grads)
    p_comp = z_comp(params, grads)
    zero2_err = _max_abs_diff(p_fp32, p_comp)

    t_z32 = _time(z_fp32, (params, grads), iters)
    t_zc = _time(z_comp, (params, grads), iters)

    compiles = [
        row for row in compile_summary()
        if str(row["entry"]).startswith("comms_bench.")
    ]
    print(json.dumps({
        "ddp_monolithic_ms": round(t_mono * 1e3, 3),
        "ddp_bucketed_ms": round(t_buck * 1e3, 3),
        "ddp_bucketed_vs_monolithic": round(t_buck / t_mono, 3),
        "zero2_fp32_ms": round(t_z32 * 1e3, 3),
        "zero2_compressed_ms": round(t_zc * 1e3, 3),
        "zero2_compressed_vs_fp32": round(t_zc / t_z32, 3),
        "bucket_bytes": BUCKET_BYTES,
        "n_buckets": n_buckets,
        "zero2_compressed_max_err": zero2_err,
        "compile_counters": compiles,
        "config": f"world={WORLD} dim={dim} n_mats={n_mats} "
                  f"elems={n_elems} iters={iters}",
    }))


if __name__ == "__main__":
    main(quick="--quick" in sys.argv[1:])
