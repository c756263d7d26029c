"""ZeRO-3 engine rungs, oracle-checked and gated — on the virtual CPU mesh.

Four claims from the ZeRO-3 ISSUE, each pinned the only way the 1-core CI
host allows (same philosophy as ``overlap_engine_bench``):

* **Parity oracle** — a 2-step ZeRO-3 run (prefetched gather -> custom_vjp
  reduce-scatter -> sharded fused update) must match ZeRO-2 on identical
  inputs BITWISE (params and master arena), uncompressed. Asserted before
  anything is printed; a silent numerics drift kills the bench, not a gate.
* **Prefetch overlap** — the forward gather is traced to a jaxpr with
  ``prefetch=1`` and ``prefetch=0`` and replayed through the deterministic
  dual-engine model (``testing/_replay``). With prefetch, each layer's
  compute is dataflow-ready the moment its bucket stripes land, so it rides
  under the later buckets' gathers; the blocking form joins every consumer
  on the full-arena concat. The child asserts the prefetch variant's
  ``overlap_fraction`` is STRICTLY higher and emits both fractions.
* **State residency** — per-rank persistent bytes (what a rank must hold
  between steps) measured through the memory ledger's AOT path
  (``measure_memory`` argument bytes): ZeRO-2 holds full params + 3 shard
  arrays, ZeRO-3 holds only the 3 shard arrays. At world=8 the ratio lands
  near (12/8) / (4 + 12/8) ~ 0.27; the child asserts <= 0.6 (the ISSUE's
  ">= 40% drop" with margin).
* **Resharding** — the final sharded state is saved at world=8 via
  ``save_shard_files`` and restored at world 4/2/1 via ``reshard_state``;
  the re-concatenated arena must match bitwise.

Replay makespans and byte counts are exact (no clocks), so the two gated
keys — ``zero3_peak_state_bytes_vs_zero2`` and
``zero3_prefetch_overlap_fraction`` — sit safely inside the parent bench's
±10% stability gate; ``pass2`` re-derives both.

Run as ``python -m beforeholiday_tpu.testing.zero3_bench`` (``--quick``
shrinks sizes) under ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``; prints one JSON line.
"""

from __future__ import annotations

import json
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np


def _shmap(f, **kw):
    kw.setdefault("check_vma", False)
    return jax.shard_map(f, **kw)


WORLD = 8

from beforeholiday_tpu.testing._replay import (  # noqa: E402
    bitwise_equal as _bitwise_equal,
    replay_fn as _replay_fn,
)


def main(quick: bool = False):
    from jax.sharding import Mesh, PartitionSpec as P

    from beforeholiday_tpu import monitor
    from beforeholiday_tpu.monitor import comms as mon_comms
    from beforeholiday_tpu.monitor.memory import measure_memory
    from beforeholiday_tpu.optimizers import (
        DistributedFusedAdam, ZeRO3FusedAdam,
    )
    from beforeholiday_tpu.optimizers import zero3
    from beforeholiday_tpu.optimizers.distributed_fused import _shard_len

    if len(jax.devices()) < WORLD or jax.default_backend() != "cpu":
        raise RuntimeError(
            f"zero3_bench needs a >= {WORLD}-device CPU platform, "
            f"got {len(jax.devices())} x {jax.default_backend()}"
        )
    mesh = Mesh(np.array(jax.devices()[:WORLD]), ("data",))

    # geometry: one (dim, dim) layer per gather bucket stripe, so layer k's
    # forward is unlocked by bucket (k mod buckets_per_shard) alone — the
    # shape that makes prefetch pipelining visible to the replay
    dim, layers, rows = (128, 16, 8) if quick else (256, 32, 16)
    bucket_bytes = dim * dim * 4
    rng = np.random.RandomState(0)
    params = {
        f"w{i:02d}": jnp.asarray(
            (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        )
        for i in range(layers)
    }
    layout = zero3.layout_of(params)
    shard = _shard_len(layout.spec.padded_total, WORLD)
    x = jnp.asarray(rng.randn(WORLD * rows, dim).astype(np.float32))

    def _loss(p, xb):
        y = xb
        for k in sorted(p):
            y = jnp.tanh(y @ p[k])
        return jnp.sum(y)

    z2 = DistributedFusedAdam(
        lr=1e-2, weight_decay=0.02, impl="jnp", bucket_bytes=bucket_bytes,
    )
    z3 = ZeRO3FusedAdam(
        lr=1e-2, weight_decay=0.02, impl="jnp", bucket_bytes=bucket_bytes,
        prefetch=1, param_residency="keep",
    )

    # ---------------- rung 1: 2-step bitwise parity oracle vs ZeRO-2
    mon_comms.reset_comms_ledger()
    state_specs = {"master": P("data"), "exp_avg": P("data"),
                   "exp_avg_sq": P("data"), "step": P()}

    def z2_body(p, xb):
        state = z2.init(p)
        for _ in range(2):
            g = jax.grad(_loss)(p, xb)
            p, state = z2.step(p, g, state)
        return p, state

    def z3_body(p, xb):
        state = z3.init(p)
        for _ in range(2):
            def loss_fn(master):
                return _loss(z3.gather_params(master, layout), xb)

            g = jax.grad(loss_fn)(state["master"])
            state = z3.step(g, state)
        return z3.gather_params(state["master"], layout), state

    z2_run = monitor.track_compiles("zero3_bench.zero2_2step")(
        jax.jit(_shmap(z2_body, mesh=mesh, in_specs=(P(), P("data")),
                       out_specs=(P(), state_specs))))
    z3_run = monitor.track_compiles("zero3_bench.zero3_2step")(
        jax.jit(_shmap(z3_body, mesh=mesh, in_specs=(P(), P("data")),
                       out_specs=(P(), state_specs))))

    p2, s2 = jax.block_until_ready(z2_run(params, x))
    p3, s3 = jax.block_until_ready(z3_run(params, x))
    if not _bitwise_equal(p2, p3):
        raise AssertionError("ZeRO-3 params diverged bitwise from ZeRO-2")
    if not _bitwise_equal(s2["master"], s3["master"]):
        raise AssertionError("ZeRO-3 master arena diverged from ZeRO-2")

    zero3_sites = sorted({
        r["site"] for r in mon_comms.comms_records()
        if r["site"].startswith("zero3.")
    })
    for want in ("zero3.gather_params", "zero3.reduce_scatter_grads",
                 "zero3.found_inf"):
        if want not in zero3_sites:
            raise AssertionError(
                f"ledger site {want!r} missing; saw {zero3_sites}"
            )

    # ---------------- rung 2: prefetch overlap replay (forward gather)
    def _fwd_fn(opt):
        def fwd(master, xb):
            return _loss(opt.gather_params(master, layout), xb)

        return _shmap(fwd, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=P())

    z3_off = ZeRO3FusedAdam(
        lr=1e-2, impl="jnp", bucket_bytes=bucket_bytes,
        prefetch=0, param_residency="keep",
    )
    master_g = jnp.asarray(np.asarray(s3["master"], np.float32))
    rep_on = _replay_fn(_fwd_fn(z3), master_g, x)
    rep_off = _replay_fn(_fwd_fn(z3_off), master_g, x)
    if rep_off["comms_us"] <= 0 or rep_on["comms_us"] <= 0:
        raise AssertionError(
            "replay saw no collectives — gather became opaque to the tracer"
        )
    if not rep_on["overlap_fraction"] > rep_off["overlap_fraction"]:
        raise AssertionError(
            f"prefetch=1 overlap {rep_on['overlap_fraction']:.4f} is not "
            f"strictly above prefetch=0 {rep_off['overlap_fraction']:.4f}"
        )

    # ---------------- rung 3: per-rank persistent state bytes (memory ledger)
    def _probe(trees):
        total = jnp.float32(0)
        for leaf in jax.tree_util.tree_leaves(trees):
            total = total + jnp.sum(leaf).astype(jnp.float32)
        return total

    sh = jnp.zeros((shard,), jnp.float32)
    z2_resident = (params, {"master": sh, "exp_avg": sh, "exp_avg_sq": sh})
    z3_resident = {"master": sh, "exp_avg": sh, "exp_avg_sq": sh}
    stats2 = measure_memory(
        jax.jit(_probe), z2_resident, entry="zero3_bench.zero2_resident")
    stats3 = measure_memory(
        jax.jit(_probe), z3_resident, entry="zero3_bench.zero3_resident")

    def _bytes(stats, trees):
        if stats and stats.get("argument_bytes"):
            return int(stats["argument_bytes"])
        # backend without memory_analysis: fall back to the leaf sum the
        # AOT path would have reported
        return int(sum(
            l.size * jnp.dtype(l.dtype).itemsize
            for l in jax.tree_util.tree_leaves(trees)
        ))

    z2_bytes = _bytes(stats2, z2_resident)
    z3_bytes = _bytes(stats3, z3_resident)
    mem_ratio = z3_bytes / z2_bytes
    if not mem_ratio <= 0.6:
        raise AssertionError(
            f"ZeRO-3 per-rank state is {mem_ratio:.3f} of ZeRO-2's "
            "(want <= 0.6 — a >= 40% drop)"
        )

    # ---------------- rung 4: reshard 8 -> {4, 2, 1} bitwise round-trip
    stacked = {
        k: np.asarray(s3[k]).reshape(WORLD, shard)
        for k in ("master", "exp_avg", "exp_avg_sq")
    }
    stacked["step"] = np.asarray(s3["step"])
    manifest = zero3.shard_manifest(layout, WORLD)
    arena_len = manifest["arena_len"]
    reshard_ok = []
    with tempfile.TemporaryDirectory() as tmp:
        zero3.save_shard_files(
            tmp, zero3.shards_from_stacked(stacked, WORLD), manifest)
        mf, shards = zero3.load_shard_files(tmp)
        for new_world in (4, 2, 1):
            re = zero3.reshard_state(shards, mf, new_world)
            for key in ("master", "exp_avg", "exp_avg_sq"):
                orig = stacked[key].reshape(-1)[:arena_len]
                back = np.concatenate(
                    [r[key] for r in re])[:arena_len]
                if not np.array_equal(orig, back):
                    raise AssertionError(
                        f"reshard 8->{new_world} broke {key!r} bitwise")
            reshard_ok.append(new_world)

    # ---------------- pass 2 re-derivation for the stability gate
    rep_on2 = _replay_fn(_fwd_fn(z3), master_g, x)
    stats2b = measure_memory(jax.jit(_probe), z2_resident)
    stats3b = measure_memory(jax.jit(_probe), z3_resident)
    ratio2 = _bytes(stats3b, z3_resident) / _bytes(stats2b, z2_resident)

    out = {
        "zero3_step_bitwise_equal_zero2": True,
        "zero3_prefetch_overlap_fraction": round(
            rep_on["overlap_fraction"], 4),
        "zero3_noprefetch_overlap_fraction": round(
            rep_off["overlap_fraction"], 4),
        "zero3_prefetch_makespan_ratio": round(
            rep_on["makespan_us"] / rep_off["makespan_us"], 4),
        "zero2_state_bytes_per_rank": z2_bytes,
        "zero3_state_bytes_per_rank": z3_bytes,
        "zero3_peak_state_bytes_vs_zero2": round(mem_ratio, 4),
        "zero3_reshard_roundtrip": reshard_ok,
        "zero3_ledger_sites": zero3_sites,
        "compile_counters": monitor.compile_summary(),
        "pass2": {
            "zero3_peak_state_bytes_vs_zero2": round(ratio2, 4),
            "zero3_prefetch_overlap_fraction": round(
                rep_on2["overlap_fraction"], 4),
        },
        "config": (
            f"world={WORLD} dim={dim} layers={layers} rows={rows} "
            f"bucket_bytes={bucket_bytes} shard={shard}"
        ),
    }
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main(quick="--quick" in sys.argv[1:])
