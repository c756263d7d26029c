"""Multi-slice hierarchical-collective rungs, oracle-checked and gated.

The 8-CPU proxy mesh is carved into 2 slices x 4 ranks
(``parallel_state.make_two_level_mesh``), and three claims from the
multi-slice ISSUE are pinned the only way a single-host CI box allows
(same philosophy as ``overlap_engine_bench`` / ``zero3_bench``):

* **Bitwise parity oracle** — the hierarchical engines (intra-slice
  reduce-scatter -> inter-slice psum on the 1/slice_size chunk -> intra
  all-gather) must match the flat bucketed reduce BITWISE, uncompressed:
  asserted for a DDP ``reduce_gradients`` tree and for a 2-step ZeRO-2
  run before anything is printed — a silent numerics drift kills the
  bench, not a gate.
* **Ledger rung** — the comms ledger's per-tier rollup
  (``comms_summary()['by_tier']``) must prove the hierarchical reduce
  moved exactly ``flat_dcn_bytes / slice_size`` over the slow tier on an
  aligned payload: ``hier_dcn_bytes_ratio`` is that measured quotient
  (== slice_size == 4 on the proxy mesh), derived from bytes the ledger
  actually booked, not from the formula.
* **Replay rung** — both engines are traced and replayed through the
  ``testing/_replay`` dual-engine model with the ``slice`` axis taxed at
  DCN rates (10x ICI per byte and per launch). The hierarchical
  schedule's makespan must be STRICTLY below the flat one;
  ``hier_vs_flat_makespan`` is the (deterministic) ratio.

Replay makespans and ledger bytes are exact integers-in-disguise, so both
gated keys sit safely inside the parent bench's ±10% stability gate;
``pass2`` re-derives them from scratch.

Run as ``python -m beforeholiday_tpu.testing.multislice_bench``
(``--quick`` shrinks sizes) under ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``; prints one JSON line.
"""

from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _shmap(f, **kw):
    kw.setdefault("check_vma", False)
    return jax.shard_map(f, **kw)


N_SLICES = 2
SLICE_SIZE = 4
WORLD = N_SLICES * SLICE_SIZE

from beforeholiday_tpu.testing._replay import (  # noqa: E402
    bitwise_equal as _bitwise_equal,
    replay_fn as _replay_fn,
)


def main(quick: bool = False):
    from jax.sharding import PartitionSpec as P

    from beforeholiday_tpu import monitor
    from beforeholiday_tpu.monitor import comms as mon_comms
    from beforeholiday_tpu.optimizers import DistributedFusedAdam
    from beforeholiday_tpu.parallel import bucketing, distributed
    from beforeholiday_tpu.parallel.parallel_state import (
        HIERARCHICAL_AXES, make_two_level_mesh,
    )

    if len(jax.devices()) < WORLD or jax.default_backend() != "cpu":
        raise RuntimeError(
            f"multislice_bench needs a >= {WORLD}-device CPU platform, "
            f"got {len(jax.devices())} x {jax.default_backend()}"
        )
    mesh = make_two_level_mesh(N_SLICES, SLICE_SIZE)
    axes = HIERARCHICAL_AXES

    # payload: LANES-aligned fp32 layers so every bucket's scatter leg
    # divides the intra tier exactly — the ledger oracle is then an exact
    # integer quotient, not a padding-slopped approximation
    dim, layers = (128, 4) if quick else (256, 8)
    bucket_bytes = dim * dim * 4
    rng = np.random.RandomState(0)
    grads = {
        f"w{i:02d}": jnp.asarray(
            (rng.randn(dim, dim) / np.sqrt(dim)).astype(np.float32)
        )
        for i in range(layers)
    }
    arena = jnp.concatenate(
        [g.reshape(-1) for g in grads.values()]
    )

    def _run(fn, *args, out_specs=P()):
        return jax.jit(_shmap(
            fn, mesh=mesh, in_specs=tuple(P() for _ in args),
            out_specs=out_specs,
        ))(*args)

    # ---------------- rung 1: bitwise parity oracle (DDP tree + ZeRO-2)
    red_flat = _run(lambda g: distributed.reduce_gradients(
        g, axis_name=axes, bucket_bytes=bucket_bytes), grads)
    red_hier = _run(lambda g: distributed.reduce_gradients(
        g, axis_name=axes, bucket_bytes=bucket_bytes, hierarchical=True),
        grads)
    if not _bitwise_equal(red_flat, red_hier):
        raise AssertionError(
            "hierarchical reduce_gradients diverged bitwise from flat"
        )

    z2_flat = DistributedFusedAdam(
        lr=1e-2, weight_decay=0.02, impl="jnp", axis_name=axes,
        bucket_bytes=bucket_bytes,
    )
    z2_hier = DistributedFusedAdam(
        lr=1e-2, weight_decay=0.02, impl="jnp", axis_name=axes,
        bucket_bytes=bucket_bytes, hierarchical=True,
    )

    def _z2_body(opt):
        def body(p, g):
            state = opt.init(p)
            for _ in range(2):
                p, state = opt.step(p, g, state)
            return p, state["master"]

        return body

    params = {k: jnp.asarray(rng.randn(*v.shape).astype(np.float32))
              for k, v in grads.items()}
    pf, mf = _run(_z2_body(z2_flat), params, grads, out_specs=(P(), P()))
    ph, mh = _run(_z2_body(z2_hier), params, grads, out_specs=(P(), P()))
    if not (_bitwise_equal(pf, ph) and _bitwise_equal(mf, mh)):
        raise AssertionError(
            "hierarchical ZeRO-2 step diverged bitwise from flat"
        )

    # ---------------- rung 2: ledger oracle — DCN bytes == flat / slice_size
    def _dcn_bytes(fn):
        """Wire bytes the ledger booked on the 'dcn' tier for one traced run
        of ``fn`` (second trace on a fresh ledger: caches are warm, so the
        booking is exactly one program's worth)."""
        _run(fn, arena)
        mon_comms.reset_comms_ledger()
        _run(fn, arena)
        total = 0
        for row in mon_comms.comms_summary():
            total += row["by_tier"].get("dcn", {}).get("bytes", 0)
        return total

    flat_dcn = _dcn_bytes(lambda a: bucketing.bucketed_psum(
        a, axes, site="multislice.flat", bucket_bytes=bucket_bytes))
    hier_dcn = _dcn_bytes(lambda a: bucketing.hierarchical_psum(
        a, axes, site="multislice.hier", bucket_bytes=bucket_bytes))
    if hier_dcn <= 0 or flat_dcn <= 0:
        raise AssertionError(
            f"ledger saw no DCN traffic (flat={flat_dcn}, hier={hier_dcn})"
        )
    dcn_ratio = flat_dcn / hier_dcn
    if dcn_ratio != float(SLICE_SIZE):
        raise AssertionError(
            f"DCN byte ratio {dcn_ratio} != slice_size {SLICE_SIZE} "
            f"(flat={flat_dcn}, hier={hier_dcn})"
        )

    # per-tier compression ratio: bf16 on the DCN wire only
    mon_comms.reset_comms_ledger()
    _run(lambda a: bucketing.hierarchical_psum(
        a, axes, site="multislice.cdcn", bucket_bytes=bucket_bytes,
        compress_dcn=True), arena)
    mon_comms.reset_comms_ledger()
    _run(lambda a: bucketing.hierarchical_psum(
        a, axes, site="multislice.cdcn", bucket_bytes=bucket_bytes,
        compress_dcn=True), arena)
    tier_rows = {
        t: r for row in mon_comms.comms_summary()
        if row["subsystem"] == "multislice"
        for t, r in row["by_tier"].items()
    }
    dcn_cr = tier_rows.get("dcn", {}).get("compression_ratio", 0.0)
    ici_cr = tier_rows.get("ici", {}).get("compression_ratio", 0.0)
    if not (dcn_cr > 1.5 and ici_cr == 1.0):
        raise AssertionError(
            f"per-tier compression ratios wrong: dcn={dcn_cr} (want ~2), "
            f"ici={ici_cr} (want 1.0)"
        )

    # ---------------- rung 3: replay with the slice axis taxed at DCN rates
    def _flat_fn(a):
        return bucketing.bucketed_psum(
            a, axes, site="replay.flat", bucket_bytes=bucket_bytes)

    def _hier_fn(a):
        return bucketing.hierarchical_psum(
            a, axes, site="replay.hier", bucket_bytes=bucket_bytes)

    def _traced(fn):
        return _shmap(fn, mesh=mesh, in_specs=(P(),), out_specs=P())

    dcn_axes = frozenset({"slice"})
    rep_flat = _replay_fn(_traced(_flat_fn), arena, dcn_axes=dcn_axes)
    rep_hier = _replay_fn(_traced(_hier_fn), arena, dcn_axes=dcn_axes)
    if rep_flat["comms_us"] <= 0 or rep_hier["comms_us"] <= 0:
        raise AssertionError(
            "replay saw no collectives — the engines became opaque"
        )
    makespan_ratio = rep_hier["makespan_us"] / rep_flat["makespan_us"]
    if not makespan_ratio < 1.0:
        raise AssertionError(
            f"hierarchical makespan ratio {makespan_ratio:.4f} is not "
            "strictly below flat under the DCN tax"
        )

    # ---------------- pass 2 re-derivation for the stability gate
    flat_dcn2 = _dcn_bytes(lambda a: bucketing.bucketed_psum(
        a, axes, site="multislice.flat", bucket_bytes=bucket_bytes))
    hier_dcn2 = _dcn_bytes(lambda a: bucketing.hierarchical_psum(
        a, axes, site="multislice.hier", bucket_bytes=bucket_bytes))
    rep_flat2 = _replay_fn(_traced(_flat_fn), arena, dcn_axes=dcn_axes)
    rep_hier2 = _replay_fn(_traced(_hier_fn), arena, dcn_axes=dcn_axes)

    out = {
        "multislice_bitwise_equal_flat": True,
        "hier_dcn_bytes_ratio": round(dcn_ratio, 4),
        "hier_vs_flat_makespan": round(makespan_ratio, 4),
        "hier_dcn_bytes": hier_dcn,
        "flat_dcn_bytes": flat_dcn,
        "hier_dcn_compression_ratio": round(dcn_cr, 4),
        "hier_ici_compression_ratio": round(ici_cr, 4),
        "flat_makespan_us": round(rep_flat["makespan_us"], 3),
        "hier_makespan_us": round(rep_hier["makespan_us"], 3),
        "compile_counters": monitor.compile_summary(),
        "pass2": {
            "hier_dcn_bytes_ratio": round(flat_dcn2 / hier_dcn2, 4),
            "hier_vs_flat_makespan": round(
                rep_hier2["makespan_us"] / rep_flat2["makespan_us"], 4),
        },
        "config": (
            f"slices={N_SLICES}x{SLICE_SIZE} dim={dim} layers={layers} "
            f"bucket_bytes={bucket_bytes}"
        ),
    }
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main(quick="--quick" in sys.argv[1:])
