"""Overlap-engine rungs, paired and gated — on the virtual CPU mesh.

The 1-core CI host cannot *measure* backward-time overlap (one thread pool
executes everything serially), so this bench derives its gated numbers from
the one thing the overlap engine actually changes: WHERE the collectives sit
in the program. Each paired rung traces both variants to jaxprs and replays
them through a deterministic dual-engine model — compute ops run in program
order on one engine, collectives in program order on the other, each op
starting at ``max(inputs ready, engine free)`` with fixed per-flop/per-byte
costs. A psum issued mid-backward overlaps the remaining backward compute;
a post-backward sweep serializes after it. The replay makespans are exact
integers-in-disguise (no clocks, no noise), so their ratios sit safely
inside the parent bench's ±10% stability gate:

* ``ddp_overlap_vs_post_backward`` — backward-time bucket reduction
  (``DistributedDataParallel(overlap_backward=True)`` / ``Reducer.hook``)
  vs the classic post-backward ``reduce_gradients`` sweep, on a scanned
  MLP (the hook rides the per-iteration parameter slice INSIDE the scan).
* ``opt_in_backward_vs_phased`` — hooked backwards + ``step_in_backward``
  vs phased reduce-then-``step_flat``, on a grad-accumulation step over K
  microbatches. Both variants reduce per microbatch and sum afterwards
  (identical wire bytes and float order, so the outputs stay bitwise
  comparable); the hook variant issues each microbatch's reductions inside
  its backward, where they ride under the next microbatch's compute.

Each rung's replayed timelines feed ``monitor.overlap.overlap_report`` and
the bench asserts the hook variant's ``overlap_fraction`` is STRICTLY higher
— the ISSUE's acceptance shape. The makespan RATIOS are gated only for
stability, not direction: in the DDP rung the hook pays per-launch wire
latency on every per-layer collective while the post-backward sweep fuses
the stacked tree into two, so at these toy sizes its ratio sits below 1 —
the latency/fusion trade the bucketing layer exists to manage. Numerics are pinned inline before any
replay: the hook variant must match the post-backward variant bitwise
(uncompressed), and the compressed hook must sit inside
``bucketing.compression_error_bound``. Wall-clock timings are emitted as
informational keys only (they mean little on this host). The pipeline rung
is proven by the overlap_engine parity tests plus the recorded
``phase_shift_ticks``, not here — a replay of a fori_loop tick engine would
model the schedule tables, not the engine.

Run as ``python -m beforeholiday_tpu.testing.overlap_engine_bench``
(``--quick`` shrinks sizes) under ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8``; prints one JSON line
with a ``pass2`` re-derivation for the stability gate.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _shmap(f, **kw):
    kw.setdefault("check_vma", False)
    return jax.shard_map(f, **kw)


WORLD = 8

# the dual-engine replay lives in testing/_replay (shared with zero3_bench);
# these aliases keep this module's internal call sites unchanged
from beforeholiday_tpu.testing._replay import (  # noqa: E402
    bitwise_equal as _bitwise_equal,
    replay_fn as _replay_fn,
)


def _time(fn, args, iters, rounds=3):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def main(quick: bool = False):
    from jax.sharding import Mesh, PartitionSpec as P

    from beforeholiday_tpu import monitor, parallel
    from beforeholiday_tpu.ops import arena
    from beforeholiday_tpu.optimizers.fused import FusedAdam
    from beforeholiday_tpu.parallel import bucketing
    from beforeholiday_tpu.parallel.distributed import (
        DistributedDataParallel, reduce_gradients,
    )

    if len(jax.devices()) < WORLD or jax.default_backend() != "cpu":
        raise RuntimeError(
            f"overlap_engine_bench needs a >= {WORLD}-device CPU platform, "
            f"got {len(jax.devices())} x {jax.default_backend()}"
        )
    mesh = Mesh(np.array(jax.devices()[:WORLD]), ("data",))
    dim, layers, rows, iters = (8, 4, 4, 2) if quick else (16, 6, 8, 5)
    rng = np.random.RandomState(0)

    def _entry(name, body, in_specs, out_specs):
        fn = jax.jit(_shmap(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs))
        return monitor.track_compiles(f"overlap_engine_bench.{name}")(fn)

    # ---------------- rung 1: DDP backward-time reduction vs post-backward
    stacked = {
        "w": jnp.asarray(rng.randn(layers, dim, dim) * 0.3, jnp.float32),
        "b": jnp.zeros((layers, dim), jnp.float32),
    }
    x = jnp.asarray(rng.randn(WORLD, rows, dim), jnp.float32)
    tgt = jnp.asarray(rng.randn(WORLD, rows, dim), jnp.float32)

    # benched variants run gradient_average=False (the scale-folded-into-
    # the-loss config): averaging puts a div on each psum RESULT, and the
    # in-order replay compute engine — unlike XLA's latency-hiding
    # scheduler — cannot hoist independent backward ops over that div, so
    # it would stall on every collective and report fake serialization.
    # Parity for the averaged path is pinned by the overlap_engine tests.
    def scan_loss(stacked, x, tgt, *, hook):
        def body(h, lp):
            if hook:
                # the per-iteration slice is the "bucket": its cotangent is
                # psummed inside the backward scan, while earlier layers'
                # backward compute is still in flight
                lp = parallel.hook_tree(lp, tag="scan_layer",
                                        axis_name="data",
                                        gradient_average=False)
            return jnp.tanh(h @ lp["w"] + lp["b"]), None

        h, _ = jax.lax.scan(body, x, stacked)
        return jnp.mean((h - tgt) ** 2)

    def ddp_hook_step(stacked, x, tgt):
        return jax.value_and_grad(
            lambda s: scan_loss(s, x, tgt, hook=True))(stacked)

    def ddp_post_step(stacked, x, tgt):
        loss, grads = jax.value_and_grad(
            lambda s: scan_loss(s, x, tgt, hook=False))(stacked)
        return loss, reduce_gradients(grads, axis_name="data",
                                      gradient_average=False)

    specs = ((P(), P("data"), P("data")), (P(), P()))
    f_hook = _entry("ddp_hook", ddp_hook_step, *specs)
    f_post = _entry("ddp_post", ddp_post_step, *specs)

    loss_h, g_h = jax.device_get(f_hook(stacked, x, tgt))
    loss_p, g_p = jax.device_get(f_post(stacked, x, tgt))
    if not (_bitwise_equal(loss_h, loss_p) and _bitwise_equal(g_h, g_p)):
        raise RuntimeError(
            "DDP hook grads are not bitwise-equal to post-backward "
            "reduce_gradients — the overlap rung changed numerics"
        )

    # compressed rung rides the same hook; parity is the analytic wire bound
    def ddp_comp_step(stacked, x, tgt):
        def body(h, lp):
            lp = parallel.hook_tree(
                lp, tag="scan_layer_c", axis_name="data",
                gradient_average=False, compress=True,
                wire_dtype=jnp.bfloat16,
            )
            return jnp.tanh(h @ lp["w"] + lp["b"]), None

        def loss_of(s):
            h, _ = jax.lax.scan(body, x, s)
            return jnp.mean((h - tgt) ** 2)

        loss, grads = jax.value_and_grad(loss_of)(stacked)
        # exact psum + per-element bound, computed in the same trace
        _, raw = jax.value_and_grad(
            lambda s: scan_loss(s, x, tgt, hook=False))(stacked)
        exact = jax.tree.map(
            lambda g: jax.lax.psum(g, "data"), raw)
        bound = jax.tree.map(
            lambda g: bucketing.compression_error_bound(
                jax.lax.psum(jnp.abs(g), "data")), raw)
        return grads, exact, bound

    f_comp = _entry("ddp_hook_compressed", ddp_comp_step,
                    (P(), P("data"), P("data")), (P(), P(), P()))
    g_c, g_e, g_bound = jax.device_get(f_comp(stacked, x, tgt))
    for gc, ge, gb in zip(jax.tree_util.tree_leaves(g_c),
                          jax.tree_util.tree_leaves(g_e),
                          jax.tree_util.tree_leaves(g_bound)):
        if np.any(np.abs(np.asarray(gc) - np.asarray(ge))
                  > np.asarray(gb) + 1e-12):
            raise RuntimeError(
                "compressed hook reduction exceeded "
                "bucketing.compression_error_bound"
            )

    def rung1():
        rep_h = _replay_fn(
            _shmap(ddp_hook_step, mesh=mesh, in_specs=specs[0],
                   out_specs=specs[1]), stacked, x, tgt)
        rep_p = _replay_fn(
            _shmap(ddp_post_step, mesh=mesh, in_specs=specs[0],
                   out_specs=specs[1]), stacked, x, tgt)
        if not (rep_h["overlap_fraction"] or 0.0) > (
                rep_p["overlap_fraction"] or 0.0):
            raise RuntimeError(
                "replayed overlap_fraction not strictly higher with the "
                f"DDP hook: hook={rep_h['overlap_fraction']} "
                f"post={rep_p['overlap_fraction']}"
            )
        return rep_h, rep_p

    rep_h, rep_p = rung1()

    # ---------------- rung 2: optimizer-in-backward vs phased
    # grad-accumulation step over K microbatches — the loop shape where the
    # in-backward path genuinely moves wire time: each microbatch's
    # reductions are ISSUED inside its backward and ride under the next
    # microbatch's compute, vs the phased sweep that issues every
    # reduction after the last backward. Both variants reduce PER
    # microbatch and sum afterwards (same wire bytes, same float order →
    # bitwise-comparable); only the issue position differs.
    K = 2 if quick else 3
    leaves = []
    for i in range(layers):
        leaves.append(
            jnp.asarray(rng.randn(dim, dim) * 0.3, jnp.float32))
        leaves.append(jnp.zeros((dim,), jnp.float32))
    flat, spec = arena.flatten(leaves)
    opt = FusedAdam(lr=1e-3)
    state0 = opt.init_flat(flat)
    xs = jnp.asarray(rng.randn(WORLD, K, rows, dim), jnp.float32)
    tgts = jnp.asarray(rng.randn(WORLD, K, rows, dim), jnp.float32)

    def mlp_loss(leaves, x, tgt):
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ leaves[2 * i] + leaves[2 * i + 1])
        return jnp.mean((h - tgt) ** 2)

    def _sum_leaves(per_mb):
        out = list(per_mb[0])
        for gs in per_mb[1:]:
            out = [a + g for a, g in zip(out, gs)]
        return out

    def opt_hook_step(flat, state, xs, tgts):
        pieces = arena.unflatten(flat, spec)
        loss = jnp.float32(0.0)
        per_mb = []
        for k in range(K):
            loss_k, g_k = jax.value_and_grad(
                lambda lv: mlp_loss(
                    parallel.hook_tree(list(lv), tag=f"opt_mb{k}",
                                       axis_name="data",
                                       gradient_average=False),
                    xs[:, k], tgts[:, k]))(pieces)
            loss = loss + loss_k
            per_mb.append(g_k)
        gleaves = _sum_leaves(per_mb)
        new_flat, new_state, flag = opt.step_in_backward(
            flat, gleaves, state, spec=spec)
        return loss, new_flat, new_state, flag

    def opt_phased_step(flat, state, xs, tgts):
        pieces = arena.unflatten(flat, spec)
        loss = jnp.float32(0.0)
        per_mb = []
        for k in range(K):
            loss_k, g_k = jax.value_and_grad(
                lambda lv: mlp_loss(list(lv), xs[:, k], tgts[:, k]))(pieces)
            loss = loss + loss_k
            per_mb.append(g_k)
        per_mb = [
            reduce_gradients(list(gs), axis_name="data",
                             gradient_average=False)
            for gs in per_mb
        ]
        gleaves = _sum_leaves(per_mb)
        new_flat, new_state = opt.step_flat(
            flat, gleaves, state, spec=spec)
        return loss, new_flat, new_state

    ospecs_in = (P(), P(), P("data"), P("data"))
    f_ohook = _entry("opt_hook", opt_hook_step, ospecs_in,
                     (P(), P(), P(), P()))
    f_ophased = _entry("opt_phased", opt_phased_step, ospecs_in,
                       (P(), P(), P()))
    _, flat_h, st_h, flag = jax.device_get(
        f_ohook(flat, state0, xs, tgts))
    _, flat_p2, st_p2 = jax.device_get(f_ophased(flat, state0, xs, tgts))
    if bool(np.asarray(flag)):
        raise RuntimeError("finite grads reported found_inf in the bench")
    if not (_bitwise_equal(flat_h, flat_p2)
            and _bitwise_equal(st_h["exp_avg"], st_p2["exp_avg"])
            and _bitwise_equal(st_h["exp_avg_sq"], st_p2["exp_avg_sq"])
            and int(st_h["step"]) == int(st_p2["step"]) == 1):
        raise RuntimeError(
            "optimizer-in-backward step is not bitwise-equal to the "
            "phased reduce-then-step"
        )

    def rung2():
        rep_oh = _replay_fn(
            _shmap(opt_hook_step, mesh=mesh, in_specs=ospecs_in,
                   out_specs=(P(), P(), P(), P())), flat, state0, xs, tgts)
        rep_op = _replay_fn(
            _shmap(opt_phased_step, mesh=mesh, in_specs=ospecs_in,
                   out_specs=(P(), P(), P())), flat, state0, xs, tgts)
        if not (rep_oh["overlap_fraction"] or 0.0) > (
                rep_op["overlap_fraction"] or 0.0):
            raise RuntimeError(
                "replayed overlap_fraction not strictly higher with "
                f"optimizer-in-backward: hook={rep_oh['overlap_fraction']} "
                f"phased={rep_op['overlap_fraction']}"
            )
        return rep_oh, rep_op

    rep_oh, rep_op = rung2()

    # informational wall clock (meaningless for overlap on this host, but a
    # regression canary for the mechanisms' raw cost)
    t_hook = _time(f_hook, (stacked, x, tgt), iters)
    t_post = _time(f_post, (stacked, x, tgt), iters)
    t_ohook = _time(f_ohook, (flat, state0, xs, tgts), iters)
    t_ophased = _time(f_ophased, (flat, state0, xs, tgts), iters)

    # deterministic second derivation for the parent's ±10% stability gate
    rep_h2, rep_p2 = rung1()
    rep_oh2, rep_op2 = rung2()

    compiles = [
        row for row in monitor.compile_summary()
        if str(row["entry"]).startswith("overlap_engine_bench.")
    ]
    print(json.dumps({
        "ddp_overlap_vs_post_backward": round(
            rep_p["makespan_us"] / rep_h["makespan_us"], 4),
        "opt_in_backward_vs_phased": round(
            rep_op["makespan_us"] / rep_oh["makespan_us"], 4),
        "ddp_hook_overlap_fraction": round(rep_h["overlap_fraction"], 4),
        "ddp_post_overlap_fraction": round(rep_p["overlap_fraction"], 4),
        "opt_hook_overlap_fraction": round(rep_oh["overlap_fraction"], 4),
        "opt_phased_overlap_fraction": round(rep_op["overlap_fraction"], 4),
        "t_ddp_hook_ms": round(t_hook * 1e3, 3),
        "t_ddp_post_ms": round(t_post * 1e3, 3),
        "t_opt_hook_ms": round(t_ohook * 1e3, 3),
        "t_opt_phased_ms": round(t_ophased * 1e3, 3),
        "compile_counters": compiles,
        "pass2": {
            "ddp_overlap_vs_post_backward": round(
                rep_p2["makespan_us"] / rep_h2["makespan_us"], 4),
            "opt_in_backward_vs_phased": round(
                rep_op2["makespan_us"] / rep_oh2["makespan_us"], 4),
        },
        "config": f"world={WORLD} dim={dim} layers={layers} rows={rows} "
                  f"iters={iters}",
    }))


if __name__ == "__main__":
    main(quick="--quick" in sys.argv[1:])
