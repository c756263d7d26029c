"""Pipeline-schedule overhead probe — runs on a virtual CPU mesh.

Quantifies the 1F1B engine's bubble + recompute tax (VERDICT r3 weak #5):
the same toy transformer stack is timed as

* ``sequential``: all stages on one device, plain grad-accumulation scan
  (``forward_backward_no_pipelining``), and
* ``pipelined``: stages sharded over a ``pipe`` axis driven by the collective
  tick-loop 1F1B schedule.

On a virtual CPU mesh the S pipeline "devices" timeshare the same host cores,
so TOTAL CPU WORK is the comparable quantity: overhead = t_pp / t_seq
(1.0 = schedule adds nothing; the excess is bubbles + backward recompute +
ring traffic). Run as ``python -m beforeholiday_tpu.testing.pp_bench`` with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``;
prints one JSON line.
"""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


HIDDEN = 256
MICRO = 8  # rows per microbatch
M = 16  # microbatches
S = 4  # pipeline stages


def stage_fn(sp, x):
    h = jax.nn.gelu(x @ sp["w1"] + sp["b1"])
    return h @ sp["w2"] + sp["b2"] + x


def loss_fn(y, tgt):
    return jnp.mean((y - tgt) ** 2)


def init_stages(key):
    ks = jax.random.split(key, 2)
    s = 1.0 / np.sqrt(HIDDEN)
    return {
        "w1": jax.random.normal(ks[0], (S, HIDDEN, 4 * HIDDEN)) * s,
        "b1": jnp.zeros((S, 4 * HIDDEN)),
        "w2": jax.random.normal(ks[1], (S, 4 * HIDDEN, HIDDEN)) * s,
        "b2": jnp.zeros((S, HIDDEN)),
    }


def _time(fn, args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    from jax.sharding import Mesh, PartitionSpec as P

    from beforeholiday_tpu.transformer import pipeline_parallel as pp

    if len(jax.devices()) < S or jax.default_backend() != "cpu":
        # a silent 1-device "mesh" would time a 1-stage model and report
        # garbage
        raise RuntimeError(
            f"pp_bench needs a >= {S}-device CPU platform, got "
            f"{len(jax.devices())} x {jax.default_backend()}"
        )
    devs = np.array(jax.devices()[:S])
    mesh = Mesh(devs, ("pipe",))

    stacked = init_stages(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    inputs = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)
    targets = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)

    # sequential baseline: the full stack as one stage, grad-accumulated
    def full_model(stacked, x):
        def body(h, sp):
            return stage_fn(sp, h), None

        return jax.lax.scan(body, x, stacked)[0]

    seq = jax.jit(functools.partial(
        pp.forward_backward_no_pipelining, full_model, loss_fn
    ))

    # pipelined: one stage slice per pipe device, 1F1B tick loop
    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("pipe"), P(), P()), out_specs=(P(), P("pipe")),
        check_vma=False,
    )
    def pipe_step(stage_params, inputs, targets):
        sp = jax.tree.map(lambda leaf: leaf[0], stage_params)
        loss, grads = pp.forward_backward_pipelining_without_interleaving(
            stage_fn, loss_fn, sp, inputs, targets, axis_name="pipe"
        )
        return loss, jax.tree.map(lambda g: g[None], grads)

    loss_seq, _ = seq(stacked, inputs, targets)
    loss_pp, _ = pipe_step(stacked, inputs, targets)
    # sanity: the schedule must reproduce the sequential loss
    err = abs(float(loss_seq) - float(loss_pp))
    if err > 1e-3 * abs(float(loss_seq)):
        raise RuntimeError(f"1F1B loss {float(loss_pp)} != sequential {float(loss_seq)}")

    t_seq = _time(seq, (stacked, inputs, targets))
    t_pp = _time(pipe_step, (stacked, inputs, targets))

    # the schedule recorded its report at trace time; fall back to the
    # closed form if the engine traced before this module imported
    report = pp.last_schedule_report() or pp.schedule_report(M, S)
    print(json.dumps({
        "pp_1f1b_ms": round(t_pp * 1e3, 2),
        "sequential_ms": round(t_seq * 1e3, 2),
        "pp_overhead_vs_sequential": round(t_pp / t_seq, 3),
        "loss_abs_err": float(err),
        "bubble_fraction": round(report["analytic_bubble_fraction"], 4),
        "engine_bubble_fraction": round(report["engine_bubble_fraction"], 4),
        "total_ticks": report["total_ticks"],
        "phase_counts": report["per_rank"],
        "config": f"S={S} M={M} hidden={HIDDEN} micro={MICRO}",
    }))


if __name__ == "__main__":
    main()
