#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main training path starts on the chip.

Drives the flagship GPT (``gpt_1024x16_8layer_s1024_b16``) through the entry
points a user calls — ``amp.initialize(..., "O5", arena_native=True)`` +
``FusedAdam`` + ``amp.scaled_value_and_grad`` + ``optimizer.step`` under
``remat.donate_step`` — as a plain Python loop of fenced steps on one fixed
batch, first on one chip, then (when the process sees >= 4) data-parallel over
four inside ``jax.shard_map(check_vma=False)`` with ``DistributedDataParallel``
reducing the gradients. It checks, and fails on:

* non-finite loss, loss not falling, ``found_inf`` on the last step;
* the Pallas kernels NOT having run compiled: the guard's dispatch counters
  must show ``flash_attention`` and ``layer_norm`` with ``pallas > 0`` and
  ``jnp == 0`` and no probe failure — which turns every quiet fallback
  (``resolve_impl`` → jnp, ``checked_impl`` degrade, shape-gate swaps) into a
  failed smoke;
* on four chips: state not on all four devices, no memory in use on one of
  them, a DP4 loss trajectory that leaves the one-chip trajectory (the DP4
  batch is the one-chip batch tiled four times, so mean loss and averaged
  gradients are the one-chip run's), replicas that drifted apart.

One process, no child, no ``jax_platforms``/``XLA_FLAGS`` set here. Exits
non-zero before compiling anything unless ``jax.default_backend() == "tpu"``.
Prints one JSON line per phase, one ``summary`` object of the run, and as the
LAST line of stdout the verdict a driver reads: exactly
``{"ok": bool, "device": {"platform", "kind", "count"}}`` with the device as
JAX reports it. Times and tokens/s are smoke observations, not benchmark
numbers; no utilisation is computed here.

    python chip_smoke.py                    # one chip (four-chip phase if present)
    python chip_smoke.py --require-chips 4  # fewer than four chips is an error
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

# the package is not installed: import it from this checkout
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

FLAGSHIP = "gpt_1024x16_8layer_s1024_b16"
FLAGSHIP_BATCH = 16
STEPS = 6
# DP4 feeds every chip the one-chip batch, so its per-chip program computes the
# one-chip gradients and the four-way mean of identical bf16 values is exact;
# what may differ is XLA's fusion/rounding order around the collective. The
# loss is an fp32 mean over 16k tokens of bf16-activation logits: bf16 carries
# 8 bits (2^-8 ~ 4e-3), and accumulated re-rounding over a handful of steps
# stays well inside a quarter of that.
DP4_LOSS_RTOL = 1e-3
_GUARDED_OPS = ("flash_attention", "layer_norm")


def flagship_cfg():
    from beforeholiday_tpu.testing import gpt

    return gpt.GPTConfig(
        vocab_size=32000, seq_len=1024, d_model=1024, n_heads=16, n_layers=8,
        dtype=jnp.bfloat16,
    )


def dispatch_totals(counters):
    """``{op: {"pallas": n, "jnp": n}}`` summed over every key of each guarded
    op in ``guard.dispatch.dispatch_counters()``."""
    return {
        op: {
            which: sum(v[which] for k, v in counters.items() if k[0] == op)
            for which in ("pallas", "jnp")
        }
        for op in _GUARDED_OPS
    }


def dispatch_errors(counters, failures):
    """Why the guarded kernels cannot be said to have run compiled — empty
    when every ``_GUARDED_OPS`` op dispatched pallas at least once, never
    jnp, and no probe failed. ``counters``/``failures`` are
    ``guard.dispatch.dispatch_counters()`` / ``probe_failures()``."""
    errors = []
    for op, n in dispatch_totals(counters).items():
        if n["pallas"] <= 0:
            errors.append(f"{op}: no pallas dispatch (resolved to jnp?)")
        if n["jnp"] != 0:
            errors.append(f"{op}: {n['jnp']} dispatch(es) degraded to jnp")
    for key, why in failures.items():
        errors.append(f"probe failed for {key[0]} {key[2]}: {why[:200]}")
    return errors


def _dispatch_report():
    from beforeholiday_tpu.guard import dispatch

    counters = dispatch.dispatch_counters()
    return (dispatch_totals(counters),
            dispatch_errors(counters, dispatch.probe_failures()))


def _reset_dispatch():
    from beforeholiday_tpu.guard import dispatch

    dispatch.reset_dispatch_counters()
    dispatch.clear_probe_cache()


def build_step(cfg, reduce_grads=None):
    """The flagship step, wired as ``benchmark/families/gpt.py`` wires the cells'.
    Returns ``(step, state)``: ``step(params, opt_state, scaler_state, tokens,
    targets) -> (params, opt_state, scaler_state, loss, found_inf)``."""
    from beforeholiday_tpu import amp
    from beforeholiday_tpu.optimizers import FusedAdam
    from beforeholiday_tpu.testing import gpt

    params = gpt.init(jax.random.PRNGKey(0), cfg)
    m = amp.initialize(
        lambda p, t: gpt.forward(p, t, cfg), params,
        FusedAdam(lr=1e-4), "O5", arena_native=True,
    )

    def loss_fn(p, tok, tgt):
        return gpt.loss_fn(p, tok, tgt, cfg, forward_fn=m.apply)

    svag = amp.scaled_value_and_grad(loss_fn, m.scaler, reduce_grads=reduce_grads)

    def step(p, o, sc, tokens, targets):
        loss, g, fi, sc = svag(p, sc, tokens, targets)
        p, o = m.optimizer.step(p, g, o, found_inf=fi)
        return p, o, sc, loss, fi

    return step, (m.params, m.optimizer.init(m.params), m.scaler.init())


def _run_steps(jstep, state, tokens, targets, steps):
    """The real step loop: every call fenced, state rebound (it is donated)."""
    p, o, sc = state
    losses, walls, found_inf = [], [], None
    for _ in range(steps):
        t0 = time.perf_counter()
        p, o, sc, loss, found_inf = jstep(p, o, sc, tokens, targets)
        jax.block_until_ready((p, o, sc, loss, found_inf))
        walls.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return (p, o, sc), losses, walls, bool(found_inf)


def _phase_result(name, devices, losses, walls, found_inf, tokens_per_step):
    """Common fields + the checks every phase shares."""
    steady = statistics.median(walls[1:])
    dispatch, errors = _dispatch_report()
    if not all(np.isfinite(losses)):
        errors.append(f"non-finite loss: {losses}")
    elif not losses[-1] < losses[0]:
        errors.append(f"loss did not fall: first {losses[0]} last {losses[-1]}")
    if found_inf:
        errors.append("found_inf is true on the last step")
    return {
        "phase": name,
        "devices": len(devices),
        "steps": len(losses),
        # first call = trace + compile (or cache load) + step 0
        "compile_s": round(walls[0] - steady, 3),
        "step_s": [round(w, 4) for w in walls[1:]],
        "tokens_per_s": round(tokens_per_step / steady, 1),
        "losses": [round(x, 5) for x in losses],
        "found_inf_last": found_inf,
        "dispatch": dispatch,
        "errors": errors,
    }


def train_1chip(cfg, batch, devices, steps):
    """``steps`` fenced flagship steps on ``devices[0]``; returns the phase's
    result dict (``ok`` false when any check failed)."""
    from beforeholiday_tpu.remat import donate_step

    dev = devices[0]
    _reset_dispatch()
    with jax.default_device(dev):
        step, state = build_step(cfg)
    state = jax.device_put(state, dev)
    tokens, targets = jax.device_put(batch, dev)
    jstep = donate_step(step, donate_argnums=(0, 1, 2))
    _, losses, walls, found_inf = _run_steps(
        jstep, state, tokens, targets, steps)
    res = _phase_result(
        "train_1chip", [dev], losses, walls, found_inf, tokens.size)
    res["ok"] = not res["errors"]
    return res


def train_4chip(cfg, batch, devices, steps, expect_losses=None):
    """The same model and step, data-parallel over ``devices`` (one ``data``
    mesh axis): state replicated, each chip fed a full copy of ``batch``.
    ``expect_losses`` is the one-chip trajectory the run must reproduce."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from beforeholiday_tpu.parallel import (
        DistributedDataParallel,
        check_replicated_consistency,
    )
    from beforeholiday_tpu.remat import donate_step

    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("data",))
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    _reset_dispatch()
    with jax.default_device(devices[0]):
        step, state = build_step(cfg, reduce_grads=DistributedDataParallel().reduce)
    state = jax.device_put(state, rep)
    tokens, targets = (
        jax.device_put(jnp.tile(x, (n, 1)), split) for x in batch
    )

    def dp_step(p, o, sc, tok, tgt):
        p, o, sc, loss, fi = step(p, o, sc, tok, tgt)
        return p, o, sc, jax.lax.pmean(loss, "data"), fi

    jstep = donate_step(
        jax.shard_map(
            dp_step, mesh=mesh,
            in_specs=(P(), P(), P(), P("data"), P("data")),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1, 2),
    )
    state, losses, walls, found_inf = _run_steps(
        jstep, state, tokens, targets, steps)
    res = _phase_result(
        "train_4chip", devices, losses, walls, found_inf, tokens.size)
    errors = res["errors"]

    want = set(devices)
    for leaf in jax.tree.leaves(state):
        if leaf.sharding.device_set != want:
            errors.append(
                f"a state array lives on {len(leaf.sharding.device_set)} of "
                f"{n} devices")
            break
    # code that has never seen two chips may put everything on the first;
    # CPU devices report no memory stats (None) and are not judged
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    res["bytes_in_use"] = in_use
    if any(b is not None and b <= 0 for b in in_use):
        errors.append(f"a device holds no memory: {in_use}")
    mismatch = jax.jit(jax.shard_map(
        lambda s: check_replicated_consistency(s, "data"),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
    ))(state)
    if bool(mismatch):
        errors.append("replicas disagree after the last step "
                      "(check_replicated_consistency)")
    if expect_losses is not None:
        res["dp_vs_1chip_max_rel"] = float(np.max(
            np.abs(np.asarray(losses) - np.asarray(expect_losses))
            / np.abs(np.asarray(expect_losses))))
        res["dp_vs_1chip_rtol"] = DP4_LOSS_RTOL
        if not res["dp_vs_1chip_max_rel"] <= DP4_LOSS_RTOL:
            errors.append(
                f"DP{n} losses {losses} left the one-chip trajectory "
                f"{list(expect_losses)} (rtol {DP4_LOSS_RTOL})")
    res["ok"] = not errors
    return res


def _cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--require-chips", type=int, default=1, metavar="N",
                    help="fail unless the process sees at least N chips "
                         "(4 makes the four-chip phase mandatory)")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs the 'tpu' backend, JAX found {backend!r}; "
              "nothing was compiled", file=sys.stderr)
        return 1
    # alone in a directory (no package beside it) this import raises: exit
    # non-zero with nothing on stdout
    from beforeholiday_tpu.testing import gpt
    from beforeholiday_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"platform: {device['platform']}  device_kind: {device['kind']}  "
          f"devices: {device['count']}  config: {FLAGSHIP}  steps: {STEPS}")
    print(f"compile cache: {cache_dir}  entries before: "
          f"{_cache_entries(cache_dir)}")
    if len(devices) < args.require_chips:
        print(f"chip_smoke: --require-chips {args.require_chips} but the "
              f"process sees {len(devices)}", file=sys.stderr)
        return 1

    cfg = flagship_cfg()
    batch = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, FLAGSHIP_BATCH)
    one = train_1chip(cfg, batch, devices, STEPS)
    print(json.dumps(one), flush=True)
    if len(devices) >= 4:
        four = train_4chip(cfg, batch, devices[:4], STEPS,
                           expect_losses=one["losses"])
        print(json.dumps(four), flush=True)
        four_ok, four_brief = four["ok"], _brief(four)
    else:
        four_ok, four_brief = True, f"not run: {len(devices)} chips"
    print(f"compile cache: {cache_dir}  entries after: "
          f"{_cache_entries(cache_dir)}")

    ok = bool(one["ok"] and four_ok)
    print(json.dumps({"summary": {
        "ok": ok, "config": FLAGSHIP,
        "train_1chip": _brief(one), "train_4chip": four_brief,
        "claim": None,
    }}))
    # the verdict line: these keys and no others, last on stdout
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


def _brief(res):
    return {
        "ok": res["ok"], "compile_s": res["compile_s"],
        "step_s_median": round(statistics.median(res["step_s"]), 4),
        "tokens_per_s": res["tokens_per_s"],
        "loss_first_last": [res["losses"][0], res["losses"][-1]],
        "errors": len(res["errors"]),
    }


if __name__ == "__main__":
    sys.exit(main())
