"""Remat-policy sweep — temp-byte + step-time cost of each checkpoint policy.

Runs the SAME GPT train step (loss + grad + momentum-SGD update, state
donated) under every registered remat policy and reports, per policy, the
compiler's own activation-memory number (``memory_analysis().temp_size_in_bytes``
via the ``monitor.memory`` ledger) next to the measured step time. The
headline pair is ``save_boundaries`` vs ``none``: the boundary-tag policy
must cut temp bytes substantially while staying within a small step-time
overhead — that trade IS the activation-memory engine's value proposition.

Temp bytes come from XLA's static analysis, so they are exact and
backend-portable; the step times are CPU proxies (a TPU rematerializes
matmuls at MXU speed, the CPU at memcpy speed), useful as a regression
trend, not as TPU numbers. Run as
``python -m beforeholiday_tpu.testing.remat_bench`` with
``JAX_PLATFORMS=cpu``; prints one JSON line.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

POLICIES = ("none", "full", "dots_saveable", "save_boundaries")

# proxy shape: big enough that saved block activations dominate temp bytes
# (vocab kept small so logits don't drown the signal), small enough for a
# subprocess on CPU
VOCAB, SEQ, D_MODEL, HEADS, LAYERS, BATCH = 2048, 128, 128, 4, 6, 8
ITERS = 6
LR, MOMENTUM = 0.01, 0.9


def _make_step(cfg, gpt, donate_step):
    """Donated full train step for one policy: value_and_grad + momentum SGD.
    State (params, momentum) is donated — the sweep loop rebinds it."""

    def train_step(state, tokens, targets):
        params, mom = state
        loss, grads = jax.value_and_grad(
            lambda p: gpt.loss_fn(p, tokens, targets, cfg)
        )(params)
        mom = jax.tree.map(lambda m, g: MOMENTUM * m + g, mom, grads)
        params = jax.tree.map(lambda p, m: p - LR * m, params, mom)
        return (params, mom), loss

    train_step.__name__ = f"remat_step_{cfg.remat_policy or 'none'}"
    return donate_step(train_step, donate_argnums=(0,))


def _init_state(cfg, gpt):
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    mom = jax.tree.map(jnp.zeros_like, params)
    return params, mom


def _time_pass(step, cfg, gpt, tokens, targets):
    """Min per-iteration step time (ms) — the noise-floor estimator; state is
    rebound every iteration (donated inputs are consumed)."""
    state = _init_state(cfg, gpt)
    state, loss = step(state, tokens, targets)  # warmup / AOT compile
    jax.block_until_ready(state)
    times = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        state, loss = step(state, tokens, targets)
        jax.block_until_ready(loss)
        times.append(time.perf_counter() - t0)
    return min(times) * 1e3


def main():
    from beforeholiday_tpu.monitor import (
        memory_records,
        memory_summary,
        track_memory,
    )
    from beforeholiday_tpu.remat import donate_step
    from beforeholiday_tpu.testing import gpt

    if jax.default_backend() != "cpu":
        # a byte-count proxy; bench.py runs it as a CPU child
        raise RuntimeError(
            f"remat_bench expects the CPU backend, got {jax.default_backend()}"
        )

    base = dict(
        vocab_size=VOCAB, seq_len=SEQ, d_model=D_MODEL, n_heads=HEADS,
        n_layers=LAYERS, dtype=jnp.float32,
    )
    tokens, targets = gpt.synthetic_batch(
        jax.random.PRNGKey(1), gpt.GPTConfig(**base), BATCH
    )

    # grad-parity reference: every policy must reproduce the un-remat grads
    ref_cfg = gpt.GPTConfig(**base)
    ref_params = gpt.init(jax.random.PRNGKey(0), ref_cfg)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_fn(p, tokens, targets, ref_cfg)
    ))(ref_params)

    out = {}
    pass2 = {}
    for policy in POLICIES:
        cfg = gpt.GPTConfig(
            **base, remat_policy=None if policy == "none" else policy
        )
        step = track_memory(f"remat_step_{policy}")(
            _make_step(cfg, gpt, donate_step).jitted
        )
        out[f"remat_step_ms_{policy}"] = round(
            _time_pass(step, cfg, gpt, tokens, targets), 2
        )
        pass2[f"remat_step_ms_{policy}"] = round(
            _time_pass(step, cfg, gpt, tokens, targets), 2
        )

        if policy != "none":
            loss_p, grads_p = jax.jit(jax.value_and_grad(
                lambda p: gpt.loss_fn(p, tokens, targets, cfg)
            ))(ref_params)
            err = max(
                float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(grads_p),
                                jax.tree.leaves(ref_grads))
            )
            err = max(err, abs(float(loss_p) - float(ref_loss)))
            out[f"remat_grad_err_{policy}"] = err

    records = memory_records()
    for policy in POLICIES:
        sigs = [s for s in records[f"remat_step_{policy}"]["signatures"] if s]
        out[f"peak_temp_bytes_{policy}"] = max(
            (s["temp_bytes"] for s in sigs), default=0
        )

    none_t, sb_t = out["peak_temp_bytes_none"], out["peak_temp_bytes_save_boundaries"]
    if none_t:
        out["remat_temp_reduction_save_boundaries"] = round(1.0 - sb_t / none_t, 4)
    out["remat_step_overhead_save_boundaries"] = round(
        out["remat_step_ms_save_boundaries"] / out["remat_step_ms_none"], 3
    )
    pass2["remat_step_overhead_save_boundaries"] = round(
        pass2["remat_step_ms_save_boundaries"] / pass2["remat_step_ms_none"], 3
    )

    out["memory_summary"] = memory_summary()
    out["pass2"] = pass2
    out["config"] = (
        f"V={VOCAB} S={SEQ} D={D_MODEL} H={HEADS} L={LAYERS} B={BATCH} "
        f"iters={ITERS} fp32"
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
