"""Audit of the training steps the benchmark times, at test size.

`device_idle_pct` reads 0.011 % in the GPT cells (`PERF.md` §5) because the
step, once compiled, is one device program fed from device buffers. These
tests pin the three properties that rests on, for every step shape the ledger
times and for the O6 tier beside them:

* **No per-step host transfer** — after warm-up, steps run to completion under
  ``jax.transfer_guard("disallow")`` with device-committed inputs. Any hidden
  ``.item()`` / implicit readback in the amp / optimizer / scaler path would
  raise here (the runtime counterpart of the AST scan in test_no_host_sync).
* **No undonated-arena warning** — the state carries a ``PackedParams`` arena;
  riding ``remat.donate_step``'s donated slot must NOT trip the
  undonated-arena sentinel (and passing it undonated MUST — the sentinel works).
* **No compile after warm-up** — over three more steps the step keeps ONE
  abstract signature (``monitor.track_compiles``) and its ``jax.jit`` holds one
  executable: what the harness reports as ``window.compilations`` 0. A state
  that came back with another dtype, shape or weak type than it went in with
  would compile again on the second step.

Shapes: ``O5`` / ``O6`` — the one-chip GPT step (``amp.initialize``
arena-native + ``scaled_value_and_grad`` + ``FusedAdam``), as
``gpt2-medium.train`` runs it at O5; ``dp`` — the same step inside
``shard_map`` with ``DistributedDataParallel().reduce`` and ``pmean`` over four
CPU devices, and ``qwen3_next`` — the Qwen3-Next step with its counters in the
state: both built by ``benchmark.run.Cell`` from the harness's own fixture
cells, so they are the programs ``gpt2-medium.train-dp4`` and
``qwen3-next-80b-a3b.train-s8k`` run, made small.

One step is built and compiled ONCE per shape (module cache): the audits are
properties of the traced program, so every test reads the same compile.
"""

import functools
import os
import sys
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from beforeholiday_tpu import amp, monitor, remat  # noqa: E402
from beforeholiday_tpu.guard import dispatch as gd  # noqa: E402
from beforeholiday_tpu.optimizers import FusedAdam  # noqa: E402
from beforeholiday_tpu.testing import gpt  # noqa: E402
from beforeholiday_tpu.utils import logging as bh_logging  # noqa: E402

_DONATION_PREFIX = "remat.donation"
_CELLS = {"dp": "tiny-gpt.train-dp4", "qwen3_next": "tiny-qwen3-next.train"}
SHAPES = ("O5", "O6", "dp", "qwen3_next")


class _Step(NamedTuple):
    step: Callable          # the donated step: step(state, *batch) -> out
    fresh_state: Callable   # () -> a state whose buffers nothing else holds
    batch: tuple            # device-committed
    state_of: Callable      # out -> the state to feed back
    quantized_counts: Any   # dispatch counts of the first trace (GPT shapes)


def _gpt_step(opt_level: str) -> _Step:
    cfg = gpt.GPTConfig(
        vocab_size=128, seq_len=16, d_model=32, n_heads=2, n_layers=1,
        dtype=jnp.bfloat16,
    )
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    batch = jax.device_put(gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, 2))
    m = amp.initialize(
        lambda p, t: gpt.forward(p, t, cfg), params,
        FusedAdam(lr=1e-4), opt_level, arena_native=True,
    )

    def loss_fn(p, tok, tgt):
        return gpt.loss_fn(p, tok, tgt, cfg, forward_fn=m.apply)

    svag = amp.scaled_value_and_grad(loss_fn, m.scaler)

    def step(s, tokens, targets):
        p, o, sc = s
        loss, g, fi, sc = svag(p, sc, tokens, targets)
        p, o = m.optimizer.step(p, g, o, found_inf=fi)
        return (p, o, sc)

    def fresh_state():
        return jax.tree_util.tree_map(
            jnp.array, (m.params, m.optimizer.init(m.params), m.scaler.init())
        )

    gd.reset_dispatch_counters()
    dstep = remat.donate_step(step, donate_argnums=(0,))
    jax.block_until_ready(dstep(fresh_state(), *batch))  # warm-up
    q_counts = {"pallas": 0, "jnp": 0}
    for key, c in gd.dispatch_counters().items():
        if key[0] == "quantized_matmul":
            q_counts["pallas"] += c["pallas"]
            q_counts["jnp"] += c["jnp"]
    return _Step(dstep, fresh_state, batch, lambda out: out, q_counts)


def _cell_step(name: str) -> _Step:
    """The step of a harness fixture cell, exactly as ``benchmark/run.py``
    builds and calls it: ``step(state, batch) -> (state, loss, found_inf)``."""
    from benchmark import run

    cell = run.load("workloads", name)
    if len(jax.devices()) < cell["chips"]:
        pytest.skip(f"needs {cell['chips']} virtual devices")
    c = run.Cell(cell, run.load("configs", cell["config"]),
                 jax.devices()[:cell["chips"]])
    c.start(5)

    def fresh_state():
        c.build()
        return c.state

    step = c.program.step
    jax.block_until_ready(step(fresh_state(), c.pool[0]))  # warm-up
    return _Step(step, fresh_state, (c.pool[0],), lambda out: out[0], None)


@functools.lru_cache(maxsize=None)
def _built(shape: str) -> _Step:
    return _cell_step(_CELLS[shape]) if shape in _CELLS else _gpt_step(shape)


def _donation_warn_keys():
    with bh_logging._WARNED_LOCK:
        return [
            k for k in bh_logging._WARNED
            if isinstance(k, tuple) and k and k[0] == _DONATION_PREFIX
        ]


@pytest.mark.parametrize("shape", SHAPES)
class TestStepAudit:
    def test_steps_run_under_transfer_guard(self, shape):
        b = _built(shape)
        state = b.fresh_state()
        with jax.transfer_guard("disallow"):
            for _ in range(3):
                out = b.step(state, *b.batch)
                state = b.state_of(out)
        # readback AFTER the guard: the step itself must be transfer-free
        assert jax.block_until_ready(out) is out

    def test_donated_arena_state_warns_nothing(self, shape):
        b = _built(shape)
        before = set(_donation_warn_keys())
        state = b.state_of(b.step(b.fresh_state(), *b.batch))
        # rebind each step — the donation contract
        state = b.state_of(b.step(state, *b.batch))
        jax.block_until_ready(state)
        new = set(_donation_warn_keys()) - before
        assert not new, f"undonated-arena warnings on {shape}: {new}"

    def test_step_compiles_nothing_after_warmup(self, shape):
        b = _built(shape)           # warmed up: one executable
        entry = f"step_audit.{shape}"
        monitor.reset_compile_counts(entry)
        tracked = monitor.track_compiles(entry)(b.step)
        state = b.fresh_state()
        for _ in range(3):
            state = b.state_of(tracked(state, *b.batch))
        jax.block_until_ready(state)
        assert monitor.compile_counts()[entry] == {"signatures": 1, "calls": 3}
        assert b.step.jitted._cache_size() == 1


def test_sentinel_catches_undonated_arena():
    """Control: the donation audit is only meaningful if the sentinel fires
    when an arena really does ride an undonated slot. The sentinel is a
    host-side arg walk, so a trivial jitted body suffices."""
    before = set(_donation_warn_keys())
    dstep = remat.donate_step(lambda n, s: n, donate_argnums=(0,))
    try:
        jax.block_until_ready(dstep(jnp.int32(0), _built("O5").fresh_state()))
        new = set(_donation_warn_keys()) - before
        assert new, "undonated PackedParams arena went unflagged"
    finally:
        for k in set(_donation_warn_keys()) - before:
            bh_logging.reset_warn_once(k)


@pytest.mark.quantized
def test_traced_o6_step_books_only_fp8():
    """Dispatch honesty on O6: tracing the step books every
    ``quantized_matmul`` on the fp8 fast path, zero jnp-oracle downgrades."""
    counts = _built("O6").quantized_counts
    assert counts["pallas"] > 0, "O6 step traced no quantized_matmul"
    assert counts["jnp"] == 0, (
        f"{counts['jnp']} quantized_matmul dispatches degraded to the "
        "jnp oracle inside the step"
    )
