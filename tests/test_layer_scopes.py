"""Layer scopes inside the training step (PR 24).

The device trace is read by the ``monitor.spans`` scope names: the benchmark's
per-layer metrics (``benchmark/layer_metrics/*.json``) match them in the
framework name (``tf_op``) of every device op. These tests pin the names on
the CPU, from the step wired exactly as ``benchmark/families/gpt.py`` wires it
(one chip, and data parallel over the CPU mesh): every scope is there, forward
and backward never share a name, every matmul and kernel lies under one of the
two, ``flash_attention`` stays the innermost scope of the attention calls —
and the split of ``jax.grad`` that made room for the scopes is the same
program (bitwise against the ``jax.grad`` oracle).

The names are read from the *compiled* module's ``op_name`` metadata: XLA
composes the call-site's name with the names inside a called function (the
scanned block is a ``closed_call``), which is what the profiler shows.
"""

import contextlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from beforeholiday_tpu import amp, monitor  # noqa: E402
from beforeholiday_tpu.amp.scaler import LossScaler  # noqa: E402
from beforeholiday_tpu.optimizers import FusedAdam  # noqa: E402
from beforeholiday_tpu.remat import donate_step  # noqa: E402
from beforeholiday_tpu.testing import gpt  # noqa: E402

_LAYOUTS = {"single": "tiny-gpt.train", "dp": "tiny-gpt.train-dp4"}
# scope -> the layouts whose step must carry it
_SCOPES = {
    "amp_forward": ("single", "dp"), "amp_backward": ("single", "dp"),
    "amp_unscale": ("single", "dp"),
    "gpt_embed": ("single", "dp"), "gpt_blocks": ("single", "dp"),
    "gpt_head": ("single", "dp"), "gpt_loss": ("single", "dp"),
    "layer_norm": ("single", "dp"), "flash_attention": ("single", "dp"),
    "fused_adam_step_flat": ("single", "dp"),
    "ddp_reduce_gradients": ("dp",),
}


def _scopes_of(name):
    return name.split("/")


@pytest.fixture(scope="module")
def op_names():
    """``layout -> the distinct op_name of every op of the compiled step``."""
    from benchmark import run as bench_run

    cache = {}

    def get(layout):
        if layout not in cache:
            cell = bench_run.load("workloads", _LAYOUTS[layout])
            cfg = bench_run.load("configs", cell["config"])
            if len(jax.devices()) < cell["chips"]:
                pytest.skip(f"needs {cell['chips']} virtual devices")
            run = bench_run.Cell(cell, cfg, jax.devices()[:cell["chips"]])
            run.start(7)
            run.build()
            compiled = run.program.step.jitted.lower(run.state, run.pool[0]).compile()
            # (names outside ``jit(..)`` belong to reducers' sub-computations)
            cache[layout] = sorted(set(re.findall(r'op_name="(jit\([^"]+)"', compiled.as_text())))
        return cache[layout]

    return get


@pytest.mark.parametrize("layout,scope", [
    (layout, scope) for scope, layouts in _SCOPES.items() for layout in layouts])
def test_scope_is_in_the_compiled_step(op_names, layout, scope):
    assert any(scope in _scopes_of(n) or f"jvp({scope})" in n for n in op_names(layout)), scope


def _pass_of(name):
    """The pass an op runs in, as the metrics decide it: ``amp_backward`` is
    the ambient scope of the whole pull-back, so it wins. (The ``custom_vjp``
    backward rule of the un-scanned final LayerNorm is named
    ``amp_backward/transpose(amp_forward)/jvp(gpt_head)/layer_norm/...``: the
    forward scope survives, wrapped, in a backward op's name.)"""
    for scope in ("amp_backward", "amp_forward"):
        if scope in name:
            return scope
    return None


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_forward_and_backward_never_share_a_name(op_names, layout):
    both = [n for n in op_names(layout) if "amp_forward" in n and "amp_backward" in n]
    # only as the wrapped form, and never the other way round
    assert all("amp_backward/transpose(amp_forward)" in n for n in both), both
    # the model scopes survive inside both passes
    for scope in ("gpt_embed", "gpt_blocks", "gpt_head", "gpt_loss"):
        assert any(f"amp_forward/jvp({scope})" in n for n in op_names(layout)), scope
        assert any(_pass_of(n) == "amp_backward" and f"jvp({scope})" in n
                   for n in op_names(layout)), scope


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_matmuls_and_kernels_lie_under_forward_or_backward(op_names, layout):
    heavy = [n for n in op_names(layout)
             if {"dot_general", "pallas_call", "layer_norm"} & set(_scopes_of(n))]
    assert len(heavy) > 10
    assert not [n for n in heavy if _pass_of(n) is None]
    # ... also inside the scanned block, a closed_call under the while body
    assert any("amp_forward/jvp(gpt_blocks)/while/body/closed_call" in n
               and n.endswith("dot_general") for n in heavy)
    assert any("amp_backward/transpose(jvp(gpt_blocks))/while/body/closed_call" in n
               and n.endswith("dot_general") for n in heavy)


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_first_level_scopes_partition_the_step(op_names, layout):
    """No op carries two of the first-level scopes the benchmark's partition
    (forward / backward / unscale / reduce / optimizer) is read by, but for
    the wrapped forward scope inside a backward name."""
    first = ("amp_backward", "amp_unscale", "ddp_reduce_gradients",
             "ddp_overlap_hook", "fused_adam_step_flat")
    twice = [n for n in op_names(layout)
             if sum(s in n for s in first) + (_pass_of(n) == "amp_forward") > 1]
    assert not twice


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_flash_attention_stays_the_innermost_scope(impl):
    """The chip's compiler names the flash custom calls after the innermost
    scope (``%flash_attention.N``, which ``flash_attn_ms`` matches): no new
    scope may open inside it, in the forward or in the backward pass."""
    cfg = gpt.GPTConfig(vocab_size=64, seq_len=128, d_model=32, n_heads=2, n_layers=2,
                        dtype=jnp.bfloat16, attention_impl=impl)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    batch = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
    svag = amp.scaled_value_and_grad(
        lambda p, tok, tgt: gpt.loss_fn(p, tok, tgt, cfg), LossScaler(loss_scale=1.0))
    text = jax.jit(svag).lower(params, LossScaler(loss_scale=1.0).init(), *batch).as_text(
        debug_info=True)
    inside = [n for n in set(re.findall(r'loc\("([^"]+)"', text)) if "flash_attention/" in n]
    assert inside
    ours = set(_SCOPES) - {"flash_attention"}
    for n in inside:
        tail = n.split("flash_attention/", 1)[1]
        assert not ours & set(_scopes_of(tail)), n
    if impl == "pallas":
        kernels = [n for n in inside if "pallas_call" in _scopes_of(n)]
        assert kernels
        assert all("flash_attention/pallas_call" in n for n in kernels)


# ---------------------------------------------------------------------------
# the split of jax.grad is the same program
# ---------------------------------------------------------------------------

def _oracle(loss_fn, scaler, *, has_aux, reduce_grads):
    """``scaled_value_and_grad`` as the parent commit wrote it: one ``jax.grad``."""
    def wrapped(params, scaler_state, *args):
        def scaled_loss_fn(p):
            res = loss_fn(p, *args)
            loss, aux = res if has_aux else (res, None)
            return scaler.scale_loss(loss, scaler_state), (loss, aux)

        scale_w, scale_g = scaler.quantized_scales(scaler_state)
        q_scope, amax = contextlib.nullcontext(), None
        if scale_w is not None:
            from beforeholiday_tpu.ops.quantized import quantized_scope

            q_scope = quantized_scope(scale_w, scale_g)
        with q_scope:
            grads, (loss, aux) = jax.grad(scaled_loss_fn, has_aux=True)(params)
        if reduce_grads is not None:
            grads = reduce_grads(grads)
        if scale_w is not None:
            from beforeholiday_tpu.ops.quantized import amax_of_tree

            amax = (amax_of_tree(params), amax_of_tree(grads))
        grads, found_inf = scaler.unscale(grads, scaler_state)
        new_state = scaler.update(scaler_state, found_inf, amax=amax)
        if has_aux:
            return loss, aux, grads, found_inf, new_state
        return loss, grads, found_inf, new_state

    return wrapped


@pytest.mark.parametrize("level", ["O5", "O6"])
@pytest.mark.parametrize("reduce", [False, True])
@pytest.mark.parametrize("has_aux", [False, True])
def test_scaled_value_and_grad_is_bitwise_the_grad_oracle(level, reduce, has_aux):
    from beforeholiday_tpu.ops import dense

    rng = np.random.default_rng(3)
    params = {"w1": jnp.asarray(rng.normal(size=(16, 32)) * 0.3, jnp.float32),
              "w2": jnp.asarray(rng.normal(size=(32, 8)) * 0.3, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(12, 16)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(12, 8)), jnp.float32)
    m = amp.initialize(
        lambda p, a: dense.fused_dense(jnp.tanh(dense.fused_dense(a, p["w1"])), p["w2"]),
        params, FusedAdam(lr=1e-3), level)

    def loss_fn(p, a, t):
        out = m.apply(p, a)
        loss = jnp.mean(jnp.square(out - t))
        return (loss, {"out_norm": jnp.linalg.norm(out)}) if has_aux else loss

    # a reducer that is not the identity, so that its place in the order shows
    reducer = (lambda g: jax.tree.map(lambda a: a * 0.5, g)) if reduce else None
    state = m.scaler.init()
    got = jax.jit(amp.scaled_value_and_grad(
        loss_fn, m.scaler, has_aux=has_aux, reduce_grads=reducer))(m.params, state, x, y)
    want = jax.jit(_oracle(
        loss_fn, m.scaler, has_aux=has_aux, reduce_grads=reducer))(m.params, state, x, y)
    got_leaves, got_tree = jax.tree.flatten(got)
    want_leaves, want_tree = jax.tree.flatten(want)
    assert got_tree == want_tree
    for a, b in zip(got_leaves, want_leaves):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(np.asarray(got[0]))


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def test_donate_step_records_nested_prepare_and_call_spans():
    step = donate_step(lambda s, x: (s + x, jnp.sum(x)), donate_argnums=(0,))
    state = jnp.zeros((4,))
    with monitor.timeline() as rec:
        with monitor.span("dispatch"):
            state, _ = step(state, jnp.ones((4,)))
            state, _ = step(state, jnp.ones((4,)))
    stack, closed = [], []
    for ev in rec.events():
        if ev["ph"] == "B":
            stack.append(ev["name"])
        elif ev["ph"] == "E":
            closed.append((stack.pop(), tuple(stack)))
    assert not stack                        # every B has its E
    ours = [c for c in closed if c[0].startswith("donate_step.")]
    # per call: prepare, then call, both directly inside the caller's span
    assert ours == [("donate_step.prepare", ("dispatch",)),
                    ("donate_step.call", ("dispatch",))] * 2
    np.testing.assert_array_equal(np.asarray(state), 2.0)


def test_host_span_does_not_leak_into_the_jitted_step_names():
    step = donate_step(lambda s, x: (s * 2.0 + x,), donate_argnums=(0,))
    with monitor.span("donate_step.call"):
        text = step.jitted.lower(jnp.zeros((4,)), jnp.ones((4,))).as_text(debug_info=True)
    assert "donate_step" not in text


def test_compile_cache_key_covers_the_scope_names():
    """A cached executable carries the scope names it was compiled with; with
    the names left out of the key (JAX's default) a re-scoped program would be
    served the old names, and the per-layer metrics would read those."""
    from beforeholiday_tpu.utils import compile_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
        compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key is True
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_compilation_cache_include_metadata_in_key", prev)


# ---------------------------------------------------------------------------
# family mellum (PR 31): window_mixer / full_mixer and the model's four scopes
# ---------------------------------------------------------------------------

_MELLUM_MODEL = ("mellum_embed", "mellum_layers", "mellum_head", "mellum_loss")
_MELLUM_SCOPES = _MELLUM_MODEL + (
    "window_mixer", "full_mixer", "amp_forward", "amp_backward", "amp_unscale",
    "fused_adam_step_flat", "layer_norm", "flash_attention", "moe_route", "moe_dispatch",
    "moe_experts", "moe_combine")


@pytest.fixture(scope="module")
def mellum_names():
    """The distinct ``op_name`` of every op of the compiled tiny-mellum step."""
    from benchmark import run as bench_run

    cell = bench_run.load("workloads", "tiny-mellum.train")
    run = bench_run.Cell(cell, bench_run.load("configs", cell["config"]), jax.devices()[:1])
    run.start(7)
    run.build()
    compiled = run.program.step.jitted.lower(run.state, run.pool[0]).compile()
    return sorted(set(re.findall(r'op_name="(jit\([^"]+)"', compiled.as_text())))


@pytest.mark.parametrize("scope", _MELLUM_SCOPES)
def test_mellum_scope_is_in_the_compiled_step(mellum_names, scope):
    assert any(scope in _scopes_of(n) or f"jvp({scope})" in n for n in mellum_names), scope


def test_mellum_first_level_scopes_partition_the_step(mellum_names):
    first = ("amp_backward", "amp_unscale", "ddp_reduce_gradients",
             "ddp_overlap_hook", "fused_adam_step_flat")
    twice = [n for n in mellum_names
             if sum(s in n for s in first) + (_pass_of(n) == "amp_forward") > 1]
    assert not twice
    both = [n for n in mellum_names if "amp_forward" in n and "amp_backward" in n]
    assert all("amp_backward/transpose(amp_forward)" in n for n in both), both
    for scope in _MELLUM_MODEL:      # the model's scopes survive inside both passes
        assert any(f"amp_forward/jvp({scope})" in n for n in mellum_names), scope
        assert any(_pass_of(n) == "amp_backward" and f"jvp({scope})" in n
                   for n in mellum_names), scope


def test_mellum_second_level_scopes_do_not_overlap(mellum_names):
    """An op is under one model scope at most, and under one of the two mixers
    or the MoE at most; the mixers and the MoE lie inside ``mellum_layers``."""
    for n in mellum_names:
        assert sum(f"({s})" in n or s in _scopes_of(n) for s in _MELLUM_MODEL) <= 1, n
        parts = [s for s in ("window_mixer", "full_mixer", "moe") if s in _scopes_of(n)]
        assert len(parts) <= 1, n
        if parts and _pass_of(n):
            assert "mellum_layers" in n, n
    heavy = [n for n in mellum_names if n.endswith("dot_general")]
    assert heavy and not [n for n in heavy if _pass_of(n) is None]


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_flash_attention_stays_innermost_in_both_mellum_mixers(kind):
    from beforeholiday_tpu.models import mellum

    cfg = mellum.MellumConfig(attention_impl="pallas", sliding_window=100, dtype=jnp.bfloat16)
    params = mellum.init(jax.random.PRNGKey(0), cfg)
    lp = {k: v[0] for k, v in params["layers"].items()}
    x = jnp.zeros((1, 256, cfg.hidden_size), jnp.bfloat16)
    table = mellum.rotary_tables(cfg, 256)[kind]
    f = lambda x, lp: jnp.sum(mellum.attention(cfg, x, lp, kind, table).astype(jnp.float32))
    text = jax.jit(jax.grad(f)).lower(x, lp).as_text(debug_info=True)
    mixer = "window_mixer" if kind == "sliding_attention" else "full_mixer"
    inside = [n for n in set(re.findall(r'loc\("([^"]+)"', text)) if "flash_attention/" in n]
    assert inside and all(mixer in n for n in inside)
    kernels = [n for n in inside if "pallas_call" in _scopes_of(n)]
    # the windowed kernels alone carry their own names (a ``pallas_call``'s name
    # is one more scope around it), under the op's scope and prefix
    named = r"flash_attention/flash_attention_window_(fwd|dqkv_blocks)/pallas_call$"
    if kind == "sliding_attention":     # the band's backward is one call since PR 48
        assert len(kernels) == 2 and all(re.search(named, n) for n in kernels), kernels
    else:
        assert kernels and all(n.endswith("flash_attention/pallas_call") for n in kernels)
        assert "flash_attention_window" not in text


# ---------------------------------------------------------------------------
# the grouped-matmul kernels of the dropless experts (PR 32)
# ---------------------------------------------------------------------------

def test_the_grouped_matmul_kernels_lie_under_the_experts_scope_in_both_passes():
    """``moe_ms[.mellum]`` reads ``/moe/``, ``grouped_matmul_ms[.mellum]`` the
    kernels' own ``name=`` (the chip prints ``%grouped_matmul_fwd.N``); XLA's
    ``ragged_dot`` carried no scope and read under ``unattributed_ms``. Now each
    kernel lies under ``moe/moe_experts`` and under exactly one pass, so the
    first-level partition holds once they leave ``unattributed_ms``."""
    from beforeholiday_tpu.moe import dropless

    T, D, E, F = 64, 128, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    n = lambda k, *shape: (jax.random.normal(k, shape) * 0.2).astype(jnp.bfloat16)
    p = {"router": n(ks[0], D, E), "w_gate": n(ks[1], E, D, F), "w_up": n(ks[2], E, D, F),
         "w_down": n(ks[3], E, F, D)}
    x = n(ks[4], T, D)
    svag = amp.scaled_value_and_grad(
        lambda p, x: jnp.sum(dropless.dropless_moe(x, p, top_k=2, impl="pallas")[0]
                             .astype(jnp.float32)),
        LossScaler(loss_scale=1.0))
    # the compiled program's names: a kernel call is a ``jax.jit`` function of its
    # own (lowered once a shape), inlined under the scopes of each call site
    text = jax.jit(svag).lower(p, LossScaler(loss_scale=1.0).init(), x).compile().as_text()
    kernels = [n for n in set(re.findall(r'op_name="(jit\([^"]+)"', text)) if "grouped_matmul_" in n]
    by_name = {k: [n for n in kernels if f"/grouped_matmul_{k}" in n]
               for k in ("fwd", "dlhs", "drhs")}
    assert all(by_name.values()), by_name
    for n in kernels:
        assert re.search(r"moe\)*/moe_experts\)*/jit\(_t?gmm\)/grouped_matmul_", n), n
    first = ("amp_backward", "amp_unscale", "ddp_reduce_gradients", "fused_adam_step_flat")
    for k, names in by_name.items():
        want = "amp_forward" if k == "fwd" else "amp_backward"
        assert all(_pass_of(n) == want for n in names), (k, names)
        assert not [n for n in names
                    if sum(s in n for s in first) + (_pass_of(n) == "amp_forward") > 1]


# ---------------------------------------------------------------------------
# family nemotron_h (PR 33): ssm_mixer / attn_mixer, ssd, moe_latent and the
# model's four scopes
# ---------------------------------------------------------------------------

_NEMOTRON_MODEL = ("nemotron_h_embed", "nemotron_h_layers", "nemotron_h_head", "nemotron_h_loss")
_NEMOTRON_SCOPES = _NEMOTRON_MODEL + (
    "ssm_mixer", "attn_mixer", "ssd", "amp_forward", "amp_backward", "amp_unscale",
    "fused_adam_step_flat", "layer_norm", "flash_attention", "moe_route", "moe_latent",
    "moe_dispatch", "moe_experts", "moe_shared", "moe_combine")


@pytest.fixture(scope="module")
def nemotron_run():
    from benchmark import run as bench_run

    cell = bench_run.load("workloads", "tiny-nemotron-h.train")
    run = bench_run.Cell(cell, bench_run.load("configs", cell["config"]), jax.devices()[:1])
    run.start(7)
    run.build()
    return run


@pytest.fixture(scope="module")
def nemotron_names(nemotron_run):
    """The distinct ``op_name`` of every op of the compiled tiny-nemotron-h step."""
    run = nemotron_run
    compiled = run.program.step.jitted.lower(run.state, run.pool[0]).compile()
    return sorted(set(re.findall(r'op_name="(jit\([^"]+)"', compiled.as_text())))


@pytest.mark.parametrize("scope", _NEMOTRON_SCOPES)
def test_nemotron_scope_is_in_the_compiled_step(nemotron_names, scope):
    assert any(scope in _scopes_of(n) or f"jvp({scope})" in n for n in nemotron_names), scope


def test_nemotron_first_level_scopes_partition_the_step(nemotron_names):
    first = ("amp_backward", "amp_unscale", "ddp_reduce_gradients",
             "ddp_overlap_hook", "fused_adam_step_flat")
    twice = [n for n in nemotron_names
             if sum(s in n for s in first) + (_pass_of(n) == "amp_forward") > 1]
    assert not twice
    # (off the TPU ``ssd`` is a checkpointed chunk scan: with the one-period
    # stack no longer a loop, PR 34, what the backward pass recomputes or transposes of it
    # carries its forward names behind the backward's)
    both = [n for n in nemotron_names if "amp_forward" in n and "amp_backward" in n
            and "/ssd/checkpoint/" not in n]
    assert all("amp_backward/transpose(amp_forward)" in n for n in both), both
    for scope in _NEMOTRON_MODEL:    # the model's scopes survive inside both passes
        assert any(f"amp_forward/jvp({scope})" in n for n in nemotron_names), scope
        assert any(_pass_of(n) == "amp_backward" and f"jvp({scope})" in n
                   for n in nemotron_names), scope


def test_nemotron_second_level_scopes_do_not_overlap(nemotron_names):
    """An op is under one model scope at most, and under one of the two mixers
    or the MoE at most (a block is one of the three); ``ssd`` lies inside
    ``ssm_mixer``, ``moe_latent`` inside ``moe``, all inside ``nemotron_h_layers``."""
    for n in nemotron_names:
        assert sum(f"({s})" in n or s in _scopes_of(n) for s in _NEMOTRON_MODEL) <= 1, n
        parts = [s for s in ("ssm_mixer", "attn_mixer", "moe") if s in _scopes_of(n)]
        assert len(parts) <= 1, n
        if parts and _pass_of(n):
            assert "nemotron_h_layers" in n, n
        if "ssd" in _scopes_of(n):
            assert "ssm_mixer" in _scopes_of(n), n
        if "moe_latent" in _scopes_of(n):
            assert "moe" in _scopes_of(n), n
        if "flash_attention" in _scopes_of(n):
            assert "attn_mixer" in _scopes_of(n), n
    heavy = [n for n in nemotron_names if n.endswith("dot_general")]
    assert heavy and not [n for n in heavy if _pass_of(n) is None]


def test_nemotron_step_counts_its_routed_rows(nemotron_run):
    """``expert_rows_per_step.nemotron_h`` and ``expert_load_max_over_mean.nemotron_h``
    read the family's counters; a step of the fixture routes 2 MoE blocks x 96
    tokens x 4 choices, a quarter of the experts held (nothing dropped: the
    bound is the worst case)."""
    from benchmark.families import nemotron_h as family

    run = nemotron_run
    before = family.counters()
    run.run_step(0)
    run.run_step(1)
    seen = family.counters()
    assert set(seen) == {"expert_rows", "expert_load_max_over_mean", "dropped_rows", "steps"}
    assert seen["steps"] - before.get("steps", 0.0) == 2.0 and seen["dropped_rows"] == 0.0
    assert 0 < seen["expert_rows"] / seen["steps"] <= 2 * 96 * 4
    assert 1.0 <= seen["expert_load_max_over_mean"] <= 4.0


def test_the_ssd_kernels_carry_their_names_under_the_mixer_in_both_passes():
    """``ssd_ms`` reads the ``/ssd/`` scope, ``ssd_roofline`` the kernels' own
    ``name=`` (the chip prints ``%ssd_fwd.N``, ``%ssd_bwd_states.N``, ``%ssd_bwd.N``):
    the forward kernel lies under ``amp_forward``, the sweep for the chunk-start
    states and the reverse walk under ``amp_backward``."""
    from beforeholiday_tpu.models import nemotron_h

    cfg = nemotron_h.NemotronHConfig(
        mamba_num_heads=2, mamba_head_dim=64, n_groups=1, ssm_state_size=128, ssd_impl="pallas",
        dtype=jnp.bfloat16)
    params = nemotron_h.init(jax.random.PRNGKey(0), cfg)
    p = {k: v[0].astype(jnp.bfloat16) for k, v in params["mamba"].items()}
    x = jnp.zeros((1, 256, cfg.hidden_size), jnp.bfloat16)
    svag = amp.scaled_value_and_grad(
        lambda p, x: jnp.sum(nemotron_h.mamba2_mixer(cfg, x, p).astype(jnp.float32)),
        LossScaler(loss_scale=1.0))
    # the compiled program's names: a kernel call is a ``jax.jit`` function of its own
    # (lowered once a shape), inlined under the scopes of each call site; the
    # ``pallas_call``'s ``name=`` is one more scope around what the interpreter makes of it
    text = jax.jit(svag).lower(p, LossScaler(loss_scale=1.0).init(), x).compile().as_text()
    names = set(re.findall(r'op_name="(jit\([^"]+)"', text))
    kernels = {k: [n for n in names if f"/{k}/" in n] for k in ("ssd_fwd", "ssd_bwd_states", "ssd_bwd")}
    assert all(kernels.values()), {k: len(v) for k, v in kernels.items()}
    for k, found in kernels.items():
        want = "amp_forward" if k == "ssd_fwd" else "amp_backward"
        for n in found:
            assert _pass_of(n) == want, (k, n)
            assert re.search(rf"ssm_mixer\)*/ssd\)*/jit\(_(?:fwd|bwd)_pallas\)/{k}/", n), n


# ---------------------------------------------------------------------------
# the DeltaNet layer's two fused passes (PR 37): under the mixer, outside the
# delta rule's scope, and named after neither it nor the norm
# ---------------------------------------------------------------------------

def test_the_deltanet_kernels_lie_under_the_mixer_and_outside_the_delta_rule(monkeypatch):
    """``linear_mixer_ms`` reads ``linear_mixer``; ``gated_delta_ms`` every op whose
    path holds ``gated_delta`` and ``gated_delta_roofline`` the kernels named
    ``%gated_delta*`` against the recurrence's products alone; ``layer_norm_ms``
    reads ``layer_norm``. The four new kernels lie under the first and under none
    of the others, the forward ones under ``amp_forward`` and the backward ones
    under ``amp_backward``; the delta rule's own four stay where they were."""
    from beforeholiday_tpu.models import qwen3_next
    from beforeholiday_tpu.ops import _pallas_util

    monkeypatch.setattr(_pallas_util, "resolve_impl", lambda impl: "pallas")
    cfg = qwen3_next.Qwen3NextConfig(
        hidden_size=64, linear_num_key_heads=1, linear_num_value_heads=2,
        linear_key_head_dim=128, linear_value_head_dim=128, gated_delta_chunk=64,
        dtype=jnp.bfloat16)
    params = qwen3_next.init(jax.random.PRNGKey(0), cfg)
    p = {k: v[0].astype(jnp.float32 if "norm" in k or k in ("a_log", "dt_bias")
                        else jnp.bfloat16) for k, v in params["linear"].items()}
    x = jnp.zeros((1, 128, cfg.hidden_size), jnp.bfloat16)
    svag = amp.scaled_value_and_grad(
        lambda p, x: jnp.sum(qwen3_next.gated_delta_net(cfg, x, p).astype(jnp.float32)),
        LossScaler(loss_scale=1.0))
    text = jax.jit(svag).lower(p, LossScaler(loss_scale=1.0).init(), x).compile().as_text()
    names = set(re.findall(r'op_name="(jit\([^"]+)"', text))
    new = {k: [n for n in names if f"/{k}/" in n or n.endswith(f"/{k}")]
           for k in ("deltanet_qkv_fwd", "deltanet_qkv_bwd", "deltanet_gate_fwd",
                     "deltanet_gate_bwd")}
    assert all(new.values()), {k: len(v) for k, v in new.items()}
    for k, found in new.items():
        span = k.rsplit("_", 1)[0]
        for n in found:
            assert _pass_of(n) == ("amp_forward" if k.endswith("fwd") else "amp_backward"), (k, n)
            assert re.search(rf"linear_mixer\)*/{span}\)*/jit\(_(?:qkv|gate)_(?:fwd|bwd)\)/{k}", n), n
            assert "gated_delta" not in n and "layer_norm" not in n, n
    under_mixer = [n for n in names if "linear_mixer" in n]
    assert not [n for n in under_mixer if "layer_norm" in n]    # the norm is in the epilogue
    for k in ("wy_prepare_fwd", "wy_prepare_bwd", "gated_delta_fwd", "gated_delta_bwd"):
        found = [n for n in under_mixer if f"/{k}" in n]
        assert found and all(re.search(r"linear_mixer\)*/gated_delta", n) for n in found), k
    assert not [n for n in names if "deltanet" in n and "gated_delta" in n]
    _, pats = _linear_split()       # and the ledger's split reads each op under the mixer once
    for n in under_mixer:
        assert sum(bool(pats[k].search(n)) for k in (
            "gated_delta_ms", "linear_proj_ms", "linear_elementwise_ms")) == 1, n
    for k in new:
        assert all(pats["linear_elementwise_ms"].search(n) for n in new[k]), k


def _linear_split():
    """The patterns of ``gated_delta_ms``, ``linear_proj_ms``, ``linear_elementwise_ms``
    and ``linear_mixer_ms`` (``benchmark/layer_metrics``: data files)."""
    import json
    import os

    base = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "benchmark")

    def pattern(name):
        with open(os.path.join(base, "layer_metrics", f"{name}.json")) as f:
            return re.compile(json.load(f)["pattern"])

    return base, {n: pattern(n) for n in ("gated_delta_ms", "linear_proj_ms",
                                          "linear_elementwise_ms", "linear_mixer_ms")}


@pytest.mark.parametrize("fixture", ("qwen3-next-80b-a3b.train-s8k",
                                     "qwen3-next-80b-a3b.train-s8k.pr37"))
def test_the_two_new_metrics_and_the_delta_rule_partition_the_mixer_on_the_chips_names(fixture):
    """On the names the chip printed before PR 37 (PR 26's fixture: the chain's
    fusions) and since (the four kernels): every op under ``linear_mixer`` is
    read by exactly one of ``gated_delta_ms``, ``linear_proj_ms`` and
    ``linear_elementwise_ms``, so the three add up to ``linear_mixer_ms``; both
    new ones read something on both generations of names; an op outside the
    mixer is read by neither."""
    import json
    import os

    base, pats = _linear_split()
    with open(os.path.join(base, "tests", "fixtures", "tf_ops_qwen3_next", f"{fixture}.json")) as f:
        ops = json.load(f)["ops"]
    parts = ("gated_delta_ms", "linear_proj_ms", "linear_elementwise_ms")
    total = {n: 0 for n in pats}
    for name, ps in ops:
        hit = [n for n in parts if pats[n].search(name)]
        assert len(hit) == (1 if pats["linear_mixer_ms"].search(name) else 0), (name, hit)
        for n in hit + (["linear_mixer_ms"] if hit else []):
            total[n] += ps
    assert all(total[n] > 0 for n in parts), total
    assert sum(total[n] for n in parts) == total["linear_mixer_ms"]


# ---------------------------------------------------------------------------
# the sort's two sides as loops (PR 34): every op of a loop carries its span
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qwen_names():
    """The distinct ``op_name`` of every op of the compiled tiny-qwen3-next step."""
    from benchmark import run as bench_run

    cell = bench_run.load("workloads", "tiny-qwen3-next.train")
    run = bench_run.Cell(cell, bench_run.load("configs", cell["config"]), jax.devices()[:1])
    run.start(7)
    run.build()
    compiled = run.program.step.jitted.lower(run.state, run.pool[0]).compile()
    return sorted(set(re.findall(r'op_name="(jit\([^"]+)"', compiled.as_text())))


def _names_with_the_sums_by_token(workload):
    """The distinct ``op_name`` of every op of a tiny step compiled with the
    layer's two sums taken by token (``ops/segment_sum.py``), as the chip takes
    them: forced here, where ``token_order`` would keep the scatter-add loop,
    and at the tiny widths (the interpreter tiles nothing)."""
    from beforeholiday_tpu.moe import dropless
    from beforeholiday_tpu.ops import segment_sum as seg
    from benchmark import run as bench_run

    with pytest.MonkeyPatch.context() as m:
        m.setattr(seg, "is_kernel_available", lambda *a: True)
        m.setattr(dropless, "token_order", lambda *a, **kw: seg.token_order(
            *a, **{**kw, "impl": "pallas"}))
        cell = bench_run.load("workloads", workload)
        run = bench_run.Cell(cell, bench_run.load("configs", cell["config"]), jax.devices()[:1])
        run.start(7)
        run.build()
        compiled = run.program.step.jitted.lower(run.state, run.pool[0]).compile()
    return sorted(set(re.findall(r'op_name="(jit\([^"]+)"', compiled.as_text())))


@pytest.mark.parametrize("workload,names_of,suffix", (
    ("tiny-qwen3-next.train", "qwen_names", ""),
    ("tiny-mellum.train", "mellum_names", ".mellum"),
    ("tiny-nemotron-h.train", "nemotron_names", ".nemotron_h")))
def test_every_op_of_the_row_loops_lies_under_its_span(request, workload, names_of, suffix):
    """The sort's two sides run inside the spans ``moe/moe_dispatch`` and
    ``moe/moe_combine`` — the gather loops, the token order and the sums by
    token (the kernel, and the gather that feeds it), the backward ones (a
    ``custom_vjp``'s) too: ``moe_ms*`` and ``moe_sort_ms*`` read them by those
    names, so an op without one would fall to ``unattributed_ms``. With the sums
    by token no row-sized ``scatter-add`` is left; off the TPU (the fixture's
    step) they are the scatter-add loops, under the same spans."""
    from benchmark import run as bench_run

    patterns = [re.compile(bench_run.load("layer_metrics", m + suffix)["pattern"])
                for m in ("moe_ms", "moe_sort_ms")]
    spans = r"moe_(dispatch|combine)\)*/"

    def held(names, inner):
        """Every op inside ``inner`` of the two spans, whatever it is."""
        found = [n for n in names if re.search(spans + inner, n)]
        for n in found:
            assert all(p.search(n) for p in patterns), n
            assert _pass_of(n) is not None, n
        return found

    def passes(found, side):
        return {_pass_of(n) for n in found if side in _scopes_of(n)}

    names = _names_with_the_sums_by_token(workload)
    # the token order once a layer, forward, beside the sort it completes
    order = held(names, r"jit\(_order\)/")
    assert order and passes(order, "moe_dispatch") == {"amp_forward"}
    # the kernel: the combine's sum forward, the dispatch's transpose backward
    sums = held(names, r"jit\(_segment_sum\)/segment_sum/")
    assert [n for n in sums if n.endswith("dot_general")]
    assert passes(sums, "moe_combine") == {"amp_forward"}
    assert passes(sums, "moe_dispatch") == {"amp_backward"}
    # four gather loops a side: the layer's own two and the two into token order
    loops = held(names, r"jit\(_gather_loop\)/while/body/")
    movers = [n for n in loops if n.endswith("/gather")]
    for side in ("moe_dispatch", "moe_combine"):
        assert passes(movers, side) == {"amp_forward", "amp_backward"}, side
    # and no scatter-add of rows: the one left is the router weights' (T, k)
    left = [n for n in names if re.search(spans, n) and n.endswith("scatter-add")]
    assert all("/while/body/" not in n and "moe_combine" not in n for n in left), left

    # the step as it compiles here: the scatter-add loops, under the same spans
    names = request.getfixturevalue(names_of)
    loops = held(names, r"jit\(_\w+_loop\)/while/body/")
    assert len(loops) >= 8
    adds = [n for n in loops if n.endswith("scatter-add")]
    assert passes(adds, "moe_combine") == {"amp_forward"}
    assert passes(adds, "moe_dispatch") == {"amp_backward"}
    assert not held(names, r"jit\(_segment_sum\)/")


# ---------------------------------------------------------------------------
# family lfm2_moe (PR 39): conv_mixer / short_conv, attn_mixer, dense_ffn and
# the model's four scopes
# ---------------------------------------------------------------------------

_LFM2_MODEL = ("lfm2_embed", "lfm2_layers", "lfm2_head", "lfm2_loss")
_LFM2_SCOPES = _LFM2_MODEL + (
    "conv_mixer", "short_conv", "attn_mixer", "dense_ffn", "amp_forward", "amp_backward",
    "amp_unscale", "fused_adam_step_flat", "layer_norm", "flash_attention", "moe_route",
    "moe_dispatch", "moe_experts", "moe_combine")


@pytest.fixture(scope="module")
def lfm2_names():
    """The distinct ``op_name`` of every op of the compiled tiny-lfm2-moe step."""
    from benchmark import run as bench_run

    cell = bench_run.load("workloads", "tiny-lfm2-moe.train")
    run = bench_run.Cell(cell, bench_run.load("configs", cell["config"]), jax.devices()[:1])
    run.start(7)
    run.build()
    compiled = run.program.step.jitted.lower(run.state, run.pool[0]).compile()
    return sorted(set(re.findall(r'op_name="(jit\([^"]+)"', compiled.as_text())))


@pytest.mark.parametrize("scope", _LFM2_SCOPES)
def test_lfm2_scope_is_in_the_compiled_step(lfm2_names, scope):
    assert any(scope in _scopes_of(n) or f"jvp({scope})" in n for n in lfm2_names), scope


def test_lfm2_first_level_scopes_partition_the_step(lfm2_names):
    first = ("amp_backward", "amp_unscale", "ddp_reduce_gradients",
             "ddp_overlap_hook", "fused_adam_step_flat")
    twice = [n for n in lfm2_names
             if sum(s in n for s in first) + (_pass_of(n) == "amp_forward") > 1]
    assert not twice
    both = [n for n in lfm2_names if "amp_forward" in n and "amp_backward" in n]
    assert all("amp_backward/transpose(amp_forward)" in n for n in both), both
    for scope in _LFM2_MODEL:        # the model's scopes survive inside both passes
        assert any(f"amp_forward/jvp({scope})" in n for n in lfm2_names), scope
        assert any(_pass_of(n) == "amp_backward" and f"jvp({scope})" in n
                   for n in lfm2_names), scope


def test_lfm2_second_level_scopes_do_not_overlap(lfm2_names):
    """An op is under one model scope at most, and under one of the two mixers,
    the dense feed-forward part or the MoE at most; ``short_conv`` lies inside
    ``conv_mixer``, ``flash_attention`` inside ``attn_mixer``, all inside
    ``lfm2_layers``; no name of the family holds another family's metric
    pattern."""
    in_path = lambda s, n: any(s == part.strip("()").split("(")[-1] for part in _scopes_of(n))
    for n in lfm2_names:
        assert sum(f"({s})" in n or s in _scopes_of(n) for s in _LFM2_MODEL) <= 1, n
        parts = [s for s in ("conv_mixer", "attn_mixer", "dense_ffn", "moe") if in_path(s, n)]
        assert len(parts) <= 1, n
        if parts and _pass_of(n):
            assert "lfm2_layers" in n, n
        if in_path("short_conv", n):
            assert parts == ["conv_mixer"], n
        if in_path("flash_attention", n):
            assert parts == ["attn_mixer"], n
        assert not re.search(r"gated_delta|ssd|window_mixer|full_mixer|ssm_mixer|moe_latent|"
                             r"moe_shared", n), n
    heavy = [n for n in lfm2_names if n.endswith("dot_general")]
    assert heavy and not [n for n in heavy if _pass_of(n) is None]


def test_the_short_conv_kernels_carry_their_names_under_the_mixer_in_both_passes():
    """``short_conv_ms`` reads the ``short_conv`` scope, ``short_conv_roofline``
    the kernels' own ``name=`` (the chip prints ``%short_conv_fwd.N``,
    ``%short_conv_bwd.N``): the forward kernel lies under ``amp_forward``, the
    backward one under ``amp_backward``, both under ``conv_mixer/short_conv``."""
    from beforeholiday_tpu.models import lfm2_moe

    cfg = lfm2_moe.Lfm2MoeConfig(hidden_size=128, num_hidden_layers=1, first_layer=22,
                                 short_conv_impl="pallas", dtype=jnp.bfloat16)
    params = lfm2_moe.init(jax.random.PRNGKey(0), cfg)
    p = {k: params["layers"][0][k].astype(jnp.bfloat16) for k in ("w_in", "conv", "w_out")}
    x = jnp.zeros((1, 96, cfg.hidden_size), jnp.bfloat16)
    svag = amp.scaled_value_and_grad(
        lambda p, x: jnp.sum(lfm2_moe.short_conv_mixer(cfg, x, p).astype(jnp.float32)),
        LossScaler(loss_scale=1.0))
    text = jax.jit(svag).lower(p, LossScaler(loss_scale=1.0).init(), x).compile().as_text()
    names = set(re.findall(r'op_name="(jit\([^"]+)"', text))
    kernels = {k: [n for n in names if f"/{k}/" in n] for k in ("short_conv_fwd", "short_conv_bwd")}
    assert all(kernels.values()), {k: len(v) for k, v in kernels.items()}
    for k, found in kernels.items():
        want = "amp_forward" if k == "short_conv_fwd" else "amp_backward"
        for n in found:
            assert _pass_of(n) == want, (k, n)
            assert re.search(rf"conv_mixer\)*/short_conv\)*/jit\(_(?:fwd|bwd)\)/{k}/", n), n
            assert not re.search(r"layer_norm|flash_attention|grouped_matmul|/moe/", n), n


# ---------------------------------------------------------------------------
# family deepseek_v3 (PR 42): mla_mixer / mla_latent, dense_ffn, the MoE with
# its shared expert and the model's four scopes
# ---------------------------------------------------------------------------

_DSV3_MODEL = ("deepseek_v3_embed", "deepseek_v3_layers", "deepseek_v3_head", "deepseek_v3_loss")
_DSV3_SCOPES = _DSV3_MODEL + (
    "mla_mixer", "mla_latent", "dense_ffn", "amp_forward", "amp_backward", "amp_unscale",
    "fused_adam_step_flat", "layer_norm", "flash_attention", "moe_route", "moe_dispatch",
    "moe_experts", "moe_shared", "moe_combine")


@pytest.fixture(scope="module")
def dsv3_names():
    """The distinct ``op_name`` of every op of the compiled tiny-deepseek-v3 step."""
    from benchmark import run as bench_run

    cell = bench_run.load("workloads", "tiny-deepseek-v3.train")
    run = bench_run.Cell(cell, bench_run.load("configs", cell["config"]), jax.devices()[:1])
    run.start(7)
    run.build()
    compiled = run.program.step.jitted.lower(run.state, run.pool[0]).compile()
    return sorted(set(re.findall(r'op_name="(jit\([^"]+)"', compiled.as_text())))


@pytest.mark.parametrize("scope", _DSV3_SCOPES)
def test_deepseek_v3_scope_is_in_the_compiled_step(dsv3_names, scope):
    assert any(scope in _scopes_of(n) or f"jvp({scope})" in n for n in dsv3_names), scope


def test_deepseek_v3_first_level_scopes_partition_the_step(dsv3_names):
    first = ("amp_backward", "amp_unscale", "ddp_reduce_gradients",
             "ddp_overlap_hook", "fused_adam_step_flat")
    twice = [n for n in dsv3_names
             if sum(s in n for s in first) + (_pass_of(n) == "amp_forward") > 1]
    assert not twice
    both = [n for n in dsv3_names if "amp_forward" in n and "amp_backward" in n]
    assert all("amp_backward/transpose(amp_forward)" in n for n in both), both
    for scope in _DSV3_MODEL:        # the model's scopes survive inside both passes
        assert any(f"amp_forward/jvp({scope})" in n for n in dsv3_names), scope
        assert any(_pass_of(n) == "amp_backward" and f"jvp({scope})" in n
                   for n in dsv3_names), scope


def test_deepseek_v3_second_level_scopes_do_not_overlap(dsv3_names):
    """An op is under one model scope at most, and under the mixer, the dense
    feed-forward part or the MoE at most; ``mla_latent`` and ``flash_attention``
    lie inside ``mla_mixer`` and not inside each other, ``moe_shared`` inside
    ``moe``, all inside ``deepseek_v3_layers``; no name of the family holds
    another family's metric pattern."""
    in_path = lambda s, n: any(s == part.strip("()").split("(")[-1] for part in _scopes_of(n))
    for n in dsv3_names:
        assert sum(f"({s})" in n or s in _scopes_of(n) for s in _DSV3_MODEL) <= 1, n
        parts = [s for s in ("mla_mixer", "dense_ffn", "moe") if in_path(s, n)]
        assert len(parts) <= 1, n
        if parts and _pass_of(n):
            assert "deepseek_v3_layers" in n, n
        inner = [s for s in ("mla_latent", "flash_attention") if in_path(s, n)]
        assert len(inner) <= 1, n
        if inner:
            assert parts == ["mla_mixer"], n
        if in_path("moe_shared", n):
            assert parts == ["moe"], n
        assert not re.search(r"gated_delta|ssd|window_mixer|full_mixer|ssm_mixer|attn_mixer|"
                             r"conv_mixer|short_conv|moe_latent|linear_mixer", n), n
    heavy = [n for n in dsv3_names if n.endswith("dot_general")]
    assert heavy and not [n for n in heavy if _pass_of(n) is None]
    # the latent's two products and its norm are under mla_latent in both passes
    latent = [n for n in dsv3_names if in_path("mla_latent", n)]
    assert {_pass_of(n) for n in latent} >= {"amp_forward", "amp_backward"}
    assert any(n.endswith("dot_general") for n in latent)
    assert any(in_path("layer_norm", n) for n in latent)


def test_the_mixers_glue_is_what_its_metric_reads(dsv3_names):
    """``mla_glue_ms`` reads what lies under ``mla_mixer`` outside
    ``flash_attention`` and is not a ``dot_general``: the rotary embedding, the
    splits, ``k_rot``'s broadcast, the concatenations and ``dk_rot``'s sum are
    there, and no product is."""
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "layer_metrics",
                           "mla_glue_ms.deepseek_v3.json")) as f:
        pattern = re.compile(json.load(f)["pattern"])
    glue = [n for n in dsv3_names if pattern.search(n)]
    assert glue and all("mla_mixer" in n for n in glue)
    assert not [n for n in glue if "flash_attention" in n or "dot_general" in n]
    kinds = {n.rsplit("/", 1)[-1] for n in glue}
    assert kinds & {"concatenate", "mul", "broadcast_in_dim", "reduce_sum", "slice", "transpose"}
    under = [n for n in dsv3_names if "mla_mixer" in n]
    assert any(n.endswith("dot_general") for n in under) and len(glue) < len(under)


# ---------------------------------------------------------------------------
# family keye_vl2 (PR 46): sparse_mixer with the indexer's two scopes and the
# selected-keys flash kernels inside it, the MoE and the model's four scopes
# ---------------------------------------------------------------------------

_KEYE_MODEL = ("keye_vl2_embed", "keye_vl2_layers", "keye_vl2_head", "keye_vl2_loss")
_KEYE_SCOPES = _KEYE_MODEL + (
    "sparse_mixer", "indexer_proj", "indexer_select", "index_select", "amp_forward",
    "amp_backward", "amp_unscale", "fused_adam_step_flat", "layer_norm", "flash_attention",
    "moe_route", "moe_dispatch", "moe_experts", "moe_combine")


@pytest.fixture(scope="module")
def keye_names():
    """The distinct ``op_name`` of every op of the compiled tiny-keye-vl2 step."""
    from benchmark import run as bench_run

    cell = bench_run.load("workloads", "tiny-keye-vl2.train")
    run = bench_run.Cell(cell, bench_run.load("configs", cell["config"]), jax.devices()[:1])
    run.start(7)
    run.build()
    compiled = run.program.step.jitted.lower(run.state, run.pool[0]).compile()
    return sorted(set(re.findall(r'op_name="(jit\([^"]+)"', compiled.as_text())))


@pytest.mark.parametrize("scope", _KEYE_SCOPES)
def test_keye_vl2_scope_is_in_the_compiled_step(keye_names, scope):
    assert any(scope in _scopes_of(n) or f"jvp({scope})" in n for n in keye_names), scope


def test_keye_vl2_first_level_scopes_partition_the_step(keye_names):
    first = ("amp_backward", "amp_unscale", "ddp_reduce_gradients",
             "ddp_overlap_hook", "fused_adam_step_flat")
    twice = [n for n in keye_names
             if sum(s in n for s in first) + (_pass_of(n) == "amp_forward") > 1]
    assert not twice
    both = [n for n in keye_names if "amp_forward" in n and "amp_backward" in n]
    assert all("amp_backward/transpose(amp_forward)" in n for n in both), both
    for scope in _KEYE_MODEL:        # the model's scopes survive inside both passes
        assert any(f"amp_forward/jvp({scope})" in n for n in keye_names), scope
        assert any(_pass_of(n) == "amp_backward" and f"jvp({scope})" in n
                   for n in keye_names), scope


def test_keye_vl2_second_level_scopes_do_not_overlap(keye_names):
    """An op is under one model scope at most, and under the mixer or the MoE at
    most; ``indexer_proj``, ``indexer_select`` and ``flash_attention`` lie inside
    ``sparse_mixer`` and not inside each other, all inside ``keye_vl2_layers``;
    the indexer runs forward only: nothing of it is in the backward pass; no name
    of the family holds another family's metric pattern."""
    in_path = lambda s, n: any(s == part.strip("()").split("(")[-1] for part in _scopes_of(n))
    for n in keye_names:
        assert sum(f"({s})" in n or s in _scopes_of(n) for s in _KEYE_MODEL) <= 1, n
        parts = [s for s in ("sparse_mixer", "moe") if in_path(s, n)]
        assert len(parts) <= 1, n
        if parts and _pass_of(n):
            assert "keye_vl2_layers" in n, n
        inner = [s for s in ("indexer_proj", "indexer_select", "flash_attention")
                 if in_path(s, n)]
        assert len(inner) <= 1, n
        if inner:
            assert parts == ["sparse_mixer"], n
        if in_path("index_select", n):
            assert inner == ["indexer_select"], n
        if inner and inner[0].startswith("indexer"):
            assert _pass_of(n) == "amp_forward", n
        assert not re.search(r"gated_delta|ssd|window_mixer|full_mixer|ssm_mixer|attn_mixer|"
                             r"conv_mixer|short_conv|moe_latent|moe_shared|linear_mixer|mla_", n), n
    heavy = [n for n in keye_names if n.endswith("dot_general")]
    assert heavy and not [n for n in heavy if _pass_of(n) is None]
    # the indexer's three products and its LayerNorm are under indexer_proj
    proj = [n for n in keye_names if in_path("indexer_proj", n)]
    assert any(n.endswith("dot_general") for n in proj)
    assert any(in_path("layer_norm", n) for n in proj)


def test_the_sparse_kernels_carry_their_names_under_the_mixer_in_both_passes():
    """``flash_sparse_ms`` / ``flash_sparse_roofline`` read the kernels' own
    ``name=`` (the chip prints ``%flash_attention_sparse_fwd.N``, ..),
    ``index_select_ms`` / ``index_select_roofline`` the indexer's
    (``%index_select.N``): the forward kernel and the indexer's lie under
    ``amp_forward``, the backward one under ``amp_backward``, all under
    ``sparse_mixer``, the flash kernels under ``flash_attention`` too (so
    ``flash_attn_ms`` reads them)."""
    from beforeholiday_tpu.models import keye_vl2

    cfg = keye_vl2.KeyeVL2Config(attention_impl="pallas", dtype=jnp.bfloat16,
                                 sa_config=keye_vl2.SparseAttentionConfig(topk=40))
    params = keye_vl2.init(jax.random.PRNGKey(0), cfg)
    p = params["layers"][0]
    x = jnp.zeros((1, 256, cfg.hidden_size), jnp.bfloat16)
    tables = keye_vl2.rotary_tables(cfg, 256)
    svag = amp.scaled_value_and_grad(
        lambda p, x: jnp.sum(keye_vl2.attention(cfg, x, p, tables)[0].astype(jnp.float32)),
        LossScaler(loss_scale=1.0))
    text = jax.jit(svag).lower(p, LossScaler(loss_scale=1.0).init(), x).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    kernels = {k: [n for n in names if f"/{k}/pallas_call" in n]
               for k in ("flash_attention_sparse_fwd", "flash_attention_sparse_dqkv",
                         "index_select")}
    assert all(kernels.values()), {k: len(v) for k, v in kernels.items()}
    for k, found in kernels.items():
        for n in found:
            assert "sparse_mixer" in n, n
            assert ("flash_attention/" in n) == k.startswith("flash"), n
            assert ("indexer_select" in n) == (k == "index_select"), n
            assert not re.search(r"grouped_matmul|/moe/|window", n), n
    assert "flash_attention_window" not in text


# ---------------------------------------------------------------------------
# family kimi_linear (PR 49): kda_mixer with its five inner scopes, mla_mixer
# without a rotary table, dense_ffn, the MoE with its shared expert and the
# model's four scopes
# ---------------------------------------------------------------------------

_KIMI_MODEL = ("kimi_linear_embed", "kimi_linear_layers", "kimi_linear_head", "kimi_linear_loss")
_KIMI_INNER = ("kda_proj", "deltanet_qkv", "kda_gate_proj", "kda", "deltanet_gate")
_KIMI_SCOPES = _KIMI_MODEL + _KIMI_INNER + (
    "kda_mixer", "mla_mixer", "mla_latent", "dense_ffn", "amp_forward",
    "amp_backward", "amp_unscale", "fused_adam_step_flat", "layer_norm", "flash_attention",
    "moe_route", "moe_dispatch", "moe_experts", "moe_shared", "moe_combine")


@pytest.fixture(scope="module")
def kimi_names():
    """The distinct ``op_name`` of every op of the compiled tiny-kimi-linear step."""
    from benchmark import run as bench_run

    cell = bench_run.load("workloads", "tiny-kimi-linear.train")
    run = bench_run.Cell(cell, bench_run.load("configs", cell["config"]), jax.devices()[:1])
    run.start(7)
    run.build()
    compiled = run.program.step.jitted.lower(run.state, run.pool[0]).compile()
    return sorted(set(re.findall(r'op_name="(jit\([^"]+)"', compiled.as_text())))


@pytest.mark.parametrize("scope", _KIMI_SCOPES)
def test_kimi_linear_scope_is_in_the_compiled_step(kimi_names, scope):
    assert any(scope in _scopes_of(n) or f"jvp({scope})" in n for n in kimi_names), scope


def test_kimi_linear_first_level_scopes_partition_the_step(kimi_names):
    first = ("amp_backward", "amp_unscale", "ddp_reduce_gradients",
             "ddp_overlap_hook", "fused_adam_step_flat")
    twice = [n for n in kimi_names
             if sum(s in n for s in first) + (_pass_of(n) == "amp_forward") > 1]
    assert not twice
    # (off the TPU the rule's chunk-local algebra is a checkpointed function: what the
    # backward pass recomputes of it carries its forward names behind the backward's)
    both = [n for n in kimi_names if "amp_forward" in n and "amp_backward" in n
            and not re.search(r"/kda/(checkpoint|remat2)", n)]
    assert all("amp_backward/transpose(amp_forward)" in n for n in both), both
    for scope in _KIMI_MODEL:        # the model's scopes survive inside both passes
        assert any(f"amp_forward/jvp({scope})" in n for n in kimi_names), scope
        assert any(_pass_of(n) == "amp_backward" and f"jvp({scope})" in n
                   for n in kimi_names), scope


def test_kimi_linear_second_level_scopes_do_not_overlap(kimi_names):
    """An op is under one model scope at most, and under one of the two mixers,
    the dense feed-forward part or the MoE at most; the five inner scopes of
    ``kda_mixer`` lie inside it and not inside each other, ``mla_latent`` and ``flash_attention`` inside ``mla_mixer``,
    ``moe_shared`` inside ``moe``, all inside ``kimi_linear_layers``; no name of
    the family holds the scalar rule's metric pattern or another mixer's."""
    in_path = lambda s, n: any(s == part.strip("()").split("(")[-1] for part in _scopes_of(n))
    for n in kimi_names:
        assert sum(f"({s})" in n or s in _scopes_of(n) for s in _KIMI_MODEL) <= 1, n
        parts = [s for s in ("kda_mixer", "mla_mixer", "dense_ffn", "moe") if in_path(s, n)]
        assert len(parts) <= 1, n
        if parts and _pass_of(n):
            assert "kimi_linear_layers" in n, n
        inner = [s for s in _KIMI_INNER if in_path(s, n)]
        assert len(inner) <= 1, n
        if inner:
            assert parts == ["kda_mixer"], n
        if [s for s in ("mla_latent", "flash_attention") if in_path(s, n)]:
            assert parts == ["mla_mixer"], n
        if in_path("moe_shared", n):
            assert parts == ["moe"], n
        assert not re.search(r"gated_delta|ssd|window_mixer|full_mixer|ssm_mixer|attn_mixer|"
                             r"conv_mixer|short_conv|moe_latent|linear_mixer|sparse_mixer", n), n
    heavy = [n for n in kimi_names if n.endswith("dot_general")]
    assert heavy and not [n for n in heavy if _pass_of(n) is None]
    # every product of the mixer outside the rule is under one of the two projection scopes
    mixer = [n for n in heavy if in_path("kda_mixer", n) and not in_path("kda", n)]
    assert mixer and all(in_path("kda_proj", n) or in_path("kda_gate_proj", n) for n in mixer)
    for scope in ("kda_proj", "kda_gate_proj", "kda"):
        assert {_pass_of(n) for n in kimi_names if in_path(scope, n)} \
            >= {"amp_forward", "amp_backward"}, scope


def test_the_kda_kernels_are_named_by_the_op_and_lie_under_its_scopes():
    """``kda_ms`` reads the ``kda`` scope, ``kda_roofline`` the kernels' own ``name=``
    (the chip prints ``%kda_fwd.N``, ``%kda_bwd.N``): each kernel sits in a
    ``jax.jit`` function of its own (``_fwd_call``, ``_bwd_call``: traced and
    lowered once for a model's layers), called under ``kda_mixer/kda`` —
    ``_fwd_call`` under ``amp_forward`` alone (the backward pass takes the grid
    steps' start states as a residual and runs no forward kernel again) and
    ``_bwd_call`` under ``amp_backward``; the layer's other two passes are
    ``ops.deltanet``'s, under their own scopes. What the compiled step makes of
    the names is ``tests/test_chip_compile.py``'s to hold."""
    from beforeholiday_tpu.models import kimi_linear
    from beforeholiday_tpu.ops import deltanet, kda

    force = lambda fn: (lambda *a, **kw: fn(*a, **{**kw, "impl": "pallas"}))
    cfg = kimi_linear.KimiLinearConfig(
        hidden_size=128, dtype=jnp.bfloat16, kda_chunk=64,
        linear_attn_config=dict(kda_layers=(1, 2, 3), full_attn_layers=(4,), num_heads=2,
                                head_dim=128, short_conv_kernel_size=4))
    p = kimi_linear.init(jax.random.PRNGKey(0), cfg)["layers"][0]
    x = jnp.zeros((1, 128, cfg.hidden_size), jnp.bfloat16)
    svag = amp.scaled_value_and_grad(
        lambda p, x: jnp.sum(kimi_linear.kda_attention(cfg, x, p).astype(jnp.float32)),
        LossScaler(loss_scale=1.0))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kda, "kda_rule", force(kda.kda_rule))
        mp.setattr(deltanet, "deltanet_qkv", force(deltanet.deltanet_qkv))
        mp.setattr(deltanet, "deltanet_gate", force(deltanet.deltanet_gate))
        text = jax.jit(svag).lower(p, LossScaler(loss_scale=1.0).init(), x).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    assert not [n for n in names if re.search(r"kda_(prepare|scan)_", n)]
    for scope in ("deltanet_qkv", "deltanet_gate"):     # their kernels sit in a jit of their own
        assert any(re.search(rf"kda_mixer\)*/{scope}\)*/", n) for n in names), scope
    for kernel, call, in_pass in (("kda_fwd", "_fwd_call", "amp_forward"),
                                  ("kda_bwd", "_bwd_call", "amp_backward")):
        assert f"{kernel}/pallas_call" in names, kernel      # inside its own jit function
        sites = [n for n in names if n.endswith(f"/jit({call})")]
        assert sites, call
        for n in sites:
            assert re.search(r"kda_mixer\)*/kda\)*/jit", n), n
            assert _pass_of(n) == in_pass, n
            assert not re.search(r"gated_delta|grouped_matmul|/moe/|flash_attention", n), n
