"""Kernels of the main path compiled for a described v5e, at the cells' widths.

Nothing runs and no chip is needed: the TPU's compiler is installed here and
compiles for a chip that is described, not attached. It refuses what the
Pallas interpreter lets through (a block Mosaic cannot tile, more VMEM than a
kernel may take), so each later PR is held to it at no chip time. The topology
is described inside a fixture: only the worker that is given this file loads
the TPU's library (see the ``on-chip-measurement`` guide, section 2). Keep every
such compile in this one file."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no compiler here, nothing to hold to
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _logits_sized_ops_the_loss_feeds(text):
    """The compiler's own instructions of a step's ``text`` above 100 MB whose
    nearest named producer is under a ``*_loss`` span, by the program ledger
    (``monitor.program_ops``): the cotangent autodiff's loss handed the head
    as two arrays was four of them, 403-671 MB each (PERF.md §5, PR 52);
    ``models/layers.py:cross_entropy``'s one expression leaves none (PR 53)."""
    import offline_step
    from beforeholiday_tpu import monitor

    return [(r["name"], r["opcode"], r["producer"])
            for r in monitor.program_ops("step", program=text)
            if not r["scope"] and r["opcode"] not in offline_step._NOT_OPS
            and max(r["bytes_in"], r["bytes_out"]) > 100e6 and "_loss" in (r["producer"] or "")]


@pytest.fixture
def compiled_not_interpreted(monkeypatch):
    from beforeholiday_tpu.ops import grouped_matmul as gm, segment_sum as seg

    monkeypatch.setattr(gm, "_interpret_default", lambda: False)
    monkeypatch.setattr(seg, "_interpret_default", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)   # unreadable without a chip
    yield gm
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("R,E,K,N,out", (
    (24576, 16, 2304, 896, jnp.float32),        # the Mellum cell, gate / up
    (24576, 16, 896, 2304, jnp.bfloat16),       # ... down
    (16384, 32, 2048, 512, jnp.float32),        # the Qwen cell, gate / up
    (16384, 32, 512, 2048, jnp.bfloat16),       # ... down
    (1000, 4, 128, 256, jnp.float32),           # a buffer that ends inside a row tile
    (8192, 8, 1024, 2688, jnp.float32),         # the Nemotron cell, up (in the latent)
    (8192, 8, 2688, 1024, jnp.bfloat16),        # ... down
    (12288, 8, 2048, 1792, jnp.float32),        # the LFM2 cell, gate / up: 14 lane tiles
    (12288, 8, 1792, 2048, jnp.bfloat16),       # ... down
    (9216, 16, 2048, 768, jnp.float32),         # the Kanana cell, gate / up: 6 lane tiles
    (9216, 16, 768, 2048, jnp.bfloat16),        # ... down
), ids=("mellum_up", "mellum_down", "qwen_up", "qwen_down", "ragged_buffer", "nemotron_up",
        "nemotron_down", "lfm2_up", "lfm2_down", "kanana_up", "kanana_down"))
def test_the_grouped_matmul_kernels_compile_for_the_chip(one_chip, compiled_not_interpreted,
                                                         R, E, K, N, out):
    gm = compiled_not_interpreted
    bf = jnp.bfloat16
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def all_three(lhs, rhs, sizes, ct):
        y, pull = jax.vjp(lambda a, b: gm.grouped_matmul(
            a, b, sizes, preferred_element_type=out, impl="pallas"), lhs, rhs)
        return y, pull(ct)

    text = jax.jit(all_three).lower(
        shape((R, K), bf), shape((E, K, N), bf), shape((E,), jnp.int32), shape((R, N), out)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for kernel in ("fwd", "dlhs", "drhs"):
        assert f"grouped_matmul_{kernel}" in text


@pytest.mark.parametrize("batch,S,H,P,G,N", (
    (1, 8192, 16, 64, 1, 128),                  # the Nemotron cell: a rank's 16 heads, one group
    (2, 1024, 8, 64, 2, 128),                   # two groups, two sequences
    (1, 1000, 2, 128, 1, 128),                  # a head a unit, a sequence that is padded
), ids=("nemotron_block", "two_groups", "wide_heads_ragged"))
def test_the_ssd_kernels_compile_for_the_chip(one_chip, monkeypatch, batch, S, H, P, G, N):
    from beforeholiday_tpu.ops import ssd as ssd_mod

    monkeypatch.setattr(ssd_mod, "_interpret_default", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    bf, f32 = jnp.bfloat16, jnp.float32
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def both(x, dt, A, B, C, D, ct):
        y, pull = jax.vjp(lambda *a: ssd_mod.ssd(*a, impl="pallas"), x, dt, A, B, C, D)
        return y, pull(ct)

    try:
        text = jax.jit(both).lower(
            shape((batch, S, H, P), bf), shape((batch, S, H), f32), shape((H,), f32),
            shape((batch, S, G, N), bf), shape((batch, S, G, N), bf), shape((H,), f32),
            shape((batch, S, H, P), bf)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert text.count("tpu_custom_call") == 3
    for kernel in ("ssd_fwd", "ssd_bwd_states", "ssd_bwd"):
        assert kernel in text


def _one_shot_rows(monkeypatch, dropless):
    """The sort's two sides as the static ops they were before PR 34: one
    gather and one scatter-add over the whole buffer, cut by a ``where``."""
    def gather(src, token, n_valid, *, scale=None, order=None):
        return jnp.where((jnp.arange(token.shape[0]) < n_valid)[:, None], src[token], 0)

    def scatter_add(rows, token, n_valid, *, out_rows, scale=None, out_dtype=None, order=None):
        valid = jnp.arange(token.shape[0]) < n_valid
        rows = jnp.where(valid[:, None], rows, 0).astype(jnp.float32) \
            * jnp.where(valid, scale, 0.0)[:, None]
        return jnp.zeros((out_rows, rows.shape[1]), out_dtype).at[token].add(rows)

    monkeypatch.setattr(dropless, "gather_rows", gather)
    monkeypatch.setattr(dropless, "scatter_add_rows", scatter_add)


@pytest.mark.parametrize("T,R,D,k,held,F,gated", (
    (8192, 16384, 2048, 10, 32, 512, True),     # the Qwen cell
    (8192, 24576, 2304, 8, 16, 896, True),      # the Mellum cell
    (8192, 8192, 1024, 22, 8, 2688, False),     # the Nemotron cell (the latent; k > held)
    (8192, 9216, 2048, 6, 16, 768, True),       # the Kanana cell
    (8192, 12288, 2048, 8, 16, 768, True),      # the Keye cell
    (8192, 12288, 2048, 4, 8, 1792, True),      # the LFM2 cell
), ids=("qwen", "mellum", "nemotron", "kanana", "keye", "lfm2"))
def test_the_dropless_layer_compiles_for_the_chip_with_its_rows_moved_in_loops(
        one_chip, compiled_not_interpreted, monkeypatch, T, R, D, k, held, F, gated):
    """Forward + backward of ``dropless_experts`` at a cell's shapes: the rows
    move in ``while`` loops whose bodies update the buffers in place (no ``copy``
    of a buffer inside one) — the layer's two gathers and the two into token
    order — the two sums are the ``segment_sum`` kernel (a ``tpu_custom_call``
    each: with the weights forward, without backward) and no loop scatter-adds;
    the program's temporaries are not above the one-shot form's plus the one
    token-ordered copy of the buffer, ``R x D`` bfloat16 (the forward's and the
    backward's never live together; 1 MiB of slack: the loops carry a few
    ``(R,)`` vectors, the token order a few more). At the Mellum cell's shapes
    the masking pass over ``xs`` (``dropless._settled``) is what keeps the
    compiler from holding the gather loop's result at twice its size: without
    it the temporaries are ``R x D`` bfloat16 larger. If this compiler stops
    doing that, the pass can go."""
    from beforeholiday_tpu.moe import dropless

    bf, f32 = jnp.bfloat16, jnp.float32
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    experts = {"w_up": shape((held, D, F), bf), "w_down": shape((held, F, D), bf)}
    if gated:
        experts["w_gate"] = shape((held, D, F), bf)

    def compiled():
        def both(x, w, idx, experts, ct):       # a fresh function: nothing from jit's cache
            (y, counters), pull = jax.vjp(lambda x, w, experts: dropless.dropless_experts(
                x, w, idx, experts, rows_bound=R, impl="pallas"), x, w, experts)
            return y, pull((ct, jax.tree.map(jnp.zeros_like, counters)))

        return jax.jit(both).lower(shape((T, D), bf), shape((T, k), f32),
                                   shape((T, k), jnp.int32), experts, shape((T, D), f32)).compile()

    loops = compiled()
    text = loops.as_text()
    sums = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*op_name="[^"]*moe_(\w+)\)*'
                      r'/jit\(_segment_sum\)', text)
    assert sorted(sums) == ["combine", "dispatch"], sums
    bodies = [c for c in text.split("\n\n") if "/while/body/" in c
              and ("moe_dispatch" in c or "moe_combine" in c)]
    assert not [b for b in bodies if "scatter-add" in b]
    movers = [b for b in bodies if "/while/body/gather" in b]
    assert len(movers) >= 4, len(movers)
    for body in movers:
        big = [l for l in body.splitlines() if re.search(r" copy\(", l)
               and re.search(rf"\[(?:{R}|{T}),{D}\]", l)]
        assert not big, big
    if R == 24576:
        monkeypatch.setattr(dropless, "_settled", lambda rows, live: rows)
        bare = compiled().memory_analysis().temp_size_in_bytes
        assert bare - loops.memory_analysis().temp_size_in_bytes >= 0.9 * R * D * 2
    _one_shot_rows(monkeypatch, dropless)
    one_shot = compiled()
    assert "moe_dispatch)/while/body" not in one_shot.as_text()
    got, was = (c.memory_analysis().temp_size_in_bytes for c in (loops, one_shot))
    assert got <= was + R * D * 2 + 2 ** 20, (got / 2 ** 20, was / 2 ** 20)


def test_the_deltanet_kernels_compile_for_the_chip(one_chip, monkeypatch):
    """Both passes of ``ops/deltanet.py``, forward and backward, at the Qwen
    cell's shapes: 8,192 rows, 16 key and 32 value heads of 128, a filter of 4."""
    from beforeholiday_tpu.ops import deltanet as dn

    monkeypatch.setattr(dn, "_interpret_default", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    bf, f32 = jnp.bfloat16, jnp.float32
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    B, S, Hk, Hv, d, K = 1, 8192, 16, 32, 128, 4
    C = 2 * Hk * d + Hv * d
    heads = dict(key_heads=Hk, value_heads=Hv, d_k=d, d_v=d)

    def both(cols, filt, cq, ck, cv, o, z, w, dy):
        qkv, pull = jax.vjp(lambda c, f: dn.deltanet_qkv(c, f, impl="pallas", **heads), cols, filt)
        y, pull_y = jax.vjp(lambda *a: dn.deltanet_gate(*a, eps=1e-6, impl="pallas"), o, z, w)
        return qkv, pull((cq, ck, cv)), y, pull_y(dy)

    heads_first, columns = shape((B, Hv, S, d), bf), shape((B, S, Hv * d), bf)
    try:
        text = jax.jit(both).lower(
            shape((B, S, C), bf), shape((C, K), f32), heads_first, heads_first, heads_first,
            heads_first, columns, shape((d,), f32), columns).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert text.count("tpu_custom_call") == 4
    for kernel in ("deltanet_qkv_fwd", "deltanet_qkv_bwd", "deltanet_gate_fwd", "deltanet_gate_bwd"):
        assert kernel in text


def test_the_qwen_step_compiled_for_the_chip_keeps_what_the_deltanet_kernels_gave(
        topo, monkeypatch):
    """The whole step of ``qwen3-next-80b-a3b.train-s8k`` compiled for a described
    v5e (``tools/offline_step.py``; ~50 s, nothing runs). The cell stands at the
    compiler's memory limit, and what a change asks for beyond it is paid in
    rematerialisation, not in an error (PERF.md, PR 34). With the DeltaNet
    layer's chain in two kernels (PR 37) the program is held to: the float32
    logits are not computed twice; of the parent's 84 rematerialised ops 9 are
    left (the three ``x @ w_cols`` products and the attention layer's q
    projection); no pad, sum or copy of a ``[8192,12288]`` activation (the
    parent cut z and the convolved columns out of one product and padded their
    cotangents back into one) nor of the 134 MB ``[8192,8192]`` columns; and the
    temporaries stay at the limit the compiler fills to (6.211 GiB; the parent's
    program read 6.158 after rematerialising from 8.19, this one needs 6.59
    with nothing rematerialised: both compiled for a v5p to see it). Since PR 53
    the loss hands the head's backward its cotangent in one expression: no
    logits-sized nameless op is fed by ``qwen3n_loss``, and the memory that
    frees leaves NOTHING rematerialised (the parent recomputed the q projection
    and three DeltaNet products, 4 ops under a pin of 12: the pin keeps that
    slack of 8)."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "tools"))
    import offline_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # resolve_impl -> pallas
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled, _ = offline_step.compile_cell("qwen3-next-80b-a3b.train-s8k", topo)
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    text = compiled.as_text()
    for kernel in ("deltanet_qkv_fwd", "deltanet_qkv_bwd", "deltanet_gate_fwd", "deltanet_gate_bwd"):
        assert len(set(re.findall(rf"%{kernel}[.\d]* = ", text))) == 3, kernel
    made = [l for l in text.splitlines() if re.match(r"\s*(ROOT )?%\S+ = ", l)]
    remat = [l for l in made if re.match(r"\s*(ROOT )?%\S*\.remat\S* = ", l)]
    assert not [l for l in remat if "f32[8192,18992]" in l or "f32[1,8192,18992]" in l]
    assert len(remat) <= 8, len(remat)
    assert not _logits_sized_ops_the_loss_feeds(text)
    mixer = [l for l in made if "linear_mixer" in l
             and re.search(r" (pad|add|copy|concatenate|transpose)\(", l)]
    wide = re.compile(r"= \S*\[(1,)?8192,(12288|8192)\]|= \S*\[1,8192,32,128\]")
    assert not [l[:160] for l in mixer if wide.search(l)]
    assert compiled.memory_analysis().temp_size_in_bytes <= 6.25 * 2 ** 30


@pytest.mark.parametrize("chunk", (64, 128))
def test_the_kda_kernels_compile_for_the_chip(one_chip, monkeypatch, chunk):
    """The two kernels of ``ops/kda.py`` at the Kimi-Linear cell's shape (32
    heads of 128, 8,192 tokens, bfloat16 with a float32 gate), at both chunk
    sizes: each makes a grid step's chunk factors and walks the chunk scan over
    them — the backward body is ``jax.vjp`` of the forward's function, traced
    inside the kernel, round the reversed walk — and the backward pass runs
    no forward kernel again (the state each grid step starts from is a residual)."""
    from beforeholiday_tpu.ops import kda

    monkeypatch.setattr(kda, "_interpret_default", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    bf, f32 = jnp.bfloat16, jnp.float32
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    B, H, S, d = 1, 32, 8192, 128

    def both(q, k, v, g, beta, do):
        o, pull = jax.vjp(lambda *a: kda.kda_rule(*a, chunk=chunk, impl="pallas",
                                                  heads_first=True), q, k, v, g, beta)
        return o, pull(do)

    heads_first = shape((B, H, S, d), bf)
    try:
        text = jax.jit(both).lower(
            heads_first, heads_first, heads_first, shape((B, H, S, d), f32),
            shape((B, H, S), f32), heads_first).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    calls = {k: len(set(re.findall(rf"%{k}[.\d]* = ", text))) for k in ("kda_fwd", "kda_bwd")}
    assert calls == {"kda_fwd": 1, "kda_bwd": 1}, calls
    assert not re.search(r"%kda_(prepare|scan)", text)
    assert text.count("tpu_custom_call") == 2


def test_the_kimi_step_compiled_for_the_chip_runs_no_kernel_twice_over(topo, monkeypatch):
    """The whole step of ``kimi-linear-48b-a3b.train-s8k`` compiled for a described
    v5e (``tools/offline_step.py``; ~60 s, nothing runs). Its 9.64 GB of state
    leave the sequence 6 GB, and the compiler rematerialises what does not fit:
    with the fused KDA kernels (PR 51: one forward and one backward kernel a
    layer, the state at each grid step's start a residual of 17 MB a layer) two
    ``x @ W_qkv`` products and three gate fusions of two arrays each, 8 arrays
    (the parent's four kernels: four ``x @ W_qkv``; with every chunk's start
    state kept, 134 MB a layer, 11 arrays; with the scan's five operands kept it
    was 19: PERF.md, PRs 49 and 51). Held to: no flash, ``kda`` or ``deltanet``
    kernel beyond what the ``custom_vjp`` rules ask for (a rematerialised kernel
    would be a layer's time again), none of the parent's four KDA kernels left,
    at most 3 rematerialised arrays (a fusion of several results is a tuple and
    its elements: the elements are the arrays; since PR 53, with the loss's
    cotangent one expression and no logits-sized nameless op fed by
    ``kimi_linear_loss``, ONE ``x @ W_qkv`` product and ONE gate fusion of two
    arrays are what is left of the 8), and temporaries under what the step
    reads (6.987 GiB) + 0.1."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "tools"))
    import offline_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # resolve_impl -> pallas
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled, _ = offline_step.compile_cell("kimi-linear-48b-a3b.train-s8k", topo)
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    text = compiled.as_text()
    calls = lambda kernel: len(set(re.findall(rf"%{kernel}[.\d]* = ", text)))
    # four KDA layers: one kernel a pass, the forward one in the forward pass alone
    for kernel, want in (("kda_fwd", 4), ("kda_bwd", 4), ("deltanet_qkv_fwd", 4),
                         ("deltanet_qkv_bwd", 4), ("deltanet_gate_fwd", 4),
                         ("deltanet_gate_bwd", 4)):
        assert calls(kernel) == want, (kernel, calls(kernel))
    assert not re.search(r"%kda_(prepare|scan)", text)
    # what ``kda_ms`` reads (the op's scope path, ``kda_mixer/kda/``) and the pass of each
    for kernel, in_pass, other in (("kda_fwd", "amp_forward", "amp_backward"),
                                   ("kda_bwd", "amp_backward", None)):
        for line in re.findall(rf"%{kernel}[.\d]* = .*", text):
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "kda_mixer/kda/" in op_name and in_pass in op_name, op_name
            assert other is None or other not in op_name, op_name
    assert calls("flash_attention") == 2            # the one latent layer: forward, fused backward
    made = [l for l in text.splitlines() if re.match(r"\s*(ROOT )?%\S+ = ", l)]
    remat = [l for l in made if re.match(r"\s*(ROOT )?%\S*\.remat\S* = [^(]", l)]
    assert len(remat) <= 3, [l.split(" = ")[0].strip() for l in remat]     # by name
    assert not [l for l in remat if "f32[8192,20480]" in l or "f32[1,8192,20480]" in l]
    assert compiled.memory_analysis().temp_size_in_bytes <= 7.09 * 2 ** 30
    assert not _logits_sized_ops_the_loss_feeds(text)


@pytest.mark.parametrize("batch,S,D,K", ((1, 8192, 2048, 3), (2, 1024, 256, 4), (1, 48, 128, 8)),
                         ids=("lfm2_mixer", "two_sequences_four_taps", "tiles_of_16_rows"))
def test_the_short_conv_kernels_compile_for_the_chip(one_chip, monkeypatch, batch, S, D, K):
    """Both passes of ``ops/short_conv.py`` at the LFM2 cell's shape (8,192 rows,
    2,048 channels, three taps: 128-row tiles at the full width of 6,144 columns)
    and at two others."""
    from beforeholiday_tpu.ops import short_conv as sc

    monkeypatch.setattr(sc, "_interpret_default", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    bf = jnp.bfloat16
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def both(bcx, w, dy):
        y, pull = jax.vjp(lambda a, f: sc.gated_short_conv(a, f, impl="pallas"), bcx, w)
        return y, pull(dy)

    try:
        text = jax.jit(both).lower(
            shape((batch, S, 3 * D), bf), shape((D, K), bf), shape((batch, S, D), bf)
        ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert text.count("tpu_custom_call") == 2
    for kernel in ("short_conv_fwd", "short_conv_bwd"):
        assert kernel in text


@pytest.fixture
def flash_compiled(monkeypatch):
    """``ops.attention`` with its kernels compiled, not interpreted, and the
    compilation cache off (a compile for a described chip cannot be read back)."""
    from beforeholiday_tpu.ops import attention as A

    monkeypatch.setattr(A, "_interpret_default", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield A
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("variant", ("plain", "lens_dlse", "dropout"))
@pytest.mark.parametrize("S,D", ((1024, 64), (1024, 128), (512, 256)),
                         ids=("gpt_cells", "widest_at_1024", "widest_at_512"))
def test_the_fused_flash_backward_compiles_for_the_chip(one_chip, flash_compiled, S, D, variant):
    """Where a head is one block the backward is ONE kernel beside the forward
    (``ops/attention.py:_fa_bwd_fused``): at the GPT cells' shape and at the two
    largest one-block shapes, where its VMEM is what Mosaic could refuse —
    plain, with ``kv_lens`` and the ``dlse`` operand, and with dropout."""
    A = flash_compiled
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    BH, x = 8, shape((8, S, D), jnp.bfloat16)
    assert A._tile_plan(S, S, D, True).one_pass

    def both(q, k, v, lens, seed, do, dlse):
        if variant == "lens_dlse":
            (o, lse), pull = jax.vjp(
                lambda *a: A._flash3_lse(*a, lens, True, 0.125), q, k, v)
            return o, pull((do, dlse))
        rate = 0.1 if variant == "dropout" else 0.0
        o, pull = jax.vjp(lambda *a: A._flash3(*a, None, seed, True, 0.125, rate), q, k, v)
        return o, pull(do)

    text = jax.jit(both).lower(
        x, x, x, shape((BH,), jnp.float32), shape((1,), jnp.int32), x,
        shape((BH, S), jnp.float32)).compile().as_text()
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("BH,S,Dk,Dv,calls", (
    (32, 8192, 192, 128, 2),                    # the Kanana cell: 8 x 8 blocks of 1,024 a head
    (8, 1024, 192, 128, 2),                     # a head that is one block: the fused backward
    (8, 2048, 64, 128, 2),                      # narrower keys than values: blocks of 1,024
    (8, 2048, 192, 128, 3),                     # the dq + dkv pair (a head's dq over the budget)
), ids=("kanana_cell", "one_block", "narrow_keys", "two_calls"))
@pytest.mark.parametrize("variant", ("plain", "lens_dlse"))
def test_the_two_width_flash_kernels_compile_for_the_chip(one_chip, flash_compiled, BH, S, Dk, Dv,
                                                          calls, variant, monkeypatch):
    """``q, k`` at ``Dk`` on ``v`` at ``Dv`` (latent attention: 192 on 128, one
    and a half lane tiles of scores): the forward and all three backward plans
    at the cell's call (the dq + dkv pair by taking the VMEM budget of the fused
    call of several blocks away), where 192-wide blocks and a 192-deep product
    are what Mosaic could refuse; results come at their own widths, with no
    ``pad`` in the program."""
    if calls == 3:
        monkeypatch.setattr(flash_compiled, "_HEAD_DQ_BYTES", 0)
    A = flash_compiled
    bf = jnp.bfloat16
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    scale = Dk ** -0.5

    def both(q, k, v, lens, seed, do, dlse):
        if variant == "lens_dlse":
            (o, lse), pull = jax.vjp(lambda *a: A._flash3_lse(*a, lens, True, scale), q, k, v)
            return o, pull((do, dlse))
        o, pull = jax.vjp(lambda *a: A._flash3(*a, None, seed, True, scale, 0.0), q, k, v)
        return o, pull(do)

    args = (shape((BH, S, Dk), bf), shape((BH, S, Dk), bf), shape((BH, S, Dv), bf),
            shape((BH,), jnp.float32), shape((1,), jnp.int32), shape((BH, S, Dv), bf),
            shape((BH, S), jnp.float32))
    text = jax.jit(both).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == calls
    o, (dq, dk, dv) = jax.eval_shape(both, *args)
    assert [t.shape[-1] for t in (o, dq, dk, dv)] == [Dv, Dk, Dk, Dv]
    assert not re.search(r"= [a-z0-9]+\[[\d,]*\]\S* pad\(", text)


# the four 8k cells' causal calls without a window: 36 live blocks of 64 a head, and 136 of 256
CALLS_8K = {
    "kanana_cell": (32, 8192, 192, 128),        # 8 x 8 blocks, 6 MiB of dq a head
    "lfm2_cell": (32, 8192, 64, 64),            # the LFM2 and Nemotron cells
    "mellum_cell": (32, 8192, 128, 128),        # the Mellum cell's full layer
    "qwen_cell": (16, 8192, 256, 256),          # 16 x 16 blocks of 512, 8 MiB of dq
}
at_the_8k_cells_calls = pytest.mark.parametrize(
    "BH,S,Dk,Dv", tuple(CALLS_8K.values()), ids=tuple(CALLS_8K))


# ... and the Mellum cell's three window layers: a band of 2 of 8 blocks of 1,024 (PR 48)
@pytest.mark.parametrize(
    "BH,S,Dk,Dv,W", tuple((*c, None) for c in CALLS_8K.values()) + ((32, 8192, 128, 128, 1024),),
    ids=tuple(CALLS_8K) + ("mellum_band",))
@pytest.mark.parametrize("variant", ("plain", "lens_dlse"))
def test_the_fused_backward_of_several_blocks_compiles_for_the_chip(
        one_chip, flash_compiled, BH, S, Dk, Dv, W, variant):
    """A causal head of several blocks takes ONE backward call that keeps the
    head's float32 dq in VMEM (``ops/attention.py:_fa_bwd_blocks``): at the four
    8k cells' calls and, on the band's grid and under the windowed kernels' own
    name (what ``^%flash_attention_window`` reads on the chip), at the Mellum
    cell's window layers; plain and with ``kv_lens`` + the ``dlse`` operand, under
    the ``vmem_limit_bytes`` the plan computes — more than Mosaic's default, and
    what it could refuse."""
    A = flash_compiled
    bf = jnp.bfloat16
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    scale = Dk ** -0.5
    plan = A._tile_plan(S, S, Dk, True, W, Dv)
    assert A._bwd_of(plan, Dk) is A._fa_bwd_blocks
    limit = A._blocks_vmem_bytes(plan, Dk, Dv, 2)
    assert 16 * 2 ** 20 < limit < 64 * 2 ** 20

    def bwd(q, k, v, do, o, lse, lens, dlse):
        if variant == "plain":
            lens = dlse = None
        return A._fa_bwd_pallas(q, k, v, do, o, lse, dlse, lens, True, scale, False, window=W)

    wide, narrow = shape((BH, S, Dk), bf), shape((BH, S, Dv), bf)
    rows = shape((BH, S, 128), jnp.float32)
    text = jax.jit(bwd).lower(wide, wide, narrow, narrow, narrow, rows,
                              shape((BH,), jnp.float32), rows).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert ("flash_attention_window_dqkv_blocks" in text) == (W is not None)
    assert f'"scoped_memory_configs":[{{"memory_space":"1","offset":"0","size":"{limit}"}}]' in text


@at_the_8k_cells_calls
@pytest.mark.parametrize("variant", ("plain", "kv_lens"))
def test_the_forward_on_its_live_blocks_compiles_for_the_chip(
        one_chip, flash_compiled, BH, S, Dk, Dv, variant):
    """A causal head of several blocks takes a forward whose grid is ``(BH, live
    blocks)``, each step's blocks — the output's among them — named through two
    int32 tables in SMEM (``ops/attention.py:_live_grid``): at the four 8k cells'
    calls, plain and with ``kv_lens`` (a third scalar operand before the tables),
    ONE kernel with no ``pad`` beside it."""
    A = flash_compiled
    bf = jnp.bfloat16
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    plan = A._tile_plan(S, S, Dk, True, None, Dv)
    assert plan.live_axis and len(plan.fwd_steps()) == plan.nq * (plan.nq + 1) // 2

    def fwd(q, k, v, lens):
        return A._fa_fwd_pallas(q, k, v, lens if variant == "kv_lens" else None, True,
                                Dk ** -0.5, False)

    wide, narrow = shape((BH, S, Dk), bf), shape((BH, S, Dv), bf)
    text = jax.jit(fwd).lower(wide, wide, narrow, shape((BH,), jnp.float32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"s32[{len(plan.fwd_steps())}]" in text            # the tables reach the kernel
    assert not re.search(r"= [a-z0-9]+\[[\d,]*\]\S* pad\(", text)


@pytest.mark.parametrize("B,H,S,D,calls", (
    (1, 32, 8192, 128, ("fwd", "dqkv_blocks")),     # the Keye cell: a head is 8 x 8 blocks
    (2, 4, 1024, 128, ("fwd", "dqkv")),             # a head is one block; two batch rows
    (1, 4, 16384, 256, ("fwd", "dq", "dkv")),       # a head too long for the fused kernel's VMEM
), ids=("keye_cell", "one_block", "two_calls"))
def test_the_selected_keys_flash_kernels_compile_for_the_chip(one_chip, flash_compiled,
                                                              B, H, S, D, calls):
    """``flash_attention(selected=)``: the forward and each backward the plan can
    take, with the kept keys' int8 ``(1, bq, bk)`` block as one more operand
    (``ops/attention.py:_sel_spec``) — what the interpreter cannot refuse: an int8
    block Mosaic cannot tile, its widening, the VMEM of the fused backward."""
    A = flash_compiled
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    x = shape((B, H, S, D), jnp.bfloat16)

    def both(q, k, v, sel, do):
        o, pull = jax.vjp(lambda *a: A.flash_attention(
            *a, causal=True, selected=sel, impl="pallas"), q, k, v)
        return o, pull(do)

    text = jax.jit(both).lower(x, x, x, shape((B, S, S), jnp.int8), x).compile().as_text()
    assert text.count("tpu_custom_call") == len(calls)
    for kernel in calls:
        assert f"flash_attention_sparse_{kernel}" in text


@pytest.mark.parametrize("B,S,Hi,d,topk", (
    (1, 8192, 16, 64, 2048),                        # the Keye cell
    (2, 1000, 4, 32, 100),                          # a sequence that is padded
), ids=("keye_cell", "ragged"))
def test_the_index_select_kernel_compiles_for_the_chip(one_chip, monkeypatch, B, S, Hi, d, topk):
    """``ops/indexer.py``: scores, the bisection and the int8 mask in one kernel,
    a ``(256, S)`` int32 scratch in VMEM under its own limit."""
    from beforeholiday_tpu.ops import indexer as X

    monkeypatch.setattr(X, "_interpret_default", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    try:
        compiled = jax.jit(lambda q, k, w: X.index_select(q, k, w, topk=topk, impl="pallas")).lower(
            shape((B, S, Hi, d), jnp.bfloat16), shape((B, S, d), jnp.bfloat16),
            shape((B, S, Hi), jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "index_select" in text
    assert f"s8[{B},{S},{S}]" in text


def test_same_step_reads_two_texts_as_one_program_when_only_source_lines_moved(
        one_chip, flash_compiled, tmp_path):
    """``tools/same_step.py`` on a compiled flash forward + backward: source
    tables with other line numbers are the same program, a kernel's body prints
    without its locations, and a changed operand is not the same program."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import same_step

    A = flash_compiled
    x = jax.ShapeDtypeStruct((2, 256, 64), jnp.bfloat16, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)

    def both(q, k, v, seed, do):
        o, pull = jax.vjp(lambda *a: A._flash3(*a, None, seed, True, 0.125, 0.0), q, k, v)
        return o, pull(do)

    text = jax.jit(both).lower(x, x, x, seed, x).compile().as_text()
    assert all(f"\n{table}\n" in text for table in same_step._TABLES)
    assert text.count('"body":"') == 2 and "stack_frame_id=" in text
    moved = re.sub(r"(function_name_id=\d+ line=)(\d+)", lambda m: m[1] + str(int(m[2]) + 7), text)
    other = text.replace("bf16[2,256,64]", "bf16[2,256,65]", 1)
    assert moved != text and other != text
    for name, content in (("a", text), ("b", moved), ("c", other)):
        (tmp_path / name).write_text(content)
    assert same_step.differing(tmp_path / "a", tmp_path / "b") == []
    assert len(same_step.differing(tmp_path / "a", tmp_path / "c")) == 1
    kernel = same_step._kernel_text(same_step._BODY.search(text).group(1))
    assert "func.func" in kernel and "loc(" not in kernel


def test_same_step_reads_a_moved_function_as_the_same_program_and_a_changed_op_as_another(
        one_chip, tmp_path):
    """A body moved into a shared function compiles to the parent's ops under one
    more call frame: every source table grows a row and the ops' frame ids
    shift. ``same_step`` reads the two as one program; with one op changed
    (``sin`` for ``cos``) beside the same move, it does not."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import same_step

    x = jax.ShapeDtypeStruct((256, 128), jnp.float32, sharding=one_chip)

    def shared(op, a):
        return op(a) * 2.0

    inline = lambda a: jnp.sum(jnp.cos(a) * 2.0)
    called = lambda a: jnp.sum(shared(jnp.cos, a))
    changed = lambda a: jnp.sum(shared(jnp.sin, a))
    for name, fn in (("inline", inline), ("called", called), ("changed", changed)):
        (tmp_path / name).write_text(jax.jit(fn).lower(x).compile().as_text().replace(
            "jit__lambda_", "jit_fn"))
    def table_rows(name):
        text = (tmp_path / name).read_text()
        return len(text.splitlines()) - len(same_step.program_lines(text))

    assert table_rows("called") > table_rows("inline") > 0         # the added frame's rows
    assert same_step.differing(tmp_path / "inline", tmp_path / "called") == []
    assert same_step.differing(tmp_path / "inline", tmp_path / "changed")
    assert same_step.differing(tmp_path / "called", tmp_path / "changed")
