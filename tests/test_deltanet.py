"""``ops/deltanet.py``: the two fused passes of a gated-DeltaNet layer against
the ``jnp`` chain they replace, the kernels under the Pallas interpreter.

Float32 operands: the kernels compute the chain's mathematics in float32 and
the chain rounds nothing then, so the two differ by reassociation only
(``_TOL`` of each tensor's largest entry; measured 1e-7 on the outputs, 4e-7
on the gradients the grid accumulates). The shapes put more than one row tile
in a sequence, so the rows a tile takes from its neighbour (the three before
it forward, the three after it backward) are real."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.guard import dispatch
from beforeholiday_tpu.ops import deltanet as dn

_TOL = 2e-6
_K = 4

# (B, S, H_k, H_v): S = 192 is three tiles of 64 rows, 96 three of 32, 128 one tile
_SHAPES = ((2, 192, 2, 4), (1, 192, 2, 2), (1, 96, 1, 2), (2, 128, 1, 1))
_IDS = ("two_value_heads_a_key_head", "one_value_head_a_key_head", "tiles_of_32_rows",
        "one_tile_a_sequence")


def _heads(Hk, Hv, d=128):
    return dict(key_heads=Hk, value_heads=Hv, d_k=d, d_v=d)


def _qkv_inputs(B, S, Hk, Hv, dtype=jnp.float32, seed=0):
    C = 2 * Hk * 128 + Hv * 128
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    cols = jax.random.normal(ks[0], (B, S, C)).astype(dtype)
    filt = jax.random.uniform(ks[1], (C, _K), jnp.float32, -0.5, 0.5)
    cts = tuple(jax.random.normal(k, (B, Hv, S, 128)).astype(dtype) for k in ks[2:])
    return cols, filt, cts


def _gate_inputs(B, S, H, dtype=jnp.float32, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    o = jax.random.normal(ks[0], (B, H, S, 128)).astype(dtype)
    z = jax.random.normal(ks[1], (B, S, H * 128)).astype(dtype)
    w = 1.0 + 0.1 * jax.random.normal(ks[2], (128,))
    return o, z, w, jax.random.normal(ks[3], (B, S, H * 128)).astype(dtype)


def _close(got, want, what, tol=_TOL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, what
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want)), what


def _qkv(impl, cols, filt, cts, **heads):
    out, pull = jax.vjp(lambda c, f: dn.deltanet_qkv(c, f, impl=impl, **heads), cols, filt)
    return dict(zip(("q", "k", "v", "dcols", "dfilt"), out + pull(cts)))


def _gate(impl, o, z, w, dy):
    y, pull = jax.vjp(lambda o, z, w: dn.deltanet_gate(o, z, w, eps=1e-6, impl=impl), o, z, w)
    return dict(zip(("y", "do", "dz", "dw"), (y,) + pull(dy)))


_RESULTS = {}


def _both(kind, shape):
    if (kind, shape) not in _RESULTS:
        B, S, Hk, Hv = shape
        if kind == "qkv":
            args = _qkv_inputs(B, S, Hk, Hv)
            run = lambda impl: _qkv(impl, *args, **_heads(Hk, Hv))
        else:
            args = _gate_inputs(B, S, Hv)
            run = lambda impl: _gate(impl, *args)
        _RESULTS[kind, shape] = run("pallas"), run("jnp")
    return _RESULTS[kind, shape]


@pytest.mark.parametrize("what", ("q", "k", "v", "dcols", "dfilt"))
@pytest.mark.parametrize("shape", _SHAPES, ids=_IDS)
def test_the_qkv_kernels_match_the_chain(shape, what):
    """Forward: convolution, SiLU, both norms, the repetition, heads first.
    Backward: the columns' cotangent (``dcols``: the group's sum, the norms',
    SiLU's and the convolution's transposes, the rows after a tile from the
    tile behind it in the grid) and the filter's gradient, which the grid
    accumulates over row tiles and sequences."""
    got, want = _both("qkv", shape)
    _close(got[what], want[what], what)


@pytest.mark.parametrize("what", ("y", "do", "dz", "dw"))
@pytest.mark.parametrize("shape", _SHAPES, ids=_IDS)
def test_the_gate_kernels_match_the_chain(shape, what):
    """``rms_norm(o) * w * silu(z)`` and its three cotangents; ``dw`` is summed
    over every tile, head and sequence of the grid."""
    got, want = _both("gate", shape)
    _close(got[what], want[what], what)


@pytest.mark.parametrize("impl", ("pallas", "jnp"))
def test_the_qkv_outputs_are_heads_first_and_each_key_head_serves_its_value_heads(impl):
    B, S, Hk, Hv = 1, 96, 2, 4
    cols, filt, _ = _qkv_inputs(B, S, Hk, Hv)
    q, k, v = dn.deltanet_qkv(cols, filt, impl=impl, **_heads(Hk, Hv))
    assert q.shape == k.shape == v.shape == (B, Hv, S, 128)
    for t in (q, k):        # value heads 2h and 2h + 1 read key head h
        np.testing.assert_array_equal(t[:, 0::2], t[:, 1::2])
    assert not np.array_equal(v[:, 0], v[:, 1])
    np.testing.assert_allclose(jnp.linalg.norm(k, axis=-1), 1.0, rtol=1e-4)
    np.testing.assert_allclose(jnp.linalg.norm(q, axis=-1), 128 ** -0.5, rtol=1e-4)


@pytest.mark.parametrize("t", (5, 64, 67, 130))
def test_the_convolution_is_causal_across_tiles(t):
    """Changing row ``t`` leaves every output row before it bit-equal (``t`` inside
    a tile, on a tile's first row, just behind it), and reaches rows ``t .. t +
    3`` and no further."""
    B, S, Hk, Hv = 1, 192, 1, 2
    cols, filt, _ = _qkv_inputs(B, S, Hk, Hv)
    run = lambda c: dn.deltanet_qkv(c, filt, impl="pallas", **_heads(Hk, Hv))
    base, moved = run(cols), run(cols.at[:, t].add(1.0))
    for a, b in zip(base, moved):
        np.testing.assert_array_equal(a[:, :, :t], b[:, :, :t])
        np.testing.assert_array_equal(a[:, :, t + _K:], b[:, :, t + _K:])
        assert not np.array_equal(a[:, :, t + _K - 1], b[:, :, t + _K - 1])


def test_the_backward_pass_is_anti_causal_across_tiles():
    """The cotangent of row ``t`` reads the output cotangents of rows ``t .. t +
    3`` alone: past a tile's edge they come from the tile the grid handled before."""
    B, S, Hk, Hv = 1, 192, 1, 2
    cols, filt, cts = _qkv_inputs(B, S, Hk, Hv)
    _, pull = jax.vjp(lambda c: dn.deltanet_qkv(c, filt, impl="pallas", **_heads(Hk, Hv)), cols)
    t = 64                  # the first row of the second tile
    bump = lambda ct: ct.at[:, :, t].add(1.0)
    (base,), (moved,) = pull(cts), pull(tuple(bump(c) for c in cts))
    np.testing.assert_array_equal(base[:, t + 1:], moved[:, t + 1:])
    np.testing.assert_array_equal(base[:, :t - _K + 1], moved[:, :t - _K + 1])
    assert not np.array_equal(base[:, t - _K + 1], moved[:, t - _K + 1])


def test_sequences_of_a_batch_do_not_see_each_other():
    B, S, Hk, Hv = 2, 96, 1, 1
    cols, filt, cts = _qkv_inputs(B, S, Hk, Hv)
    both = _qkv("pallas", cols, filt, cts, **_heads(Hk, Hv))
    alone = _qkv("pallas", cols[1:], filt, tuple(c[1:] for c in cts), **_heads(Hk, Hv))
    for name in ("q", "k", "v", "dcols"):
        np.testing.assert_array_equal(both[name][1:], alone[name])


def test_the_kernels_round_once_where_the_chain_rounds_thrice():
    """bfloat16 operands: everything between a kernel's read and its write is
    float32, so its output is the float32 chain's, rounded once (within one
    bfloat16 step of it: 2^-8 relative); the chain in bfloat16 rounds the
    convolution's output and SiLU's on the way and lies further off."""
    B, S, Hk, Hv = 1, 128, 1, 2
    bf = jnp.bfloat16
    cols, filt, cts = _qkv_inputs(B, S, Hk, Hv, bf)
    heads = _heads(Hk, Hv)
    exact = dn.deltanet_qkv(cols.astype(jnp.float32), filt, impl="jnp", **heads)
    kernel = dn.deltanet_qkv(cols, filt, impl="pallas", **heads)
    chain = dn.deltanet_qkv(cols, filt, impl="jnp", **heads)
    for got, rounded, want in zip(kernel, chain, exact):
        assert got.dtype == bf
        err = lambda t: np.abs(np.asarray(t, np.float32) - np.asarray(want))
        floor = 2.0 ** -8 * np.abs(np.asarray(want)) + 1e-6
        assert np.all(err(got) <= floor)
        assert np.mean(err(got)) < np.mean(err(rounded))
    o, z, w, dy = _gate_inputs(B, S, Hv, bf)
    exact = dn.deltanet_gate(o.astype(jnp.float32), z.astype(jnp.float32), w, eps=1e-6, impl="jnp")
    got = dn.deltanet_gate(o, z, w, eps=1e-6, impl="pallas")
    assert got.dtype == bf
    err = np.abs(np.asarray(got, np.float32) - np.asarray(exact))
    assert np.all(err <= 2.0 ** -8 * np.abs(np.asarray(exact)) + 1e-6)


def test_bfloat16_cotangents_stay_close_to_the_chains():
    B, S, Hk, Hv = 1, 128, 1, 2
    bf = jnp.bfloat16
    args = _qkv_inputs(B, S, Hk, Hv, bf)
    got, want = (_qkv(impl, *args, **_heads(Hk, Hv)) for impl in ("pallas", "jnp"))
    for name in ("dcols", "dfilt"):
        _close(got[name], want[name], name, tol=2e-2)
    args = _gate_inputs(B, S, Hv, bf)
    got, want = (_gate(impl, *args) for impl in ("pallas", "jnp"))
    for name in ("do", "dz", "dw"):
        _close(got[name], want[name], name, tol=2e-2)


def test_by_key_head_puts_a_key_heads_columns_side_by_side():
    Hk, Hv, dk, dv = 2, 4, 3, 5
    n = 2 * Hk * dk + Hv * dv
    t = jnp.arange(2 * n).reshape(2, n)
    got = dn.by_key_head(t, key_heads=Hk, value_heads=Hv, d_k=dk, d_v=dv)
    q, k, v = t[:, :Hk * dk], t[:, Hk * dk:2 * Hk * dk], t[:, 2 * Hk * dk:]
    want = jnp.concatenate([
        jnp.concatenate([q[:, h * dk:(h + 1) * dk], k[:, h * dk:(h + 1) * dk],
                         v[:, h * 2 * dv:(h + 1) * 2 * dv]], axis=1) for h in range(Hk)], axis=1)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        dn.by_key_head(t.T, key_heads=Hk, value_heads=Hv, d_k=dk, d_v=dv, axis=0), want.T)
    with pytest.raises(ValueError, match="by_key_head"):
        dn.by_key_head(t[:, 1:], key_heads=Hk, value_heads=Hv, d_k=dk, d_v=dv)


@pytest.mark.parametrize("S,dk,dv,K,ok", (
    (8192, 128, 128, 4, True), (48, 256, 128, 8, True), (40, 128, 128, 4, False),
    (64, 64, 128, 4, False), (64, 128, 192, 4, False), (64, 128, 128, 9, False),
))
def test_the_shape_gate(S, dk, dv, K, ok):
    assert dn.is_kernel_available(S, dk, dv, K) is ok


def _counted():
    return {k[0]: v for k, v in dispatch.dispatch_counters().items()}


def test_dispatch_is_guarded_and_counted(monkeypatch):
    dispatch.reset_dispatch_counters()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # resolve_impl -> pallas
    monkeypatch.setattr(dn, "_interpret_default", lambda: True)
    cols, filt, _ = _qkv_inputs(1, 48, 1, 2)
    q, _, v = dn.deltanet_qkv(cols, filt, **_heads(1, 2))
    dn.deltanet_gate(v, cols[..., :256], jnp.ones((128,)), eps=1e-6)
    counted = _counted()
    for op in ("deltanet_qkv", "deltanet_gate"):
        assert counted[op]["pallas"] == 1 and counted[op]["jnp"] == 0, op


def test_mismatched_shapes_are_refused():
    cols, filt, _ = _qkv_inputs(1, 48, 1, 2)
    with pytest.raises(ValueError, match="shapes mismatch"):
        dn.deltanet_qkv(cols, filt[:-1], **_heads(1, 2))
    with pytest.raises(ValueError, match="shapes mismatch"):
        dn.deltanet_qkv(cols, filt, **_heads(1, 3))
    o, z, w, _ = _gate_inputs(1, 48, 2)
    with pytest.raises(ValueError, match="shapes mismatch"):
        dn.deltanet_gate(o, z[..., :128], w, eps=1e-6)


def test_the_backward_kernels_keep_only_their_inputs():
    """Residuals are the kernels' operands: nothing float32, nothing computed."""
    cols, filt, _ = _qkv_inputs(1, 48, 1, 2)
    filt8 = dn._filter_rows(filt)
    p = dn._Plan(1, 2, 128, 128, _K, 16)
    _, res = dn._qkv_pallas_fwd(cols, filt8, p)
    assert len(res) == 2 and res[0] is cols and res[1] is filt8
    o, z, w, _ = _gate_inputs(1, 48, 2)
    w = w.reshape(1, 128)
    _, res = dn._gate_pallas_fwd(o, z, w, 2, 16, 1e-6)
    assert len(res) == 3 and all(r is a for r, a in zip(res, (o, z, w)))


def test_the_kernels_are_named_for_the_trace_and_not_after_the_delta_rule():
    """``gated_delta_ms`` reads every op whose scope path holds ``gated_delta``,
    ``gated_delta_roofline`` every kernel named ``gated_delta*``, ``layer_norm_ms``
    the scope ``layer_norm``: these four kernels are none of them."""
    from jax._src import core

    # the lowered text below holds the call stack of whoever traced the jitted
    # kernel calls first: not a model's, if another file ran in this process
    jax.clear_caches()

    def kernels(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in core.jaxprs_in_params(eqn.params):
                yield from kernels(sub)

    cols, filt, cts = _qkv_inputs(1, 48, 1, 2)
    o, z, w, dy = _gate_inputs(1, 48, 2)

    def both(cols, filt, o, z, w):
        q, k, v = dn.deltanet_qkv(cols, filt, impl="pallas", **_heads(1, 2))
        y = dn.deltanet_gate(o, z, w, eps=1e-6, impl="pallas")
        return sum(jnp.sum(a * b) for a, b in zip((q, k, v), cts)) + jnp.sum(y * dy)

    grad = jax.grad(both, argnums=range(5))
    names = set(kernels(jax.make_jaxpr(grad)(cols, filt, o, z, w).jaxpr))
    assert names == {"deltanet_qkv_fwd", "deltanet_qkv_bwd", "deltanet_gate_fwd",
                     "deltanet_gate_bwd"}
    text = jax.jit(grad).lower(cols, filt, o, z, w).as_text(debug_info=True)
    for span in ("deltanet_qkv", "deltanet_gate"):
        assert span in text
    assert "gated_delta" not in text and "layer_norm" not in text


# -- the gate's activation (PR 49): the sigmoid of a Kimi Delta Attention layer --------

@pytest.mark.parametrize("what", ("y", "do", "dz", "dw"))
@pytest.mark.parametrize("impl", ("pallas", "jnp"))
def test_the_sigmoid_gate_is_written_out(impl, what):
    """``activation="sigmoid"``: ``rms_norm(o) * w * sigmoid(z)`` and its three
    cotangents against the expression written out and differentiated by XLA, in
    the kernels and in the chain; the default stays SiLU."""
    o, z, w, dy = _gate_inputs(2, 96, 4, seed=11)

    def written_out(o, z, w):
        x = jnp.moveaxis(o, 1, 2)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
        return (x * jax.nn.sigmoid(z.reshape(x.shape))).reshape(z.shape)

    y, pull = jax.vjp(written_out, o, z, w)
    want = dict(zip(("y", "do", "dz", "dw"), (y,) + pull(dy)))
    y, pull = jax.vjp(lambda o, z, w: dn.deltanet_gate(o, z, w, eps=1e-6, activation="sigmoid",
                                                       impl=impl), o, z, w)
    got = dict(zip(("y", "do", "dz", "dw"), (y,) + pull(dy)))
    _close(got[what], want[what], what)
    silu = _gate(impl, o, z, w, dy)
    assert np.max(np.abs(np.asarray(silu[what]) - np.asarray(want[what]))) \
        > 1e-2 * np.max(np.abs(np.asarray(want[what]))), what


def test_an_activation_that_is_not_built_raises():
    o, z, w, _ = _gate_inputs(1, 32, 2)
    with pytest.raises(ValueError, match="silu.*sigmoid"):
        dn.deltanet_gate(o, z, w, eps=1e-6, activation="tanh")
