"""The gated delta rule (``ops/gated_delta.py``) against the recurrence itself.

The op is chunk-wise (the WY factors in ``jax.numpy`` or in two Pallas kernels,
the chunk scan as a Pallas kernel with a hand-written backward, or as a
``lax.scan``); the oracle here is the token-by-token recurrence, written from
the equations and differentiated by ``jax.grad``. Float32 at ``highest`` matmul
precision, so the tolerance is that of reassociation: 2e-5 of each tensor's
largest value (the chunk-wise form adds its products in another order; measured
2.5e-6). The WY kernels are held to ``wy_prepare`` itself, output by output and
cotangent by cotangent (``_WY_TOL``)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.ops import gated_delta as gd

_TOL = 2e-5
_NAMES = ("q", "k", "v", "g", "beta")


def recurrence(q, k, v, g, beta):
    B, S, H, dk = q.shape

    def token(state, xs):
        q, k, v, g, b = xs
        decayed = jnp.exp(g)[..., None, None] * state
        predicted = jnp.einsum("bhkv,bhk->bhv", decayed, k)
        state = decayed + jnp.einsum("bhk,bhv->bhkv", k, b[..., None] * (v - predicted))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((B, H, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def inputs(seed, B, S, H, dk, dv, decay=1.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -jax.random.uniform(ks[3], (B, S, H)) * decay
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, S, H, dv))


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(got, want, what):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= _TOL * scale, what


# (S, chunk): whole chunks, one chunk, a tail that is not a whole chunk, S < chunk
_SHAPES = ((256, 128), (128, 128), (320, 128), (72, 128), (192, 64))


@pytest.mark.parametrize("impl", ("pallas", "jnp"))
@pytest.mark.parametrize("S,chunk", _SHAPES)
def test_forward_matches_the_recurrence(impl, S, chunk):
    args, _ = inputs(S, 2, S, 3, 128, 128)
    got = jax.jit(lambda *a: gd.gated_delta_rule(*a, chunk=chunk, impl=impl))(*args)
    _close(got, recurrence(*args), f"o, S={S}")


@pytest.mark.parametrize("impl", ("pallas", "jnp"))
@pytest.mark.parametrize("S,chunk", _SHAPES[::2])
def test_gradients_match_the_recurrence(impl, S, chunk):
    args, ct = inputs(7 + S, 1, S, 2, 128, 128)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * ct), argnums=range(5))(*args)
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(gd.gated_delta_rule(*a, chunk=chunk, impl=impl) * ct),
        argnums=range(5)))(*args)
    for name, a, b in zip(_NAMES, got, want):
        _close(a, b, f"d{name}, S={S}")


@pytest.mark.parametrize("impl", ("pallas", "jnp"))
@pytest.mark.parametrize("S,chunk", ((256, 128), (72, 64)), ids=("whole_chunks", "padded_tail"))
def test_the_heads_first_entry_is_the_public_one_without_its_two_re_layouts(impl, S, chunk):
    """``heads_first=True`` takes ``(B, H, S, d)`` and ``(B, H, S)`` and returns
    ``(B, H, S, d_v)``: what ``ops.deltanet.deltanet_qkv`` writes and
    ``deltanet_gate`` reads. Outputs and cotangents are bit-equal to the public
    layout's: the same kernels on the same operands."""
    args, ct = inputs(S, 2, S, 3, 128, 128)
    first = lambda t: jnp.moveaxis(t, 2, 1)

    def run(heads_first, args, ct):
        o, pull = jax.vjp(lambda *a: gd.gated_delta_rule(
            *a, chunk=chunk, impl=impl, heads_first=heads_first), *args)
        return (o,) + pull(ct)

    want = run(False, args, ct)
    got = run(True, tuple(first(t) for t in args), first(ct))
    assert got[0].shape == (2, 3, S, 128)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, first(w))
    with pytest.raises(ValueError, match="shapes mismatch"):
        gd.gated_delta_rule(first(args[0]), *args[1:], heads_first=True)


def test_a_strong_decay_neither_overflows_nor_loses_the_answer():
    # log decay down to -20 a token: exp(-20 * 128) underflows to 0, and no
    # quotient of decays may be formed on the way
    args, ct = inputs(3, 1, 256, 2, 128, 128, decay=20.0)
    got = jax.jit(lambda *a: gd.gated_delta_rule(*a, impl="pallas"))(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    _close(got, recurrence(*args), "o under strong decay")
    grads = jax.grad(lambda *a: jnp.sum(gd.gated_delta_rule(*a, impl="pallas") * ct),
                     argnums=range(5))(*args)
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in grads)


@pytest.mark.parametrize("dk,dv,chunk", ((64, 128, 128), (128, 96, 128), (128, 128, 32)))
def test_the_shape_gate(dk, dv, chunk):
    """Tiles that are not MXU-shaped: a forced kernel is refused, the default
    falls back to the scan and is still right."""
    assert not gd.is_kernel_available(chunk, dk, dv)
    args, _ = inputs(11, 1, 2 * chunk, 2, dk, dv)
    with pytest.raises(ValueError, match="impl='pallas' forced"):
        gd.gated_delta_rule(*args, chunk=chunk, impl="pallas")
    _close(gd.gated_delta_rule(*args, chunk=chunk), recurrence(*args), "fallback")


def test_mismatched_shapes_are_refused():
    (q, k, v, g, beta), _ = inputs(1, 1, 128, 2, 128, 128)
    with pytest.raises(ValueError, match="shapes mismatch"):
        gd.gated_delta_rule(q, k[:, :64], v, g, beta)


def test_bfloat16_operands_stay_close_and_keep_their_dtype():
    # bf16 operands, float32 state and accumulation: 2^-8 relative roundings of
    # operands that enter sums of 128..256 terms; 2% of the largest value holds
    args, _ = inputs(5, 1, 256, 2, 128, 128)
    q, k, v = (t.astype(jnp.bfloat16) for t in args[:3])
    got = gd.gated_delta_rule(q, k, v, *args[3:], impl="pallas")
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(t.astype(jnp.float32) for t in (q, k, v)), *args[3:])
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) <= 0.02 * float(
        jnp.max(jnp.abs(want)))


def test_the_backward_sweep_recomputes_the_states():
    """The chunk-start states are an output of the forward kernel that the
    custom_vjp does not keep: its residuals are its six operands and no more."""
    args, _ = inputs(2, 1, 256, 2, 128, 128)
    operands = gd.wy_prepare(*(jnp.moveaxis(t, 2, 1).reshape(2, 2, 128, *t.shape[3:])
                               for t in args))
    _, res = gd._scan_pallas_fwd(*operands)
    assert len(res) == len(operands) and all(r is o for r, o in zip(res, operands))


def test_dispatch_is_guarded_and_counted(monkeypatch):
    from beforeholiday_tpu.guard import dispatch

    dispatch.reset_dispatch_counters()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # resolve_impl -> pallas
    monkeypatch.setattr(gd, "_interpret_default", lambda: True)
    args, _ = inputs(4, 1, 128, 1, 128, 128)
    gd.gated_delta_rule(*args)
    counted = {k[0]: v for k, v in dispatch.dispatch_counters().items()}
    assert counted["gated_delta_rule"]["pallas"] == 1 and counted["gated_delta_rule"]["jnp"] == 0


def test_the_kernels_lie_under_their_scope():
    args, _ = inputs(4, 1, 128, 1, 128, 128)
    hlo = jax.jit(lambda *a: gd.gated_delta_rule(*a, impl="jnp")).lower(*args).compile().as_text()
    assert "gated_delta/gated_delta_scan" in hlo


@pytest.mark.parametrize("C", (8, 16, 32, 128))
def test_unit_lower_inverse(C):
    key = jax.random.PRNGKey(C)
    L = jnp.tril(jax.random.normal(key, (3, 2, C, C)) * 0.3, -1)
    T = gd.unit_lower_inverse(L)
    eye = jnp.eye(C)
    np.testing.assert_allclose(jnp.matmul(eye + L, T), jnp.broadcast_to(eye, T.shape),
                               atol=1e-6 * max(1.0, float(jnp.max(jnp.abs(T)))))
    assert float(jnp.max(jnp.abs(jnp.triu(T, 1)))) == 0.0
    ct = jax.random.normal(jax.random.fold_in(key, 1), T.shape)
    got = jax.grad(lambda L: jnp.sum(gd.unit_lower_inverse(L) * ct))(L)
    want = jax.grad(lambda L: jnp.sum(jnp.linalg.inv(eye + jnp.tril(L, -1)) * ct))(L)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4 * float(jnp.max(jnp.abs(want))))


def test_unit_lower_inverse_with_repeated_keys():
    """Every entry of L at one (the same key at every position, beta = 1, no
    decay): the plain Neumann series would cancel terms of C(127, 63)."""
    C = 128
    L = jnp.tril(jnp.ones((C, C)), -1)
    T = gd.unit_lower_inverse(L)
    want = jnp.eye(C) - jnp.eye(C, k=-1)        # (I + L)^-1 of the all-ones L
    np.testing.assert_allclose(T, want, atol=1e-3)


def test_a_chunk_that_is_not_a_power_of_two_is_refused():
    args, _ = inputs(1, 1, 96, 1, 128, 128)
    with pytest.raises(ValueError, match="power of two"):
        gd.gated_delta_rule(*args, chunk=96)


# ---------------------------------------------------------------------------------
# the chunk-local algebra in its two kernels (the interpreter here) against wy_prepare
# ---------------------------------------------------------------------------------

# the kernels write Precision.HIGH by hand, three bfloat16 passes a product (2^-16
# of its operands), where wy_prepare on the CPU multiplies in full float32: a
# chain of six such products in the inverse and one in the solve
_WY_TOL = 1e-4
_WY_SHAPES = ((128, 128, 128), (64, 128, 128))          # (C, d_k, d_v)
_WY_OUT = ("w", "u", "qg", "kd", "p", "gl")


def chunked(seed, BH, N, C, dk, dv, decay):
    """``inputs`` as the chunk scan takes them: (BH, N, C, .)."""
    args, _ = inputs(seed, BH, N * C, 1, dk, dv, decay)
    return tuple(t[:, :, 0].reshape(BH, N, C, *t.shape[3:]) for t in args)


@functools.lru_cache(maxsize=None)
def wy_both(C, dk, dv, decay=0.05):
    """Outputs and input cotangents (on random output cotangents, ``gl``'s
    too) of the kernels and of ``wy_prepare``."""
    args = chunked(C, 2, 3, C, dk, dv, decay)
    with jax.default_matmul_precision("highest"):
        want, pull_want = jax.vjp(gd.wy_prepare, *args)
        got, pull_got = jax.vjp(jax.jit(gd._wy_pallas), *args)
        keys = jax.random.split(jax.random.PRNGKey(C), len(want))
        cts = tuple(jax.random.normal(kk, t.shape) for kk, t in zip(keys, want))
        return (dict(zip(_WY_OUT, zip(got, want))),
                dict(zip(_NAMES, zip(pull_got(cts), pull_want(cts)))))


def _wy_close(got, want, what):
    assert got.shape == want.shape and got.dtype == want.dtype, what
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= _WY_TOL * scale, what


@pytest.mark.parametrize("name", _WY_OUT)
@pytest.mark.parametrize("C,dk,dv", _WY_SHAPES)
def test_wy_kernel_outputs_match_wy_prepare(C, dk, dv, name):
    _wy_close(*wy_both(C, dk, dv)[0][name], f"{name}, C={C}")


@pytest.mark.parametrize("name", _NAMES)
@pytest.mark.parametrize("C,dk,dv", _WY_SHAPES)
def test_wy_kernel_cotangents_match_the_transpose_of_wy_prepare(C, dk, dv, name):
    _wy_close(*wy_both(C, dk, dv)[1][name], f"d{name}, C={C}")


def test_wy_kernels_under_a_strong_decay():
    # log decay down to -20 a token: the kernel may only exponentiate differences <= 0
    out, grads = wy_both(128, 128, 128, decay=20.0)
    for name, (got, want) in {**out, **grads}.items():
        assert bool(jnp.all(jnp.isfinite(got))), name
        _wy_close(got, want, f"{name} under strong decay")


def test_wy_kernels_with_repeated_keys():
    """The same key at every position, beta = 1, no decay: L is all ones below
    the diagonal, (I + L)^-1 = I - shift, so u_i = v_i - v_(i-1) and w_i = 0
    past the first row; a plain Neumann series would cancel terms of C(127, 63)."""
    C, d = 128, 128
    key = jnp.zeros((d,)).at[0].set(1.0)
    k = jnp.broadcast_to(key, (1, 2, C, d))
    v = jax.random.normal(jax.random.PRNGKey(0), (1, 2, C, d))
    g, beta = jnp.zeros((1, 2, C)), jnp.ones((1, 2, C))
    w, u, *_ = gd._wy_pallas(k, k, v, g, beta)
    np.testing.assert_allclose(u, v - jnp.pad(v, ((0, 0), (0, 0), (1, 0), (0, 0)))[:, :, :-1],
                               atol=1e-3 * float(jnp.max(jnp.abs(v))))
    np.testing.assert_allclose(w, k.at[:, :, 1:].set(0.0), atol=1e-3)


def test_the_wy_backward_kernel_recomputes_the_inverse():
    """The custom_vjp keeps the chunked operands (the per-row scalars as one
    (8, C) tile a chunk) and no (C, C) float32 tensor."""
    q, k, v, g, beta = (t.reshape(-1, *t.shape[2:]) for t in chunked(2, 1, 2, 128, 128, 128, 1.5))
    rows, _ = gd._wy_rows(g, beta)
    _, res = gd._wy_kernels_fwd(q, k, v, rows)
    assert len(res) == 4 and all(r is o for r, o in zip(res, (q, k, v, rows)))


def test_the_wy_kernels_lie_under_the_scope_and_are_not_named_after_it():
    """``gated_delta_ms`` reads the scope path, ``gated_delta_roofline`` the
    kernels named ``gated_delta*``: the scan's two, not these."""
    from jax._src import core

    def kernels(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in core.jaxprs_in_params(eqn.params):
                yield from kernels(sub)

    args, ct = inputs(4, 1, 128, 1, 128, 128)
    grad = jax.grad(lambda *a: jnp.sum(gd.gated_delta_rule(*a, impl="pallas") * ct),
                    argnums=range(5))
    names = set(kernels(jax.make_jaxpr(grad)(*args).jaxpr))
    assert names == {"wy_prepare_fwd", "wy_prepare_bwd", "gated_delta_fwd", "gated_delta_bwd"}
    text = jax.jit(grad).lower(*args).as_text(debug_info=True)
    for name in ("wy_prepare_fwd", "wy_prepare_bwd"):
        assert re.search(r"gated_delta\)*/" + name, text), name
        assert not re.search(r"gated_delta_scan\)*/" + name, text), name
