"""``ops/kda.py`` — the delta rule under a decay a key channel — against the
recurrence itself, one token at a time (``lax.scan``; the oracle, never a path).

Float32 operands on the CPU: the chunked forms differ from the scan by float32
reassociation and by the three-bfloat16-pass products of the triangular solve
(~1e-6), so outputs and every gradient — ``dg`` and ``dbeta`` too — are held to
1e-4 of the scan's largest entry. The Pallas kernels run in the interpreter at
head dims of 128; what Mosaic makes of them is ``tests/test_chip_compile.py``'s
and ``testing/tpu_checks.py`` ``check_kda``'s to hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.ops import gated_delta as gd, kda

NAMES = ("q", "k", "v", "g", "beta")


def token_scan(q, k, v, g, beta):
    """``S~ = Diag(e^g_t) S; S = S~ + k (beta (v - S~^T k))^T; o = S^T q`` on
    ``(B, S, H, d)`` operands, float32 at ``highest``."""
    B, S, H, dk = q.shape

    def token(state, xs):
        q, k, v, g, b = xs
        decayed = jnp.exp(g)[..., None] * state
        predicted = jnp.einsum("bhkv,bhk->bhv", decayed, k, precision="highest")
        state = decayed + k[..., None] * (b[..., None] * (v - predicted))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q, precision="highest")

    xs = [jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)]
    _, o = jax.lax.scan(token, jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


def operands(S, H, dk, dv, seed=0, gate="drawn"):
    """``q, k`` normalised as the layer hands them over; ``gate``: ``drawn`` (a
    decay a channel, -softplus of a normal draw times a rate a head in 1 .. 16),
    ``strongest`` (every channel at -16 softplus(4) = -64.3 a token: what the
    parameters allow at most) or a float (that log-decay on every channel)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, S, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (1, S, H, dk)))
    v = jax.random.normal(ks[2], (1, S, H, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (1, S, H)))
    if gate == "drawn":
        rate = jax.random.uniform(ks[4], (1, 1, H, 1), jnp.float32, 1.0, 16.0)
        g = -rate * jax.nn.softplus(jax.random.normal(ks[5], (1, S, H, dk)) - 2.0)
    else:
        g = jnp.full((1, S, H, dk), -16.0 * float(jax.nn.softplus(4.0))
                     if gate == "strongest" else gate, jnp.float32)
    return q, k, v, g, beta


def both(fn, args, seed=9):
    """``(o, (dq, dk, dv, dg, dbeta))`` of ``fn`` under one seeded cotangent."""
    ct = jax.random.normal(jax.random.PRNGKey(seed), args[2].shape)
    o, pull = jax.vjp(fn, *args)
    return o, pull(ct)


def close(got, want, tol=1e-4):
    scale = float(jnp.max(jnp.abs(want)))
    assert bool(jnp.all(jnp.isfinite(got)))
    return float(jnp.max(jnp.abs(got - want))) <= tol * max(scale, 1e-30)


_WANT = {}


def oracle(S, H, d, gate):
    key = (S, H, d, gate)
    if key not in _WANT:
        _WANT[key] = both(token_scan, operands(S, H, d, d, gate=gate))
    return _WANT[key]


# (impl, d, chunk, S): several chunks with a ragged tail, chunk sizes 64 and 128 (and 16
# for the jnp form: every level of the triangle down to pairs of rows)
# for the kernels also six chunks a head — grid steps of TWO chunks, since neither eight nor
# four divides six —, twelve (three steps of four) and sixteen (two of eight): the state
# crosses grid steps, and the backward kernel walks a step's chunks forward from its start
# state before it walks them back; and six chunks of 128
_CASES = (("jnp", 32, 16, 72), ("jnp", 32, 64, 160), ("jnp", 32, 128, 288),
          ("pallas", 128, 64, 160), ("pallas", 128, 128, 288),
          ("pallas", 128, 64, 384), ("pallas", 128, 64, 768), ("pallas", 128, 64, 1024),
          ("pallas", 128, 128, 768))


@pytest.mark.parametrize("gate", ("drawn", "strongest"))
@pytest.mark.parametrize("impl,d,chunk,S", _CASES, ids=lambda c: str(c))
@pytest.mark.parametrize("what", ("o",) + tuple("d" + n for n in NAMES))
def test_the_chunked_forms_are_the_recurrence(what, impl, d, chunk, S, gate):
    args = operands(S, 2, d, d, gate=gate)
    key = ("got", impl, d, chunk, S, gate)
    if key not in _WANT:
        _WANT[key] = both(lambda *a: kda.kda_rule(*a, chunk=chunk, impl=impl), args)
    (o, grads), (o_want, grads_want) = _WANT[key], oracle(S, 2, d, gate)
    if what == "o":
        assert close(o, o_want)
        return
    i = NAMES.index(what[1:])
    if gate == "strongest" and what == "dg":       # e^-64 of anything: nothing to hold to but zero
        assert bool(jnp.all(jnp.isfinite(grads[i]))) and float(jnp.max(jnp.abs(grads[i]))) < 1e-20
        return
    assert float(jnp.max(jnp.abs(grads_want[i]))) > 0, what          # a gradient to hold to
    assert close(grads[i], grads_want[i]), what


@pytest.mark.parametrize("impl,d", (("jnp", 32), ("pallas", 128)))
def test_a_gate_constant_over_the_channels_is_the_scalar_rule(impl, d):
    """One decay a head on every channel of it: ``ops.gated_delta`` on the same
    operands, output and gradients (``dg`` summed over the channels)."""
    q, k, v, _, beta = operands(160, 2, d, d, seed=3)
    g1 = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(4), (1, 160, 2)))
    wide = lambda g: jnp.broadcast_to(g[..., None], q.shape)
    o, grads = both(lambda q, k, v, g, b: kda.kda_rule(q, k, v, wide(g), b, chunk=64, impl=impl),
                    (q, k, v, g1, beta))
    o_want, grads_want = both(
        lambda *a: gd.gated_delta_rule(*a, chunk=64, impl="jnp"), (q, k, v, g1, beta))
    assert close(o, o_want)
    for name, got, want in zip(NAMES, grads, grads_want):
        assert close(got, want), name


# (chunk, S, chunks a grid step): eight chunks a step where eight divides a head's chunks, else
# four, two, one; (128, 1024) is ONE step of eight a head, the walk inside a step alone
_STEPS = ((64, 384, 2), (64, 768, 4), (64, 1024, 8), (128, 384, 1), (128, 768, 2), (128, 1024, 8),
          (128, 2048, 8))


@pytest.mark.parametrize("chunk,S,n", _STEPS, ids=lambda c: str(c))
def test_the_state_crosses_a_grid_steps_boundary(chunk, S, n):
    """No decay (a gate of zeros) and ``beta`` = 1 on the first chunk only: every
    later chunk leaves the state alone, so the LAST chunk's ``o`` is the first
    chunk's state read by its queries — through every chunk of its own grid step
    and every grid step's start state between them — and its gradient reaches
    the first chunk's keys and nowhere else among the keys."""
    q, k, v, _, _ = operands(S, 2, 128, 128, seed=7)
    g = jnp.zeros(q.shape, jnp.float32)
    beta = jnp.zeros(q.shape[:3], jnp.float32).at[:, :chunk].set(1.0)
    last = lambda fn: (lambda k: jnp.sum(fn(q, k, v, g, beta)[:, -chunk:] ** 2))
    rule = lambda *a: kda.kda_rule(*a, chunk=chunk, impl="pallas")
    chunked = (jnp.moveaxis(t, 2, 1).reshape(2, S // chunk, chunk, *t.shape[3:])
               for t in (q, k, v, g, beta))
    _, starts = jax.eval_shape(lambda *a: kda._fwd(kda._operands(*a), 2), *chunked)
    assert starts.shape[0] == 2 * (S // chunk) // n         # a start state a grid step, 2 heads
    got, want = rule(q, k, v, g, beta)[:, -chunk:], token_scan(q, k, v, g, beta)[:, -chunk:]
    assert float(jnp.max(jnp.abs(want))) > 1e-3 and close(got, want)
    dk, dk_want = jax.grad(last(rule))(k), jax.grad(last(token_scan))(k)
    assert float(jnp.max(jnp.abs(dk_want[:, :chunk]))) > 1e-4 and close(dk, dk_want)
    assert float(jnp.max(jnp.abs(dk[:, chunk:-chunk]))) == 0.0


def test_the_strongest_decay_forgets_everything_and_overflows_nothing():
    """At -64.3 a token a channel the state is gone within a token: ``o_t`` is
    ``beta_t (q_t . k_t) v_t``; every intermediate is finite, in both forms, and
    bfloat16 operands too."""
    args = operands(160, 2, 128, 128, gate="strongest")
    q, k, v, _, beta = args
    want = beta[..., None] * jnp.sum(q * k, -1, keepdims=True) * v
    for impl in ("jnp", "pallas"):
        assert close(kda.kda_rule(*args, chunk=64, impl=impl), want, 1e-5), impl
    bf = lambda t: t.astype(jnp.bfloat16)
    o, grads = both(lambda q, k, v, g, b: kda.kda_rule(q, k, v, g, b, chunk=64, impl="pallas")
                    .astype(jnp.float32), (bf(q), bf(k), bf(v), args[3], beta))
    assert all(bool(jnp.all(jnp.isfinite(t.astype(jnp.float32)))) for t in (o,) + grads)


def test_no_exponent_is_of_a_positive_difference(monkeypatch):
    """The module's overflow rule, read off the running program: every ``exp`` of
    the chunk-local algebra takes an operand that is <= 0, at a decay strong
    enough to overflow any quotient of decays (e^{64 * 63})."""
    seen, exp = [], jax.lax.exp

    def recorded(x):
        seen.append(float(jnp.max(x)))
        return exp(x)

    monkeypatch.setattr(kda.lax, "exp", recorded)
    for gate in ("strongest", "drawn"):
        args = operands(128, 1, 32, 32, gate=gate)
        chunked = tuple(jnp.moveaxis(t, 2, 1).reshape(1, 2, 64, *t.shape[3:]) for t in args)
        with jax.disable_jit():
            out = kda.kda_prepare(*chunked)
        assert all(bool(jnp.all(jnp.isfinite(t))) for t in out)
    assert len(seen) >= 2 * (6 + 2) and max(seen) <= 0.0, seen


def test_shapes_layouts_and_refusals():
    q, k, v, g, beta = operands(100, 2, 32, 48)
    o = kda.kda_rule(q, k, v, g, beta, chunk=32)
    assert o.shape == (1, 100, 2, 48) and o.dtype == v.dtype
    hf = lambda t: jnp.moveaxis(t, 2, 1)
    o2 = kda.kda_rule(hf(q), hf(k), hf(v), hf(g), hf(beta), chunk=32, heads_first=True)
    np.testing.assert_array_equal(np.asarray(hf(o)), np.asarray(o2))
    with pytest.raises(ValueError, match="power of two"):
        kda.kda_rule(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError, match="shapes mismatch"):
        kda.kda_rule(q, k, v, g[..., 0], beta)
    with pytest.raises(ValueError, match="forced"):
        kda.kda_rule(q, k, v, g, beta, chunk=32, impl="pallas")


@pytest.mark.parametrize("chunk,S", ((64, 256), (128, 768)))
def test_the_kernels_and_the_jnp_form_agree_in_bfloat16(chunk, S):
    """The parity ``check_kda`` holds on the chip, at interpreter size: the fused
    pair against ``_scan_jnp`` of ``kda_prepare``, one grid step of four chunks
    and three of two."""
    args = operands(S, 2, 128, 128, seed=5)
    bf = lambda t: t.astype(jnp.bfloat16)
    args = (bf(args[0]), bf(args[1]), bf(args[2]), args[3], args[4])
    run = lambda impl: both(lambda *a: kda.kda_rule(*a, chunk=chunk, impl=impl)
                            .astype(jnp.float32), args)
    (o, grads), (o_want, grads_want) = run("pallas"), run("jnp")
    assert close(o, o_want, 2e-2)
    for name, got, want in zip(NAMES, grads, grads_want):
        assert close(got.astype(jnp.float32), want.astype(jnp.float32), 2e-2), name


def test_the_row_read_is_exact_and_its_transpose_sums():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32)) * 1e4
    for half in (32, 8, 1):
        got = kda._rows_through(x, half)
        rows = (np.arange(64) // (2 * half)) * 2 * half + half
        np.testing.assert_array_equal(np.asarray(got), np.asarray(x)[:, rows])
        ct = jax.random.normal(jax.random.PRNGKey(1), x.shape)
        (dx,) = jax.vjp(lambda x: kda._rows_through(x, half), x)[1](ct)
        want = np.zeros_like(np.asarray(x))
        np.add.at(want, (slice(None), rows), np.asarray(ct))
        np.testing.assert_allclose(np.asarray(dx), want, rtol=2e-5, atol=2e-5)


def test_the_running_sum_is_a_float32_cumsum_and_its_transpose_a_reverse_one():
    """``_rows_through(g, None)``: three exact bfloat16 parts through the triangular ones,
    accumulated in float32 — a cumsum to float32's own rounding, at magnitudes
    (-64 a row) where a single bfloat16 pass would be off by whole units."""
    g = -64.3 * jax.random.uniform(jax.random.PRNGKey(2), (2, 128, 32))
    want = np.cumsum(np.asarray(g, np.float64), axis=1)
    np.testing.assert_allclose(np.asarray(kda._rows_through(g, None)), want, rtol=3e-7, atol=1e-6)
    ct = jax.random.normal(jax.random.PRNGKey(3), g.shape)
    (dg,) = jax.vjp(lambda g: kda._rows_through(g, None), g)[1](ct)
    want = np.flip(np.cumsum(np.flip(np.asarray(ct, np.float64), 1), axis=1), 1)
    np.testing.assert_allclose(np.asarray(dg), want, rtol=2e-5, atol=2e-4)
