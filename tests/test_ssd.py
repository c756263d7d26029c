"""``ops.ssd``: the chunked state-space recurrence against the token-by-token one.

The oracle here is the recurrence as the Mamba-2 equations state it, a
``lax.scan`` over the tokens in float32; both of the op's paths (the ``jnp``
chunk scan and the Pallas kernels, interpreted off the TPU) are held to it,
forward and in every cotangent."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.guard import dispatch
from beforeholiday_tpu.ops import ssd as ssd_mod
from beforeholiday_tpu.ops.ssd import is_kernel_available, ssd

NAMES = ("x", "dt", "A", "B", "C", "D")


def token_scan(x, dt, A, B, C, D):
    """``S_t = a_t S_{t-1} + dt_t B_t x_t^T``, ``y_t = S_t^T C_t + D x_t``."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    Bh, Ch = (jnp.repeat(t, H // G, axis=2) for t in (B, C))

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :]
        return state, jnp.einsum("bhnp,bhn->bhp", state, c_t) + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((b, H, N, P)),
                        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, Bh, Ch)))
    return jnp.moveaxis(y, 0, 1)


def operands(seed, b, S, H, P, G, N, decay=16.0, shift=2.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (b, S, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)) - shift),
            -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=decay),
            jax.random.normal(ks[3], (b, S, G, N)) * 0.3,
            jax.random.normal(ks[4], (b, S, G, N)) * 0.3,
            jax.random.normal(ks[5], (H,)))


def assert_matches_the_token_scan(args, chunk, impl, rtol=5e-5, rtol_dA=None):
    w = jax.random.normal(jax.random.PRNGKey(99), args[0].shape)
    want, got = token_scan(*args), ssd(*args, chunk=chunk, impl=impl)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=rtol * scale, rtol=0)
    g_want = jax.grad(lambda *a: jnp.sum(token_scan(*a) * w), argnums=range(6))(*args)
    g_got = jax.grad(lambda *a: jnp.sum(ssd(*a, chunk=chunk, impl=impl) * w),
                     argnums=range(6))(*args)
    for name, a, b in zip(NAMES, g_got, g_want):
        tol = rtol_dA if name == "A" and rtol_dA else rtol
        np.testing.assert_allclose(a, b, atol=tol * float(jnp.max(jnp.abs(b))), rtol=0,
                                   err_msg=f"d{name}")


# (batch, S, H, P, G, N), chunk
JNP_CASES = {
    "two_groups": ((2, 96, 4, 8, 2, 16), 32),
    "one_group_serves_all_heads": ((1, 64, 6, 8, 1, 16), 16),
    "a_head_a_group": ((1, 64, 2, 16, 2, 8), 32),
    "not_whole_chunks": ((2, 75, 4, 8, 2, 16), 32),
    "shorter_than_a_chunk": ((1, 20, 2, 8, 1, 16), 64),
    "chunk_of_one_token": ((1, 12, 2, 8, 1, 8), 1),
}


@pytest.mark.parametrize("case", JNP_CASES)
def test_the_jnp_chunk_scan_is_the_token_scan(case):
    shape, chunk = JNP_CASES[case]
    assert_matches_the_token_scan(operands(1, *shape), chunk, "jnp")


KERNEL_CASES = {
    "pairs_of_64_wide_heads_two_groups": (1, 256, 4, 64, 2, 128),
    "heads_sharing_one_group": (1, 256, 4, 64, 1, 128),
    "not_whole_chunks": (1, 300, 2, 64, 1, 128),
    "a_128_wide_head_is_a_unit": (1, 256, 2, 128, 2, 128),
    "four_32_wide_heads_a_unit": (2, 128, 4, 32, 1, 128),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernels_are_the_token_scan(case):
    assert_matches_the_token_scan(operands(2, *KERNEL_CASES[case]), 128, "pallas")


@pytest.mark.parametrize("impl,shape,chunk", (
    ("jnp", (1, 96, 2, 8, 1, 16), 32), ("pallas", (1, 256, 2, 64, 1, 128), 128)))
def test_strong_decay_stays_finite_and_right(impl, shape, chunk):
    """Steps near 5 against ``A`` down to -64: a chunk's decay underflows to
    zero, and every exponent is still of a difference <= 0. ``dA`` is then a sum
    over the tokens of terms that all but cancel (0.02 left of terms near 1), so
    it is held to a percent here; against float64 the chunked form's own error
    in it is 5e-6 of the terms (PERF.md, PR 33)."""
    args = operands(3, *shape, decay=64.0, shift=-5.0)
    assert float(jnp.min(args[1] * args[2][None, None])) < -100.0      # a step: e^-100
    assert_matches_the_token_scan(args, chunk, impl, rtol_dA=1e-2)


def test_bfloat16_operands_keep_their_dtype_and_track_float32():
    args = operands(4, 1, 256, 2, 64, 1, 128)
    low = tuple(a.astype(jnp.bfloat16) if a.ndim == 4 else a for a in args)
    want = token_scan(*(a.astype(jnp.float32) for a in low))
    for impl in ("jnp", "pallas"):
        got = ssd(*low, impl=impl)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(got.astype(jnp.float32), want,
                                   atol=3e-2 * float(jnp.max(jnp.abs(want))), rtol=0)


def test_the_two_paths_agree_on_bfloat16_cotangents():
    args = operands(5, 1, 256, 4, 64, 2, 128)
    low = tuple(a.astype(jnp.bfloat16) if a.ndim == 4 else a for a in args)
    grads = {impl: jax.grad(lambda *a: jnp.sum(ssd(*a, impl=impl).astype(jnp.float32) ** 2),
                            argnums=range(6))(*low) for impl in ("jnp", "pallas")}
    for name, a, b in zip(NAMES, grads["pallas"], grads["jnp"]):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        np.testing.assert_allclose(a, b, atol=4e-2 * float(jnp.max(jnp.abs(b))), rtol=0,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("chunk,P,N,Hg,ok", (
    (128, 64, 128, 16, True),       # the Nemotron cell: 8 units of two heads
    (128, 64, 128, 3, False),       # half a unit left over
    (128, 128, 128, 5, True),       # a head a unit
    (128, 256, 128, 1, True),
    (128, 192, 128, 2, False),      # a head of one and a half tiles
    (64, 64, 128, 2, False),        # the (C, C) tile would be half its lanes
    (128, 64, 64, 2, False),        # ... the state's
    (128, 4, 128, 32, False),       # heads narrower than a sublane tile
    (128, 64, 128, 130, False),     # more heads a group than lanes for their scalars
))
def test_the_shape_gate(chunk, P, N, Hg, ok):
    assert is_kernel_available(chunk, P, N, Hg) is ok


def test_the_default_on_the_kernels_shapes_is_probed_counted_and_booked(monkeypatch):
    args = operands(7, 1, 256, 2, 64, 1, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # resolve_impl -> pallas
    monkeypatch.setattr(ssd_mod, "_interpret_default", lambda: True)
    dispatch.reset_dispatch_counters()
    dispatch.clear_probe_cache("ssd")
    jax.clear_caches()      # a kernel call is a jit function: booked when traced, not when hit
    got = jax.grad(lambda x: jnp.sum(ssd(x, *args[1:])))(args[0])
    np.testing.assert_allclose(
        got, jax.grad(lambda x: jnp.sum(ssd(x, *args[1:], impl="jnp")))(args[0]), atol=1e-4)
    counted = {k[0]: v for k, v in dispatch.dispatch_counters().items()}
    assert counted["ssd"] == {"pallas": 1, "jnp": 0, "probes": 1}
    tiles = {k[1]: v for k, v in dispatch.tile_counters().items() if k[0] == "ssd"}
    assert set(tiles) == {"fwd", "bwd_states", "bwd"}
    assert all(v["total"] == v["live"] == 2 for v in tiles.values())      # 2 chunks x 1 unit


def test_shapes_that_do_not_belong_together_are_refused():
    x, dt, A, B, C, D = operands(8, 1, 32, 4, 8, 2, 16)
    with pytest.raises(ValueError, match="shapes mismatch"):
        ssd(x, dt, A, B, C[:, :, :1], D)
    with pytest.raises(ValueError, match="shapes mismatch"):
        ssd(x, dt, A, jnp.concatenate([B, B[:, :, :1]], 2), jnp.concatenate([C, C[:, :, :1]], 2), D)
    with pytest.raises(ValueError, match="chunk"):
        ssd(x, dt, A, B, C, D, chunk=0)
