"""``ops/short_conv.py``: the double-gated short convolution of an LFM2 mixer
against the ``jnp`` chain it replaces and against a float64 loop, the kernels
under the Pallas interpreter.

Float32 operands: the kernels compute the chain's mathematics in float32 and
the chain rounds nothing then, so the two differ by reassociation only
(``_TOL`` of each tensor's largest entry). The shapes put more than one row
tile in a sequence, so the rows a tile takes from its neighbour (the two
before it forward, the two after it backward) are real."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.guard import dispatch
from beforeholiday_tpu.ops import short_conv as sc

_TOL = 2e-6
_K = 3

# (B, S, D): S = 96 is three tiles of 32 rows, 48 three of 16, 64 one tile
_SHAPES = ((2, 96, 128), (1, 48, 256), (2, 64, 128), (1, 192, 128))
_IDS = ("tiles_of_32_rows", "tiles_of_16_rows", "one_tile_a_sequence", "tiles_of_64_rows")
_PARTS = ("y", "dbcx", "dw")


def _inputs(B, S, D, K=_K, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcx = jax.random.normal(ks[0], (B, S, 3 * D)).astype(dtype)
    w = jax.random.uniform(ks[1], (D, K), jnp.float32, -0.577, 0.577)
    return bcx, w, jax.random.normal(ks[2], (B, S, D)).astype(dtype)


def _run(impl, bcx, w, dy):
    y, pull = jax.vjp(lambda a, f: sc.gated_short_conv(a, f, impl=impl), bcx, w)
    return dict(zip(_PARTS, (y,) + pull(dy)))


def _loop64(bcx, w, dy):
    """The definition, token by token and tap by tap, in float64 numpy, and its
    three cotangents by the chain rule written out."""
    bcx, w, dy = (np.asarray(t, np.float64) for t in (bcx, w, dy))
    B, S, D3 = bcx.shape
    D, K = w.shape
    b, c, x = bcx[..., :D], bcx[..., D:2 * D], bcx[..., 2 * D:]
    z = b * x
    conv, dz, dw = np.zeros_like(z), np.zeros_like(z), np.zeros_like(w)
    dconv = dy * c
    for t in range(S):
        for j in range(K):
            s = t - (K - 1) + j
            if s >= 0:
                conv[:, t] += w[:, j] * z[:, s]
                dz[:, s] += w[:, j] * dconv[:, t]
                dw[:, j] += np.sum(dconv[:, t] * z[:, s], axis=0)
    return {"y": c * conv, "dbcx": np.concatenate([dz * x, dy * conv, dz * b], -1), "dw": dw}


def _close(got, want, what, tol=_TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want)), what


_RESULTS = {}


def _all(shape):
    if shape not in _RESULTS:
        args = _inputs(*shape)
        _RESULTS[shape] = _run("pallas", *args), _run("jnp", *args), _loop64(*args)
    return _RESULTS[shape]


@pytest.mark.parametrize("what", _PARTS)
@pytest.mark.parametrize("shape", _SHAPES, ids=_IDS)
def test_the_kernels_match_the_chain(shape, what):
    kernels, chain, _ = _all(shape)
    _close(kernels[what], chain[what], what)


@pytest.mark.parametrize("what", _PARTS)
@pytest.mark.parametrize("impl", ("pallas", "jnp"))
@pytest.mark.parametrize("shape", _SHAPES[:2], ids=_IDS[:2])
def test_both_match_a_float64_loop(shape, impl, what):
    kernels, chain, loop = _all(shape)
    _close((kernels if impl == "pallas" else chain)[what], loop[what], what)


@pytest.mark.parametrize("what", _PARTS)
@pytest.mark.parametrize("S", (1, 2))
def test_a_sequence_shorter_than_the_filter(S, what):
    """``S < K``: not the kernels' shape; the chain pads and is right."""
    args = _inputs(2, S, 128)
    _close(_run(None, *args)[what], _loop64(*args)[what], what)


@pytest.mark.parametrize("K", (1, 2, 4, 8))
def test_other_filter_widths(K):
    args = _inputs(1, 48, 128, K=K, seed=K)
    kernels, loop = _run("pallas", *args), _loop64(*args)
    for what in _PARTS:
        _close(kernels[what], loop[what], (K, what))


@pytest.mark.parametrize("t", (31, 32, 33, 64))
def test_the_convolution_is_causal_across_tiles(t):
    """A change of row ``t`` moves rows ``t .. t + K - 1`` of the output and no
    other: over the boundary of the 32-row tiles too (the rows a tile takes
    from the one before)."""
    bcx, w, _ = _inputs(1, 96, 128)
    moved = bcx.at[0, t].add(1.0)
    diff = np.abs(np.asarray(sc.gated_short_conv(moved, w, impl="pallas")
                             - sc.gated_short_conv(bcx, w, impl="pallas"))).max(axis=(0, 2))
    assert np.all(diff[:t] == 0) and np.all(diff[t + _K:] == 0)
    assert np.all(diff[t:t + _K] > 0)


def test_the_backward_pass_is_anti_causal_across_tiles():
    """A cotangent on row ``t`` alone reaches rows ``t - (K - 1) .. t`` of the
    input's: back over a tile boundary (the rows a tile takes from the one the
    step before handled)."""
    bcx, w, _ = _inputs(1, 96, 128)
    for t in (32, 33, 64):
        dy = jnp.zeros((1, 96, 128)).at[0, t].set(1.0)
        rows = np.abs(np.asarray(_run("pallas", bcx, w, dy)["dbcx"])).max(axis=(0, 2))
        assert np.all(rows[:t - (_K - 1)] == 0) and np.all(rows[t + 1:] == 0)
        assert np.all(rows[t - (_K - 1):t + 1] > 0)


def test_sequences_of_a_batch_do_not_see_each_other():
    bcx, w, dy = _inputs(2, 96, 128)
    both = _run("pallas", bcx, w, dy)
    for b in range(2):
        one = _run("pallas", bcx[b:b + 1], w, dy[b:b + 1])
        _close(both["y"][b:b + 1], one["y"], "y", tol=0)
        _close(both["dbcx"][b:b + 1], one["dbcx"], "dbcx", tol=0)


def test_the_kernels_round_once_where_the_chain_rounds_thrice():
    """bfloat16 operands: the chain rounds ``z`` and the convolution before the
    gate, the kernels only the result, so the kernels lie nearer the float64
    loop on the same (bfloat16-valued) inputs."""
    bcx, w, dy = _inputs(2, 96, 128, dtype=jnp.bfloat16)
    kernels, chain = _run("pallas", bcx, w, dy), _run("jnp", bcx, w, dy)
    loop = _loop64(bcx.astype(jnp.float32), w, dy.astype(jnp.float32))
    assert kernels["y"].dtype == kernels["dbcx"].dtype == jnp.bfloat16
    assert kernels["dw"].dtype == w.dtype
    err = lambda got, what: np.sqrt(np.mean(np.square(
        np.asarray(got[what], np.float64) - loop[what])))
    for what in _PARTS:
        assert err(kernels, what) <= err(chain, what), what
        _close(kernels[what], loop[what], what, tol=2e-2)


@pytest.mark.parametrize("S,D,K,ok", (
    (8192, 2048, 3, True), (48, 256, 8, True), (40, 128, 3, False), (2, 128, 3, False),
    (64, 64, 3, False), (64, 192, 3, False), (64, 128, 9, False),
))
def test_the_shape_gate(S, D, K, ok):
    assert sc.is_kernel_available(S, D, K) is ok


def test_the_row_tile_follows_the_width():
    assert sc._row_tile(8192, 2048) == 128 and sc._row_tile(8192, 128) == 512
    assert sc._row_tile(96, 128) == 32 and sc._row_tile(40, 128) is None


def _counted():
    """``{op: {"pallas", "jnp", "probes"}}``, summed over an op's shapes."""
    out = {}
    for key, row in dispatch.dispatch_counters().items():
        seen = out.setdefault(key[0], dict.fromkeys(row, 0))
        for k, v in row.items():
            seen[k] += v
    return out


def test_dispatch_is_guarded_counted_and_booked(monkeypatch):
    from beforeholiday_tpu import monitor

    dispatch.reset_dispatch_counters()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # resolve_impl -> pallas
    monkeypatch.setattr(sc, "_interpret_default", lambda: True)
    bcx, w, _ = _inputs(1, 48, 128, seed=7)
    sc.gated_short_conv(bcx, w)
    assert _counted()["short_conv"] == {"pallas": 1, "jnp": 0, "probes": 1}
    booked = {t["kernel"]: t for t in monitor.tile_records() if t["op"] == "short_conv"}
    assert booked["fwd"]["total"] == booked["bwd"]["total"] == 3       # the probe ran both


def test_mismatched_shapes_are_refused():
    bcx, w, _ = _inputs(1, 48, 128)
    with pytest.raises(ValueError, match="shapes mismatch"):
        sc.gated_short_conv(bcx[..., :256], w)
    with pytest.raises(ValueError, match="impl must be"):
        sc.gated_short_conv(bcx, w, impl="xla")


def test_the_backward_kernel_keeps_only_its_inputs():
    """Residuals are the kernel's operands: nothing float32 of the
    activation's size, nothing computed."""
    bcx, w, _ = _inputs(1, 48, 128)
    w8 = sc._filter_rows(w)
    _, res = sc._pallas_fwd(bcx, w8, sc._Plan(128, _K, 16))
    assert len(res) == 2 and res[0] is bcx and res[1] is w8


def test_the_kernels_are_named_for_the_trace():
    """``short_conv_ms`` and ``short_conv_roofline`` read the kernels by their
    ``name=``; no other metric's pattern (``layer_norm``, ``flash_attention``,
    ``grouped_matmul``) is in them."""
    from jax._src import core

    def kernels(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in core.jaxprs_in_params(eqn.params):
                yield from kernels(sub)

    bcx, w, dy = _inputs(1, 48, 128)
    loss = lambda a, f: jnp.sum(sc.gated_short_conv(a, f, impl="pallas") * dy)
    names = sorted(kernels(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(bcx, w).jaxpr))
    assert names == ["short_conv_bwd", "short_conv_fwd"]
