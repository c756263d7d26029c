"""The sort's two sums by token (``ops/segment_sum.py``) against the scatter-add
loop of ``moe/dropless.py`` on the same operands, in the Pallas interpreter.

**The bound.** Both forms form every term ``rows[r] * scale[r]`` to float32
accuracy and add a token's ``n`` terms in float32; they differ in the order of
the additions, and the kernel adds a scaled term as its three exact bfloat16 x
bfloat16 partial products (nine for float32 rows). So with ``eps = 2**-24``
and ``S = sum_r |rows[r] * scale[r]|`` over a token's rows, each side is
within ``3 n eps S`` of the exact sum and the two within ``6 n eps S`` of each
other, element by element (``_bound``; measured: under ``2 eps S``). Rounded
to bfloat16 the two can land on neighbouring values: one bfloat16 ulp,
``2**-7`` of the value, on top."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.moe import dropless
from beforeholiday_tpu.ops import segment_sum as seg

_EPS = 2.0 ** -24
BF, F32 = jnp.bfloat16, jnp.float32
T, R, D, MOST = 600, 1000, 128, 4       # three tiles of 256 tokens; four chunks of 256 rows


def _operands(seed, dtype, scaled, n_valid, token=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    if token is None:       # four experts' groups of ascending tokens
        token = jnp.sort(jax.random.randint(ks[0], (4, R // 4), 0, T), axis=1).reshape(-1)
    rows = jax.random.normal(ks[1], (token.shape[0], D), F32).astype(dtype)
    # what the grouped kernel leaves past the last group: never read into a sum
    rows = jnp.where((jnp.arange(token.shape[0]) >= n_valid)[:, None], jnp.nan, rows)
    scale = jax.random.uniform(ks[2], token.shape, F32, 0.05, 1.0) if scaled else None
    return rows, token.astype(jnp.int32), scale


def _both(rows, token, scale, n_valid, out_dtype, out_rows=T, carried=True):
    order = dropless.token_order(token, n_valid, out_rows=out_rows, width=rows.shape[1],
                                 dtype=rows.dtype, scale=scale if carried else None,
                                 impl="pallas")
    assert order is not None and (order.scale is None) == (scale is None or not carried)
    kw = dict(out_rows=out_rows, scale=scale)
    # the loop summed in float32 and rounded once, as the dispatch's transpose takes it
    return (dropless.scatter_add_rows(rows, token, n_valid, order=order, out_dtype=out_dtype, **kw),
            dropless.scatter_add_rows(rows, token, n_valid, out_dtype=F32, **kw).astype(out_dtype))


def _bound(rows, token, scale, n_valid, out_rows=T):
    """``6 n eps S`` a token and column (float64 on the host)."""
    live = np.arange(token.shape[0]) < n_valid
    terms = np.abs(np.where(live[:, None], np.asarray(rows.astype(F32), np.float64), 0.0))
    if scale is not None:
        terms = terms * np.asarray(scale, np.float64)[:, None]
    S = np.zeros((out_rows, rows.shape[1]))
    np.add.at(S, np.asarray(token)[live], terms[live])
    n = np.bincount(np.asarray(token)[live], minlength=out_rows)[:, None]
    return 6 * n * _EPS * S


def _hold(got, want, bound, out_dtype):
    got, want = (np.asarray(a.astype(F32), np.float64) for a in (got, want))
    assert np.all(np.isfinite(got))
    if out_dtype == BF:
        bound = bound + 2.0 ** -7 * np.abs(want)
    assert np.all(np.abs(got - want) <= bound), float(np.max(np.abs(got - want) - bound))


@pytest.mark.parametrize("out_dtype", (F32, BF), ids=("to_f32", "to_bf16"))
@pytest.mark.parametrize("n_valid", (0, 1, 333, R))
@pytest.mark.parametrize("scaled", (False, True), ids=("plain", "scaled"))
@pytest.mark.parametrize("dtype", (BF, F32), ids=("bf16", "f32"))
def test_the_sum_by_token_is_the_loops_sum_to_float32_rounding(dtype, scaled, n_valid, out_dtype):
    """No row, one row, a count that is a multiple of no tile (333: not of the
    gather's, not of the 256-row chunk, not of 8) and the full buffer (1,000
    rows: padded to four chunks), the tail NaN."""
    rows, token, scale = _operands(3, dtype, scaled, n_valid)
    got, want = _both(rows, token, scale, n_valid, out_dtype)
    assert got.shape == want.shape == (T, D) and got.dtype == want.dtype == out_dtype
    _hold(got, want, _bound(rows, token, scale, n_valid), out_dtype)
    if n_valid == 1 and not scaled:         # one term, one part: nothing to reorder
        assert jnp.array_equal(got, want)


def _laid_out():
    """Tokens by hand, landed rows first: token 0 has three rows, token 1 none,
    tokens 2..65 ``MOST`` each, 259 rows in the first tile of 256 tokens, so
    that the first chunk of 256 rows ends inside it; nothing in tokens 66..511 (a whole
    tile of tokens without a row); tokens 512..599 one row each. Then shuffled
    into two ascending groups, as a sort by expert leaves them."""
    token = np.concatenate([[0, 0, 0], np.repeat(np.arange(2, 66), MOST), np.arange(512, 600)])
    assert token.shape == (347,) and list(np.flatnonzero(token == 65)) == [255, 256, 257, 258]
    at = np.random.RandomState(0).permutation(token.shape[0])
    token = np.concatenate([np.sort(token[at[:200]]), np.sort(token[at[200:]])])
    return np.concatenate([token, np.full(R - token.shape[0], 7)]), 347


@pytest.mark.parametrize("scaled", (False, True), ids=("plain", "scaled"))
@pytest.mark.parametrize("dtype", (BF, F32), ids=("bf16", "f32"))
def test_tokens_of_no_one_and_most_rows_an_empty_tile_and_a_straddling_segment(dtype, scaled):
    token, n_valid = _laid_out()
    rows, token, scale = _operands(5, dtype, scaled, n_valid, jnp.asarray(token))
    order = dropless.token_order(token, n_valid, out_rows=T, width=D, dtype=dtype, impl="pallas")
    listed = np.asarray(order.token).reshape(-1)
    # by tile of 256 tokens, the buffer's order (two ascending groups) inside a tile
    assert np.all(np.diff(listed[:n_valid] // 256) >= 0) and np.all(listed[n_valid:] == 3 * 256)
    assert list(listed[:n_valid]) == [t for lo in (0, 256, 512) for t in np.asarray(token[:n_valid])
                                      if lo <= t < lo + 256]
    # tokens with rows on both sides of the first chunk's end
    assert set(listed[:256]) & set(listed[256:259])
    flags, tile, chunk = (np.asarray(v) for v in order.visits)
    visited = [(int(a), int(b)) for f, a, b in zip(flags, tile, chunk) if f]
    # tile 0 walks chunks 0 and 1, the empty tile 1 is opened and closed over no
    # row, tile 2 shares chunk 1; every step after repeats the last and does nothing
    assert visited == [(0, 0), (0, 1), (1, 1), (2, 1)] and not flags[4:].any()
    got, want = _both(rows, token, scale, n_valid, F32)
    _hold(got, want, _bound(rows, token, scale, n_valid), F32)
    assert not np.asarray(got[1]).any() and not np.asarray(got[256:512]).any()
    if not scaled:          # one term, one part: nothing to reorder
        assert jnp.array_equal(got[512:], want[512:])


@pytest.mark.parametrize("rows_,tokens_", ((8, 8), (40, 24), (300, 9), (256, 256), (257, 513)))
def test_any_buffer_and_any_count_of_tokens(rows_, tokens_):
    """Buffers below a chunk and tokens below a tile, and one past each."""
    rows, token, scale = _operands(rows_, BF, True, rows_ - 1,
                                   jnp.arange(rows_, dtype=jnp.int32) * 7 % tokens_)
    got, want = _both(rows, token, scale, rows_ - 1, F32, out_rows=tokens_)
    _hold(got, want, _bound(rows, token, scale, rows_ - 1, tokens_), F32)


def test_a_scale_the_order_does_not_carry_is_gathered_to_the_same_sum():
    rows, token, scale = _operands(6, BF, True, 500)
    (got, _), (same, _) = (_both(rows, token, scale, 500, F32, carried=c) for c in (True, False))
    assert jnp.array_equal(got, same)


def test_off_the_kernels_shapes_the_order_is_none_and_forcing_it_raises():
    token = jnp.zeros((64,), jnp.int32)
    assert not seg.is_kernel_available(64, 32, 96, BF)            # not whole lane tiles
    assert not seg.is_kernel_available(64, 32, 128, jnp.float16)
    assert seg.is_kernel_available(64, 32, 128, BF) and seg.is_kernel_available(64, 32, 256, F32)
    assert dropless.token_order(token, 3, out_rows=32, width=96, dtype=BF) is None
    assert dropless.token_order(token, 3, out_rows=32, width=128, dtype=BF) is None   # off the TPU
    assert dropless.token_order(token, 3, out_rows=32, width=128, dtype=BF, impl="jnp") is None
    with pytest.raises(ValueError, match="whole lane tiles"):
        dropless.token_order(token, 3, out_rows=32, width=96, dtype=BF, impl="pallas")


def test_an_order_of_another_buffer_or_of_other_tokens_is_refused():
    rows, token, _ = _operands(1, BF, False, 10)
    order = dropless.token_order(token[:512], 10, out_rows=T, width=D, dtype=BF, impl="pallas")
    with pytest.raises(ValueError, match="lists a buffer of"):
        dropless.scatter_add_rows(rows, token, 10, out_rows=T, order=order)
    order = dropless.token_order(token, 10, out_rows=T, width=D, dtype=BF, impl="pallas")
    with pytest.raises(ValueError, match="not what this order lists"):
        dropless.scatter_add_rows(rows, token, 10, out_rows=2 * T, order=order)


def test_a_failed_probe_leaves_the_loop_and_counts_it():
    from beforeholiday_tpu.guard import dispatch
    from beforeholiday_tpu.testing import faults

    rows, token, scale = _operands(2, BF, True, 500)
    dispatch.reset_dispatch_counters()
    with faults.force_probe_failure("segment_sum"):
        got, want = _both(rows, token, scale, 500, F32)
    assert jnp.array_equal(got, want)
    taken = [c for k, c in dispatch.dispatch_counters().items() if k[0] == "segment_sum"]
    assert sum(c["jnp"] for c in taken) == 1 and sum(c["pallas"] for c in taken) == 0


def test_the_kernel_is_counted_and_its_tiles_booked():
    from beforeholiday_tpu import monitor
    from beforeholiday_tpu.guard import dispatch

    rows, token, scale = _operands(4, BF, True, 700)
    dispatch.reset_dispatch_counters()
    jax.clear_caches()
    _both(rows, token, scale, 700, F32)
    _both(rows, token, None, 700, BF)
    taken = [c for k, c in dispatch.dispatch_counters().items() if k[0] == "segment_sum"]
    assert sum(c["pallas"] for c in taken) == 2 and sum(c["jnp"] for c in taken) == 0
    booked = [r for r in monitor.tile_records() if r["kernel"] == "segment_sum"]
    assert [(r["op"], r["key"], r["total"], r["live"], r["masked"]) for r in booked] == [
        ("moe_rows", repr((1024, D, "bfloat16", 256)), 4 + 3 - 1, 4, 3 - 1)]
    assert booked[0]["traces"] == 2         # with a scale and without: two bodies


# -- the layer through it ------------------------------------------------------------

def _layer(seed, k, held, E=8, F=128, tokens=96):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda key, *shape: (jax.random.normal(key, shape) * 0.3).astype(BF)
    x = n(ks[0], tokens, D)
    w = jax.random.uniform(ks[1], (tokens, k), F32, 0.1, 1.0)
    idx = jnp.argsort(jax.random.uniform(ks[2], (tokens, E)), axis=1)[:, :k].astype(jnp.int32)
    experts = {"w_gate": n(ks[3], held, D, F), "w_up": n(ks[4], held, D, F),
               "w_down": n(ks[5], held, F, D)}
    return x, w, idx, experts


@pytest.mark.parametrize("k,held,first,rows_bound", (
    (4, 8, 0, None),        # every choice lands: the buffer is full
    (4, 2, 2, None),        # most choices are for absent experts
    (4, 2, 5, 100),         # a bound tighter than the worst case
    (6, 3, 1, None),        # more choices than held experts
), ids=("full", "a_share", "bounded", "k_over_held"))
def test_the_layers_values_and_gradients_are_the_oracles(monkeypatch, k, held, first, rows_bound):
    """``dropless_experts`` with its two sums by token against the same layer on
    the loops (``impl="jnp"`` both: the grouped matmul is ``ragged_dot`` on both
    sides, so nothing else differs). The result is float32 sums of at most
    ``min(k, held)`` terms in another order; ``dx`` is such a sum rounded to
    bfloat16 (one ulp where the two land on neighbours); the weights' and the
    experts' cotangents come from the gather loops, which did not change, on a
    cotangent (``cos(y)``) that differs by the result's float32 rounding."""
    x, w, idx, experts = _layer(k * held, k, held)

    def loss(form):
        def run(x, w, experts):
            y, _ = dropless.dropless_experts(x, w, idx, experts, first_expert=first,
                                             rows_bound=rows_bound, impl="jnp")
            return jnp.sum(jnp.sin(y)), y
        with monkeypatch.context() as m:
            if form == "by_token":
                m.setattr(dropless, "token_order", lambda *a, **kw: seg.token_order(
                    *a, **{**kw, "impl": "pallas"}))
            return jax.jit(jax.value_and_grad(run, argnums=(0, 1, 2), has_aux=True))(x, w, experts)

    ((_, y), (dx, dw, dex)), ((_, y0), (dx0, dw0, dex0)) = loss("by_token"), loss("loop")
    assert float(jnp.max(jnp.abs(y0))) > 0.1
    assert float(jnp.max(jnp.abs(y - y0))) <= 1e-6 * float(jnp.max(jnp.abs(y0)))
    gap = jnp.abs(dx.astype(F32) - dx0.astype(F32))
    assert float(jnp.max(gap)) <= 2.0 ** -6 * float(jnp.max(jnp.abs(dx0.astype(F32))))
    assert float(jnp.mean(gap > 0)) < 0.05          # and nearly everywhere the same value
    assert float(jnp.max(jnp.abs(dw - dw0))) <= 1e-5 * float(jnp.max(jnp.abs(dw0)))
    for name in dex0:
        scale = float(jnp.max(jnp.abs(dex0[name].astype(F32))))
        assert float(jnp.max(jnp.abs(dex[name].astype(F32) - dex0[name].astype(F32)))) \
            <= 2.0 ** -6 * scale, name


@pytest.mark.parametrize("n_valid", (100, 1024, 1025))
def test_the_tiles_the_gather_never_reached_are_never_read(monkeypatch, n_valid):
    """The gather into the order's list leaves the tiles past the last landed
    row unwritten (``unwritten_like``: zeros in the interpreter, whatever was
    there on the chip). Here they hold NaN, so a sum that read one would show
    it: a buffer of three gather tiles (2,304 rows: nine chunks) with one or two
    of them reached."""
    monkeypatch.setattr(dropless, "_unwritten_like",
                        lambda rows, shape, dtype: jnp.full(shape, jnp.nan, dtype))
    dropless._gather_loop.clear_cache()
    token = jnp.arange(2304, dtype=jnp.int32) * 5 % T
    rows, token, scale = _operands(8, BF, True, n_valid, token)
    order = dropless.token_order(token, n_valid, out_rows=T, width=D, dtype=BF, impl="pallas")
    listed = dropless._gather_loop(rows, order.perm, n_valid, None, None, 1024, BF, fill=False)[0]
    assert bool(jnp.all(jnp.isnan(listed[2048:].astype(F32))))       # the poison is there
    got, want = _both(rows, token, scale, n_valid, F32)
    dropless._gather_loop.clear_cache()
    _hold(got, want, _bound(rows, token, scale, n_valid), F32)
