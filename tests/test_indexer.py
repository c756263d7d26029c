"""``ops.indexer.index_select``: the kernel (interpret mode on the CPU) and the
jnp form against ``lax.top_k`` on materialised float32 scores — ties, short
rows, sequences that are not whole tiles — and the dispatch's bookkeeping."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.ops import indexer as X

IMPLS = ("pallas", "jnp")


def _operands(key, B=2, S=256, Hi=4, d=32, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (B, S, Hi, d)).astype(dtype),
            jax.random.normal(ks[1], (B, S, d)).astype(dtype),
            jax.random.normal(ks[2], (B, S, Hi)))


def _scores(q, k, w):
    """``I (B, S, S)`` float32, every pair, the heads summed in their order."""
    s = jnp.einsum("bthd,bsd->bths", q.astype(jnp.float32), k.astype(jnp.float32),
                   precision="highest")
    s = jnp.maximum(s, 0.0) * w[..., None]
    total = s[:, :, 0]
    for j in range(1, s.shape[2]):
        total = total + s[:, :, j]
    return total


def _by_top_k(scores, topk):
    """The selection of ``scores (B, S, S)`` by ``lax.top_k`` over ``s <= t``
    (the lower index first among equals), as a bool mask."""
    B, S, _ = scores.shape
    causal = jnp.tril(jnp.ones((S, S), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, S))
    keep = jnp.zeros((B, S, S), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None], idx].set(True)
    return keep & causal


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("S,topk", ((256, 40), (384, 128), (200, 17), (100, 100), (130, 500),
                                    (640, 1)),
                         ids=("S256-k40", "S384-k128", "S200-ragged", "S100-keeps-all",
                              "S130-k-over-S", "S640-one-key"))
def test_the_selection_is_top_k_of_the_scores(impl, S, topk):
    q, k, w = _operands(jax.random.PRNGKey(S + topk), S=S)
    got = X.index_select(q, k, w, topk=topk, impl=impl)
    assert got.shape == (2, S, S) and got.dtype == jnp.int8
    want = _by_top_k(_scores(q, k, w), topk)
    assert bool(jnp.array_equal(got != 0, want)), int(jnp.sum((got != 0) != want))
    rows = jnp.sum(got, axis=-1, dtype=jnp.int32)
    assert bool(jnp.all(rows == jnp.minimum(jnp.arange(S) + 1, topk)))
    assert int(jnp.sum(rows)) == 2 * X.selected_pairs(S, topk)
    assert not bool(jnp.any(jnp.triu(got, 1)))              # a kept key is never after its query


@pytest.mark.parametrize("impl", IMPLS)
def test_a_row_shorter_than_topk_keeps_every_key(impl):
    q, k, w = _operands(jax.random.PRNGKey(0), B=1, S=256)
    got = X.index_select(q, k, w, topk=64, impl=impl)[0]
    assert bool(jnp.array_equal(got[:64, :64], jnp.tril(jnp.ones((64, 64), jnp.int8))))
    assert int(jnp.sum(got[64])) == 64 and int(jnp.sum(got[255])) == 64


@pytest.mark.parametrize("impl", IMPLS)
def test_ties_go_to_the_lower_index(impl):
    """Constructed ties: keys that repeat give equal scores, to the bit. Among
    equals at a row's ``topk``-th score the lower indices are kept — and with
    every key the same, the first ``topk``."""
    B, S, Hi, d, topk = 1, 256, 4, 32, 24
    q, k, w = _operands(jax.random.PRNGKey(1), B=B, S=S, Hi=Hi, d=d)
    k = k[:, jnp.arange(S) % 7]                     # seven distinct keys, repeated
    got = X.index_select(q, k, w, topk=topk, impl=impl)
    scores = _scores(q, k, w)
    assert len(np.unique(np.asarray(scores[0, 200, :201]))) <= 7
    assert bool(jnp.array_equal(got != 0, _by_top_k(scores, topk)))
    same = X.index_select(q, jnp.broadcast_to(k[:, :1], k.shape), w, topk=topk, impl=impl)
    first = (jnp.arange(S)[None, :] < topk) & jnp.tril(jnp.ones((S, S), bool))
    assert bool(jnp.array_equal(same[0] != 0, first))


@pytest.mark.parametrize("impl", IMPLS)
def test_all_zero_scores_and_negative_zero_are_one_tie(impl):
    """``relu`` zeroes a pair's every head where the products are negative; with a
    negative weight the sum is ``-0.0``, which counts as ``0.0``: one tie, the
    lower indices."""
    B, S, Hi, d, topk = 1, 128, 2, 16, 10
    q = jnp.ones((B, S, Hi, d))
    k = -jnp.ones((B, S, d))                         # every product negative: relu gives 0
    w = jnp.where(jnp.arange(S)[None, :, None] % 2 == 0, -1.0, 1.0) * jnp.ones((B, S, Hi))
    got = X.index_select(q, k, w, topk=topk, impl=impl)
    first = (jnp.arange(S)[None, :] < topk) & jnp.tril(jnp.ones((S, S), bool))
    assert bool(jnp.array_equal(got[0] != 0, first))
    # and keys whose sign of zero differs within a row
    k = k.at[:, ::3].set(1.0)                        # a third of the keys score > 0 or < 0 by w
    got = X.index_select(q, k, w, topk=topk, impl=impl)
    assert bool(jnp.array_equal(got != 0, _by_top_k(_scores(q, k, w) + 0.0, topk)))


def test_the_two_forms_agree_on_bfloat16_operands():
    """The training path's operands: products of bfloat16 accumulate in float32
    in both forms, and the selection is that of the float32 scores."""
    q, k, w = _operands(jax.random.PRNGKey(3), S=384, dtype=jnp.bfloat16)
    a, b = (X.index_select(q, k, w, topk=100, impl=impl) for impl in IMPLS)
    assert bool(jnp.array_equal(a, b))
    assert bool(jnp.array_equal(a != 0, _by_top_k(_scores(q, k, w), 100)))


def test_the_image_orders_int32_as_float32():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, jnp.inf], jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    image = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    assert bool(jnp.all(jnp.diff(image) > 0)) and int(image[0]) > X._INT_MIN
    assert float(X._zero_sign(jnp.float32(-0.0))) == 0.0
    assert not np.signbit(np.asarray(X._zero_sign(jnp.float32(-0.0))))


def test_plans_and_counts():
    assert X._plan(8192, 2048) == X._Plan(2048, 256, 512, 8192)
    assert X._plan(200, 17) == X._Plan(17, 256, 256, 256)
    assert X._plan(384, 9) == X._Plan(9, 128, 128, 384)
    assert X.selected_pairs(8192, 2048) == 14_681_088
    assert X.selected_pairs(100, 2048) == 100 * 101 // 2
    assert X.is_kernel_available(8192, 16, 64) and not X.is_kernel_available(8192, 16, 256)


def test_it_passes_no_gradient_and_checks_its_shapes():
    q, k, w = _operands(jax.random.PRNGKey(0), B=1, S=128)
    g = jax.grad(lambda q: jnp.sum(X.index_select(q, k, w, topk=8, impl="jnp").astype(jnp.float32)))
    assert not bool(jnp.any(g(q)))
    with pytest.raises(ValueError, match="shapes mismatch"):
        X.index_select(q, k[:, :64], w, topk=8)
    with pytest.raises(ValueError, match="at least one key"):
        X.index_select(q, k, w, topk=0)
    with pytest.raises(ValueError, match="forced"):
        X.index_select(jnp.zeros((1, 128, 4, 256)), jnp.zeros((1, 128, 256)),
                       jnp.zeros((1, 128, 4)), topk=8, impl="pallas")


def test_the_dispatch_is_guarded_and_counted(monkeypatch):
    """Off a forced ``impl`` the kernel is probed once a key and counted under
    ``index_select``; a probe that fails degrades to the jnp form."""
    from beforeholiday_tpu.guard import dispatch as gd
    from beforeholiday_tpu.testing import faults

    q, k, w = _operands(jax.random.PRNGKey(0), B=1, S=128)
    want = X.index_select(q, k, w, topk=8, impl="jnp")
    monkeypatch.setattr(X, "_dispatch", lambda op, impl, *a, **kw: ("pallas", False))
    gd.clear_probe_cache("index_select")
    gd.reset_dispatch_counters()
    assert bool(jnp.array_equal(X.index_select(q, k, w, topk=8), want))
    (key,) = [key for key in gd.dispatch_counters() if key[0] == "index_select"]
    assert gd.dispatch_counters()[key] == {"pallas": 1, "jnp": 0, "probes": 1}
    gd.clear_probe_cache("index_select")
    with faults.force_probe_failure("index_select"):
        assert bool(jnp.array_equal(X.index_select(q, k, w, topk=8), want))
    assert gd.dispatch_counters()[key]["jnp"] == 1
    gd.clear_probe_cache("index_select")
    gd.reset_dispatch_counters()
