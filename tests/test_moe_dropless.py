"""Dropless top-k routing (``moe/dropless.py``) against dense masked sums, and
the share test: the parts that all the expert-parallel shares give add up to
the uncut layer of the benchmark's plain reference.

Float32 at ``highest`` matmul precision: the program sorts rows by expert and
runs grouped matmuls, the oracle runs every expert on every token and masks, so
the two differ by the order of a ten-term weighted sum: 1e-5 of the largest
output (measured 1e-6).

Every case runs on both implementations of the grouped matmul (``impl``):
``jnp`` is ``jax.lax.ragged_dot``, ``pallas`` the kernels of
``ops/grouped_matmul.py`` in the Pallas interpreter, which is why the widths
are whole lane tiles."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from beforeholiday_tpu.moe import dropless  # noqa: E402
from benchmark.reference import qwen3_next as reference  # noqa: E402

_TOL = 1e-5
T, D, E, F, K = 96, 128, 16, 128, 4


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=("jnp", "pallas"))
def impl(request):
    return request.param


def layer_params(seed, router_skew=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda k, *shape: jax.random.normal(k, shape) * 0.2
    router = n(ks[0], D, E)
    if router_skew:      # one expert's column dominates: it takes most rows
        router = router.at[:, 5].set(router_skew)        # with tokens(skewed=True)
    return {
        "router": router, "w_gate": n(ks[1], E, D, F), "w_up": n(ks[2], E, D, F),
        "w_down": n(ks[3], E, F, D), "shared_w_gate": n(ks[4], D, F),
        "shared_w_up": n(ks[5], D, F), "shared_w_down": n(ks[6], F, D),
        "shared_score": n(ks[7], D, 1),
    }


def tokens(seed, skewed=False):
    x = jax.random.normal(jax.random.PRNGKey(100 + seed), (T, D))
    return jnp.abs(x) if skewed else x


def dense_routed(x, p, first, held):
    """Every held expert on every token, weighted by its gate or by zero."""
    w, idx = dropless.route_topk(x, p["router"], K)
    out = jnp.zeros_like(x)
    for e in range(first, first + held):
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        out = out + gate[:, None] * dropless.swiglu(
            x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return out


def held_slice(p, first, held):
    return {n: p[n][first:first + held] for n in ("w_gate", "w_up", "w_down")}


def _close(got, want, what):
    assert float(jnp.max(jnp.abs(got - want))) <= _TOL * float(jnp.max(jnp.abs(want))), what


def test_route_topk_renormalises_over_all_the_chosen():
    p, x = layer_params(0), tokens(0)
    w, idx = dropless.route_topk(x, p["router"], K)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    assert w.shape == idx.shape == (T, K) and idx.dtype == jnp.int32
    _close(jnp.sum(w, -1), jnp.ones(T), "weights sum to one")
    picked = jnp.take_along_axis(probs, idx, axis=-1)
    _close(w, picked / jnp.sum(picked, -1, keepdims=True), "weights are the softmax's")
    raw, _ = dropless.route_topk(x, p["router"], K, renormalize=False)
    _close(raw, picked, "norm_topk_prob off")


@pytest.mark.parametrize("first,held", ((0, E), (0, 4), (4, 4), (12, 4), (5, 1)))
def test_held_experts_match_the_dense_masked_sum(first, held, impl):
    p, x = layer_params(1), tokens(1)
    w, idx = dropless.route_topk(x, p["router"], K)
    got, counters = jax.jit(lambda x, w, idx, ex: dropless.dropless_experts(
        x, w, idx, ex, first_expert=first, impl=impl))(x, w, idx, held_slice(p, first, held))
    _close(got, dense_routed(x, p, first, held), f"experts {first}..{first + held}")
    rows = int(jnp.sum((idx >= first) & (idx < first + held)))
    assert int(counters["expert_rows"]) == rows and int(counters["dropped_rows"]) == 0


@pytest.mark.parametrize("first,held", ((0, E), (4, 4)))
def test_a_skewed_router_drops_nothing(first, held, impl):
    """One expert takes a row of nearly every token; none is dropped."""
    p, x = layer_params(2, router_skew=0.3), tokens(2, skewed=True)
    w, idx = dropless.route_topk(x, p["router"], K)
    share = float(jnp.mean(jnp.any(idx == 5, axis=-1)))
    assert share > 0.9, share
    got, counters = dropless.dropless_experts(
        x, w, idx, held_slice(p, first, held), first_expert=first, impl=impl)
    _close(got, dense_routed(x, p, first, held), "skewed")
    assert int(counters["dropped_rows"]) == 0
    assert float(counters["expert_load_max_over_mean"]) > 1.5   # of at most 4 held experts


def test_gradients_match_the_dense_masked_sum(impl):
    p, x = layer_params(3), tokens(3)
    ct = jax.random.normal(jax.random.PRNGKey(9), (T, D))

    def program(x, p):
        w, idx = dropless.route_topk(x, p["router"], K)
        y, _ = dropless.dropless_experts(x, w, idx, held_slice(p, 4, 8), first_expert=4,
                                         impl=impl)
        return jnp.sum(y * ct)

    got = jax.grad(program, argnums=(0, 1))(x, p)
    want = jax.grad(lambda x, p: jnp.sum(dense_routed(x, p, 4, 8) * ct), argnums=(0, 1))(x, p)
    _close(got[0], want[0], "dx")
    for name in ("router", "w_gate", "w_up", "w_down"):
        _close(got[1][name], want[1][name], f"d{name}")


def test_a_tight_rows_bound_counts_what_it_cuts(impl):
    p, x = layer_params(4), tokens(4)
    w, idx = dropless.route_topk(x, p["router"], K)
    rows = int(jnp.sum(idx < 8))
    _, loose = dropless.dropless_experts(x, w, idx, held_slice(p, 0, 8), rows_bound=rows,
                                         impl=impl)
    _, tight = dropless.dropless_experts(x, w, idx, held_slice(p, 0, 8), rows_bound=rows - 7,
                                         impl=impl)
    assert int(loose["dropped_rows"]) == 0 and int(tight["dropped_rows"]) == 7
    assert int(tight["expert_rows"]) == rows


def _reference_cfg(held, first):
    return {"num_experts_per_tok": K, "norm_topk_prob": True, "num_experts": held,
            "first_expert": first}


@pytest.mark.parametrize("shares", (16, 4, 2))
def test_the_shares_add_up_to_the_uncut_reference_layer(shares, impl):
    """Expert parallelism over ``shares`` chips: each holds E / shares experts,
    routes over all E and computes its own part; the shared expert is computed
    alike on every chip and counted once. The sum is the whole layer, as the
    benchmark's plain reference gives it with every expert held."""
    p, x = layer_params(5), tokens(5)
    whole = reference.moe(x, p, _reference_cfg(E, 0), "float32")
    held = E // shares
    total = jnp.zeros_like(x)
    for rank in range(shares):
        w, idx = dropless.route_topk(x, p["router"], K)
        part, _ = dropless.dropless_experts(
            x, w, idx, held_slice(p, rank * held, held), first_expert=rank * held, impl=impl)
        total = total + part
    total = total + dropless.shared_expert(
        x, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"], p["shared_score"])
    _close(total, whole, f"{shares} shares")


@pytest.mark.parametrize("first,held", ((0, 4), (8, 8)))
def test_one_share_matches_the_reference_given_the_same_share(first, held, impl):
    p, x = layer_params(6), tokens(6)
    mine = dict(p, **held_slice(p, first, held))
    got, _ = dropless.dropless_moe(x, mine, top_k=K, first_expert=first, impl=impl)
    _close(got, reference.moe(x, mine, _reference_cfg(held, first), "float32"), "one share")


def test_scopes(impl):
    p, x = layer_params(7), tokens(7)
    hlo = jax.jit(lambda x, p: dropless.dropless_moe(x, p, top_k=K, impl=impl)[0]).lower(
        x, p).compile().as_text()
    for scope in ("moe/moe_route", "moe/moe_dispatch", "moe/moe_experts",
                  "moe/moe_shared", "moe/moe_combine"):
        assert scope in hlo, scope


def _poisoned(real):
    """The grouped matmul as the chip runs it: rows that belong to no group are
    left unspecified, in the result and in the cotangent of the rows operand
    (``ragged_dot`` on the CPU leaves them zero, which hid a wrong dx until the
    chip run of PR 26; the kernels leave whatever the buffer held). Here they are
    set to 1e30."""
    def outside(group_sizes, n):
        return (jnp.arange(n) >= jnp.sum(group_sizes))[:, None]

    @jax.custom_vjp
    def poisoned(a, w, group_sizes):
        out = real(a, w, group_sizes, preferred_element_type=jnp.float32)
        return jnp.where(outside(group_sizes, a.shape[0]), 1e30, out)

    def fwd(a, w, group_sizes):
        return poisoned(a, w, group_sizes), (a, w, group_sizes)

    def bwd(res, ct):
        a, w, group_sizes = res
        _, pull = jax.vjp(lambda a, w: real(a, w, group_sizes,
                                            preferred_element_type=jnp.float32), a, w)
        da, dw = pull(jnp.where(outside(group_sizes, a.shape[0]), 0.0, ct))
        return jnp.where(outside(group_sizes, a.shape[0]), 1e30, da), dw, None

    poisoned.defvjp(fwd, bwd)
    return lambda a, w, group_sizes, **kw: poisoned(a, w, group_sizes)


def test_rows_of_no_group_never_reach_the_result_or_its_gradients(monkeypatch, impl):
    monkeypatch.setattr(dropless, "_grouped_matmul", _poisoned(
        functools.partial(dropless._grouped_matmul, impl=impl)))
    p, x = layer_params(8), tokens(8)
    ct = jax.random.normal(jax.random.PRNGKey(10), (T, D))

    def program(x, p):
        w, idx = dropless.route_topk(x, p["router"], K)
        y, _ = dropless.dropless_experts(x, w, idx, held_slice(p, 4, 4), first_expert=4)
        return jnp.sum(y * ct)

    got = jax.grad(program, argnums=(0, 1))(x, p)
    want = jax.grad(lambda x, p: jnp.sum(dense_routed(x, p, 4, 4) * ct), argnums=(0, 1))(x, p)
    _close(got[0], want[0], "dx")
    for name in ("router", "w_gate", "w_up", "w_down"):
        _close(got[1][name], want[1][name], f"d{name}")


# -- a model without a shared expert (PR 31) ---------------------------------------

_SHARED = ("shared_w_gate", "shared_w_up", "shared_w_down", "shared_score")


@pytest.mark.parametrize("first,held", ((0, E), (4, 4), (12, 4)))
def test_without_a_shared_expert_the_layer_is_its_routed_part(first, held, impl):
    p, x = layer_params(8), tokens(8)
    mine = {k: v for k, v in dict(p, **held_slice(p, first, held)).items() if k not in _SHARED}
    got, counters = dropless.dropless_moe(x, mine, top_k=K, first_expert=first, impl=impl)
    w, idx = dropless.route_topk(x, p["router"], K)
    routed, want_counters = dropless.dropless_experts(
        x, w, idx, held_slice(p, first, held), first_expert=first, impl=impl)
    assert got.dtype == x.dtype and bool(jnp.array_equal(got, routed.astype(x.dtype)))
    _close(got, dense_routed(x, p, first, held), "routed alone")
    assert {k: float(v) for k, v in counters.items()} == \
        {k: float(v) for k, v in want_counters.items()}


def test_with_a_shared_expert_the_layer_is_what_it_was(impl):
    """Bit for bit: the routed part plus the gated shared expert, summed in
    float32 and cast once, as before the branch."""
    p, x = layer_params(9), tokens(9)
    got, _ = dropless.dropless_moe(x, p, top_k=K, impl=impl)
    w, idx = dropless.route_topk(x, p["router"], K)
    routed, _ = dropless.dropless_experts(x, w, idx, held_slice(p, 0, E), impl=impl)
    shared = dropless.shared_expert(*(p[k] if k != "x" else x for k in ("x",) + _SHARED))
    assert bool(jnp.array_equal(got, (routed + shared.astype(jnp.float32)).astype(x.dtype)))


def test_the_shared_span_opens_only_where_there_is_a_shared_expert(impl):
    p, x = layer_params(7), tokens(7)
    bare = {k: v for k, v in p.items() if k not in _SHARED}
    hlo = jax.jit(lambda x, p: dropless.dropless_moe(x, p, top_k=K, impl=impl)[0]).lower(
        x, bare).compile().as_text()
    for scope in ("moe/moe_route", "moe/moe_dispatch", "moe/moe_experts", "moe/moe_combine"):
        assert scope in hlo, scope
    assert "moe_shared" not in hlo
