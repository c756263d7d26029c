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


# -- the second router, the second expert form, the latent (PR 33) -----------------

DL = 128                 # the latent's width
K_MANY = 6               # more choices than a narrow share holds experts


def latent_params(seed):
    """A LatentMoE part: two-matrix experts in a ``DL``-wide latent, an ungated
    shared expert on the full width."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda k, *shape: jax.random.normal(k, shape) * 0.2
    return {"router": n(ks[0], D, E), "fc1_latent": n(ks[1], D, DL), "fc2_latent": n(ks[2], DL, D),
            "w_up": n(ks[3], E, DL, F), "w_down": n(ks[4], E, F, DL),
            "shared_w_up": n(ks[5], D, F), "shared_w_down": n(ks[6], F, D)}


def dense_sigmoid_weights(x, router, k, bias, scale, renormalize=True):
    """``(T, E)``: each expert's weight by the formula, zero where it was not
    chosen; the choice by a full argsort of score + bias."""
    scores = jax.nn.sigmoid(x @ router)
    ranked = jnp.argsort(-(scores + (0.0 if bias is None else bias)), axis=-1)[:, :k]
    chosen = jnp.sum(jax.nn.one_hot(ranked, router.shape[1]), axis=1) > 0
    picked = jnp.where(chosen, scores, 0.0)
    if renormalize:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return picked * scale


def dense_relu2_routed(x, gates, p, first, held):
    out = jnp.zeros((x.shape[0], p["w_down"].shape[-1]))
    for e in range(first, first + held):
        out = out + gates[:, e:e + 1] * dropless.relu2_mlp(x, p["w_up"][e], p["w_down"][e])
    return out


def scattered(w, idx):
    """``(T, k)`` weights and ids as ``(T, E)``."""
    return jnp.sum(jax.nn.one_hot(idx, E) * w[..., None], axis=1)


@pytest.mark.parametrize("bias_seed,scale,renormalize", ((None, 1.0, True), (3, 5.0, True),
                                                         (3, 2.5, False)))
def test_route_sigmoid_is_the_dense_formula(bias_seed, scale, renormalize):
    p, x = layer_params(10), tokens(10)
    bias = None if bias_seed is None else jax.random.normal(jax.random.PRNGKey(bias_seed), (E,))
    w, idx = dropless.route_sigmoid(x, p["router"], K, bias=bias, scale=scale,
                                    renormalize=renormalize)
    assert w.shape == idx.shape == (T, K) and idx.dtype == jnp.int32 and w.dtype == jnp.float32
    _close(scattered(w, idx), dense_sigmoid_weights(x, p["router"], K, bias, scale, renormalize),
           "weights")
    if renormalize:
        _close(jnp.sum(w, -1), jnp.full((T,), scale), "the chosen sum to the scale")


def test_the_bias_moves_the_choice_and_never_the_weights_or_the_gradient():
    p, x = layer_params(11), tokens(11)
    bias = jnp.zeros((E,)).at[3].set(10.0)          # expert 3 is now everyone's first choice
    w, idx = dropless.route_sigmoid(x, p["router"], K, bias=bias, renormalize=False)
    assert bool(jnp.all(jnp.any(idx == 3, axis=-1)))
    scores = jax.nn.sigmoid(x @ p["router"])
    _close(w, jnp.take_along_axis(scores, idx, axis=-1), "the weights are plain scores")
    assert float(jnp.max(w)) <= 1.0
    grad = jax.grad(lambda b: jnp.sum(dropless.route_sigmoid(x, p["router"], K, bias=b)[0]))(bias)
    assert float(jnp.max(jnp.abs(grad))) == 0.0
    got = jax.grad(lambda r: jnp.sum(dropless.route_sigmoid(x, r, K, bias=bias, scale=5.0)[0] ** 2))(
        p["router"])
    want = jax.grad(lambda r: jnp.sum(dense_sigmoid_weights(x, r, K, bias, 5.0) ** 2))(p["router"])
    _close(got, want, "d router")


@pytest.mark.parametrize("first,held,k", ((0, E, K), (4, 4, K), (12, 4, K_MANY), (5, 1, K_MANY),
                                          (0, 2, K_MANY)))
def test_two_matrix_relu2_experts_match_the_dense_masked_sum(first, held, k, impl):
    """Also where a token has more choices than the share holds experts
    (``k > held``): the held choices are compacted a token before the sort."""
    p, x = latent_params(12), tokens(12)[:, :DL]
    w, idx = dropless.route_sigmoid(tokens(12), p["router"], k, scale=5.0)
    experts = {n: p[n][first:first + held] for n in ("w_up", "w_down")}
    got, counters = jax.jit(lambda x, w, idx, ex: dropless.dropless_experts(
        x, w, idx, ex, first_expert=first, impl=impl))(x, w, idx, experts)
    _close(got, dense_relu2_routed(x, scattered(w, idx), p, first, held), "relu2 experts")
    rows = int(jnp.sum((idx >= first) & (idx < first + held)))
    assert int(counters["expert_rows"]) == rows and int(counters["dropped_rows"]) == 0


def test_relu2_experts_gradients_where_choices_outnumber_the_held(impl):
    p, x = latent_params(13), tokens(13)[:, :DL]
    ct = jax.random.normal(jax.random.PRNGKey(14), (T, DL))
    first, held = 8, 4

    def program(x, p):
        w, idx = dropless.route_sigmoid(tokens(13), p["router"], K_MANY, scale=5.0)
        y, _ = dropless.dropless_experts(
            x, w, idx, {n: p[n][first:first + held] for n in ("w_up", "w_down")},
            first_expert=first, impl=impl)
        return jnp.sum(y * ct)

    def dense(x, p):
        gates = dense_sigmoid_weights(tokens(13), p["router"], K_MANY, None, 5.0)
        return jnp.sum(dense_relu2_routed(x, gates, p, first, held) * ct)

    got, want = (jax.grad(f, argnums=(0, 1))(x, p) for f in (program, dense))
    _close(got[0], want[0], "dx")
    for name in ("router", "w_up", "w_down"):
        _close(got[1][name], want[1][name], f"d{name}")


def test_a_tight_bound_counts_what_it_cuts_where_choices_outnumber_the_held(impl):
    p, x = latent_params(15), tokens(15)[:, :DL]
    w, idx = dropless.route_sigmoid(tokens(15), p["router"], K_MANY)
    experts = {n: p[n][:4] for n in ("w_up", "w_down")}
    rows = int(jnp.sum(idx < 4))
    _, counters = dropless.dropless_experts(x, w, idx, experts, rows_bound=rows - 7, impl=impl)
    assert int(counters["expert_rows"]) == rows and int(counters["dropped_rows"]) == 7


@pytest.mark.parametrize("renormalize", (True, False))
@pytest.mark.parametrize("first,held", ((0, E), (4, 4), (0, 2)))
def test_the_latent_layer_is_its_formula(first, held, renormalize, impl):
    """``fc2(sum_k w_k relu2_k(fc1 x)) + relu2_shared(x)``, the router and the
    shared expert on the full width; ``renormalize=`` reaches whichever router
    the layer is given."""
    p, x = latent_params(16), tokens(16)
    route = functools.partial(dropless.route_sigmoid, scale=5.0)
    mine = dict(p, w_up=p["w_up"][first:first + held], w_down=p["w_down"][first:first + held])
    got, counters = dropless.dropless_moe(x, mine, top_k=K_MANY, first_expert=first,
                                          renormalize=renormalize, route=route, impl=impl)
    gates = dense_sigmoid_weights(x, p["router"], K_MANY, None, 5.0, renormalize)
    want = dense_relu2_routed(x @ p["fc1_latent"], gates, p, first, held) @ p["fc2_latent"] \
        + dropless.relu2_mlp(x, p["shared_w_up"], p["shared_w_down"])
    _close(got, want, "latent layer")
    assert got.shape == x.shape and int(counters["dropped_rows"]) == 0


def test_the_latent_span_opens_only_where_there_is_a_latent(impl):
    p, x = latent_params(17), tokens(17)
    lowered = lambda p: jax.jit(lambda x, p: dropless.dropless_moe(
        x, p, top_k=K, route=dropless.route_sigmoid, impl=impl)[0]).lower(x, p).compile().as_text()
    hlo = lowered(p)
    for scope in ("moe/moe_route", "moe/moe_latent", "moe/moe_dispatch", "moe/moe_experts",
                  "moe/moe_shared", "moe/moe_combine"):
        assert scope in hlo, scope
    q = layer_params(17)
    assert "moe_latent" not in jax.jit(lambda x, p: dropless.dropless_moe(
        x, p, top_k=K, impl=impl)[0]).lower(x, q).compile().as_text()


def test_swiglu_softmax_layers_trace_what_they_traced(impl):
    """No op of the new forms enters the jaxpr of a SwiGLU / softmax layer: one
    ``top_k`` (the router's: the compaction of ``k > held`` would be a second),
    no square of a relu, no sigmoid but two silus and the shared score (PR 33)."""
    p, x = layer_params(18), tokens(18)
    trace = lambda p: str(jax.make_jaxpr(
        lambda x, p: dropless.dropless_moe(x, p, top_k=K, impl=impl))(x, p))
    text = trace(p)
    assert text.count("top_k") == 1 and "square" not in text and "integer_pow" not in text
    assert text.count("logistic") == 3        # silu of the experts and the shared one, the score
    bare = trace({k: v for k, v in p.items() if k not in _SHARED})
    assert bare.count("top_k") == 1 and bare.count("logistic") == 1 and "square" not in bare
