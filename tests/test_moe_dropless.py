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


# -- the sort's two sides walk only the rows that land (PR 34) ----------------------

ROWS = 64                # a sorted-rows buffer of the two functions' own tests


def _rows_case(seed=20, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    token = jax.random.randint(ks[0], (ROWS,), 0, T)
    n = lambda k, *shape: jax.random.normal(k, shape).astype(dtype)
    return {"token": token, "src": n(ks[1], T, D), "rows": n(ks[2], ROWS, D),
            "scale": jax.random.normal(ks[3], (ROWS,)), "ct_rows": n(ks[4], ROWS, D),
            "ct_out": n(ks[5], T, D)}


def _plain_gather(src, token, n_valid, scale=None):
    got = src[token] if scale is None else src[token] * scale[:, None]
    return jnp.where((jnp.arange(token.shape[0]) < n_valid)[:, None], got, 0)


def _plain_scatter_add(rows, token, n_valid, scale=None):
    rows = jnp.where((jnp.arange(token.shape[0]) < n_valid)[:, None], rows, 0)
    return jnp.zeros((T, rows.shape[1]), rows.dtype).at[token].add(
        rows if scale is None else rows * scale[:, None])


# n_valid: none, one row, on a tile's edge, off it, inside the last tile, the whole buffer;
# tile: divides the buffer, does not (the last tile is moved back), is larger than it
_FILLS = (0, 1, 16, 17, 50, ROWS)
_TILES = (16, 24, 100)


@pytest.mark.parametrize("scaled", (False, True), ids=("plain", "scaled"))
@pytest.mark.parametrize("tile", _TILES)
@pytest.mark.parametrize("n_valid", _FILLS)
def test_gather_rows_is_plain_indexing_up_to_the_rows_that_landed(
        monkeypatch, n_valid, tile, scaled):
    monkeypatch.setattr(dropless, "_row_tile", lambda D: tile)
    c = _rows_case()
    scale = c["scale"] if scaled else None
    got = jax.jit(lambda src, n, scale: dropless.gather_rows(
        src, c["token"], n, scale=scale))(c["src"], n_valid, scale)
    want = _plain_gather(c["src"], c["token"], n_valid, scale)
    assert got.dtype == want.dtype and got.shape == (ROWS, D)
    if n_valid:                                                 # (a zero oracle has no scale)
        _close(got, want, "rows")
    assert not bool(jnp.any(got[n_valid:]))                     # the tail is zero, not small
    args = (c["src"],) + ((scale,) if scaled else ())
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * c["ct_rows"]),
                               argnums=tuple(range(len(args))))(*args)
    got = grads(lambda src, scale=None: dropless.gather_rows(
        src, c["token"], n_valid, scale=scale))
    want = grads(lambda src, scale=None: _plain_gather(src, c["token"], n_valid, scale))
    for g, w, what in zip(got, want, ("d src", "d scale")):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert float(jnp.max(jnp.abs(g - w))) <= _TOL * max(float(jnp.max(jnp.abs(w))), 1.0), what


@pytest.mark.parametrize("scaled", (False, True), ids=("plain", "scaled"))
@pytest.mark.parametrize("tile", _TILES)
@pytest.mark.parametrize("n_valid", _FILLS)
def test_scatter_add_rows_is_plain_indexing_up_to_the_rows_that_landed(
        monkeypatch, n_valid, tile, scaled):
    monkeypatch.setattr(dropless, "_row_tile", lambda D: tile)
    c = _rows_case(21)
    scale = c["scale"] if scaled else None
    got = jax.jit(lambda rows, n, scale: dropless.scatter_add_rows(
        rows, c["token"], n, scale=scale, out_rows=T))(c["rows"], n_valid, scale)
    want = _plain_scatter_add(c["rows"], c["token"], n_valid, scale)
    assert got.dtype == want.dtype and got.shape == (T, D)
    assert float(jnp.max(jnp.abs(got - want))) <= _TOL * max(float(jnp.max(jnp.abs(want))), 1.0)
    args = (c["rows"],) + ((scale,) if scaled else ())
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * c["ct_out"]),
                               argnums=tuple(range(len(args))))(*args)
    got = grads(lambda rows, scale=None: dropless.scatter_add_rows(
        rows, c["token"], n_valid, scale=scale, out_rows=T))
    want = grads(lambda rows, scale=None: _plain_scatter_add(rows, c["token"], n_valid, scale))
    for g, w, what in zip(got, want, ("d rows", "d scale")):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert float(jnp.max(jnp.abs(g - w))) <= _TOL * max(float(jnp.max(jnp.abs(w))), 1.0), what
        assert not bool(jnp.any(g[n_valid:])), what             # nothing flows into the tail


@pytest.mark.parametrize("tile", _TILES)
def test_what_the_tail_holds_is_never_read_into_a_sum(monkeypatch, tile):
    """NaN in ``rows`` and in the cotangent of the gathered rows from ``n_valid``
    on (the scale is the router's weights: finite everywhere): values and
    cotangents are those of a clean tail."""
    monkeypatch.setattr(dropless, "_row_tile", lambda D: tile)
    c, n_valid = _rows_case(22), 37
    tail = (jnp.arange(ROWS) >= n_valid)
    nan = lambda a: jnp.where(tail.reshape((-1,) + (1,) * (a.ndim - 1)), jnp.nan, a)
    add = lambda rows, scale: dropless.scatter_add_rows(
        rows, c["token"], n_valid, scale=scale, out_rows=T)
    got, pull = jax.vjp(add, nan(c["rows"]), c["scale"])
    want, pull_clean = jax.vjp(add, c["rows"], c["scale"])
    assert bool(jnp.array_equal(got, want))
    for g, w in zip(pull(c["ct_out"]), pull_clean(c["ct_out"])):
        assert bool(jnp.array_equal(g, w))
    _, pull = jax.vjp(lambda src: dropless.gather_rows(src, c["token"], n_valid),
                      c["src"])
    assert bool(jnp.array_equal(pull(nan(c["ct_rows"]))[0], pull(jnp.where(
        tail[:, None], 0, c["ct_rows"]))[0]))


def test_the_sums_are_taken_where_they_were(monkeypatch):
    """bfloat16 rows: the gather rounds ``src * scale`` once from float32; the
    combine's accumulator is float32 and its product ``w * y`` is float32; the
    dispatch's transpose sums a token's rows in float32 across the tiles they
    lie in and rounds once, as XLA's one-shot scatter-add of bfloat16 does."""
    monkeypatch.setattr(dropless, "_row_tile", lambda D: 16)
    c, n_valid, bf = _rows_case(23, jnp.bfloat16), 50, jnp.bfloat16
    got = dropless.gather_rows(c["src"], c["token"], n_valid, scale=c["scale"])
    want = _plain_gather(c["src"].astype(jnp.float32), c["token"], n_valid, c["scale"]).astype(bf)
    assert got.dtype == bf and bool(jnp.array_equal(got, want))
    got = dropless.scatter_add_rows(c["rows"], c["token"], n_valid, scale=c["scale"],
                                    out_rows=T, out_dtype=jnp.float32)
    want = _plain_scatter_add(c["rows"].astype(jnp.float32), c["token"], n_valid, c["scale"])
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-6 * float(jnp.max(jnp.abs(want)))
    d_src = jax.grad(lambda s: jnp.sum(dropless.gather_rows(s, c["token"], n_valid)
                                       .astype(jnp.float32)))(c["src"])
    assert d_src.dtype == bf
    _, pull = jax.vjp(lambda s: dropless.gather_rows(s, c["token"], n_valid), c["src"])
    once = _plain_scatter_add(c["ct_rows"].astype(jnp.float32), c["token"], n_valid).astype(bf)
    assert bool(jnp.array_equal(pull(c["ct_rows"])[0], once))
    d_rows, d_scale = jax.grad(lambda r, s: jnp.sum(dropless.scatter_add_rows(
        r, c["token"], n_valid, scale=s, out_rows=T, out_dtype=jnp.float32)),
        argnums=(0, 1))(c["rows"], c["scale"])
    assert d_rows.dtype == bf and d_scale.dtype == jnp.float32
    assert bool(jnp.array_equal(d_rows, jnp.where((jnp.arange(ROWS) < n_valid)[:, None],
                                                  c["scale"][:, None], 0).astype(bf)
                                * jnp.ones((ROWS, D), bf)))


def test_the_loops_are_booked_once_a_traced_layer():
    from beforeholiday_tpu import monitor

    before = {(r["kernel"], r["key"]): r["traces"] for r in monitor.tile_records()
              if r["op"] == "moe_rows"}
    p, x = layer_params(24), tokens(24)
    w, idx = dropless.route_topk(x, p["router"], K)
    jax.jit(jax.grad(lambda x: jnp.sum(dropless.dropless_experts(
        x, w, idx, held_slice(p, 0, 8), rows_bound=200)[0]))).lower(x)
    after = {(r["kernel"], r["key"]): r for r in monitor.tile_records() if r["op"] == "moe_rows"}
    tile = min(200, dropless._row_tile(D))
    for kernel in ("gather", "scatter_add"):
        key = (kernel, str((200, D, "float32", tile)))
        assert after[key]["traces"] == before.get(key, 0) + 1, after
        assert after[key]["total"] == -(-200 // tile)


# ... and the layer end to end, against every held expert on every token

def _dense_layer(x, p, first, held, k, relu2):
    """Softmax top-k weights scattered to ``(T, E)``, every held expert on every
    token."""
    w, idx = dropless.route_topk(x, p["router"], k)
    gates = scattered(w, idx)
    out = jnp.zeros_like(x)
    for e in range(first, first + held):
        y = dropless.relu2_mlp(x, p["w_up"][e], p["w_down"][e]) if relu2 else \
            dropless.swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
        out = out + gates[:, e:e + 1] * y
    return out


# (case, first, held, k, rows_bound, tile, relu2 experts)
_LAYER_CASES = (
    ("k_under_held", 4, 8, K, None, 64, False),
    ("k_over_held_compacted", 12, 4, K_MANY, None, 64, True),
    ("one_held_expert_tile_over_buffer", 5, 1, K, None, 1024, False),
    ("bound_on_a_tile_edge", 0, 8, K, 256, 64, False),
    ("bound_off_a_tile_edge", 0, 8, K, 250, 48, True),
    ("tile_larger_than_the_buffer", 4, 4, K, 120, 4096, False),
)


@pytest.mark.parametrize("case,first,held,k,rows_bound,tile,relu2", _LAYER_CASES,
                         ids=[c[0] for c in _LAYER_CASES])
def test_the_layer_matches_the_dense_sum_in_values_and_cotangents(
        monkeypatch, impl, case, first, held, k, rows_bound, tile, relu2):
    """``y``, ``dx``, the router's cotangent (through the weights) and the
    experts' matrices, with the grouped matmul leaving 1e30 in the rows of no
    group, forward and backward (``_poisoned``)."""
    monkeypatch.setattr(dropless, "_row_tile", lambda D: tile)
    monkeypatch.setattr(dropless, "_grouped_matmul", _poisoned(
        functools.partial(dropless._grouped_matmul, impl=impl)))
    p, x = layer_params(25), tokens(25)
    ct = jax.random.normal(jax.random.PRNGKey(26), (T, D))
    names = ("w_up", "w_down") if relu2 else ("w_gate", "w_up", "w_down")

    def program(x, p):
        w, idx = dropless.route_topk(x, p["router"], k)
        y, counters = dropless.dropless_experts(
            x, w, idx, {n: p[n][first:first + held] for n in names}, first_expert=first,
            rows_bound=rows_bound)
        return jnp.sum(y * ct), (y, counters)

    (_, (y, counters)), got = jax.jit(jax.value_and_grad(program, argnums=(0, 1), has_aux=True))(x, p)
    assert int(counters["dropped_rows"]) == 0, case
    want_y = _dense_layer(x, p, first, held, k, relu2)
    want = jax.grad(lambda x, p: jnp.sum(_dense_layer(x, p, first, held, k, relu2) * ct),
                    argnums=(0, 1))(x, p)
    _close(y, want_y, "y")
    _close(got[0], want[0], "dx")
    for name in ("router",) + names:
        _close(got[1][name], want[1][name], f"d{name}")


@pytest.mark.parametrize("tile", (32, 64, 4096))
def test_an_expert_with_no_rows_and_a_token_with_no_landed_choice(monkeypatch, impl, tile):
    """The router never chooses expert 6 (its column is far below the rest), and
    the tokens whose every choice is absent from the share get a zero row."""
    monkeypatch.setattr(dropless, "_row_tile", lambda D: tile)
    p, x = layer_params(27), tokens(27).at[:, 0].set(1.0)
    p = dict(p, router=p["router"].at[:, 6].set(0.0).at[0, 6].set(-50.0))   # its logit is -50
    w, idx = dropless.route_topk(x, p["router"], K)
    first, held = 4, 4
    assert not bool(jnp.any(idx == 6))
    unreached = ~jnp.any((idx >= first) & (idx < first + held), axis=-1)
    assert int(jnp.sum(unreached)) > 0
    y, counters = dropless.dropless_experts(x, w, idx, held_slice(p, first, held),
                                            first_expert=first, impl=impl)
    _close(y, dense_routed(x, p, first, held), "y")
    assert not bool(jnp.any(jnp.where(unreached[:, None], y, 0)))
    assert int(counters["dropped_rows"]) == 0


@pytest.mark.parametrize("tile", (32, 4096))
def test_no_choice_lands_at_all(monkeypatch, impl, tile):
    """``n_valid = 0``: no trip of either loop; the part is zero and so is every
    cotangent it hands back."""
    monkeypatch.setattr(dropless, "_row_tile", lambda D: tile)
    p, x = layer_params(28), tokens(28)
    w, idx = dropless.route_topk(x, p["router"], K)
    ex = held_slice(p, 0, 2)
    program = lambda x, w, ex: dropless.dropless_experts(
        x, w, idx, ex, first_expert=E + 3, impl=impl)         # ids the router never gives
    (y, counters), pull = jax.vjp(program, x, w, ex)
    assert int(counters["expert_rows"]) == 0 and not bool(jnp.any(y))
    for g in jax.tree.leaves(pull((jnp.ones_like(y), jax.tree.map(jnp.zeros_like, counters)))):
        assert not bool(jnp.any(g))


@pytest.mark.parametrize("tile", (32, 48, 4096))
def test_a_buffer_that_overflows_is_full_and_counts_the_rest(monkeypatch, impl, tile):
    """``n_valid = R``: every tile is walked; the assignments beyond the buffer
    are the last of the sort (the highest experts' last tokens), counted, and
    what landed is summed as the dense sum over exactly those."""
    monkeypatch.setattr(dropless, "_row_tile", lambda D: tile)
    p, x = layer_params(29), tokens(29)
    w, idx = dropless.route_topk(x, p["router"], K)
    first, held = 0, 8
    landed = (idx >= first) & (idx < first + held)
    rows = int(jnp.sum(landed))
    R = rows - 19
    y, counters = dropless.dropless_experts(x, w, idx, held_slice(p, first, held),
                                            first_expert=first, rows_bound=R, impl=impl)
    assert int(counters["dropped_rows"]) == 19 and int(counters["expert_rows"]) == rows
    # the kept assignments: the first R in (expert, token) order
    order = jnp.argsort(jnp.where(landed, idx, E).reshape(-1), stable=True)[:R]
    kept = jnp.zeros((T * K,), bool).at[order].set(True).reshape(T, K)
    gates = scattered(jnp.where(kept, w, 0.0), idx)
    want = jnp.zeros_like(x)
    for e in range(first, first + held):
        want = want + gates[:, e:e + 1] * dropless.swiglu(
            x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    _close(y, want, "what landed")


def _row_movers(jaxpr, inside=False, found=None):
    """``[(primitive, inside a while?, shapes)]`` of every gather and scatter of
    two-dimensional row blocks in ``jaxpr`` and what it calls."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("gather", "scatter-add", "scatter_add", "scatter"):
            moved = eqn.outvars[0].aval if name == "gather" else eqn.invars[2].aval
            if moved.ndim == 2:
                found.append((name, inside, moved.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _row_movers(sub, inside or name == "while", found)
    return found


def test_the_gradient_of_a_layer_moves_rows_inside_loops_only(monkeypatch):
    """Four movements a layer, each a loop: no gather or scatter of ``D``-wide
    rows outside a ``while`` — the buffer's ``R`` rows are never moved by one
    static op — and inside them a tile at a time."""
    R, tile = 256, 64
    monkeypatch.setattr(dropless, "_row_tile", lambda D: tile)
    p, x = layer_params(30), tokens(30)

    def program(x, p):
        y, _ = dropless.dropless_moe(x, p, top_k=K, rows_bound=R)
        return jnp.sum(y)

    jaxpr = jax.make_jaxpr(jax.grad(program, argnums=(0, 1)))(x, p).jaxpr
    moved = [m for m in _row_movers(jaxpr) if m[2][1] == D]
    assert not [m for m in moved if not m[1]], moved
    assert sorted(m[0] for m in moved) == ["gather", "gather", "scatter-add", "scatter-add"], moved
    assert all(m[2] == (tile, D) for m in moved), moved
    # the lowered module holds them as ``while`` ops (a body may be shared)
    assert "stablehlo.while" in jax.jit(jax.grad(program, argnums=(0, 1))).lower(x, p).as_text()


# -- the third shared expert: an ungated SwiGLU (PR 42) -----------------------------

def test_an_ungated_swiglu_shared_expert_is_selected_by_its_keys(impl):
    """``shared_w_gate`` without ``shared_score``: the routed part plus
    ``swiglu(x)``, summed in float32 and cast once, under the ``moe_shared``
    span; not the gated form (which reads ``shared_score``) and not the
    ``relu^2`` pair (which has no ``shared_w_gate``)."""
    p, x = layer_params(11), tokens(11)
    ungated = {k: v for k, v in p.items() if k != "shared_score"}
    route = functools.partial(dropless.route_sigmoid, bias=jnp.zeros((E,)), scale=2.448)
    got, counters = dropless.dropless_moe(x, ungated, top_k=K, route=route, impl=impl)
    w, idx = route(x, p["router"], K, renormalize=True)
    routed, want_counters = dropless.dropless_experts(x, w, idx, held_slice(p, 0, E), impl=impl)
    shared = dropless.swiglu(x, p["shared_w_gate"], p["shared_w_up"], p["shared_w_down"])
    assert got.dtype == x.dtype
    assert bool(jnp.array_equal(got, (routed + shared.astype(jnp.float32)).astype(x.dtype)))
    assert {k: float(v) for k, v in counters.items()} == \
        {k: float(v) for k, v in want_counters.items()}
    size = float(jnp.max(jnp.abs(got)))
    gated, _ = dropless.dropless_moe(x, p, top_k=K, route=route, impl=impl)
    squared, _ = dropless.dropless_moe(
        x, {k: v for k, v in ungated.items() if k != "shared_w_gate"}, top_k=K, route=route,
        impl=impl)
    for other in (gated, squared):
        assert float(jnp.max(jnp.abs(other - got))) > 1e-2 * size
    hlo = jax.jit(lambda x, p: dropless.dropless_moe(
        x, p, top_k=K, route=route, impl=impl)[0]).lower(x, ungated).compile().as_text()
    assert "moe/moe_shared" in hlo
    # a gradient reaches each of its three matrices and no ``shared_score`` is asked for
    grads = jax.grad(lambda p: jnp.sum(jnp.square(dropless.dropless_moe(
        x, p, top_k=K, route=route, impl=impl)[0])))(ungated)
    for name in ("shared_w_gate", "shared_w_up", "shared_w_down"):
        assert float(jnp.max(jnp.abs(grads[name]))) > 0, name
