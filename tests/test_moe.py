"""Mixture-of-Experts subsystem tests.

The keystone is the bitwise-parity contract from ``moe/dispatch.py``: at
sufficient capacity the expert-parallel forward equals the dense no-drop
oracle bitwise, on any (data, tensor, pipe, expert) carve of the 8-device
CPU mesh. Around it: router determinism and the analytic capacity-drop
bound, the Switch aux-loss gradient against a closed-form numpy oracle, the
two-level hierarchical dispatch with its per-tier ledger split, the GPT
``moe_every`` composition, remat boundary tags, and the O6 quantized path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.moe import (
    MoEConfig,
    dense_gates,
    dense_oracle,
    expert_all_to_all,
    expert_ffn,
    init_experts,
    moe_layer,
    route,
    router_logits,
)
from beforeholiday_tpu.monitor import comms
from beforeholiday_tpu.parallel.parallel_state import (
    DATA_AXIS,
    EXPERT_AXIS,
    MOE_MESH_AXIS_NAMES,
    PIPE_AXIS,
    TENSOR_AXIS,
    make_moe_mesh,
)
from beforeholiday_tpu.testing import moe_model as mm


def _smap(fn, mesh, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _bitwise(a, b) -> bool:
    return bool(np.array_equal(np.asarray(a), np.asarray(b)))


def _setup(seed=0, n_experts=8, top_k=2, capacity_factor=8.0,
           D=32, F=64, T=16):
    """Common fixture: params + router weights + tokens, fp32. The huge
    default capacity factor makes drop_fraction exactly 0 (parity regime)."""
    rng = np.random.RandomState(seed)
    cfg = MoEConfig(
        n_experts=n_experts, top_k=top_k, capacity_factor=capacity_factor
    )
    params = init_experts(jax.random.PRNGKey(seed), n_experts, D, F)
    w_router = jnp.asarray(rng.randn(D, n_experts).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(T, D).astype(np.float32))
    return cfg, params, w_router, x


def _oracle_at_slab_rows(x, w_router, params, cfg, rows):
    """``dense_oracle`` batched as the expert-parallel program batches: all
    groups' tokens in one call, zero-padded to ``rows`` — the ``ep *
    capacity`` rows an expert's slab holds after the dispatch all_to_all.
    The oracle's gates are per token (top-k of that token's logits, no
    capacity), so neither the other groups' tokens nor the padding can touch
    a token's output; what the batching changes is the M of the expert
    GEMMs, which now equals the distributed program's."""
    n = x.shape[0]
    xp = jnp.concatenate([x, jnp.zeros((rows - n, x.shape[1]), x.dtype)])
    y, _ = jax.jit(lambda xx: dense_oracle(xx, w_router, params, cfg))(xp)
    return np.asarray(y)[:n]


def _reorder_bound(x, w_router, params, cfg):
    """Elementwise bound on ``|a - b|`` for two float32 evaluations of the
    oracle's output that differ only in the ORDER of the second expert
    GEMM's ``F``-term sums.

    ``out[t,d] = sum_e gate[t,e] * y[e,t,d]`` with ``y = sum_f g*wo + bo``.
    A float32 sum of k products, in any order, with or without FMAs, is
    within ``gamma_k * sum|a_i b_i|`` of the exact sum (``gamma_k = k*u /
    (1 - k*u)``, ``u = 2**-24``; Higham, Accuracy and Stability, §3.1), so
    two orders differ by at most ``2 * gamma_F * S`` with ``S[e,t,d] =
    sum_f |g*wo| + |bo|``. The combine is the same E-term einsum on both
    sides: it carries that difference through its gates and adds its own
    rounding on each side, ``gamma_(E+1) * sum_e gate*|y|`` at most, with
    ``|y| <= S``. Hence ``|a - b| <= 2 * (gamma_F + gamma_(E+1)) * A``, ``A =
    sum_e gate * S``, which ``(F + E + 2) * eps * A`` covers (``eps = 2u``)."""
    f64 = np.float64
    gates = np.asarray(dense_gates(router_logits(x, w_router), cfg)[0], f64)
    h = jnp.einsum("td,edf->etf", x, params["wi"]) + params["bi"][:, None, :]
    g = np.abs(np.asarray(jax.nn.gelu(h), f64))
    S = np.einsum("etf,efd->etd", g, np.abs(np.asarray(params["wo"], f64)))
    S = S + np.abs(np.asarray(params["bo"], f64))[:, None, :]
    A = np.einsum("te,etd->td", gates, S)
    F, E = params["wo"].shape[1], cfg.n_experts
    return (F + E + 2) * float(np.finfo(np.float32).eps) * A


# ---------------------------------------------------------------- config


pytestmark = pytest.mark.moe


def test_config_validation():
    with pytest.raises(ValueError):
        MoEConfig(n_experts=4, top_k=3)
    with pytest.raises(ValueError):
        MoEConfig(n_experts=1)
    cfg = MoEConfig(n_experts=8, top_k=2, capacity_factor=1.25)
    # ceil(2 * 16 * 1.25 / 8) = 5
    assert cfg.capacity(16) == 5
    # tiny groups floor at 1 slot
    assert MoEConfig(n_experts=64, top_k=1, capacity_factor=1.0).capacity(4) == 1


def test_make_moe_mesh_carves():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_moe_mesh(data=2, tensor=2, expert=2)
    assert mesh.axis_names == (DATA_AXIS, EXPERT_AXIS, TENSOR_AXIS)
    assert mesh.devices.shape == (2, 2, 2)
    # degenerate axes drop; the all-ones carve keeps a size-1 data axis
    assert make_moe_mesh().axis_names == (DATA_AXIS,)
    assert make_moe_mesh(pipeline=2, expert=4).axis_names == (
        PIPE_AXIS, EXPERT_AXIS
    )
    # axis order is the canonical MOE_MESH_AXIS_NAMES order
    full = [n for n in MOE_MESH_AXIS_NAMES]
    m = make_moe_mesh(data=2, pipeline=2, expert=2)
    assert list(m.axis_names) == [n for n in full if n != TENSOR_AXIS]
    with pytest.raises((ValueError, RuntimeError)):
        make_moe_mesh(data=0)
    with pytest.raises(RuntimeError):
        make_moe_mesh(data=16, expert=2)  # 32 > 8 devices


# ---------------------------------------------------------------- router


def test_router_determinism_and_gate_normalization():
    cfg, _, w_router, x = _setup()
    logits = router_logits(x, w_router)
    C = cfg.capacity(x.shape[0])
    d1 = jax.jit(lambda l: route(l, cfg, C))(logits)
    d2 = jax.jit(lambda l: route(l, cfg, C))(logits)
    assert _bitwise(d1.dispatch, d2.dispatch)
    assert _bitwise(d1.combine, d2.combine)
    # dispatch is 0/1; each token occupies at most top_k slots
    dis = np.asarray(d1.dispatch)
    assert set(np.unique(dis)) <= {0.0, 1.0}
    assert (dis.sum(axis=(1, 2)) <= cfg.top_k).all()
    # each (expert, slot) holds at most one token
    assert (dis.sum(axis=0) <= 1.0).all()
    # GShard top-2 gates renormalize to 1 over the chosen pair (no drops
    # at this capacity, so every token keeps both choices)
    gates = np.asarray(d1.combine).sum(axis=(1, 2))
    np.testing.assert_allclose(gates, 1.0, rtol=1e-6)


def test_route_matches_dense_gates_at_sufficient_capacity():
    """combine.sum over slots IS the dense gate matrix when nothing drops —
    the keystone identity of the parity chain."""
    for top_k in (1, 2):
        cfg, _, w_router, x = _setup(top_k=top_k)
        logits = router_logits(x, w_router)
        dec = jax.jit(lambda l: route(l, cfg, cfg.capacity(x.shape[0])))(logits)
        gates, aux, z = jax.jit(lambda l: dense_gates(l, cfg))(logits)
        assert float(dec.drop_fraction) == 0.0
        assert _bitwise(jnp.sum(dec.combine, axis=-1), gates)
        assert _bitwise(dec.aux_loss, aux)
        assert _bitwise(dec.z_loss, z)


def test_router_decisions_mesh_independent(devices8):
    """The same token group routes bit-identically standalone and inside an
    expert-parallel shard_map body — routing is per-group by construction."""
    cfg, _, w_router, x4 = _setup(T=64)
    T = 16
    C = cfg.capacity(T)
    mesh = Mesh(np.asarray(devices8[:4]), (EXPERT_AXIS,))
    dist = jax.jit(_smap(
        lambda xl: route(router_logits(xl, w_router), cfg, C).dispatch,
        mesh, (P(EXPERT_AXIS),), P(EXPERT_AXIS),
    ))
    got = np.asarray(dist(x4)).reshape(4, T, cfg.n_experts, C)
    for g in range(4):
        want = jax.jit(
            lambda xg: route(router_logits(xg, w_router), cfg, C).dispatch
        )(x4[g * T:(g + 1) * T])
        assert _bitwise(got[g], want)


def test_capacity_drop_fraction_analytic():
    """Force every token onto the same expert pair and check the kept count
    against the analytic bound min(n_e, capacity), with first-choice-first
    (earlier tokens win) slot assignment."""
    T, E, C = 16, 4, 3
    cfg = MoEConfig(n_experts=E, top_k=2)
    logits = jnp.tile(
        jnp.asarray([4.0, 2.0, 0.0, -2.0], jnp.float32), (T, 1)
    )
    dec = jax.jit(lambda l: route(l, cfg, C))(logits)
    # expert 0 keeps C first choices, expert 1 keeps C second choices
    kept = float(np.asarray(dec.dispatch).sum())
    assert kept == 2 * C
    assert float(dec.drop_fraction) == pytest.approx(
        1.0 - (2 * C) / (cfg.top_k * T)
    )
    # position-based dropping: tokens 0..C-1 keep, the rest drop entirely
    row_kept = np.asarray(dec.dispatch).sum(axis=(1, 2))
    assert (row_kept[:C] == 2.0).all()
    assert (row_kept[C:] == 0.0).all()
    # dropped tokens have all-zero combine rows -> residual pass-through
    comb = np.asarray(dec.combine)
    assert (comb[C:] == 0.0).all()

    # top-1 variant: drop_fraction = 1 - C/T when all tokens pick one expert
    cfg1 = MoEConfig(n_experts=E, top_k=1)
    dec1 = jax.jit(lambda l: route(l, cfg1, C))(logits)
    assert float(dec1.drop_fraction) == pytest.approx(1.0 - C / T)


def test_dropped_tokens_pass_through_residual():
    """moe_layer returns an all-zero y row for dropped tokens: adding the
    residual is exactly the identity for them."""
    T, E = 16, 4
    cfg = MoEConfig(n_experts=E, top_k=1)
    params = init_experts(jax.random.PRNGKey(0), E, 8, 16)
    # router weights that send every token to expert 0
    w_router = jnp.zeros((8, E), jnp.float32).at[:, 0].set(1.0)
    x = jnp.abs(jnp.asarray(
        np.random.RandomState(0).randn(T, 8).astype(np.float32)
    )) + 0.1
    C = 3
    y, aux = jax.jit(
        lambda xx: moe_layer(xx, w_router, params, cfg, capacity=C)
    )(x)
    y = np.asarray(y)
    assert float(aux["moe_drop_fraction"]) > 0.0
    assert (y[C:] == 0.0).all()          # dropped rows contribute nothing
    assert (np.abs(y[:C]) > 0.0).any()   # kept rows do


def test_aux_loss_gradient_vs_numpy_oracle():
    """Switch eq. 4 gradient flows through P only: closed-form numpy
    d/dl[t,i] = (E/T) * (f_i * P[t,i] - P[t,i] * sum_e f_e * P[t,e])."""
    cfg, _, w_router, x = _setup()
    logits = np.asarray(router_logits(x, w_router), np.float64)
    T, E = logits.shape

    g = jax.jit(jax.grad(
        lambda l: route(l, cfg, cfg.capacity(T)).aux_loss
    ))(jnp.asarray(logits, jnp.float32))

    P_ = np.exp(logits - logits.max(-1, keepdims=True))
    P_ /= P_.sum(-1, keepdims=True)
    f = np.zeros(E)
    np.add.at(f, P_.argmax(-1), 1.0 / T)
    inner = (P_ * f[None, :]).sum(-1, keepdims=True)
    want = (E / T) * (P_ * f[None, :] - P_ * inner)
    np.testing.assert_allclose(np.asarray(g), want, rtol=1e-5, atol=1e-8)


def test_z_loss_gradient_vs_numpy_oracle():
    """z-loss = mean(logsumexp^2): d/dl[t,i] = (2/T) * lse_t * P[t,i]."""
    cfg, _, w_router, x = _setup()
    logits = np.asarray(router_logits(x, w_router), np.float64)
    T, E = logits.shape
    g = jax.jit(jax.grad(
        lambda l: route(l, cfg, cfg.capacity(T)).z_loss
    ))(jnp.asarray(logits, jnp.float32))
    lse = np.log(np.exp(logits).sum(-1))
    P_ = np.exp(logits - logits.max(-1, keepdims=True))
    P_ /= P_.sum(-1, keepdims=True)
    want = (2.0 / T) * lse[:, None] * P_
    np.testing.assert_allclose(np.asarray(g), want, rtol=1e-5, atol=1e-8)


# ------------------------------------------------------- bitwise parity


def test_moe_layer_matches_dense_oracle_bitwise():
    cfg, params, w_router, x = _setup()
    y, aux = jax.jit(lambda xx: moe_layer(xx, w_router, params, cfg))(x)
    y_ref, aux_ref = jax.jit(
        lambda xx: dense_oracle(xx, w_router, params, cfg)
    )(x)
    assert float(aux["moe_drop_fraction"]) == 0.0
    assert _bitwise(y, y_ref)
    assert _bitwise(aux["moe_aux_loss"], aux_ref["moe_aux_loss"])
    assert _bitwise(aux["moe_z_loss"], aux_ref["moe_z_loss"])


def test_backward_contract_vs_dense_oracle():
    """Router-weight and token gradients are bitwise (identical per-token
    contraction shapes); expert WEIGHT grads reduce over capacity slots vs
    tokens — different grouping, so tight-allclose only."""
    cfg, params, w_router, x = _setup()

    def loss(layer):
        def f(w, p, xx):
            y, aux = layer(xx, w, p, cfg)
            return jnp.sum(y ** 2) + aux["moe_aux_loss"] + aux["moe_z_loss"]
        return f

    g_moe = jax.jit(jax.grad(loss(
        lambda xx, w, p, c: moe_layer(xx, w, p, c)
    ), argnums=(0, 1, 2)))(w_router, params, x)
    g_ref = jax.jit(jax.grad(loss(
        lambda xx, w, p, c: dense_oracle(xx, w, p, c)
    ), argnums=(0, 1, 2)))(w_router, params, x)

    assert _bitwise(g_moe[0], g_ref[0])   # d/d w_router
    assert _bitwise(g_moe[2], g_ref[2])   # d/d x
    for k in ("wi", "bi", "wo", "bo"):
        np.testing.assert_allclose(
            np.asarray(g_moe[1][k]), np.asarray(g_ref[1][k]),
            rtol=1e-5, atol=1e-9,
        )


def test_expert_parallel_bitwise(devices8):
    """EP over 4 ranks == the dense oracle, forward bitwise — with the
    oracle batched as the distributed program batches.

    Red from the seed to PR 28 against a PER-GROUP oracle (16 tokens a
    call). The routing, the slot assignment and the all_to_all pair are
    exact on both sides (``test_router_decisions_mesh_independent`` holds
    the first two bitwise); the first expert GEMM (K = D = 32) agreed too.
    What differed is the reduction order of the SECOND expert GEMM (K = F =
    64, N = 32) on XLA:CPU under jax 0.9.0: its per-row result depends on
    the slab's M — one order for M <= 32, another from M = 64 up — so
    ``expert_ffn`` is row-stable only among slabs on the same side of that
    line. The distributed slab holds ``ep * C`` = 128 rows an expert, the
    per-group oracle's 16 (and the single-device ``moe_layer``'s C = 32). Run
    at the distributed program's 128 rows the oracle agrees to the bit; the
    per-group oracle stays inside the bound a reordered F-term sum allows
    (``_reorder_bound``)."""
    cfg, params, w_router, _ = _setup()
    T, D = 16, 32
    x = jnp.asarray(
        np.random.RandomState(3).randn(4 * T, D).astype(np.float32)
    )
    C = cfg.capacity(T)
    mesh = Mesh(np.asarray(devices8[:4]), (EXPERT_AXIS,))
    dist = jax.jit(_smap(
        lambda xl, w, p: moe_layer(
            xl, w, p, cfg, expert_axis=EXPERT_AXIS, capacity=C
        )[0],
        mesh, (P(EXPERT_AXIS), P(), P(EXPERT_AXIS)), P(EXPERT_AXIS),
    ))
    got = np.asarray(dist(x, w_router, params))
    assert _bitwise(got, _oracle_at_slab_rows(x, w_router, params, cfg, 4 * C))
    bound = _reorder_bound(x, w_router, params, cfg)
    for g in range(4):
        want, _ = jax.jit(
            lambda xg: dense_oracle(xg, w_router, params, cfg)
        )(x[g * T:(g + 1) * T])
        gap = np.abs(got[g * T:(g + 1) * T] - np.asarray(want))
        assert (gap <= bound[g * T:(g + 1) * T]).all()


@pytest.mark.parametrize("carve", [(2, 1, 1, 4), (2, 2, 1, 2), (1, 2, 2, 2)])
def test_4d_mesh_parity(devices8, carve):
    """The full workload — DP x TP x PP x EP — against the single-device
    reference, bitwise on outputs AND per-group aux rows."""
    dp, tp, pp, ep = carve
    D, F, Tl = 32, 64, 16
    cfg = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0)
    params = mm.init_moe_stack(jax.random.PRNGKey(0), cfg, D, F)
    mesh = make_moe_mesh(data=dp, tensor=tp, pipeline=pp, expert=ep)
    names = set(mesh.axis_names)
    pa = PIPE_AXIS if PIPE_AXIS in names else None
    ta = TENSOR_AXIS if TENSOR_AXIS in names else None
    ea = EXPERT_AXIS if EXPERT_AXIS in names else None
    da = DATA_AXIS if DATA_AXIS in names else None
    groups = dp * ep
    x = jnp.asarray(
        np.random.RandomState(1).randn(groups * Tl, D).astype(np.float32)
    )
    in_spec, out_spec = mm.data_specs(data_axis=da, expert_axis=ea)
    group_axes = tuple(a for a in (da, ea) if a is not None)
    aux_spec = P(group_axes if group_axes else None, None)
    f = jax.jit(_smap(
        lambda xx, pr: mm.moe_stack_forward(
            pr, xx, cfg, pipe_axis=pa, tensor_axis=ta, expert_axis=ea
        ),
        mesh,
        (in_spec, mm.moe_stack_param_specs(tensor_axis=ta, expert_axis=ea)),
        (out_spec, aux_spec),
    ))
    y, aux = f(x, params)
    y_ref, aux_ref = jax.jit(
        lambda xx, pr: mm.moe_stack_reference(
            pr, xx, cfg, groups=groups, tensor=tp
        )
    )(x, params)
    assert _bitwise(y, y_ref)
    assert _bitwise(aux, aux_ref)


def test_hierarchical_two_level(devices8):
    """Two-level expert routing over ("slice", "intra"): bitwise against
    both the joint collective and the dense oracle (batched at the slab's
    rows; the per-group oracle within the reorder bound), with the dispatch
    payload booked per interconnect tier — the slice stage on DCN, the
    intra stage on ICI, exact bytes each."""
    cfg, params, w_router, _ = _setup()
    T, D = 16, 32
    x = jnp.asarray(
        np.random.RandomState(5).randn(8 * T, D).astype(np.float32)
    )
    C = cfg.capacity(T)
    mesh = Mesh(
        np.asarray(devices8).reshape(2, 4), ("slice", "intra")
    )
    ax = ("slice", "intra")
    comms.reset_comms_ledger()
    hier = jax.jit(_smap(
        lambda xl, w, p: moe_layer(
            xl, w, p, cfg, expert_axis=ax, capacity=C, hierarchical=True
        )[0],
        mesh, (P(ax), P(), P(ax)), P(ax),
    ))
    got = np.asarray(hier(x, w_router, params))
    joint = jax.jit(_smap(
        lambda xl, w, p: moe_layer(
            xl, w, p, cfg, expert_axis=ax, capacity=C
        )[0],
        mesh, (P(ax), P(), P(ax)), P(ax),
    ))
    assert _bitwise(got, joint(x, w_router, params))
    # the oracle at the slab's 8 * C rows, as in test_expert_parallel_bitwise
    # (there: why the per-group oracle is bounded, not bitwise)
    assert _bitwise(got, _oracle_at_slab_rows(x, w_router, params, cfg, 8 * C))
    bound = _reorder_bound(x, w_router, params, cfg)
    for g in range(8):
        want, _ = jax.jit(
            lambda xg: dense_oracle(xg, w_router, params, cfg)
        )(x[g * T:(g + 1) * T])
        gap = np.abs(got[g * T:(g + 1) * T] - np.asarray(want))
        assert (gap <= bound[g * T:(g + 1) * T]).all()

    # per-tier ledger: each stage moves the full (E, C, D) payload once per
    # a2a, per direction (dispatch + combine)
    payload = cfg.n_experts * C * D * 4
    rows = {r["site"]: r for r in comms.comms_records()}
    for site, tier in [
        ("moe.dispatch.slice", "dcn"), ("moe.combine.slice", "dcn"),
        ("moe.dispatch.intra", "ici"), ("moe.combine.intra", "ici"),
    ]:
        assert rows[site]["tier"] == tier, site
        assert rows[site]["bytes"] == payload, site
    # the joint collective's tuple axis touches "slice" -> booked dcn
    assert rows["moe.dispatch"]["tier"] == "dcn"


def test_ring_attention_and_expert_parallel_share_one_axis(devices8):
    """Long-context composition: the same 8 ranks are the context ring of a
    causal ring attention AND the expert-parallel world of the MoE FFN behind
    it (each rank's S/8 tokens are one routing group). Executed against full
    attention + the per-group dense oracle; then traced only, at 4x the
    sequence, for the analytic byte oracle — the ledger books at trace time,
    so ``eval_shape`` pins a long program's wire bytes without running it."""
    from beforeholiday_tpu.moe import expert_param_specs
    from beforeholiday_tpu.transformer.context_parallel import ring_attention

    H, Dh, cp = 2, 16, 8
    Dm, S = H * Dh, 256
    Sl = S // cp
    cfg = MoEConfig(n_experts=8, top_k=2, capacity_factor=8.0)
    rng = np.random.RandomState(7)
    params = init_experts(jax.random.PRNGKey(3), 8, Dm, 2 * Dm)
    w_router = jnp.asarray(rng.randn(Dm, 8).astype(np.float32) * 0.1)
    x = jnp.asarray((rng.randn(S, Dm) * 0.5).astype(np.float32))
    mesh = Mesh(np.asarray(devices8), ("context",))
    specs = (P("context", None), P(), expert_param_specs(expert_axis="context"))

    def block(xl, w, p):
        sl = xl.shape[0]
        q = xl.reshape(1, sl, H, Dh).transpose(0, 2, 1, 3)
        a = ring_attention(q, q, q, causal=True, axis_name="context")
        h = xl + a.transpose(0, 2, 1, 3).reshape(xl.shape)
        y, _ = moe_layer(h, w, p, cfg, expert_axis="context",
                         capacity=cfg.capacity(sl))
        return h + y

    comms.reset_comms_ledger()
    got = np.asarray(jax.jit(_smap(block, mesh, specs, P("context", None)))(
        x, w_router, params))
    rows = {r["site"]: r for r in comms.comms_records()}
    assert {"cp.ring_attention.kv", "moe.dispatch", "moe.combine"} <= set(rows)

    qkv = x.reshape(S, H, Dh).transpose(1, 0, 2)
    scores = jnp.einsum("hqd,hkd->hqk", qkv, qkv) / np.sqrt(Dh)
    scores = jnp.where(np.tril(np.ones((S, S), bool)), scores, -1e30)
    attn = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), qkv)
    h_ref = x + attn.transpose(1, 0, 2).reshape(S, Dm)
    want = np.concatenate([
        np.asarray(h_ref[g * Sl:(g + 1) * Sl] + dense_oracle(
            h_ref[g * Sl:(g + 1) * Sl], w_router, params, cfg)[0])
        for g in range(cp)])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    S_big = 4 * S
    Sl_big = S_big // cp
    comms.reset_comms_ledger()
    jax.eval_shape(
        _smap(block, mesh, specs, P("context", None)),
        jax.ShapeDtypeStruct((S_big, Dm), jnp.float32),
        jax.ShapeDtypeStruct((Dm, 8), jnp.float32),
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
    )
    rows = {r["site"]: r for r in comms.comms_records()}
    # the ring's ppermute sits in a scan body and records once per trace:
    # one hop's k + v; the dispatch moves the (E, C, D) slot tensor once
    assert rows["cp.ring_attention.kv"]["bytes"] == 2 * H * Sl_big * Dh * 4
    assert rows["moe.dispatch"]["bytes"] == (
        cfg.n_experts * cfg.capacity(Sl_big) * Dm * 4)


def test_hierarchical_requires_axis_pair():
    with pytest.raises(ValueError):
        expert_all_to_all(
            jnp.zeros((4, 2, 8)), EXPERT_AXIS, site="moe.dispatch",
            hierarchical=True,
        )


# ------------------------------------------------------------ composition


def test_gpt_moe_every_forward_and_grads():
    from beforeholiday_tpu.testing import gpt

    cfg = gpt.GPTConfig(
        vocab_size=64, seq_len=16, d_model=32, n_heads=2, n_layers=4,
        use_flash_attention=False, moe_every=2, moe_experts=4,
        moe_capacity_factor=8.0,
    )
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    assert params["moe"]["w_router"].shape == (2, 32, 4)
    assert params["moe"]["experts"]["wi"].shape == (2, 4, 32, 128)
    # specs tree mirrors the params tree
    jax.tree.map(lambda a, b: None, params, gpt.param_specs(cfg))

    tok, tgt = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
    logits, aux = jax.jit(
        lambda p: gpt.forward(p, tok, cfg, return_aux=True)
    )(params)
    assert logits.shape == (2, 16, 64)
    assert set(aux) == {"moe_aux_loss", "moe_z_loss", "moe_drop_fraction"}
    assert float(aux["moe_aux_loss"]) > 0.0
    assert float(aux["moe_drop_fraction"]) == 0.0  # cf=8 -> no drops

    # loss folds the weighted router losses; the router trains
    loss, aux2 = jax.jit(lambda p: gpt.loss_and_aux(p, tok, tgt, cfg))(params)
    ce = float(loss) - cfg.moe_aux_weight * float(aux2["moe_aux_loss"]) \
        - cfg.moe_z_weight * float(aux2["moe_z_loss"])
    assert ce > 0.0
    g = jax.jit(jax.grad(lambda p: gpt.loss_fn(p, tok, tgt, cfg)))(params)
    assert float(jnp.linalg.norm(jnp.ravel(g["moe"]["w_router"]))) > 0.0
    assert float(jnp.linalg.norm(jnp.ravel(g["moe"]["experts"]["wi"]))) > 0.0
    # the MoE layers' dense-MLP slots are dead params: zero gradient
    wi_g = np.asarray(g["blocks"]["wi"])
    assert (wi_g[1] == 0.0).all() and (wi_g[3] == 0.0).all()
    assert (np.abs(wi_g[0]) > 0.0).any() and (np.abs(wi_g[2]) > 0.0).any()


def test_gpt_dense_path_unchanged_by_moe_knobs():
    """moe_every=0 must be byte-for-byte the pre-MoE model: no moe subtree,
    identical logits from identical keys."""
    from beforeholiday_tpu.testing import gpt

    cfg = gpt.GPTConfig(
        vocab_size=64, seq_len=16, d_model=32, n_heads=2, n_layers=2,
        use_flash_attention=False,
    )
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    assert "moe" not in params
    tok, _ = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
    a = jax.jit(lambda p: gpt.forward(p, tok, cfg))(params)
    b, aux = jax.jit(
        lambda p: gpt.forward(p, tok, cfg, return_aux=True)
    )(params)
    assert _bitwise(a, b)
    assert all(float(v) == 0.0 for v in aux.values())


def test_gpt_moe_remat_save_boundaries_grads():
    """save_boundaries saves the moe dispatch/combine tags and recomputes the
    expert FFN between them; grads match the no-remat run to the repo's remat
    tolerance (fusion regrouping — same contract as tests/test_remat.py)."""
    from beforeholiday_tpu.testing import gpt

    base = dict(
        vocab_size=64, seq_len=16, d_model=32, n_heads=2, n_layers=2,
        use_flash_attention=False, moe_every=2, moe_experts=4,
        moe_capacity_factor=8.0,
    )
    cfg = gpt.GPTConfig(**base)
    cfg_r = gpt.GPTConfig(**base, remat_policy="save_boundaries")
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    tok, tgt = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
    l, g = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_fn(p, tok, tgt, cfg)
    ))(params)
    l_r, g_r = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_fn(p, tok, tgt, cfg_r)
    ))(params)
    np.testing.assert_allclose(float(l_r), float(l), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_r)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_moe_remat_tags_registered():
    from beforeholiday_tpu.remat.policies import (
        BOUNDARY_TAGS, TAG_MOE_COMBINE, TAG_MOE_DISPATCH,
    )

    assert TAG_MOE_DISPATCH in BOUNDARY_TAGS
    assert TAG_MOE_COMBINE in BOUNDARY_TAGS


def test_quantized_moe_path(devices8):
    """O6: same layout is deterministic-bitwise; cross-layout agrees only to
    fp8 quantization noise (amax scales are slab-local — documented)."""
    from beforeholiday_tpu.ops._autocast import quantized_compute

    cfg, params, w_router, _ = _setup()
    T, D = 16, 32
    x = jnp.asarray(
        np.random.RandomState(7).randn(4 * T, D).astype(np.float32)
    )
    C = cfg.capacity(T)
    y_fp32 = np.asarray(jax.jit(
        lambda xg: moe_layer(xg, w_router, params, cfg, capacity=C)[0]
    )(x[:T]))
    with quantized_compute():
        single = jax.jit(
            lambda xg: moe_layer(xg, w_router, params, cfg, capacity=C)[0]
        )
        q1 = np.asarray(single(x[:T]))
        q1b = np.asarray(single(x[:T]))
        mesh = Mesh(np.asarray(devices8[:4]), (EXPERT_AXIS,))
        dist = jax.jit(_smap(
            lambda xl, w, p: moe_layer(
                xl, w, p, cfg, expert_axis=EXPERT_AXIS, capacity=C
            )[0],
            mesh, (P(EXPERT_AXIS), P(), P(EXPERT_AXIS)), P(EXPERT_AXIS),
        ))
        q4 = np.asarray(dist(x, w_router, params))
    assert np.array_equal(q1, q1b)                      # deterministic
    assert not np.array_equal(q1, y_fp32)               # actually quantized
    scale = np.abs(y_fp32).max()
    np.testing.assert_allclose(q4[:T] / scale, q1 / scale, atol=0.1)


def test_expert_ffn_tensor_emulation_matches_unchunked_closely():
    """emulate_tensor re-groups the d_ff reduction — not bitwise vs the
    unchunked FFN (that's the point: it matches the DISTRIBUTED grouping
    instead, pinned by test_4d_mesh_parity), but numerically tight."""
    _, params, _, _ = _setup()
    x = jnp.asarray(
        np.random.RandomState(9).randn(8, 4, 32).astype(np.float32)
    )
    y1 = jax.jit(lambda a: expert_ffn(params, a))(x)
    y2 = jax.jit(lambda a: expert_ffn(params, a, emulate_tensor=2))(x)
    np.testing.assert_allclose(
        np.asarray(y1), np.asarray(y2), rtol=1e-5, atol=1e-6
    )
    with pytest.raises(ValueError):
        expert_ffn(params, x, tensor_axis="tensor", emulate_tensor=2)


# -------------------------------------------------------------- monitor


@pytest.mark.monitor
def test_train_monitor_moe_keys():
    from beforeholiday_tpu.monitor.metrics import TrainMonitor

    mon = TrainMonitor()
    for k in ("moe_aux_loss", "moe_z_loss", "moe_drop_fraction"):
        assert k in mon.keys
    m = mon.init()
    m = mon.update(
        m,
        loss=jnp.asarray(1.0),
        moe={
            "moe_aux_loss": jnp.asarray(1.25),
            "moe_z_loss": jnp.asarray(0.5),
            "moe_drop_fraction": jnp.asarray(0.125),
        },
    )
    out = mon.unpack_host(np.asarray(mon.pack(m)))
    assert out["moe_aux_loss"] == pytest.approx(1.25)
    assert out["moe_z_loss"] == pytest.approx(0.5)
    assert out["moe_drop_fraction"] == pytest.approx(0.125)
