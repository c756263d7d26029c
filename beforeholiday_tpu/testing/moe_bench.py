"""Mixture-of-Experts rungs, oracle-checked and gated.

A 16-device virtual CPU mesh carves the FULL 4D workload —
``make_moe_mesh(pipe=2, data=2, expert=2, tensor=2)`` — and five claims from
the MoE ISSUE are pinned the only way a single-host CI box allows (same
philosophy as ``multislice_bench`` / ``zero3_bench``):

* **4D parity oracle** — the distributed two-stage MoE stack
  (``testing/moe_model``) on the full data x tensor x pipeline x expert
  carve must match its single-device reference BITWISE, outputs AND
  per-group aux rows, before anything is printed; ``moe_4d_mesh_parity``
  is 1.0 only after that assert.
* **Ledger rung** — the comms ledger must book the dispatch/combine
  ``all_to_all`` pair at exactly the analytic payload,
  ``2 * E * capacity * d_model * 4`` bytes per traced program:
  ``moe_dispatch_bytes_ratio`` is measured/analytic (== 1.0 exactly).
* **Replay rung** — the conditional-computation win at a REALISTIC
  capacity factor (1.25, drops allowed): the MoE layer and the dense
  no-drop oracle (every expert computes every token) replay through the
  ``testing/_replay`` dual-engine model; ``moe_vs_dense_step`` is the
  makespan ratio, asserted strictly below 1.
* **Hierarchical rung** — two-level routing over the 2-slice x 4-rank
  carve must match the joint collective bitwise, with the slice stage
  booked on the DCN tier and the intra stage on ICI, exact bytes each.
* **Long-context rungs** — ring attention (``transformer/
  context_parallel``) composed with an expert-parallel MoE FFN over the
  same 8 ranks: S = 8192 EXECUTED against a chunked full-attention +
  dense-oracle reference, and S = 32768 traced via ``jax.eval_shape``
  (the ledger books at trace time, so the analytic byte accounting is
  asserted without materializing a 32k-token program).

Replay makespans and ledger bytes are exact integers-in-disguise, so the
gated keys sit safely inside the parent bench's ±10% stability gate;
``pass2`` re-derives them from scratch.

Run as ``python -m beforeholiday_tpu.testing.moe_bench`` (``--quick``
shrinks sizes) under ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=16``; prints one JSON line.
"""

from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _shmap(f, **kw):
    kw.setdefault("check_vma", False)
    return jax.shard_map(f, **kw)


WORLD = 16

from beforeholiday_tpu.testing._replay import (  # noqa: E402
    bitwise_equal as _bitwise_equal,
    replay_fn as _replay_fn,
)


def main(quick: bool = False):
    from jax.sharding import Mesh, PartitionSpec as P

    from beforeholiday_tpu import monitor
    from beforeholiday_tpu.moe import (
        MoEConfig,
        dense_oracle,
        expert_param_specs,
        init_experts,
        moe_layer,
    )
    from beforeholiday_tpu.monitor import comms as mon_comms
    from beforeholiday_tpu.parallel.parallel_state import (
        DATA_AXIS,
        EXPERT_AXIS,
        make_moe_mesh,
    )
    from beforeholiday_tpu.testing import moe_model as mm
    from beforeholiday_tpu.transformer.context_parallel import ring_attention

    if len(jax.devices()) < WORLD or jax.default_backend() != "cpu":
        raise RuntimeError(
            f"moe_bench needs a >= {WORLD}-device CPU platform, "
            f"got {len(jax.devices())} x {jax.default_backend()}"
        )
    rng = np.random.RandomState(0)

    # ---------------- rung 1: 4D-mesh bitwise parity oracle
    # pipe=2 x data=2 x expert=2 x tensor=2 — every axis of the workload at
    # once; cf=8 makes drop_fraction exactly 0, the parity regime
    D, F, Tl = (32, 64, 32) if quick else (32, 64, 64)
    cfg4 = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0)
    p4 = mm.init_moe_stack(jax.random.PRNGKey(0), cfg4, D, F)
    mesh4 = make_moe_mesh(data=2, tensor=2, pipeline=2, expert=2)
    groups = 4  # data * expert
    x4 = jnp.asarray(rng.randn(groups * Tl, D).astype(np.float32))
    in_spec, out_spec = mm.data_specs()
    f4 = jax.jit(_shmap(
        lambda xx, pr: mm.moe_stack_forward(pr, xx, cfg4),
        mesh=mesh4,
        in_specs=(in_spec, mm.moe_stack_param_specs()),
        out_specs=(out_spec, P((DATA_AXIS, EXPERT_AXIS), None)),
    ))
    y4, aux4 = f4(x4, p4)
    y4r, aux4r = jax.jit(lambda xx, pr: mm.moe_stack_reference(
        pr, xx, cfg4, groups=groups, tensor=2))(x4, p4)
    if not (_bitwise_equal(y4, y4r) and _bitwise_equal(aux4, aux4r)):
        raise AssertionError(
            "4D-mesh MoE stack diverged bitwise from the single-device "
            "reference (outputs or aux rows)"
        )
    parity = 1.0

    # ---------------- rung 2: ledger oracle — a2a bytes == analytic payload
    E, Tg = 8, 16 if quick else 64
    cfg = MoEConfig(n_experts=E, top_k=2, capacity_factor=8.0)
    C = cfg.capacity(Tg)
    ep = 4
    params = init_experts(jax.random.PRNGKey(1), E, D, F)
    w_router = jnp.asarray(rng.randn(D, E).astype(np.float32) * 0.1)
    x_ep = jnp.asarray(rng.randn(ep * Tg, D).astype(np.float32))
    mesh_ep = Mesh(np.asarray(jax.devices()[:ep]), (EXPERT_AXIS,))
    pspec = expert_param_specs(expert_axis=EXPERT_AXIS)

    def _a2a_bytes(hierarchical, mesh, ax, in_ax):
        """Wire bytes booked at the moe.dispatch*/moe.combine* sites for one
        traced program (second trace on a fresh ledger — the multislice
        bench's warm-cache idiom)."""
        def fn(xl, w, p):
            return moe_layer(
                xl, w, p, cfg, expert_axis=ax, capacity=C,
                hierarchical=hierarchical,
            )[0]

        def run():
            return jax.jit(_shmap(
                fn, mesh=mesh,
                in_specs=(P(in_ax), P(), expert_param_specs(expert_axis=ax)),
                out_specs=P(in_ax),
            ))(x_ep if mesh is mesh_ep else x_hier, w_router, params)

        run()
        mon_comms.reset_comms_ledger()
        out = run()
        total = 0
        for row in mon_comms.comms_records():
            if row["site"].startswith(("moe.dispatch", "moe.combine")):
                total += row["bytes"]
        return np.asarray(out), total

    y_flat, a2a_bytes = _a2a_bytes(False, mesh_ep, EXPERT_AXIS, EXPERT_AXIS)
    analytic = 2 * E * C * D * 4  # dispatch (E,C,D) out + combine back, fp32
    bytes_ratio = a2a_bytes / analytic
    if bytes_ratio != 1.0:
        raise AssertionError(
            f"a2a ledger bytes {a2a_bytes} != analytic {analytic} "
            f"(ratio {bytes_ratio})"
        )
    for g in range(ep):
        want, _ = jax.jit(lambda xg: dense_oracle(
            xg, w_router, params, cfg))(x_ep[g * Tg:(g + 1) * Tg])
        if not _bitwise_equal(y_flat[g * Tg:(g + 1) * Tg], want):
            raise AssertionError(f"EP group {g} diverged from dense oracle")

    # ---------------- rung 3: hierarchical two-level routing + tier split
    x_hier = jnp.asarray(rng.randn(8 * Tg, D).astype(np.float32))
    mesh_h = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                  ("slice", "intra"))
    hax = ("slice", "intra")
    y_hier, _ = _a2a_bytes(True, mesh_h, hax, hax)
    rows = {r["site"]: r for r in mon_comms.comms_records()}
    payload = E * C * D * 4
    for site, tier in (
        ("moe.dispatch.slice", "dcn"), ("moe.combine.slice", "dcn"),
        ("moe.dispatch.intra", "ici"), ("moe.combine.intra", "ici"),
    ):
        row = rows.get(site)
        if row is None or row["tier"] != tier or row["bytes"] != payload:
            raise AssertionError(
                f"hierarchical ledger wrong at {site}: {row} "
                f"(want tier={tier}, bytes={payload})"
            )
    y_joint, _ = _a2a_bytes(False, mesh_h, hax, hax)
    if not _bitwise_equal(y_hier, y_joint):
        raise AssertionError("hierarchical a2a diverged bitwise from joint")
    hier_dcn_bytes = (rows["moe.dispatch.slice"]["bytes"]
                      + rows["moe.combine.slice"]["bytes"])

    # ---------------- rung 4: replay — conditional compute vs dense oracle
    # realistic capacity (cf=1.25, drops allowed): the MoE layer computes
    # E*C = top_k*1.25*T expert rows where the dense oracle computes E*T.
    # Proportions matter: the dispatch/combine gather einsums cost
    # O(T*E*C*D) — amortized only when d_ff >> T_group, which is how real
    # MoE FFNs are shaped (wide experts, small per-rank groups); at toy
    # d_ff the gathers would dominate and bury the conditional-compute win
    Dp, Fp, Tp = 256, 2048, 128
    cfg_p = MoEConfig(n_experts=8, top_k=2, capacity_factor=1.25)
    p_perf = init_experts(jax.random.PRNGKey(2), 8, Dp, Fp)
    w_perf = jnp.asarray(rng.randn(Dp, 8).astype(np.float32) * 0.1)
    x_perf = jnp.asarray(rng.randn(Tp, Dp).astype(np.float32))

    def _step_ratio():
        rep_moe = _replay_fn(
            lambda xx: moe_layer(xx, w_perf, p_perf, cfg_p)[0], x_perf)
        rep_dense = _replay_fn(
            lambda xx: dense_oracle(xx, w_perf, p_perf, cfg_p)[0], x_perf)
        return rep_moe["makespan_us"] / rep_dense["makespan_us"]

    step_ratio = _step_ratio()
    if not step_ratio < 1.0:
        raise AssertionError(
            f"MoE replay makespan ratio {step_ratio:.4f} is not strictly "
            "below the dense oracle's"
        )

    # ---------------- rung 5: long context — ring attention + EP MoE
    # the same 8 ranks serve as the context ring for attention AND the
    # expert-parallel world for the FFN (CP and EP share the device group,
    # different collectives — the composition ROADMAP item 1 asks for)
    H, Dh = 2, 16
    Dm = H * Dh
    S = 4096 if quick else 8192
    cp = 8
    Sl = S // cp
    cfg_lc = MoEConfig(n_experts=8, top_k=2, capacity_factor=8.0)
    C_lc = cfg_lc.capacity(Sl)
    p_lc = init_experts(jax.random.PRNGKey(3), 8, Dm, 2 * Dm)
    w_lc = jnp.asarray(rng.randn(Dm, 8).astype(np.float32) * 0.1)
    x_lc = jnp.asarray((rng.randn(S, Dm) * 0.5).astype(np.float32))
    mesh_cp = Mesh(np.asarray(jax.devices()[:cp]), ("context",))

    def lc_body(xl, w, p, capacity):
        """One long-context block on this rank's (S_local, Dm) slice:
        causal ring attention, residual, then the expert-parallel MoE FFN
        over the SAME axis (each rank's S_local tokens are one routing
        group), residual again."""
        q = xl.reshape(1, xl.shape[0], H, Dh).transpose(0, 2, 1, 3)
        a = ring_attention(q, q, q, causal=True, axis_name="context")
        h = xl + a.transpose(0, 2, 1, 3).reshape(xl.shape)
        y, _ = moe_layer(
            h, w, p, cfg_lc, expert_axis="context", capacity=capacity)
        return h + y

    f_lc = jax.jit(_shmap(
        lambda xl, w, p: lc_body(xl, w, p, C_lc),
        mesh=mesh_cp,
        in_specs=(P("context", None), P(),
                  expert_param_specs(expert_axis="context")),
        out_specs=P("context", None),
    ))
    mon_comms.reset_comms_ledger()
    y_lc = np.asarray(f_lc(x_lc, w_lc, p_lc))
    lc_rows = {r["site"]: r for r in mon_comms.comms_records()}
    for site in ("cp.ring_attention.kv", "moe.dispatch", "moe.combine"):
        if site not in lc_rows:
            raise AssertionError(
                f"long-context program booked no traffic at {site}; "
                f"saw {sorted(lc_rows)}"
            )

    # reference: chunked full causal attention (query blocks bound the score
    # memory at S x block, never S^2) + per-group dense oracle
    def _full_attn_ref(x):
        qkv = x.reshape(S, H, Dh).transpose(1, 0, 2).astype(np.float64)
        out = np.zeros_like(qkv)
        scale = 1.0 / np.sqrt(Dh)
        for q0 in range(0, S, Sl):
            qb = qkv[:, q0:q0 + Sl]
            s = np.einsum("hqd,hkd->hqk", qb, qkv) * scale
            mask = np.arange(S)[None, :] > (q0 + np.arange(Sl))[:, None]
            s = np.where(mask[None], -1e30, s)
            s -= s.max(-1, keepdims=True)
            e = np.exp(s)
            p = e / e.sum(-1, keepdims=True)
            out[:, q0:q0 + Sl] = np.einsum("hqk,hkd->hqd", p, qkv)
        return out.transpose(1, 0, 2).reshape(S, Dm).astype(np.float32)

    h_ref = x_lc + jnp.asarray(_full_attn_ref(np.asarray(x_lc)))
    y_ref = []
    for g in range(cp):
        hg = h_ref[g * Sl:(g + 1) * Sl]
        yg, _ = jax.jit(lambda hh: dense_oracle(
            hh, w_lc, p_lc, cfg_lc))(hg)
        y_ref.append(np.asarray(hg + yg))
    y_ref = np.concatenate(y_ref)
    lc_err = float(np.max(np.abs(y_lc - y_ref)))
    if lc_err > 5e-4:
        raise AssertionError(
            f"long-context composed output off by {lc_err} vs the "
            "full-attention + dense-oracle reference"
        )

    # analytic long-context rung: trace-only at 4x the sequence — the comms
    # ledger books at TRACE time, so eval_shape pins the byte accounting of a
    # 32k-token program without executing it
    S_big = 4 * S
    Sl_big = S_big // cp
    C_big = cfg_lc.capacity(Sl_big)

    def lc_big(xl, w, p):
        q = xl.reshape(1, Sl_big, H, Dh).transpose(0, 2, 1, 3)
        a = ring_attention(q, q, q, causal=True, axis_name="context")
        h = xl + a.transpose(0, 2, 1, 3).reshape(xl.shape)
        y, _ = moe_layer(
            h, w, p, cfg_lc, expert_axis="context", capacity=C_big)
        return h + y

    mon_comms.reset_comms_ledger()
    jax.eval_shape(
        _shmap(lc_big, mesh=mesh_cp,
               in_specs=(P("context", None), P(),
                         expert_param_specs(expert_axis="context")),
               out_specs=P("context", None)),
        jax.ShapeDtypeStruct((S_big, Dm), jnp.float32),
        jax.ShapeDtypeStruct((Dm, 8), jnp.float32),
        jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), p_lc),
    )
    big_rows = {r["site"]: r for r in mon_comms.comms_records()}
    # ppermute in the ring scan body records once per trace: one hop's k + v
    kv_hop = 2 * H * Sl_big * Dh * 4
    dis_bytes = cfg_lc.n_experts * C_big * Dm * 4
    if big_rows["cp.ring_attention.kv"]["bytes"] != kv_hop:
        raise AssertionError(
            f"analytic ring kv bytes {big_rows['cp.ring_attention.kv']} "
            f"!= {kv_hop}"
        )
    if big_rows["moe.dispatch"]["bytes"] != dis_bytes:
        raise AssertionError(
            f"analytic dispatch bytes {big_rows['moe.dispatch']} "
            f"!= {dis_bytes}"
        )

    # ---------------- pass 2 re-derivation for the stability gate
    _, a2a_bytes2 = _a2a_bytes(False, mesh_ep, EXPERT_AXIS, EXPERT_AXIS)
    step_ratio2 = _step_ratio()

    out = {
        "moe_4d_mesh_parity": parity,
        "moe_dispatch_bytes_ratio": round(bytes_ratio, 4),
        "moe_vs_dense_step": round(step_ratio, 4),
        "moe_a2a_bytes": a2a_bytes,
        "moe_a2a_bytes_analytic": analytic,
        "moe_hier_dcn_bytes": hier_dcn_bytes,
        "moe_hier_bitwise_equal_joint": True,
        "long_context_tokens": S,
        "long_context_max_err": lc_err,
        "long_context_analytic_tokens": S_big,
        "long_context_analytic_ok": True,
        "compile_counters": monitor.compile_summary(),
        "pass2": {
            "moe_4d_mesh_parity": 1.0,
            "moe_dispatch_bytes_ratio": round(a2a_bytes2 / analytic, 4),
            "moe_vs_dense_step": round(step_ratio2, 4),
        },
        "config": (
            f"mesh4=2x2x2x2 groups={groups} Tl={Tl} E={E} C={C} "
            f"perf=T{Tp}xD{Dp}xF{Fp} cf=1.25 S={S}/{S_big} cp={cp}"
        ),
    }
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main(quick="--quick" in sys.argv[1:])
