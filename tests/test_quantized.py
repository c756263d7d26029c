"""O6 fp8-style quantized matmul tier (ops.quantized + amp/guard wiring).

Covers the tier's contracts end to end: the analytic per-matmul error bound,
e4m3-forward / e5m2-backward format selection, delayed-scaling amax history
(roll, non-finite clamp, scale derivation), StepGuard skip-and-halve on a
quantized grad overflow, scaler checkpoint round-trips across the schema
change, guard-probed dispatch with a bitwise-identical oracle, and the O6
frontend opt level.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu import amp
from beforeholiday_tpu.amp.scaler import LossScaler
from beforeholiday_tpu.guard import dispatch as gd
from beforeholiday_tpu.guard.step import StepGuard
from beforeholiday_tpu.ops import quantized as Q
from beforeholiday_tpu.optimizers import FusedAdam
from beforeholiday_tpu.testing.faults import force_probe_failure

pytestmark = pytest.mark.quantized


def _rand(shape, dtype=np.float32, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape).astype(dtype))


class TestQuantizedMatmul:
    def test_2d_fp32_within_analytic_bound(self):
        x = _rand((32, 48), seed=1)
        w = _rand((48, 24), seed=2)
        y = Q.quantized_matmul(x, w)
        assert y.dtype == jnp.float32
        err = float(jnp.max(jnp.abs(y - x @ w)))
        bound = float(Q.quantized_matmul_error_bound(x, w))
        assert err <= bound
        # the bound is an envelope, not a tautology: it must sit well under
        # the trivial K*amax(x)*amax(w) product bound
        trivial = 48 * float(jnp.max(jnp.abs(x))) * float(jnp.max(jnp.abs(w)))
        assert bound < trivial

    def test_3d_bf16_within_bound_grads_in_primal_dtype(self):
        x = _rand((2, 16, 32), seed=3).astype(jnp.bfloat16)
        w = _rand((32, 24), seed=4).astype(jnp.bfloat16)
        y, vjp = jax.vjp(lambda a, b: Q.quantized_matmul(a, b), x, w)
        assert y.shape == (2, 16, 24) and y.dtype == jnp.float32
        ref = x.astype(jnp.float32) @ w.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(y - ref)))
        assert err <= float(Q.quantized_matmul_error_bound(x, w))
        dx, dw = vjp(jnp.ones_like(y))
        # boundary casts are transposed by autodiff: grads land in the
        # primal dtypes, matching ops.dense._matmul's cast-back contract
        assert dx.dtype == jnp.bfloat16 and dx.shape == x.shape
        assert dw.dtype == jnp.bfloat16 and dw.shape == w.shape

    def test_forward_e4m3_backward_e5m2(self):
        x = _rand((8, 16), seed=5)
        w = _rand((16, 8), seed=6)
        fwd = str(jax.make_jaxpr(Q.quantized_matmul)(x, w))
        assert "e4m3" in fwd  # both fwd operands quantize to e4m3
        assert "e5m2" not in fwd  # e5m2 is a backward-only format

        grad = str(jax.make_jaxpr(
            jax.grad(lambda a, b: jnp.sum(Q.quantized_matmul(a, b)),
                     argnums=(0, 1))
        )(x, w))
        assert "e5m2" in grad  # the cotangent quantizes to e5m2

    def test_scope_with_exact_scales_matches_jit(self):
        """Delayed scales equal to the just-in-time scales must reproduce the
        scopeless result bitwise — the scope changes WHERE the scale comes
        from, never the arithmetic."""
        x = _rand((16, 32), seed=7)
        w = _rand((32, 16), seed=8)
        y_jit = Q.quantized_matmul(x, w)
        sw = Q.E4M3_MAX / float(jnp.max(jnp.abs(w)))
        with Q.quantized_scope(sw, 1.0):
            y_scoped = Q.quantized_matmul(x, w)
        np.testing.assert_array_equal(np.asarray(y_jit), np.asarray(y_scoped))

    def test_unsupported_dtype_raises(self):
        x_i = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
        w = _rand((4, 2), seed=9)
        with pytest.raises(TypeError, match="unsupported dtype"):
            Q.quantized_matmul(x_i, w)
        with pytest.raises(TypeError, match="unsupported dtype"):
            Q.quantized_matmul(w.T, x_i)

    def test_bad_shapes_raise(self):
        with pytest.raises(ValueError, match="expects x"):
            Q.quantized_matmul(_rand((4, 4)), _rand((4, 4, 4)))

    def test_bad_impl_raises(self):
        with pytest.raises(ValueError, match="impl"):
            Q.quantized_matmul(_rand((4, 4)), _rand((4, 4)), impl="cuda")


class TestAmaxHistory:
    def test_init_shape_and_validation(self):
        h = Q.init_amax_history(4)
        assert h.shape == (len(Q.HISTORY_ROLES), 4)
        assert not np.asarray(h).any()
        with pytest.raises(ValueError, match=">= 1"):
            Q.init_amax_history(0)

    def test_update_rolls_newest_into_slot0(self):
        h = Q.init_amax_history(3)
        h = Q.update_amax_history(h, 2.0, 5.0)
        h = Q.update_amax_history(h, 3.0, 1.0)
        got = np.asarray(h)
        np.testing.assert_array_equal(got[0], [3.0, 2.0, 0.0])  # weight row
        np.testing.assert_array_equal(got[1], [1.0, 5.0, 0.0])  # grad row

    def test_nonfinite_observations_clamp_to_zero(self):
        """An overflow step's inf amax must never poison the delayed scale —
        found_inf already handles the event; the history ignores it."""
        h = Q.update_amax_history(Q.init_amax_history(2), jnp.inf, jnp.nan)
        assert not np.asarray(h).any()

    def test_scales_from_history(self):
        h = Q.init_amax_history(4)
        sw, sg = Q.scales_from_history(h)
        assert float(sw) == 1.0 and float(sg) == 1.0  # no observations yet
        h = Q.update_amax_history(h, 4.0, 16.0)
        sw, sg = Q.scales_from_history(h, margin=2.0)
        assert float(sw) == pytest.approx(Q.E4M3_MAX / 2.0 / 4.0)
        assert float(sg) == pytest.approx(Q.E5M2_MAX / 2.0 / 16.0)
        with pytest.raises(ValueError, match="margin"):
            Q.scales_from_history(h, margin=0.5)

    def test_amax_of_tree_floats_only(self):
        tree = {"a": jnp.asarray([-3.0, 1.0]), "b": jnp.arange(5),
                "c": jnp.asarray([[0.5]], jnp.bfloat16)}
        assert float(Q.amax_of_tree(tree)) == 3.0
        assert float(Q.amax_of_tree({"i": jnp.arange(3)})) == 0.0


class TestDispatch:
    def test_fp8_path_counted_and_oracle_bitwise_identical(self):
        x = _rand((16, 24), seed=10)
        w = _rand((24, 8), seed=11)
        gd.reset_dispatch_counters()
        y_fast = Q.quantized_matmul(x, w)
        y_oracle = Q.quantized_matmul(x, w, impl="jnp")
        # the oracle upcasts the SAME quantized values to fp32; both paths
        # accumulate fp32, so a probe downgrade can never change values
        np.testing.assert_array_equal(np.asarray(y_fast), np.asarray(y_oracle))

        # an explicit impl="jnp" bypasses the probe (and its counter) by
        # design; only the guarded default books — under "pallas"
        counts = {"pallas": 0, "jnp": 0}
        for key, c in gd.dispatch_counters().items():
            if key[0] == "quantized_matmul":
                counts["pallas"] += c["pallas"]
                counts["jnp"] += c["jnp"]
        assert counts["pallas"] >= 1 and counts["jnp"] == 0

    def test_probe_failure_degrades_bitwise_equal_and_counts_jnp(self):
        x = _rand((16, 24), seed=12)
        w = _rand((24, 8), seed=13)
        y_fast = Q.quantized_matmul(x, w)
        gd.reset_dispatch_counters()
        with force_probe_failure("quantized_matmul"):
            y_degraded = Q.quantized_matmul(x, w)
        np.testing.assert_array_equal(
            np.asarray(y_fast), np.asarray(y_degraded)
        )
        jnp_count = sum(
            c["jnp"] for key, c in gd.dispatch_counters().items()
            if key[0] == "quantized_matmul"
        )
        assert jnp_count >= 1  # the downgrade is visible telemetry

    def test_fp8_spelling_accepted(self):
        x = _rand((4, 8), seed=14)
        w = _rand((8, 4), seed=15)
        np.testing.assert_array_equal(
            np.asarray(Q.quantized_matmul(x, w, impl="fp8")),
            np.asarray(Q.quantized_matmul(x, w)),
        )


class TestStepGuardOverflow:
    def test_quantized_grad_overflow_skips_step_and_halves_scale(self):
        """A stale delayed grad scale that saturates e5m2 must ride the
        found_inf plumbing: step skipped (params/moments bitwise-unchanged),
        loss scale halved — the same event loop as a bf16 overflow."""
        scaler = LossScaler(quantized=True, amax_history_len=4)
        guard = StepGuard(scaler)
        params = {"w": _rand((8, 4), seed=16)}
        x = _rand((6, 8), seed=17)
        opt = FusedAdam(lr=1e-2)
        opt_state = opt.init(params)
        gstate = guard.init(params)
        # poison the grad row: amax 1e-30 -> scale_g ~ 2.9e34, so the bwd
        # cotangent (further amplified by the 2^16 loss scale) overflows e5m2
        gstate["scaler"]["amax_history"] = (
            gstate["scaler"]["amax_history"].at[1, 0].set(1e-30)
        )

        def loss_fn(p):
            return jnp.sum(Q.quantized_matmul(x, p["w"]))

        loss, grads, verdict = guard.value_and_grad(loss_fn)(params, gstate)
        assert bool(verdict["grad_overflow"])
        assert "amax" in verdict  # the step's observations ride the verdict
        new_p, new_o, new_g = guard.apply_update(
            opt, params, grads, opt_state, gstate, verdict
        )
        np.testing.assert_array_equal(
            np.asarray(new_p["w"]), np.asarray(params["w"])
        )
        assert float(new_g["scaler"]["scale"]) == pytest.approx(
            float(gstate["scaler"]["scale"]) / 2.0
        )
        assert int(new_g["health"]["skipped_total"]) == 1
        # the inf grad amax was clamped, not rolled into the history
        assert np.isfinite(np.asarray(new_g["scaler"]["amax_history"])).all()

    def test_clean_step_rolls_amax_observations(self):
        scaler = LossScaler(quantized=True, amax_history_len=4)
        guard = StepGuard(scaler)
        params = {"w": _rand((8, 4), seed=18)}
        x = _rand((6, 8), seed=19)
        opt = FusedAdam(lr=1e-2)
        gstate = guard.init(params)

        def loss_fn(p):
            return jnp.mean(Q.quantized_matmul(x, p["w"]) ** 2)

        loss, grads, verdict = guard.value_and_grad(loss_fn)(params, gstate)
        assert not bool(verdict["grad_overflow"])
        _, _, new_g = guard.apply_update(
            opt, params, grads, opt.init(params), gstate, verdict
        )
        hist = np.asarray(new_g["scaler"]["amax_history"])
        assert hist[0, 0] > 0  # weight observation landed in slot 0
        assert hist[1, 0] > 0  # grad observation landed in slot 0


class TestScalerStateDict:
    def test_roundtrip_preserves_amax_history(self):
        scaler = LossScaler(quantized=True, amax_history_len=3)
        state = scaler.init()
        state = scaler.update(state, False, amax=(2.0, 7.0))
        sd = scaler.state_dict(state)
        assert isinstance(sd["amax_history"], list)  # JSON-ready
        restored = scaler.load_state_dict(sd)
        np.testing.assert_array_equal(
            np.asarray(restored["amax_history"]),
            np.asarray(state["amax_history"]),
        )
        assert float(restored["scale"]) == float(state["scale"])

    def test_pre_o6_checkpoint_into_quantized_scaler(self):
        """Loading a pre-O6 state_dict (no amax_history) into a quantized
        scaler gets a fresh history — the delayed scales re-warm from
        just-in-time fallbacks in one window."""
        old = LossScaler().state_dict(LossScaler().init())
        assert "amax_history" not in old
        restored = LossScaler(quantized=True, amax_history_len=5).load_state_dict(old)
        hist = np.asarray(restored["amax_history"])
        assert hist.shape == (len(Q.HISTORY_ROLES), 5)
        assert not hist.any()

    def test_quantized_checkpoint_into_plain_scaler(self):
        """The forward direction: a pre-O6 loader ignores nothing it needs —
        the extra key rides along and the core fields restore."""
        q = LossScaler(quantized=True)
        sd = q.state_dict(q.init())
        restored = LossScaler().load_state_dict(sd)
        assert float(restored["scale"]) == sd["loss_scale"]


class TestO6Frontend:
    def test_o6_properties(self):
        p = amp.opt_levels["O6"]
        assert p.cast_model_type == jnp.bfloat16
        assert p.quantized is True
        assert p.loss_scale == "dynamic"
        assert p.master_weights is True

    def test_unknown_level_error_lists_o6(self):
        with pytest.raises(RuntimeError, match="O6"):
            amp.initialize(lambda p: p, {"w": jnp.ones(2)}, None, "O9")

    def test_initialize_o6_builds_quantized_scaler(self):
        params = {"w": _rand((8, 4), seed=20)}
        m = amp.initialize(
            lambda p, a: Q.quantized_matmul(a, p["w"]),
            params, FusedAdam(lr=1e-3), "O6",
        )
        assert m.scaler.quantized is True
        assert "amax_history" in m.scaler.init()
        # O5 storage policy: params cast to bf16
        assert m.params["w"].dtype == jnp.bfloat16

    def test_o6_apply_routes_dense_through_quantized(self):
        """Inside the O6 apply scope every ops.dense GEMM must take the
        quantized path — visible as e4m3 in the traced program."""
        from beforeholiday_tpu.ops import dense

        params = {"w": _rand((8, 4), seed=21).astype(jnp.bfloat16)}
        x = _rand((6, 8), seed=22).astype(jnp.bfloat16)
        m = amp.initialize(
            lambda p, a: dense.fused_dense(a, p["w"]),
            params, FusedAdam(lr=1e-3), "O6",
        )
        assert "e4m3" in str(jax.make_jaxpr(m.apply)(m.params, x))
        # O5 traces the identical model without any fp8 op
        m5 = amp.initialize(
            lambda p, a: dense.fused_dense(a, p["w"]),
            params, FusedAdam(lr=1e-3), "O5",
        )
        assert "e4m3" not in str(jax.make_jaxpr(m5.apply)(m5.params, x))

    def test_o6_dense_output_within_matmul_bound(self):
        from beforeholiday_tpu.ops import dense
        from beforeholiday_tpu.ops._autocast import quantized_compute

        x = _rand((16, 32), seed=23)
        w = _rand((32, 16), seed=24)
        y_ref = dense.fused_dense(x, w)
        with quantized_compute():
            y_q = dense.fused_dense(x, w)
        err = float(jnp.max(jnp.abs(y_q - y_ref)))
        assert err <= float(Q.quantized_matmul_error_bound(x, w))


class TestLossParityBound:
    def test_monotone_in_all_arguments(self):
        b0 = Q.loss_parity_bound(0, n_matmuls=8, loss_ceiling=6.0)
        assert b0 > 0
        assert Q.loss_parity_bound(10, n_matmuls=8, loss_ceiling=6.0) > b0
        assert Q.loss_parity_bound(0, n_matmuls=16, loss_ceiling=6.0) > b0
        assert Q.loss_parity_bound(0, n_matmuls=8, loss_ceiling=12.0) > b0
        with pytest.raises(ValueError, match="n_matmuls"):
            Q.loss_parity_bound(0, n_matmuls=0, loss_ceiling=6.0)

    def test_o6_train_run_stays_inside_the_bound(self):
        """An O6 GPT train run beside O5 from identical init and batch: at
        EVERY step the loss deviation sits inside ``loss_parity_bound`` (the
        per-matmul e4m3 envelope composed across the quantized GEMMs,
        compounded per step), no step is skipped on either side, and the
        delayed-scaling state really ran: both amax-history rows populated."""
        from beforeholiday_tpu.testing import gpt

        cfg = gpt.GPTConfig(vocab_size=512, seq_len=64, d_model=64, n_heads=4,
                            n_layers=2, dtype=jnp.bfloat16)
        steps = 12

        def losses_of(opt_level):
            params = gpt.init(jax.random.PRNGKey(0), cfg)
            batch = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, 4)
            m = amp.initialize(lambda p, t: gpt.forward(p, t, cfg), params,
                               FusedAdam(lr=1e-3), opt_level)
            svag = amp.scaled_value_and_grad(
                lambda p, tok, tgt: gpt.loss_fn(p, tok, tgt, cfg,
                                                forward_fn=m.apply), m.scaler)

            @jax.jit
            def step(p, o, sc):
                loss, g, fi, sc = svag(p, sc, *batch)
                p, o = m.optimizer.step(p, g, o, found_inf=fi)
                return p, o, sc, loss, fi

            p, o, sc = m.params, m.optimizer.init(m.params), m.scaler.init()
            losses, skipped = [], 0
            for _ in range(steps):
                p, o, sc, loss, fi = step(p, o, sc)
                losses.append(float(loss))
                skipped += int(float(fi) > 0)
            return losses, sc, skipped

        l5, _, skip5 = losses_of("O5")
        l6, sc6, skip6 = losses_of("O6")
        assert (skip5, skip6) == (0, 0)
        ceiling = max(abs(v) for v in l5)
        for t, (a, b) in enumerate(zip(l5, l6)):
            # every quantized GEMM on the loss path: 4 fused_dense per block
            bound = Q.loss_parity_bound(
                t, n_matmuls=4 * cfg.n_layers, loss_ceiling=ceiling)
            assert abs(a - b) <= bound, (t, a, b, bound)
        hist = np.asarray(sc6["amax_history"])
        assert hist.shape[0] == len(Q.HISTORY_ROLES)
        assert all((hist[i] > 0).any() for i in range(hist.shape[0]))
