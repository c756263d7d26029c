"""The robustness layer: guarded Pallas dispatch + the StepGuard state machine.

Acceptance contracts (ISSUE 1):

* a forced probe failure degrades the op to its jnp oracle with EXACTLY one
  structured warning, and the numerics still match the oracle;
* NaN grads -> step skipped, params BIT-identical, scale halved;
* K consecutive overflows with the scaler at ``min_loss_scale`` -> params roll
  back to the last clean snapshot;
* no happy-path overhead: verdicts cache per static key, the guarded step jits.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.amp.scaler import LossScaler
from beforeholiday_tpu.guard import (
    SKIP_GRAD_OVERFLOW,
    SKIP_LOSS_NONFINITE,
    SKIP_PARAM_NONFINITE,
    SKIP_ROLLBACK,
    StepGuard,
    checked_impl,
    clear_probe_cache,
    probe_failures,
)
from beforeholiday_tpu.guard import dispatch as guard_dispatch
from beforeholiday_tpu.optimizers import FusedSGD
from beforeholiday_tpu.testing.faults import force_probe_failure


class _Capture(logging.Handler):
    """The repo logger sets propagate=False (utils/logging.py), so caplog never
    sees it — capture by attaching a handler directly."""

    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(record)


@pytest.fixture
def capture_guard_log():
    h = _Capture()
    guard_dispatch.logger.addHandler(h)
    yield h
    guard_dispatch.logger.removeHandler(h)


@pytest.fixture(autouse=True)
def _fresh_probe_cache():
    clear_probe_cache()
    yield
    clear_probe_cache()


# -------------------------------------------------------------------------------
# guarded dispatch
# -------------------------------------------------------------------------------


class TestCheckedImpl:
    def test_non_pallas_impl_passes_through_unprobed(self):
        def boom(x):
            raise RuntimeError("probe must not run")

        x = jnp.ones((4,))
        assert checked_impl("op", "jnp", boom, x) == "jnp"

    def test_passing_probe_keeps_pallas_and_caches(self, capture_guard_log):
        calls = []

        def fine(x):
            calls.append(1)
            return x * 2

        x = jnp.ones((4, 4))
        assert checked_impl("op_ok", "pallas", fine, x) == "pallas"
        assert checked_impl("op_ok", "pallas", fine, x) == "pallas"
        assert len(calls) == 1  # second call is a cache hit
        assert capture_guard_log.records == []

    def test_failing_probe_degrades_with_exactly_one_warning(
        self, capture_guard_log
    ):
        calls = []

        def broken(x):
            calls.append(1)
            raise RuntimeError("no tiling for you")

        x = jnp.ones((4, 4))
        for _ in range(3):
            assert checked_impl("op_bad", "pallas", broken, x) == "jnp"
        assert len(calls) == 1
        warnings = [
            r for r in capture_guard_log.records if r.levelno == logging.WARNING
        ]
        assert len(warnings) == 1
        assert "op_bad" in warnings[0].getMessage()
        assert "jnp oracle" in warnings[0].getMessage()
        assert any(v == "RuntimeError: no tiling for you"
                   for v in probe_failures().values())

    def test_verdicts_key_on_shape_and_dtype(self, capture_guard_log):
        seen = []

        def shape_picky(x):
            seen.append(x.shape)
            if x.shape[0] % 2:
                raise RuntimeError("odd rows unsupported")
            return x

        even = jnp.ones((4, 8))
        odd = jnp.ones((3, 8))
        assert checked_impl("op_shape", "pallas", shape_picky, even) == "pallas"
        assert checked_impl("op_shape", "pallas", shape_picky, odd) == "jnp"
        # both keys independently cached
        assert checked_impl("op_shape", "pallas", shape_picky, even) == "pallas"
        assert checked_impl("op_shape", "pallas", shape_picky, odd) == "jnp"
        assert len(seen) == 2

    def test_traced_kwargs_probe_as_structs(self):
        """Optimizer kernels receive traced kwargs (lr, found_inf...) — the
        probe must key them by shape/dtype and never leak a tracer."""
        def fn(x, *, lr):
            return x * lr

        def run(x, lr):
            impl = checked_impl("op_kw", "pallas", fn, x, lr=lr)
            assert impl == "pallas"
            return x * lr

        out = jax.jit(run)(jnp.ones((4,)), jnp.float32(0.5))
        np.testing.assert_allclose(np.asarray(out), 0.5)

    def test_clear_probe_cache_per_op(self):
        def broken(x):
            raise RuntimeError("x")

        x = jnp.ones((2,))
        checked_impl("op_a", "pallas", broken, x)
        checked_impl("op_b", "pallas", broken, x)
        assert len(probe_failures()) == 2
        clear_probe_cache("op_a")
        assert [k[0] for k in probe_failures()] == ["op_b"]

    def test_trace_clean_sees_the_installed_jax(self):
        """``"auto"`` picks the compile probe only on a clean trace; a probe
        helper that always answers False (as the removed ``jax.core`` name
        made it) would pin every TPU probe to eval_shape."""
        assert guard_dispatch._trace_clean() is True
        seen = []
        jax.jit(lambda x: (seen.append(guard_dispatch._trace_clean()), x)[1])(1.0)
        assert seen == [False]

    def test_probe_mode_off_trusts_kernel(self):
        def broken(x):
            raise RuntimeError("x")

        prev = guard_dispatch.set_probe_mode("off")
        try:
            assert checked_impl("op_off", "pallas", broken, jnp.ones(2)) == "pallas"
        finally:
            guard_dispatch.set_probe_mode(prev)
        with pytest.raises(ValueError):
            guard_dispatch.set_probe_mode("yolo")

    def test_forced_failure_real_op_parity(self, monkeypatch, capture_guard_log):
        """End-to-end acceptance: force layer_norm's probe to fail while the
        dispatch policy would pick pallas -> the op silently runs the jnp
        oracle (numerics identical) and warns exactly once."""
        from beforeholiday_tpu.ops import normalization

        monkeypatch.setattr(
            normalization, "_resolve_impl", lambda impl: impl or "pallas"
        )
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(6, 32), jnp.float32)
        w = jnp.asarray(rng.randn(32), jnp.float32)
        b = jnp.asarray(rng.randn(32), jnp.float32)
        want = normalization.fused_layer_norm(x, w, b, impl="jnp")
        with force_probe_failure("layer_norm"):
            got1 = normalization.fused_layer_norm(x, w, b)
            got2 = normalization.fused_layer_norm(x, w, b)
        np.testing.assert_array_equal(np.asarray(got1), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got2), np.asarray(want))
        warnings = [
            r for r in capture_guard_log.records if r.levelno == logging.WARNING
        ]
        assert len(warnings) == 1

    def test_passing_probe_real_op_stays_pallas(self, monkeypatch):
        """Control for the forced-failure test: with no fault injected the
        guard probes the real kernels (interpret mode) and keeps pallas."""
        from beforeholiday_tpu.ops import normalization, softmax

        monkeypatch.setattr(
            normalization, "_resolve_impl", lambda impl: impl or "pallas"
        )
        monkeypatch.setattr(
            softmax, "_resolve_impl", lambda impl: impl or "pallas"
        )
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(8, 128), jnp.float32)
        w = jnp.ones((128,), jnp.float32)
        b = jnp.zeros((128,), jnp.float32)
        y = normalization.fused_layer_norm(x, w, b)
        want = normalization.fused_layer_norm(x, w, b, impl="jnp")
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert ("layer_norm" not in {k[0] for k in probe_failures()})

        s = softmax.scaled_softmax(x, 0.5)
        want_s = softmax.scaled_softmax(x, 0.5, impl="jnp")
        np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                                   rtol=2e-5, atol=2e-6)

    def test_explicit_pallas_request_bypasses_guard(self, monkeypatch):
        """impl='pallas' keeps the honor-the-request contract — the guard only
        covers default-on dispatch (normalization/softmax/attention)."""
        from beforeholiday_tpu.ops import normalization

        x = jnp.ones((4, 16), jnp.float32)
        w = jnp.ones((16,), jnp.float32)
        with force_probe_failure("layer_norm"):
            # explicit request: probe never consulted, pallas (interpret) runs
            y = normalization.fused_layer_norm(x, w, impl="pallas")
        assert probe_failures() == {}
        assert y.shape == (4, 16)


# -------------------------------------------------------------------------------
# StepGuard
# -------------------------------------------------------------------------------


def _setup(scaler=None, **guard_kw):
    params = {"w": jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)}
    opt = FusedSGD(lr=0.1)
    guard = StepGuard(scaler, **guard_kw)
    return params, opt, opt.init(params), guard, guard.init(params)


def _loss(p, x):
    return jnp.sum(p["w"] * x)


class TestStepGuard:
    def test_clean_step_matches_unguarded(self):
        params, opt, ostate, guard, gstate = _setup(
            LossScaler(init_scale=4.0, min_loss_scale=1.0)
        )
        vg = guard.value_and_grad(_loss)
        x = jnp.asarray([1.0, -1.0, 2.0, 0.5], jnp.float32)

        @jax.jit
        def step(params, ostate, gstate, x):
            loss, grads, verdict = vg(params, gstate, x)
            p, o, g = guard.apply_update(opt, params, grads, ostate, gstate, verdict)
            return p, o, g, loss

        p2, o2, gs2, loss = step(params, ostate, gstate, x)
        g_ref = jax.grad(_loss)(params, x)
        p_ref, _ = opt.step(params, g_ref, opt.init(params))
        np.testing.assert_allclose(np.asarray(p2["w"]), np.asarray(p_ref["w"]),
                                   rtol=1e-6)
        np.testing.assert_allclose(float(loss), float(_loss(params, x)), rtol=1e-6)
        health = {k: int(v) for k, v in gs2["health"].items()}
        assert health["skipped_total"] == 0
        assert health["consecutive_overflows"] == 0
        assert float(gs2["scaler"]["scale"]) == 4.0

    def test_nan_grads_skip_bit_identical_params_scale_halved(self):
        params, opt, ostate, guard, gstate = _setup(
            LossScaler(init_scale=4.0, min_loss_scale=1.0)
        )
        vg = guard.value_and_grad(_loss)

        @jax.jit
        def step(params, ostate, gstate, x):
            loss, grads, verdict = vg(params, gstate, x)
            return guard.apply_update(opt, params, grads, ostate, gstate, verdict)

        bad = jnp.asarray([jnp.nan, 1.0, 1.0, 1.0], jnp.float32)
        p2, o2, gs2 = step(params, ostate, gstate, bad)
        np.testing.assert_array_equal(np.asarray(p2["w"]), np.asarray(params["w"]))
        # optimizer momentum also held (identity-select in the fused kernel)
        for a, b in zip(jax.tree_util.tree_leaves(o2),
                        jax.tree_util.tree_leaves(ostate)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(gs2["scaler"]["scale"]) == 2.0  # halved
        health = {k: int(v) for k, v in gs2["health"].items()}
        assert health["skipped_total"] == 1
        assert health["consecutive_overflows"] == 1
        assert health["last_skip_reason"] == SKIP_LOSS_NONFINITE

    def test_grad_overflow_reason_without_nan_loss(self):
        """Non-finite grads under a finite loss (the check_grads entry point
        for externally produced grads) -> reason is grad_overflow."""
        params, opt, ostate, guard, gstate = _setup(
            LossScaler(init_scale=2.0, min_loss_scale=1.0)
        )
        grads = {"w": jnp.asarray([jnp.inf, 0.0, 0.0, 0.0], jnp.float32)}
        verdict = guard.check_grads(jnp.float32(1.25), grads)
        assert bool(verdict["grad_overflow"])
        assert not bool(verdict["loss_nonfinite"])
        p2, o2, gs2 = guard.apply_update(opt, params, grads, ostate, gstate, verdict)
        np.testing.assert_array_equal(np.asarray(p2["w"]), np.asarray(params["w"]))
        assert int(gs2["health"]["last_skip_reason"]) == SKIP_GRAD_OVERFLOW

    def test_param_sentinel_reverts_params_and_opt_state(self):
        class BlowupOpt:
            """Finite grads, non-finite update — the lr/eps blowup class the
            grad flag cannot see."""

            def init(self, params):
                return {"calls": jnp.int32(0)}

            def step(self, params, grads, state, *, found_inf=None,
                     grad_scale=1.0):
                skip = jnp.asarray(found_inf) != 0
                new = jax.tree_util.tree_map(
                    lambda p: jnp.where(skip, p, p + jnp.inf), params
                )
                return new, {"calls": state["calls"] + jnp.where(skip, 0, 1)}

        params = {"w": jnp.ones((4,), jnp.float32)}
        opt = BlowupOpt()
        guard = StepGuard(
            LossScaler(init_scale=4.0, min_loss_scale=1.0), check_params=True
        )
        gstate = guard.init(params)
        vg = guard.value_and_grad(_loss)
        loss, grads, verdict = vg(params, gstate, jnp.ones((4,)))
        assert not bool(verdict["grad_overflow"])
        p2, o2, gs2 = guard.apply_update(
            opt, params, grads, opt.init(params), gstate, verdict
        )
        np.testing.assert_array_equal(np.asarray(p2["w"]), np.asarray(params["w"]))
        assert int(o2["calls"]) == 0  # opt state reverted too
        assert int(gs2["health"]["last_skip_reason"]) == SKIP_PARAM_NONFINITE
        assert float(gs2["scaler"]["scale"]) == 2.0  # shrinks like an overflow

    def test_rollback_after_k_consecutive_overflows_at_min_scale(self):
        params, opt, ostate, guard, gstate = _setup(
            LossScaler(init_scale=2.0, min_loss_scale=1.0),
            rollback_after=2,
        )
        vg = guard.value_and_grad(_loss)

        @jax.jit
        def step(params, ostate, gstate, x):
            loss, grads, verdict = vg(params, gstate, x)
            return guard.apply_update(opt, params, grads, ostate, gstate, verdict)

        good = jnp.asarray([1.0, -1.0, 0.5, 2.0], jnp.float32)
        bad = jnp.asarray([jnp.nan, 1.0, 1.0, 1.0], jnp.float32)

        # one clean step establishes the snapshot
        p1, o1, gs1 = step(params, ostate, gstate, good)
        clean = np.asarray(p1["w"])
        np.testing.assert_array_equal(np.asarray(gs1["snapshot"]["w"]), clean)

        # overflow 1: scale 2 -> 1 (hits the floor), no rollback yet
        p2, o2, gs2 = step(p1, o1, gs1, bad)
        assert float(gs2["scaler"]["scale"]) == 1.0
        assert int(gs2["health"]["rollbacks_total"]) == 0

        # overflow 2: consec == 2 at min scale -> rollback to the snapshot
        p3, o3, gs3 = step(p2, o2, gs2, bad)
        np.testing.assert_array_equal(np.asarray(p3["w"]), clean)
        health = {k: int(v) for k, v in gs3["health"].items()}
        assert health["rollbacks_total"] == 1
        assert health["last_skip_reason"] == SKIP_ROLLBACK
        assert health["consecutive_overflows"] == 0  # reset: fresh start
        assert health["skipped_total"] == 2

    def test_snapshot_tracks_clean_steps_only(self):
        params, opt, ostate, guard, gstate = _setup(
            LossScaler(init_scale=2.0, min_loss_scale=1.0), rollback_after=3
        )
        vg = guard.value_and_grad(_loss)

        def step(params, ostate, gstate, x):
            loss, grads, verdict = vg(params, gstate, x)
            return guard.apply_update(opt, params, grads, ostate, gstate, verdict)

        good = jnp.ones((4,), jnp.float32)
        bad = jnp.full((4,), jnp.nan, jnp.float32)
        p1, o1, gs1 = step(params, ostate, gstate, good)
        p2, o2, gs2 = step(p1, o1, gs1, bad)  # skip: snapshot must NOT move
        np.testing.assert_array_equal(
            np.asarray(gs2["snapshot"]["w"]), np.asarray(p1["w"])
        )
        p3, o3, gs3 = step(p2, o2, gs2, good)  # clean: snapshot advances
        np.testing.assert_array_equal(
            np.asarray(gs3["snapshot"]["w"]), np.asarray(p3["w"])
        )

    def test_state_dict_roundtrip_and_backcompat(self):
        params, opt, ostate, guard, gstate = _setup(
            LossScaler(init_scale=8.0, min_loss_scale=1.0), rollback_after=2
        )
        vg = guard.value_and_grad(_loss)
        loss, grads, verdict = vg(params, gstate, jnp.full((4,), jnp.nan))
        _, _, gs2 = guard.apply_update(opt, params, grads, ostate, gstate, verdict)

        sd = guard.state_dict(gs2)
        assert sd["loss_scale"] == 4.0
        assert sd["health"]["skipped_total"] == 1
        restored = guard.load_state_dict(sd, params=params)
        assert float(restored["scaler"]["scale"]) == 4.0
        assert int(restored["health"]["skipped_total"]) == 1
        np.testing.assert_array_equal(
            np.asarray(restored["snapshot"]["w"]), np.asarray(params["w"])
        )

        # pre-guard checkpoint: bare scaler dict, no health
        old = {"loss_scale": 16.0, "unskipped": 7}
        restored_old = guard.load_state_dict(old, params=params)
        assert float(restored_old["scaler"]["scale"]) == 16.0
        assert all(int(v) == 0 for v in restored_old["health"].values())

        with pytest.raises(ValueError, match="needs params"):
            guard.load_state_dict(sd)  # rollback armed, params required

    def test_invalid_rollback_after(self):
        with pytest.raises(ValueError):
            StepGuard(rollback_after=-1)


# -------------------------------------------------------------------------------
# scaler satellites + amp integration
# -------------------------------------------------------------------------------


class TestScalerHealth:
    def test_consecutive_overflows_counts_and_resets(self):
        s = LossScaler(init_scale=16.0, min_loss_scale=1.0)
        st = s.init()
        st = s.update(st, jnp.bool_(True))
        st = s.update(st, jnp.bool_(True))
        assert int(st["consecutive_overflows"]) == 2
        st = s.update(st, jnp.bool_(False))
        assert int(st["consecutive_overflows"]) == 0

    def test_consecutive_overflows_on_static_scale(self):
        s = LossScaler(loss_scale=128.0)
        st = s.init()
        st = s.update(st, jnp.bool_(True))
        assert int(st["consecutive_overflows"]) == 1
        assert float(st["scale"]) == 128.0  # static scale never moves

    def test_at_min_scale(self):
        dyn = LossScaler(init_scale=2.0, min_loss_scale=1.0)
        st = dyn.init()
        assert not bool(dyn.at_min_scale(st))
        st = dyn.update(st, jnp.bool_(True))  # 2 -> 1 (clamped)
        assert float(st["scale"]) == 1.0
        assert bool(dyn.at_min_scale(st))
        # no floor -> can always shrink; static -> can never shrink
        assert not bool(LossScaler().at_min_scale(LossScaler().init()))
        stat = LossScaler(loss_scale=8.0)
        assert bool(stat.at_min_scale(stat.init()))

    def test_state_dict_tolerates_old_checkpoints(self):
        s = LossScaler()
        st = s.load_state_dict({"loss_scale": 4.0, "unskipped": 3})
        assert int(st["consecutive_overflows"]) == 0
        sd = s.state_dict({"scale": jnp.float32(4.0), "unskipped": jnp.int32(3)})
        assert sd["consecutive_overflows"] == 0

    def test_amp_state_dict_carries_health(self):
        from beforeholiday_tpu import amp

        params = {"w": jnp.ones((4, 4), jnp.float32)}
        model = amp.initialize(
            lambda p, x: x @ p["w"], params, FusedSGD(lr=0.1), "O2"
        )
        guard = StepGuard(model.scaler)
        gstate = guard.init(model.params)
        sd = model.state_dict(gstate)
        assert "loss_scaler0" in sd and "health0" in sd
        assert sd["health0"]["skipped_total"] == 0
        restored = model.load_state_dict(sd)
        assert set(restored) == {"scaler", "health"}
        assert int(restored["health"]["skipped_total"]) == 0

        # a bare scaler state still round-trips the old way
        sstate = model.scaler.init()
        sd_old = model.state_dict(sstate)
        assert "health0" not in sd_old
        restored_old = model.load_state_dict(sd_old)
        assert "scale" in restored_old  # bare scaler state, not guard-shaped
