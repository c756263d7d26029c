"""Fault-injection drills (``-m faults``): every injector in
``beforeholiday_tpu.testing.faults`` driven through the guardrail it exists to
rehearse — poisoned grads through the skip-step, a forced probe failure through
the jnp degradation, and a perturbed rank through the consistency fingerprint
on the 8-device CPU mesh.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.amp.scaler import LossScaler
from beforeholiday_tpu.guard import StepGuard, probe_failures
from beforeholiday_tpu.optimizers import FusedSGD
from beforeholiday_tpu.parallel import reduce_gradients
from beforeholiday_tpu.testing.faults import (
    force_probe_failure,
    perturb_rank_grads,
    poison_grads,
)

pytestmark = pytest.mark.faults


def shard_map(f=None, **kw):
    kw.setdefault("check_vma", False)
    if f is None:
        return lambda g: jax.shard_map(g, **kw)
    return jax.shard_map(f, **kw)


class TestPoisonGrads:
    def _grads(self):
        rng = np.random.RandomState(0)
        return {
            "a": jnp.asarray(rng.randn(4, 4), jnp.float32),
            "b": jnp.asarray(rng.randn(8), jnp.float32),
            "step": jnp.int32(3),  # integer leaf must never be poisoned
        }

    def test_deterministic_and_counted(self):
        g = self._grads()
        p1 = poison_grads(g, n=1, seed=42)
        p2 = poison_grads(g, n=1, seed=42)
        nan1 = [bool(jnp.any(jnp.isnan(l)))
                for l in jax.tree_util.tree_leaves(p1)]
        nan2 = [bool(jnp.any(jnp.isnan(l)))
                for l in jax.tree_util.tree_leaves(p2)]
        assert nan1 == nan2  # same seed -> same leaf poisoned
        assert sum(nan1) == 1
        assert int(p1["step"]) == 3

    def test_all_leaves_and_custom_value(self):
        g = self._grads()
        p = poison_grads(g, n=2, value=float("inf"), seed=0, whole_leaf=True)
        assert bool(jnp.all(jnp.isinf(p["a"]))) and bool(jnp.all(jnp.isinf(p["b"])))
        with pytest.raises(ValueError):
            poison_grads(g, n=-1)
        with pytest.raises(ValueError):
            poison_grads({"i": jnp.int32(1)})  # no inexact leaves

    def test_poisoned_grads_skip_step_params_bit_identical(self):
        """The acceptance drill: NaN grads -> step skipped, params
        bit-identical, scale halved, health records it."""
        params = {"w": jnp.asarray([1.0, 2.0, 3.0], jnp.float32),
                  "v": jnp.asarray([[0.5, -0.5]], jnp.float32)}
        opt = FusedSGD(lr=0.1)
        guard = StepGuard(LossScaler(init_scale=8.0, min_loss_scale=1.0))
        gstate = guard.init(params)
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        bad = poison_grads(grads, n=1, seed=7)

        @jax.jit
        def step(params, ostate, gstate, loss, grads):
            verdict = guard.check_grads(loss, grads)
            return guard.apply_update(opt, params, grads, ostate, gstate, verdict)

        ostate = opt.init(params)
        p2, o2, gs2 = step(params, ostate, gstate, jnp.float32(1.0), bad)
        for a, b in zip(jax.tree_util.tree_leaves(p2),
                        jax.tree_util.tree_leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(gs2["scaler"]["scale"]) == 4.0
        assert int(gs2["health"]["skipped_total"]) == 1

        # clean grads through the same jitted step DO move params
        p3, o3, gs3 = step(params, ostate, gstate, jnp.float32(1.0), grads)
        assert not np.array_equal(np.asarray(p3["w"]), np.asarray(params["w"]))
        assert int(gs3["health"]["skipped_total"]) == 0


class TestForceProbeFailure:
    def test_scoped_registration_and_cache_reset(self, monkeypatch):
        from beforeholiday_tpu.guard import dispatch
        from beforeholiday_tpu.ops import softmax

        monkeypatch.setattr(
            softmax, "_resolve_impl", lambda impl: impl or "pallas"
        )
        x = jnp.asarray(np.random.RandomState(1).randn(4, 16), jnp.float32)
        want = softmax.scaled_softmax(x, 2.0, impl="jnp")
        with force_probe_failure("softmax"):
            assert "softmax" in dispatch._FORCED_FAILURES
            got = softmax.scaled_softmax(x, 2.0)  # degraded -> oracle
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            assert any(k[0] == "softmax" for k in probe_failures())
        # exit: injection removed AND the poisoned verdicts dropped
        assert "softmax" not in dispatch._FORCED_FAILURES
        assert not any(k[0] == "softmax" for k in probe_failures())
        y = softmax.scaled_softmax(x, 2.0)  # re-probes, passes, runs pallas
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_nested_ops_and_unknown_exit_safe(self):
        from beforeholiday_tpu.guard import dispatch

        with force_probe_failure("op_x", "op_y"):
            with force_probe_failure("op_x"):  # already registered by outer
                assert {"op_x", "op_y"} <= dispatch._FORCED_FAILURES
            # inner exit must not unregister the outer "op_x"... (discard
            # semantics: it does remove it; outer exit is then a no-op)
        assert "op_x" not in dispatch._FORCED_FAILURES
        assert "op_y" not in dispatch._FORCED_FAILURES


class TestRankConsistency:
    @pytest.fixture
    def data_mesh(self, devices8):
        return Mesh(np.asarray(devices8).reshape(8), ("data",))

    def _run(self, data_mesh, *, rank=None, eps=1e-3, value=None):
        """Replicated grads in; optionally perturb one rank inside the
        shard_map; reduce with the fingerprint check."""
        g = jnp.asarray(np.random.RandomState(0).randn(16), jnp.float32)

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(),), out_specs=(P(), P()),
        )
        def f(g):
            grads = {"g": g}
            if rank is not None:
                grads = perturb_rank_grads(
                    grads, "data", rank=rank, eps=eps, value=value
                )
            reduced, mismatch = reduce_gradients(
                grads, check_consistency=True
            )
            return reduced["g"], mismatch

        return jax.jit(f)(g)

    def test_agreeing_ranks_no_mismatch(self, data_mesh):
        reduced, mismatch = self._run(data_mesh)
        assert not bool(mismatch)

    def test_perturbed_rank_flags_mismatch(self, data_mesh):
        reduced, mismatch = self._run(data_mesh, rank=3)
        assert bool(mismatch)

    def test_nonfinite_rank_flags_mismatch(self, data_mesh):
        reduced, mismatch = self._run(data_mesh, rank=5, value=float("nan"))
        assert bool(mismatch)

    def test_check_consistency_false_keeps_old_return(self, data_mesh):
        g = jnp.ones((16,), jnp.float32)

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(),), out_specs=P(),
        )
        def f(g):
            return reduce_gradients({"g": g})["g"]

        out = jax.jit(f)(g)
        np.testing.assert_allclose(np.asarray(out), 1.0)


class TestChaosInjectors:
    """The PR-16 injectors: suppressed heartbeats and torn host manifests
    (their end-to-end drills live in test_chaos.py / test_elastic.py —
    here just the injector contracts)."""

    def test_preemption_exception_is_the_elastic_one(self):
        """``preempt_after`` raises the exception the trainer's real SIGTERM
        path raises: one class, defined beside the handler, re-exported here."""
        from beforeholiday_tpu.elastic import signals
        from beforeholiday_tpu.testing import faults

        assert faults.SimulatedPreemption is signals.SimulatedPreemption
        with pytest.raises(signals.SimulatedPreemption):
            faults.preempt_after(1)()

    def test_hang_rank_targets_one_rank_after_step(self):
        from beforeholiday_tpu.elastic import HangWatchdog
        from beforeholiday_tpu.testing.faults import hang_rank

        wd = HangWatchdog(4, hang_timeout_s=5.0)
        sup = hang_rank(wd, 1, after_step=3)
        assert wd.beat(1, 2)       # before the onset step: alive
        assert not wd.beat(1, 3)   # from after_step on: suppressed
        assert wd.beat(0, 3) and wd.beat(2, 3) and wd.beat(3, 3)
        wd.remove_suppressor(sup)  # the return value un-hangs the rank
        assert wd.beat(1, 4)

    def test_hang_rank_validates(self):
        from beforeholiday_tpu.elastic import HangWatchdog
        from beforeholiday_tpu.testing.faults import hang_rank

        wd = HangWatchdog(2, hang_timeout_s=5.0)
        with pytest.raises(ValueError, match="rank"):
            hang_rank(wd, 2)
        with pytest.raises(ValueError, match="rank"):
            hang_rank(wd, -1)

    def test_tear_host_generation(self, tmp_path):
        from beforeholiday_tpu.optimizers import zero3
        from beforeholiday_tpu.testing.faults import tear_host_generation

        gen = tmp_path / "gen_00000002"
        gen.mkdir()
        target = zero3.host_manifest_path(str(gen), 1)
        with open(target, "w") as f:
            f.write("{}")
        assert tear_host_generation(str(gen), 1) == target
        assert not os.path.exists(target)
        with pytest.raises(FileNotFoundError):
            tear_host_generation(str(gen), 1)
        with pytest.raises(FileNotFoundError):
            tear_host_generation(str(gen), 0)   # never existed
