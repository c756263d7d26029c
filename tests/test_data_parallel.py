"""Data-parallel layer semantics on an 8-device CPU mesh.

Ports of the reference's contracts: DP training is semantics-identical to
single-device training on the concatenated batch (tests/distributed/DDP),
SyncBN matches BatchNorm over the full batch
(tests/distributed/synced_batchnorm/two_gpu_unit_test.py), LARC trust-ratio
math (apex/parallel/LARC.py:79-94).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
from jax.sharding import Mesh, PartitionSpec as P


def shard_map(f=None, **kw):
    kw.setdefault("check_vma", False)
    if f is None:
        return lambda g: jax.shard_map(g, **kw)
    return jax.shard_map(f, **kw)

from beforeholiday_tpu.optimizers import FusedSGD
from beforeholiday_tpu.parallel import (
    DistributedDataParallel,
    LARC,
    Reducer,
    init_batch_norm,
    reduce_gradients,
    sync_batch_norm,
)


@pytest.fixture
def data_mesh(devices8):
    return Mesh(np.asarray(devices8).reshape(8), ("data",))


def _loss_fn(params, x, y):
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


class TestReduceGradients:
    def test_ddp_grads_match_global_batch(self, data_mesh):
        """The key DDP oracle: per-shard grads + psum-average == full-batch grads."""
        rng = np.random.RandomState(0)
        params = {"w": jnp.asarray(rng.randn(8, 4), jnp.float32),
                  "b": jnp.zeros((4,), jnp.float32)}
        x = jnp.asarray(rng.randn(32, 8), jnp.float32)
        y = jnp.asarray(rng.randn(32, 4), jnp.float32)

        ddp = DistributedDataParallel()

        @functools.partial(
            shard_map, mesh=data_mesh,
            in_specs=(P(), P("data"), P("data")), out_specs=(P(), P()),
        )
        def sharded_grads(params, x, y):
            loss, grads = ddp.value_and_grad(_loss_fn)(params, x, y)
            return jax.lax.pmean(loss, "data"), grads

        loss_dp, grads_dp = jax.jit(sharded_grads)(params, x, y)
        loss_ref, grads_ref = jax.value_and_grad(_loss_fn)(params, x, y)
        np.testing.assert_allclose(float(loss_dp), float(loss_ref), rtol=1e-6)
        for k in grads_ref:
            np.testing.assert_allclose(
                np.asarray(grads_dp[k]), np.asarray(grads_ref[k]), rtol=1e-5, atol=1e-6
            )

    def test_predivide_factor_equivalent(self, data_mesh):
        """predivide: /f before, /(world/f) after == plain average (up to fp error)."""
        grads = {"g": jnp.arange(16, dtype=jnp.float32).reshape(16)}

        def run(**kw):
            @functools.partial(
                shard_map, mesh=data_mesh, in_specs=(P("data"),), out_specs=P("data")
            )
            def f(g):
                return reduce_gradients({"g": g}, **kw)["g"]

            return np.asarray(jax.jit(f)(grads["g"]))

        plain = run()
        pre = run(gradient_predivide_factor=4.0)
        np.testing.assert_allclose(pre, plain, rtol=1e-6)

    def test_no_average_sums(self, data_mesh):
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P("data"),), out_specs=P("data")
        )
        def f(g):
            return reduce_gradients({"g": g}, gradient_average=False)["g"]

        g = jnp.ones((8,), jnp.float32)
        out = np.asarray(jax.jit(f)(g))
        np.testing.assert_allclose(out, 8.0)

    def test_fp32_allreduce_roundtrips_dtype(self, data_mesh):
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P("data"),), out_specs=P("data")
        )
        def f(g):
            out = reduce_gradients({"g": g}, allreduce_always_fp32=True)["g"]
            return out

        g = jnp.ones((8,), jnp.bfloat16)
        out = jax.jit(f)(g)
        assert out.dtype == jnp.bfloat16

    def test_fp32_allreduce_composes_with_predivide(self, data_mesh):
        """allreduce_always_fp32 + gradient_predivide_factor together: the
        /f -> psum -> /(world/f) chain runs in fp32 and round-trips to the
        input dtype, and the result still equals the plain average (ref:
        apex/parallel/distributed.py:316-349 allreduce_fallback, which
        applies both options in exactly this order)."""
        vals = np.linspace(-3.0, 4.0, 8).astype(np.float32)

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P("data"),), out_specs=P("data")
        )
        def f(g):
            return reduce_gradients(
                {"g": g},
                allreduce_always_fp32=True,
                gradient_predivide_factor=4.0,
            )["g"]

        g16 = jnp.asarray(vals, jnp.bfloat16)
        out = jax.jit(f)(g16)
        assert out.dtype == jnp.bfloat16
        want = jnp.asarray(vals, jnp.bfloat16).astype(jnp.float32).mean()
        np.testing.assert_allclose(
            np.asarray(out, np.float32), float(want), rtol=1e-2
        )

    def test_ddp_training_identical_to_single_device(self, data_mesh):
        """Several optimizer steps: DP on 8 shards == single device, bitwise-ish."""
        rng = np.random.RandomState(1)
        params = {"w": jnp.asarray(rng.randn(8, 4), jnp.float32),
                  "b": jnp.zeros((4,), jnp.float32)}
        opt = FusedSGD(lr=0.1, momentum=0.9, impl="jnp")
        xs = jnp.asarray(rng.randn(5, 32, 8), jnp.float32)
        ys = jnp.asarray(rng.randn(5, 32, 4), jnp.float32)

        ddp = DistributedDataParallel()

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh,
            in_specs=(P(), P(), P("data"), P("data")),
            out_specs=(P(), P()),
        )
        def dp_step(params, state, x, y):
            _, grads = ddp.value_and_grad(_loss_fn)(params, x, y)
            return opt.step(params, grads, state)

        p_dp, s_dp = params, opt.init(params)
        p_ref, s_ref = params, opt.init(params)
        for i in range(5):
            p_dp, s_dp = dp_step(p_dp, s_dp, xs[i], ys[i])
            g_ref = jax.grad(_loss_fn)(p_ref, xs[i], ys[i])
            p_ref, s_ref = opt.step(p_ref, g_ref, s_ref)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p_dp[k]), np.asarray(p_ref[k]), rtol=1e-5, atol=1e-6
            )

    def test_reducer(self, data_mesh):
        r = Reducer()

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P("data"),), out_specs=P("data")
        )
        def f(x):
            return r.reduce({"x": x})["x"]

        out = np.asarray(jax.jit(f)(jnp.arange(8, dtype=jnp.float32)))
        np.testing.assert_allclose(out, np.full(8, np.arange(8).mean()))

    def test_broadcast_params_selects_rank0_when_diverged(self, data_mesh):
        """broadcast repairs divergence with rank 0's exact values, not a mean
        (ref: apex/parallel/distributed.py:254)."""
        r = Reducer()

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P("data"),), out_specs=P("data")
        )
        def f(p):
            return r.broadcast_params({"w": p})["w"]

        diverged = jnp.arange(8, dtype=jnp.float32) * 3.0 + 7.0  # rank i holds 3i+7
        out = np.asarray(jax.jit(f)(diverged))
        np.testing.assert_allclose(out, np.full(8, 7.0), atol=0)

    def test_broadcast_params_integer_leaves_exact(self, data_mesh):
        """Integer leaves (step counters, embeddings' index tables) broadcast
        exactly — the masked-psum trick must neither promote the dtype nor
        round the values, even when ranks disagree."""
        r = Reducer()

        @functools.partial(
            shard_map, mesh=data_mesh,
            in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data")),
        )
        def f(w, step):
            out = r.broadcast_params({"w": w, "step": step})
            return out["w"], out["step"]

        w = jnp.arange(8, dtype=jnp.float32) * 2.0 - 5.0  # rank i holds 2i-5
        step = jnp.arange(8, dtype=jnp.int32) + 100       # rank i holds 100+i
        ow, ostep = jax.jit(f)(w, step)
        assert ostep.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(ow), np.full(8, -5.0))
        np.testing.assert_array_equal(np.asarray(ostep), np.full(8, 100))


class TestSyncBatchNorm:
    def test_shifted_onepass_stats_contract(self):
        """The single-device one-pass moments are exact within their
        documented contract: cold start with near-zero means, and steady
        state (running mean tracking) at ANY magnitude. The adversarial
        out-of-contract case (cold start at |mean|/std=1000) must be served
        correctly by stats='two_pass'."""
        rng = np.random.RandomState(0)
        # contract case 1: cold start, zero-ish means (standard-init regime)
        x = rng.randn(64, 3, 32, 32).astype(np.float32)
        params, state = init_batch_norm(3)
        y, st = sync_batch_norm(jnp.asarray(x), params, state, training=True)
        np.testing.assert_allclose(np.asarray(y).std(axis=(0, 2, 3)), 1.0, atol=1e-2)

        # contract case 2: steady state at magnitude 1000 (shift == mean)
        xl = (1000.0 + rng.randn(64, 3, 32, 32)).astype(np.float32)
        warm = type(state)(jnp.asarray(xl.mean(axis=(0, 2, 3))), state.running_var)
        y2, st2 = sync_batch_norm(jnp.asarray(xl), params, warm, training=True)
        np.testing.assert_allclose(np.asarray(y2).std(axis=(0, 2, 3)), 1.0, atol=1e-2)
        np.testing.assert_allclose(np.asarray(y2).mean(axis=(0, 2, 3)), 0.0, atol=5e-3)

        # out-of-contract: the two_pass option restores exactness
        y3, st3 = sync_batch_norm(jnp.asarray(xl), params, state,
                                  training=True, stats="two_pass")
        want_var = xl.astype(np.float64).var(axis=(0, 2, 3))
        got_var = (np.asarray(st3.running_var, np.float64)
                   - 0.9 * np.asarray(state.running_var)) / 0.1
        np.testing.assert_allclose(got_var, want_var, rtol=5e-3)
        np.testing.assert_allclose(np.asarray(y3).std(axis=(0, 2, 3)), 1.0, atol=1e-2)

    def test_shifted_onepass_grads_match_twopass(self):
        """stop_gradient on the subsample shift is exact: mean/var are
        shift-invariant, so grads must equal the (sync, two-pass) formula's.
        Run the same data through the axis_name path on a 1-device mesh as
        the two-pass reference."""
        from jax.sharding import Mesh, PartitionSpec as P

        rng = np.random.RandomState(3)
        x = rng.randn(8, 4, 6, 6).astype(np.float32) * 2.0 + 1.5
        params, state = init_batch_norm(4)

        def loss_1p(x):
            y, _ = sync_batch_norm(jnp.asarray(x), params, state, training=True)
            return jnp.sum(jnp.sin(y))

        mesh1 = Mesh(np.array(jax.devices()[:1]), ("d1",))

        def loss_2p(x):
            @functools.partial(shard_map, mesh=mesh1, in_specs=(P(),),
                               out_specs=P())
            def f(xs):
                y, _ = sync_batch_norm(xs, params, state, axis_name="d1",
                                       training=True)
                return y

            return jnp.sum(jnp.sin(f(x)))

        g1 = jax.grad(loss_1p)(jnp.asarray(x))
        g2 = jax.grad(loss_2p)(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-4, atol=2e-5)

    def test_matches_torch_bn_over_full_batch(self, data_mesh):
        """SyncBN on 8 shards == torch BatchNorm2d on the concatenated batch."""
        rng = np.random.RandomState(2)
        x = rng.randn(16, 6, 4, 4).astype(np.float32)
        params, state = init_batch_norm(6)

        @functools.partial(
            shard_map, mesh=data_mesh,
            in_specs=(P("data"),), out_specs=(P("data"), P()),
        )
        def f(xs):
            y, st = sync_batch_norm(xs, params, state, axis_name="data", training=True)
            return y, st

        y, new_state = jax.jit(f)(jnp.asarray(x))

        bn = torch.nn.BatchNorm2d(6, eps=1e-5, momentum=0.1)
        with torch.no_grad():
            ty = bn(torch.tensor(x))
        np.testing.assert_allclose(np.asarray(y), ty.numpy(), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(new_state.running_mean), bn.running_mean.numpy(), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(new_state.running_var), bn.running_var.numpy(), rtol=1e-4, atol=1e-4
        )

    def test_backward_matches_full_batch(self, data_mesh):
        """Standard DDP pattern: local loss, grads summed across shards ==
        grads of the same loss over the concatenated batch (the contract of
        the reference's allreduce of (sum_dy, sum_dy_xmu) in SyncBatchnormFunction
        backward)."""
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(16, 6, 3, 3), jnp.float32)
        params, state = init_batch_norm(6)

        def local_loss(params, xs):
            y, _ = sync_batch_norm(xs, params, state, axis_name="data", training=True)
            return jnp.sum(y**2)

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P("data")), out_specs=P(),
        )
        def dp_grads(params, xs):
            g = jax.grad(local_loss)(params, xs)
            return reduce_gradients(g, gradient_average=False)

        g_dp = jax.jit(dp_grads)(params, x)

        def full_loss(params):
            y, _ = sync_batch_norm(x, params, state, training=True)
            return jnp.sum(y**2)

        g_ref = jax.grad(full_loss)(params)
        np.testing.assert_allclose(
            np.asarray(g_dp.scale), np.asarray(g_ref.scale), rtol=1e-3, atol=1e-3
        )
        np.testing.assert_allclose(
            np.asarray(g_dp.bias), np.asarray(g_ref.bias), rtol=1e-3, atol=1e-3
        )

    def test_eval_mode_uses_running_stats(self):
        params, state = init_batch_norm(4)
        state = state._replace(
            running_mean=jnp.full((4,), 2.0), running_var=jnp.full((4,), 4.0)
        )
        x = jnp.full((2, 4, 2), 6.0)
        y, st = sync_batch_norm(x, params, state, training=False)
        np.testing.assert_allclose(np.asarray(y), (6.0 - 2.0) / np.sqrt(4.0 + 1e-5), rtol=1e-5)
        assert st is state

    def test_channel_last_and_fuse_relu(self):
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(8, 4, 4, 6), jnp.float32)  # NHWC
        params, state = init_batch_norm(6)
        y, _ = sync_batch_norm(x, params, state, channel_last=True, fuse_relu=True)
        x_nchw = jnp.transpose(x, (0, 3, 1, 2))
        y2, _ = sync_batch_norm(x_nchw, params, state)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(jax.nn.relu(jnp.transpose(y2, (0, 2, 3, 1)))),
            rtol=1e-5, atol=1e-5,
        )


class TestLARC:
    def test_rejects_inner_weight_decay(self):
        with pytest.raises(ValueError, match="weight decay"):
            LARC(FusedSGD(lr=0.1, weight_decay=0.1, impl="jnp"))

    def test_matches_manual_larc_math(self):
        # single param: verify the adaptive lr against the reference formula
        p = {"w": jnp.full((16,), 2.0)}
        g = {"w": jnp.full((16,), 0.5)}
        inner = FusedSGD(lr=0.1, impl="jnp")
        larc = LARC(inner, trust_coefficient=0.02, clip=False, weight_decay=0.0)
        state = larc.init(p)
        p1, _ = larc.step(p, g, state)

        p_norm = np.sqrt(16 * 4.0)
        g_norm = np.sqrt(16 * 0.25)
        adaptive = 0.02 * p_norm / (g_norm + 1e-8)
        expected = 2.0 - 0.1 * adaptive * 0.5
        np.testing.assert_allclose(np.asarray(p1["w"]), expected, rtol=1e-5)

    def test_clip_caps_effective_lr(self):
        # huge param norm → adaptive_lr >> lr; clip caps the multiplier at 1
        p = {"w": jnp.full((16,), 100.0)}
        g = {"w": jnp.full((16,), 1e-3)}
        inner = FusedSGD(lr=0.1, impl="jnp")
        larc = LARC(inner, trust_coefficient=0.02, clip=True)
        p1, _ = larc.step(p, g, larc.init(p))
        # clipped: step = lr * g exactly
        np.testing.assert_allclose(np.asarray(p1["w"]), 100.0 - 0.1 * 1e-3, rtol=1e-6)

    def test_zero_grad_keeps_unit_scale(self):
        p = {"w": jnp.full((4,), 3.0)}
        g = {"w": jnp.zeros((4,))}
        larc = LARC(FusedSGD(lr=0.1, impl="jnp"), clip=False)
        p1, _ = larc.step(p, g, larc.init(p))
        np.testing.assert_allclose(np.asarray(p1["w"]), 3.0)

    def test_trains_with_weight_decay(self):
        p = {"w": jnp.full((32,), 2.0)}
        larc = LARC(FusedSGD(lr=0.5, momentum=0.9, impl="jnp"),
                    weight_decay=1e-3, clip=True)
        state = larc.init(p)
        step = jax.jit(lambda p, s: larc.step(p, {"w": p["w"]}, s))
        hist = [4.0]
        for _ in range(20):
            p, state = step(p, state)
            hist.append(float(jnp.mean(p["w"] ** 2)))
        assert hist[-1] < hist[0]


class TestSimpleDistributedExample:
    def test_runs_on_cpu_mesh(self):
        """The smallest DDP+amp onboarding script (the reference's
        examples/simple/distributed) must run as-is on an 8-CPU mesh."""
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = os.path.join(
            repo, "examples", "simple", "distributed",
            "distributed_data_parallel.py",
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8").strip()
        env["PYTHONPATH"] = repo
        out = subprocess.run(
            [sys.executable, script], env=env, capture_output=True, text=True,
            timeout=300,
        )
        assert out.returncode == 0, out.stderr[-500:]
        assert "final loss" in out.stdout
        final = float(out.stdout.strip().split()[-1])
        assert np.isfinite(final) and final < 2.5
