"""ZeRO-2 sharded optimizers: parity with the unsharded fused optimizers.

Port of the reference contract (apex/contrib/test/optimizers/test_dist_adam.py:391):
DistributedFusedAdam trajectories must equal ordinary FusedAdam on the same
(summed) gradients, while holding only 1/world of the optimizer state.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.optimizers import (
    DistributedFusedAdam,
    DistributedFusedLAMB,
    FusedAdam,
    FusedLAMB,
)


def shard_map(f=None, **kw):
    kw.setdefault("check_vma", False)
    if f is None:
        return lambda g: jax.shard_map(g, **kw)
    return jax.shard_map(f, **kw)


@pytest.fixture
def data_mesh(devices8):
    return Mesh(np.asarray(devices8), ("data",))


def _params(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w1": jnp.asarray(rng.randn(37, 19).astype(np.float32)),
        "w2": jnp.asarray(rng.randn(128,).astype(np.float32)),
        "w3": jnp.asarray(rng.randn(5, 3, 7).astype(np.float32)),
    }


def _grad_seq(seed, n):
    rng = np.random.RandomState(seed)
    return [
        {
            "w1": rng.randn(37, 19).astype(np.float32),
            "w2": rng.randn(128).astype(np.float32),
            "w3": rng.randn(5, 3, 7).astype(np.float32),
        }
        for _ in range(n)
    ]


class TestDistributedFusedAdam:
    def test_matches_unsharded_fused_adam(self, data_mesh):
        """Each rank contributes grads/8; ZeRO trajectory == FusedAdam on the mean."""
        params = _params()
        grad_seq = _grad_seq(1, 8)

        dopt = DistributedFusedAdam(lr=1e-2, weight_decay=0.02, impl="jnp")
        ropt = FusedAdam(lr=1e-2, weight_decay=0.02, impl="jnp")

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P("data")), out_specs=P(),
        )
        def zero_run(params, per_rank_noise):
            state = dopt.init(params)
            p = params
            for g in base_grads:
                # rank-varying grads whose cross-rank mean equals the reference
                grads = jax.tree.map(
                    lambda a: a + per_rank_noise - jax.lax.pmean(per_rank_noise, "data"),
                    g,
                )
                p, state = dopt.step(p, grads, state)
            return p

        base_grads = [
            {k: jnp.asarray(v) for k, v in g.items()} for g in grad_seq
        ]
        noise = jnp.arange(8, dtype=jnp.float32)
        p_zero = zero_run(params, noise)

        p_ref, s_ref = params, ropt.init(params)
        for g in grad_seq:
            p_ref, s_ref = ropt.step(p_ref, {k: jnp.asarray(v) for k, v in g.items()}, s_ref)

        for k in params:
            np.testing.assert_allclose(
                np.asarray(p_zero[k]), np.asarray(p_ref[k]), rtol=1e-4, atol=1e-5
            )

    def test_state_is_sharded(self, data_mesh):
        # shards are TILE-quantized (32768 elems), so use a model big enough
        # for the 1/world memory saving to be visible
        params = {"w": jnp.ones((1024, 1024), jnp.float32)}

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=P(), out_specs=P("data"),
        )
        def state_sizes(params):
            dopt = DistributedFusedAdam(impl="jnp")
            state = dopt.init(params)
            return jnp.asarray([state["master"].shape[0]])[None]

        sizes = np.asarray(jax.jit(state_sizes)(params))
        total = 1024 * 1024
        assert sizes.max() * 8 >= total
        assert sizes.max() == total // 8  # exactly 1/world of the arena

    def test_skip_step_on_overflow(self, data_mesh):
        params = _params()

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=P(), out_specs=(P(), P()),
        )
        def run(params):
            dopt = DistributedFusedAdam(lr=1e-2, impl="jnp")
            state = dopt.init(params)
            # rank 3 contributes an inf grad
            bad = jnp.where(jax.lax.axis_index("data") == 3, jnp.inf, 1.0)
            grads = jax.tree.map(lambda p: jnp.full_like(p, bad), params)
            p1, s1 = dopt.step(params, grads, state)
            return p1, s1["step"]

        p1, step = run(params)
        assert int(step) == 0
        for k in params:
            np.testing.assert_array_equal(np.asarray(p1[k]), np.asarray(params[k]))

    def test_bf16_params_fp32_master(self, data_mesh):
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), _params())

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=P(), out_specs=P(),
        )
        def run(params):
            dopt = DistributedFusedAdam(lr=1e-2, impl="jnp")
            state = dopt.init(params)
            grads = jax.tree.map(jnp.ones_like, params)
            p1, s1 = dopt.step(params, grads, state)
            return p1

        p1 = run(params)
        assert p1["w1"].dtype == jnp.bfloat16


class TestDistributedFusedLAMB:
    def test_matches_unsharded_fused_lamb(self, data_mesh):
        params = _params(3)
        grad_seq = _grad_seq(4, 6)

        dopt = DistributedFusedLAMB(lr=1e-2, weight_decay=0.01, impl="jnp")
        ropt = FusedLAMB(lr=1e-2, weight_decay=0.01, impl="jnp")

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=P(), out_specs=P(),
        )
        def zero_run(params):
            state = dopt.init(params)
            p = params
            for g in base_grads:
                p, state = dopt.step(p, g, state)
            return p

        base_grads = [{k: jnp.asarray(v) for k, v in g.items()} for g in grad_seq]
        p_zero = zero_run(params)

        p_ref, s_ref = params, ropt.init(params)
        for g in base_grads:
            p_ref, s_ref = ropt.step(p_ref, g, s_ref)

        for k in params:
            np.testing.assert_allclose(
                np.asarray(p_zero[k]), np.asarray(p_ref[k]), rtol=2e-4, atol=2e-5
            )


class TestZeroCheckpoint:
    """state_dict(gather_on_root)/load_state_dict round-trip
    (ref: distributed_fused_adam.py:1123-1150)."""

    @pytest.mark.parametrize("opt_cls", [DistributedFusedAdam, DistributedFusedLAMB])
    def test_gathered_state_shapes_match_params(self, data_mesh, opt_cls):
        params = _params()
        dopt = opt_cls(lr=1e-3, impl="jnp")
        grads = _grad_seq(3, 1)[0]

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=P(), out_specs=P(),
        )
        def run(params):
            state = dopt.init(params)
            g = {k: jnp.asarray(v) for k, v in grads.items()}
            _, state = dopt.step(params, g, state)
            return dopt.state_dict(params, state)

        sd = jax.jit(run)(params)  # eager shard_map runs op by op on jax 0.9
        for key in ("master", "exp_avg", "exp_avg_sq"):
            assert set(sd[key]) == set(params)
            for name, leaf in sd[key].items():
                assert leaf.shape == params[name].shape, (key, name)
                assert leaf.dtype == jnp.float32
        assert int(sd["step"]) == 1

    @pytest.mark.parametrize("opt_cls", [DistributedFusedAdam, DistributedFusedLAMB])
    def test_roundtrip_resumes_identically(self, data_mesh, opt_cls):
        """save after 2 steps → reload → 2 more steps == 4 uninterrupted steps."""
        params = _params()
        dopt = opt_cls(lr=1e-2, impl="jnp")
        gseq = _grad_seq(11, 4)

        @functools.partial(shard_map, mesh=data_mesh, in_specs=P(), out_specs=P())
        def uninterrupted(params):
            state = dopt.init(params)
            p = params
            for g in gseq:
                p, state = dopt.step(p, {k: jnp.asarray(v) for k, v in g.items()}, state)
            return p

        @functools.partial(shard_map, mesh=data_mesh, in_specs=P(), out_specs=P())
        def first_half(params):
            state = dopt.init(params)
            p = params
            for g in gseq[:2]:
                p, state = dopt.step(p, {k: jnp.asarray(v) for k, v in g.items()}, state)
            return p, dopt.state_dict(params, state)

        # jitted: eager shard_map executes op by op on jax 0.9 (minutes here)
        p_mid, sd = jax.jit(first_half)(params)

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P()), out_specs=P(),
        )
        def second_half(p, sd):
            state = dopt.load_state_dict(p, sd)
            for g in gseq[2:]:
                p, state = dopt.step(p, {k: jnp.asarray(v) for k, v in g.items()}, state)
            return p

        p_resumed = jax.jit(second_half)(p_mid, sd)
        p_straight = jax.jit(uninterrupted)(params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6
            ),
            p_resumed, p_straight,
        )

    def test_local_shard_mode(self, data_mesh):
        """gather_on_root=False returns the 1/world shard untouched."""
        params = _params()
        dopt = DistributedFusedAdam(impl="jnp")

        @functools.partial(shard_map, mesh=data_mesh, in_specs=P(), out_specs=P("data"))
        def run(params):
            state = dopt.init(params)
            sd = dopt.state_dict(params, state, gather_on_root=False)
            return sd["master"][None]

        shards = run(params)
        total = sum(int(np.prod(v.shape)) for v in params.values())
        assert shards.shape[0] == 8 and shards.shape[1] * 8 >= total
