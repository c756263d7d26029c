"""ASP sparsity, groupbn, halo exchange, (spatial) bottleneck
(ref: apex/contrib/test/{groupbn,bottleneck}; sparsity tests compare mask
density and magnitude-optimality like the reference's checkmodel)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.contrib import (
    ASP,
    batch_norm_nhwc,
    bottleneck,
    conv_bias_relu,
    create_mask,
    halo_exchange_1d,
    init_bottleneck,
    spatial_bottleneck,
)
from beforeholiday_tpu.optimizers import FusedSGD
from beforeholiday_tpu.parallel.sync_batch_norm import init_batch_norm


def _smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)


class TestASP:
    def test_m4n2_1d_density_and_optimality(self):
        w = jnp.asarray(np.random.RandomState(0).randn(16, 32).astype(np.float32))
        m = create_mask(w, "m4n2_1d")
        assert float(m.mean()) == 0.5
        groups = np.asarray(m).reshape(-1, 4)
        assert np.all(groups.sum(-1) == 2)
        # kept entries are the 2 largest |w| per group
        wa = np.abs(np.asarray(w)).reshape(-1, 4)
        kept = np.sort(np.where(groups, wa, -1), axis=-1)[:, -2:]
        np.testing.assert_allclose(kept, np.sort(wa, axis=-1)[:, -2:])

    def test_m4n2_2d_row_and_col_constraint(self):
        w = jnp.asarray(np.random.RandomState(1).randn(8, 8).astype(np.float32))
        m = np.asarray(create_mask(w, "m4n2_2d_best"))
        assert m.mean() == 0.5
        blocks = m.reshape(2, 4, 2, 4).transpose(0, 2, 1, 3)
        assert np.all(blocks.sum(-1) == 2)  # rows
        assert np.all(blocks.sum(-2) == 2)  # cols

    def test_wrapped_optimizer_keeps_sparsity(self):
        params = {"w": jnp.asarray(np.random.RandomState(2).randn(8, 8), jnp.float32),
                  "b": jnp.ones((5,))}  # ineligible leaf stays dense
        asp = ASP()
        masks = asp.compute_sparse_masks(params)
        assert float(masks["b"].mean()) == 1.0
        params = ASP.apply_masks(params, masks)
        opt = asp.wrap_optimizer(FusedSGD(lr=0.1, impl="jnp"), masks)
        state = opt.init(params)
        grads = {"w": jnp.ones((8, 8)), "b": jnp.ones((5,))}
        for _ in range(3):
            params, state = opt.step(params, grads, state)
        zero_frac = float((params["w"] == 0).mean())
        assert zero_frac == 0.5  # pruned slots stayed zero through updates

    def test_masks_master_weights_too(self):
        """amp MasterWeights: the fp32 masters must stay pruned, or every
        master->model cast would resurrect the pruned slots."""
        from beforeholiday_tpu.amp import MasterWeights

        params = {"w": jnp.asarray(np.random.RandomState(3).randn(8, 8), jnp.float32)}
        asp = ASP()
        masks = asp.compute_sparse_masks(params)
        params = ASP.apply_masks(params, masks)
        opt = asp.wrap_optimizer(MasterWeights(FusedSGD(lr=0.1, impl="jnp")), masks)
        state = opt.init(params)
        for _ in range(2):
            params, state = opt.step(params, {"w": jnp.ones((8, 8))}, state)
        assert float((state["master"]["w"] == 0).mean()) == 0.5
        assert float((params["w"] == 0).mean()) == 0.5

    def test_rejects_zero_sharded_optimizer(self):
        from beforeholiday_tpu.optimizers import DistributedFusedAdam

        asp = ASP()
        masks = asp.compute_sparse_masks({"w": jnp.ones((8, 8))})
        with pytest.raises(TypeError, match="ZeRO-sharded"):
            asp.wrap_optimizer(DistributedFusedAdam(), masks)


class TestGroupBN:
    def test_bn_group_syncs_subgroups_only(self, devices8):
        """bn_group=4: ranks 0-3 share stats, 4-7 share stats — feeding
        different data to the two halves must give different normalization."""
        mesh = Mesh(np.asarray(devices8), ("data",))
        params, state = init_batch_norm(3)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(8, 2, 4, 4, 3).astype(np.float32) * 3)

        @functools.partial(_smap, mesh=mesh, in_specs=(P("data"), P(), P()),
                           out_specs=(P("data"), P("data")))
        def run(x, params, state):
            y, new_state = batch_norm_nhwc(
                x[0], params, state, axis_name="data", bn_group=4,
            )
            return y[None], jax.tree.map(lambda s: s[None], new_state)

        y, new_state = run(x, params, state)
        # oracle: normalize each half-batch jointly
        xf = np.asarray(x, np.float64)
        for half in (slice(0, 4), slice(4, 8)):
            grp = xf[half].reshape(-1, 3)
            mean, var = grp.mean(0), grp.var(0)
            want = (xf[half] - mean) / np.sqrt(var + 1e-5)
            np.testing.assert_allclose(np.asarray(y)[half], want, atol=1e-3)
        # running means differ between subgroups
        rm = np.asarray(new_state.running_mean)
        assert not np.allclose(rm[0], rm[4])
        assert np.allclose(rm[0], rm[3])

    def test_fused_add_relu(self):
        params, state = init_batch_norm(2)
        x = jnp.asarray(np.random.RandomState(1).randn(2, 4, 4, 2), jnp.float32)
        z = jnp.asarray(np.random.RandomState(2).randn(2, 4, 4, 2), jnp.float32)
        y, _ = batch_norm_nhwc(x, params, state, residual=z, fuse_relu=True)
        y_plain, _ = batch_norm_nhwc(x, params, state)
        np.testing.assert_allclose(
            np.asarray(y), np.maximum(np.asarray(y_plain) + np.asarray(z), 0),
            atol=1e-6,
        )


class TestHaloExchange:
    def test_matches_unsharded_rows(self, devices8):
        mesh = Mesh(np.asarray(devices8), ("spatial",))
        full = jnp.arange(8 * 4 * 2, dtype=jnp.float32).reshape(1, 8 * 4, 2)

        @functools.partial(_smap, mesh=mesh, in_specs=P(None, "spatial", None),
                           out_specs=P(None, "spatial", None))
        def run(x):
            return halo_exchange_1d(x, 2, axis_name="spatial", dim=1)

        out = np.asarray(run(full))  # (1, 8*(4+4), 2): each shard grew by 2+2
        shards = out.reshape(1, 8, 8, 2)
        fullr = np.asarray(full).reshape(1, 8, 4, 2)
        for r in range(8):
            np.testing.assert_array_equal(shards[0, r, 2:6], fullr[0, r])
            if r > 0:
                np.testing.assert_array_equal(shards[0, r, :2], fullr[0, r - 1][-2:])
            else:
                assert np.all(shards[0, 0, :2] == 0)
            if r < 7:
                np.testing.assert_array_equal(shards[0, r, 6:], fullr[0, r + 1][:2])
            else:
                assert np.all(shards[0, 7, 6:] == 0)


class TestBottleneck:
    def test_conv_bias_relu(self):
        x = jnp.asarray(np.random.RandomState(0).randn(1, 5, 5, 3), jnp.float32)
        w = jnp.asarray(np.random.RandomState(1).randn(3, 3, 3, 4) * 0.2, jnp.float32)
        b = jnp.asarray(np.random.RandomState(2).randn(4) * 0.1, jnp.float32)
        y = conv_bias_relu(x, w, b)
        assert y.shape == (1, 5, 5, 4) and float(y.min()) >= 0.0

    def test_bottleneck_shapes(self):
        p = init_bottleneck(jax.random.PRNGKey(0), 16, 8, 32)
        x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 8, 16), jnp.float32)
        y = bottleneck(x, p)
        assert y.shape == (2, 8, 8, 32)
        y2 = bottleneck(x, p, stride=2)
        assert y2.shape == (2, 4, 4, 32)

    def test_spatial_matches_dense(self, devices8):
        """H-sharded spatial bottleneck (halo-exchanged 3x3) == the dense
        bottleneck on the gathered input — the reference's spatial oracle."""
        mesh = Mesh(np.asarray(devices8), ("spatial",))
        p = init_bottleneck(jax.random.PRNGKey(0), 8, 4, 8, downsample=False)
        x = jnp.asarray(np.random.RandomState(3).randn(1, 32, 6, 8), jnp.float32)

        @functools.partial(_smap, mesh=mesh, in_specs=(P(None, "spatial"), P()),
                           out_specs=P(None, "spatial"))
        def run(x, p):
            return spatial_bottleneck(x, p, axis_name="spatial")

        got = run(x, p)
        want = bottleneck(x, p)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_spatial_stride2_matches_dense(self, devices8):
        """Stride-2 H-sharded spatial bottleneck (stage-boundary geometry,
        with downsample) == the dense stride-2 bottleneck
        (ref: SpatialBottleneck's strided path, bottleneck.py:380-603)."""
        mesh = Mesh(np.asarray(devices8), ("spatial",))
        p = init_bottleneck(jax.random.PRNGKey(1), 8, 4, 16)  # downsample on
        x = jnp.asarray(np.random.RandomState(5).randn(2, 32, 6, 8), jnp.float32)

        @functools.partial(_smap, mesh=mesh, in_specs=(P(None, "spatial"), P()),
                           out_specs=P(None, "spatial"))
        def run(x, p):
            return spatial_bottleneck(x, p, axis_name="spatial", stride=2)

        got = run(x, p)
        want = bottleneck(x, p, stride=2)
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_spatial_stride2_no_downsample_identity_residual_rejected(self, devices8):
        """stride 2 with an identity residual cannot type-check (spatial dims
        shrink); the error must be loud, not a silent shape blow-up."""
        mesh = Mesh(np.asarray(devices8[:2]), ("spatial",))
        p = init_bottleneck(jax.random.PRNGKey(0), 8, 4, 8, downsample=False)
        x = jnp.asarray(np.random.RandomState(0).randn(1, 8, 4, 8), jnp.float32)

        @functools.partial(_smap, mesh=mesh, in_specs=(P(None, "spatial"), P()),
                           out_specs=P(None, "spatial"))
        def run(x, p):
            return spatial_bottleneck(x, p, axis_name="spatial", stride=2)

        with pytest.raises(Exception):
            run(x, p)

    def test_spatial_stride2_odd_local_h_rejected(self, devices8):
        mesh = Mesh(np.asarray(devices8[:2]), ("spatial",))
        p = init_bottleneck(jax.random.PRNGKey(0), 8, 4, 16)
        x = jnp.asarray(np.random.RandomState(0).randn(1, 6, 4, 8), jnp.float32)

        @functools.partial(_smap, mesh=mesh, in_specs=(P(None, "spatial"), P()),
                           out_specs=P(None, "spatial"))
        def run(x, p):
            return spatial_bottleneck(x, p, axis_name="spatial", stride=2)

        with pytest.raises(ValueError, match="even per-rank H"):
            run(x, p)

    def test_spatial_stride2_odd_width_matches_dense(self, devices8):
        """Odd W exercises the (1,1) SAME split for the strided 3x3 — the
        W-padding parity must follow XLA SAME, not a hardcoded (0,1)."""
        mesh = Mesh(np.asarray(devices8), ("spatial",))
        p = init_bottleneck(jax.random.PRNGKey(2), 8, 4, 16)
        x = jnp.asarray(np.random.RandomState(6).randn(1, 32, 7, 8), jnp.float32)

        @functools.partial(_smap, mesh=mesh, in_specs=(P(None, "spatial"), P()),
                           out_specs=P(None, "spatial"))
        def run(x, p):
            return spatial_bottleneck(x, p, axis_name="spatial", stride=2)

        got = run(x, p)
        want = bottleneck(x, p, stride=2)
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
