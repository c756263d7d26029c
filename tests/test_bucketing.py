"""Bucketed/compressed collective oracles on the 8-device CPU mesh.

The contracts this file pins (see beforeholiday_tpu/parallel/bucketing.py):

* uncompressed bucketing is BITWISE-identical to the monolithic collective,
  for any bucket size including ragged tails — bucketing may only change
  scheduling, never values;
* compressed (wire-dtype) reduction stays within the analytic
  ``compression_error_bound`` — fp32 accumulation means the error never grows
  with the reduction-tree depth;
* the DDP / ZeRO-2 / TP wiring inherits both properties end-to-end;
* every bucketed collective is ledger-visible with WIRE bytes (not logical
  fp32) and per-site call counts equal to the bucket count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P


def shard_map(f=None, **kw):
    kw.setdefault("check_vma", False)
    if f is None:
        return lambda g: jax.shard_map(g, **kw)
    return jax.shard_map(f, **kw)

from beforeholiday_tpu.monitor import comms
from beforeholiday_tpu.ops.arena import LANES, PackedParams, flatten, make_spec
from beforeholiday_tpu.parallel import bucketing, reduce_gradients
from beforeholiday_tpu.parallel.bucketing import (
    bucket_slices,
    bucketed_all_gather,
    bucketed_psum,
    bucketed_psum_scatter,
    bucketed_tree_psum,
    chunked_all_gather,
    chunked_reduce_scatter,
    compression_error_bound,
    n_buckets,
    partition_leaves,
)

WORLD = 8


@pytest.fixture
def mesh(devices8):
    return Mesh(np.asarray(devices8).reshape(WORLD), ("data",))


@pytest.fixture(autouse=True)
def _fresh_ledger():
    comms.reset_comms_ledger()
    yield
    comms.reset_comms_ledger()


def _rows(x):
    """Per-rank input: rank r sees row r of a (WORLD, ...) array."""
    return jnp.asarray(x)


def _run(mesh, fn, *args, in_specs=None, out_specs=P()):
    if in_specs is None:
        in_specs = (P("data"),) * len(args)
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )(*args)


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


# ---------------------------------------------------------------- geometry


class TestBucketSlices:
    def test_covers_exactly_with_ragged_tail(self):
        n = 5 * LANES + 37
        slices = bucket_slices(n, 4, bucket_bytes=2 * LANES * 4)
        assert slices[0][0] == 0
        # contiguous, no overlap, full coverage
        for (o1, l1), (o2, _) in zip(slices, slices[1:]):
            assert o1 + l1 == o2
        assert slices[-1][0] + slices[-1][1] == n
        # all offsets lane-aligned; only the tail may be ragged
        assert all(off % LANES == 0 for off, _ in slices)
        assert all(ln % LANES == 0 for _, ln in slices[:-1])

    def test_none_means_one_bucket(self):
        assert bucket_slices(999, 4, None) == ((0, 999),)
        assert n_buckets(999, 4, None) == 1

    def test_tiny_budget_clamps_to_align(self):
        slices = bucket_slices(4 * LANES, 4, bucket_bytes=1)
        assert all(ln == LANES for _, ln in slices)

    def test_empty_payload_raises(self):
        with pytest.raises(ValueError):
            bucket_slices(0, 4)

    def test_n_buckets_counts(self):
        assert n_buckets(10 * LANES, 4, LANES * 4) == 10


# ------------------------------------------------------- flat-arena oracles


class TestBucketedPsum:
    @pytest.mark.parametrize(
        "bucket_bytes", [None, 512, 64 * 1024, 10**9]
    )
    def test_bitwise_vs_monolithic(self, mesh, bucket_bytes):
        n = 3 * 32768 + 4096 + 37  # ragged, non-lane-aligned tail
        x = _rand((WORLD, n), 0)

        ref = _run(mesh, lambda v: jax.lax.psum(v[0], "data"), x)
        got = _run(
            mesh,
            lambda v: bucketed_psum(
                v[0], "data", site="t.psum", bucket_bytes=bucket_bytes
            ),
            x,
        )
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_compressed_within_analytic_bound(self, mesh):
        n = 2 * 32768 + 513
        x = _rand((WORLD, n), 1)
        ref = _run(mesh, lambda v: jax.lax.psum(v[0], "data"), x)
        got = _run(
            mesh,
            lambda v: bucketed_psum(
                v[0], "data", site="t.cpsum", bucket_bytes=64 * 1024,
                compress=True,
            ),
            x,
        )
        bound = np.asarray(
            compression_error_bound(jnp.sum(jnp.abs(x), axis=0))
        )
        err = np.abs(np.asarray(ref) - np.asarray(got))
        assert (err <= bound + 1e-12).all()
        # and compression actually rounds — exact equality would mean the
        # wire cast silently didn't happen
        assert err.max() > 0

    def test_rejects_non_flat(self, mesh):
        with pytest.raises(ValueError, match="flat"):
            _run(
                mesh,
                lambda v: bucketed_psum(v, "data", site="t.bad"),
                _rand((WORLD, 4, 4), 2),
                in_specs=(P("data"),),
            )


class TestBucketedPsumScatter:
    @pytest.mark.parametrize("bucket_bytes", [None, 2048, 10**9])
    def test_bitwise_vs_monolithic(self, mesh, bucket_bytes):
        shard = 3 * LANES + 64  # ragged column tail
        x = _rand((WORLD, WORLD * shard), 3)

        def ref(v):
            return jax.lax.psum_scatter(
                v[0], "data", scatter_dimension=0, tiled=True
            )

        def got(v):
            return bucketed_psum_scatter(
                v[0], "data", site="t.rs", bucket_bytes=bucket_bytes
            )

        a = _run(mesh, ref, x, out_specs=P("data"))
        b = _run(mesh, got, x, out_specs=P("data"))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_compressed_within_bound(self, mesh):
        shard = 2 * LANES + 96
        x = _rand((WORLD, WORLD * shard), 4)

        def ref(v):
            return jax.lax.psum_scatter(
                v[0], "data", scatter_dimension=0, tiled=True
            )

        def got(v):
            return bucketed_psum_scatter(
                v[0], "data", site="t.crs", bucket_bytes=1024, compress=True
            )

        a = np.asarray(_run(mesh, ref, x, out_specs=P("data")))
        b = np.asarray(_run(mesh, got, x, out_specs=P("data")))
        # reduce-scatter form: one wire rounding per rank, fp32 accumulation,
        # fp32 result — within wire_eps * psum|x|
        sum_abs = np.abs(np.asarray(x)).sum(axis=0)
        bound = bucketing.wire_eps(jnp.bfloat16) * sum_abs
        assert (np.abs(a - b) <= bound + 1e-12).all()

    def test_indivisible_raises(self, mesh):
        with pytest.raises(ValueError, match="divisible"):
            _run(
                mesh,
                lambda v: bucketed_psum_scatter(v[0], "data", site="t.bad"),
                _rand((WORLD, WORLD * 100 + 1), 5),
            )


class TestBucketedAllGather:
    @pytest.mark.parametrize("bucket_bytes", [None, 1024, 10**9])
    def test_bitwise_vs_monolithic(self, mesh, bucket_bytes):
        shard = 5 * LANES + 33
        x = _rand((WORLD, shard), 6)

        def ref(v):
            return jax.lax.all_gather(v[0], "data", axis=0, tiled=True)

        def got(v):
            return bucketed_all_gather(
                v[0], "data", site="t.ag", bucket_bytes=bucket_bytes
            )

        a = _run(mesh, ref, x)
        b = _run(mesh, got, x)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestChunkedND:
    @pytest.mark.parametrize("dim", [0, 1, -1])
    def test_all_gather_matches(self, mesh, dim):
        x = _rand((WORLD, 6, 8, 10), 7)

        def ref(v):
            return jax.lax.all_gather(v[0], "data", axis=dim, tiled=True)

        def got(v):
            return chunked_all_gather(
                v[0], "data", site="t.cag", dim=dim, chunk_bytes=256
            )

        a = _run(mesh, ref, x)
        b = _run(mesh, got, x)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("dim", [0, -1])
    def test_reduce_scatter_matches(self, mesh, dim):
        x = _rand((WORLD, WORLD * 3, 5, WORLD * 4), 8)

        def ref(v):
            return jax.lax.psum_scatter(
                v[0], "data", scatter_dimension=dim % 3, tiled=True
            )

        def got(v):
            return chunked_reduce_scatter(
                v[0], "data", site="t.crs2", dim=dim, chunk_bytes=256
            )

        a = _run(mesh, ref, x, out_specs=P("data"))
        b = _run(mesh, got, x, out_specs=P("data"))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- tree grads


def _grad_tree(seed, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return {
        "w1": jnp.asarray(rng.randn(WORLD, 96, 64), dtype),
        "w2": jnp.asarray(rng.randn(WORLD, 200, 33), dtype),
        "b": jnp.asarray(rng.randn(WORLD, 77), dtype),
        "steps": jnp.asarray(
            rng.randint(0, 5, size=(WORLD, 3)), jnp.int32
        ),
    }


class TestTreePsum:
    def test_partition_is_dtype_uniform_and_complete(self):
        leaves = [
            jnp.zeros((100,), jnp.float32),
            jnp.zeros((50,), jnp.bfloat16),
            jnp.zeros((200,), jnp.float32),
            jnp.zeros((10,), jnp.int32),
        ]
        groups = partition_leaves(leaves, bucket_bytes=512)
        assert sorted(i for g in groups for i in g) == [0, 1, 2, 3]
        for g in groups:
            dts = {np.dtype(jnp.result_type(leaves[i])) for i in g}
            assert len(dts) == 1

    def test_bitwise_vs_per_leaf(self, mesh):
        tree = _grad_tree(9)

        def ref(t):
            local = jax.tree.map(lambda v: v[0], t)
            return jax.tree.map(lambda g: jax.lax.psum(g, "data"), local)

        def got(t):
            local = jax.tree.map(lambda v: v[0], t)
            leaves, treedef = jax.tree_util.tree_flatten(local)
            red = bucketed_tree_psum(
                leaves, "data", site="t.tree", bucket_bytes=16 * 1024
            )
            return jax.tree_util.tree_unflatten(treedef, red)

        a = _run(mesh, ref, tree)
        b = _run(mesh, got, tree)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_compressed_keeps_int_leaves_exact(self, mesh):
        tree = _grad_tree(10)

        def got(t):
            local = jax.tree.map(lambda v: v[0], t)
            leaves, treedef = jax.tree_util.tree_flatten(local)
            red = bucketed_tree_psum(
                leaves, "data", site="t.ctree", bucket_bytes=16 * 1024,
                compress=True,
            )
            return jax.tree_util.tree_unflatten(treedef, red)

        out = _run(mesh, got, tree)
        # int leaf reduced exactly, never cast
        np.testing.assert_array_equal(
            np.asarray(out["steps"]),
            np.asarray(tree["steps"]).sum(axis=0),
        )
        assert out["steps"].dtype == jnp.int32
        # float leaves within the analytic bound, dtypes preserved
        for k in ("w1", "w2", "b"):
            exact = np.asarray(tree[k]).sum(axis=0)
            bound = np.asarray(
                compression_error_bound(jnp.sum(jnp.abs(tree[k]), axis=0))
            )
            assert out[k].dtype == tree[k].dtype
            assert (np.abs(np.asarray(out[k]) - exact) <= bound + 1e-12).all()


# --------------------------------------------------------------- DDP wiring


class TestReduceGradientsBucketed:
    def test_bucketed_matches_default_bitwise(self, mesh):
        tree = _grad_tree(11)

        def run(bucket_bytes):
            def body(t):
                local = jax.tree.map(lambda v: v[0], t)
                return reduce_gradients(
                    local, axis_name="data", bucket_bytes=bucket_bytes
                )

            return _run(mesh, body, tree)

        a, b = run(None), run(8 * 1024)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_compressed_close_and_scaled(self, mesh):
        tree = _grad_tree(12)

        def run(**kw):
            def body(t):
                local = jax.tree.map(lambda v: v[0], t)
                return reduce_gradients(local, axis_name="data", **kw)

            return _run(mesh, body, tree)

        a = run()
        b = run(bucket_bytes=8 * 1024, compress=True)
        for k in ("w1", "w2", "b"):
            # averaged outputs: bound divides by world too
            bound = np.asarray(
                compression_error_bound(jnp.sum(jnp.abs(tree[k]), axis=0))
            ) / WORLD
            err = np.abs(np.asarray(a[k]) - np.asarray(b[k]))
            assert (err <= bound + 1e-12).all()

    def test_packed_params_arena_path_bitwise(self, mesh):
        tree = _grad_tree(13)
        del tree["steps"]  # PackedParams is float-only

        def ref(t):
            local = jax.tree.map(lambda v: v[0], t)
            return reduce_gradients(local, axis_name="data")

        def got(t):
            local = jax.tree.map(lambda v: v[0], t)
            packed = PackedParams.pack(local)
            red = reduce_gradients(
                packed, axis_name="data", bucket_bytes=8 * 1024
            )
            return red.unpack()

        a = _run(mesh, ref, tree)
        b = _run(mesh, got, tree)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------------- ZeRO-2 wiring


def _zero2_setup(seed):
    rng = np.random.RandomState(seed)
    params = {
        "w": jnp.asarray(rng.randn(120, 65), jnp.float32),
        "b": jnp.asarray(rng.randn(333), jnp.float32),
    }
    grads = jax.tree.map(
        lambda p: jnp.asarray(
            np.random.RandomState(seed + 1).randn(WORLD, *p.shape), p.dtype
        ),
        params,
    )
    return params, grads


_Z2_STATE_SPECS = {
    "master": P("data"), "exp_avg": P("data"), "exp_avg_sq": P("data"),
    "step": P(),
}


class TestZero2Bucketed:
    def _step(self, mesh, params, grads, **opt_kw):
        from beforeholiday_tpu.optimizers import DistributedFusedAdam

        opt = DistributedFusedAdam(axis_name="data", **opt_kw)

        def body(p, g):
            local_g = jax.tree.map(lambda v: v[0], g)
            st = opt.init(p)
            for _ in range(2):
                p, st = opt.step(p, local_g, st)
            return p

        return _run(
            mesh, body, params, grads, in_specs=(P(), P("data")),
            out_specs=P(),
        )

    def _programs(self, mesh, **opt_kw):
        """(init, step), each its own jitted program — a training loop's
        form: one compiled step, driven from the host, state through HBM."""
        from beforeholiday_tpu.optimizers import DistributedFusedAdam

        opt = DistributedFusedAdam(axis_name="data", **opt_kw)
        init = jax.jit(shard_map(
            opt.init, mesh=mesh, in_specs=(P(),), out_specs=_Z2_STATE_SPECS))

        def body(p, g, st):
            return opt.step(p, jax.tree.map(lambda v: v[0], g), st)

        step = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(), P("data"), _Z2_STATE_SPECS),
            out_specs=(P(), _Z2_STATE_SPECS)))
        return init, step

    def test_bucketed_step_matches_unbucketed_bitwise(self, mesh):
        """Bucketing the ZeRO-2 collectives changes no bit of the params or
        of the sharded state, step after step.

        Red from the seed to PR 28 in another form: both optimizers unrolled
        TWO steps inside one jit. No reduction order differs there — each
        element of the reduce-scatter is the same 8-term sum in rank order
        whatever bucket carries it (asserted bitwise below). What differed
        is where XLA:CPU (jax 0.9.0) rounds the elementwise Adam chain: the
        unbucketed unrolled program becomes ONE loop fusion holding both
        updates, whose a*b+c pairs LLVM contracts to FMAs, while the
        per-bucket slices of the bucketed program stop that fusion and the
        bias-corrected moments are written to memory as float32 between the
        two updates. One step agreed bitwise, two differed in the last place
        (1 of 333 and 14 of 7800 elements, 1 ulp). A step per jit call is
        both the shape a training loop runs and the same elementwise program
        over the same (shard,) arenas on both sides, so that is what is
        compared — and it is compared on everything the step returns."""
        params, grads = _zero2_setup(20)
        bb = 16 * 1024

        (init_a, step_a), (init_b, step_b) = (
            self._programs(mesh), self._programs(mesh, bucket_bytes=bb))
        pa, sa = params, init_a(params)
        pb, sb = params, init_b(params)

        # order-independent: the bucket geometry tiles the shard exactly, in
        # more than one bucket, and the reduced gradient shard is bitwise
        padded = sa["master"].size
        shard = padded // WORLD
        assert shard * WORLD == padded
        assert padded >= sum(int(np.prod(v.shape)) for v in params.values())
        slices = bucket_slices(shard, 4 * WORLD, bb)
        assert len(slices) > 1
        assert slices[0][0] == 0
        assert all(a[0] + a[1] == b[0] for a, b in zip(slices, slices[1:]))
        assert slices[-1][0] + slices[-1][1] == shard
        gflat = _rand((WORLD, padded), 22)
        mono = _run(
            mesh, lambda g: jax.lax.psum_scatter(
                g[0], "data", scatter_dimension=0, tiled=True), gflat)
        buck = _run(
            mesh, lambda g: bucketed_psum_scatter(
                g[0], "data", site="t.z2", bucket_bytes=bb), gflat)
        np.testing.assert_array_equal(np.asarray(mono), np.asarray(buck))

        for _ in range(3):
            pa, sa = step_a(pa, grads, sa)
            pb, sb = step_b(pb, grads, sb)
            for x, y in zip(jax.tree.leaves((pa, sa)),
                            jax.tree.leaves((pb, sb))):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_compressed_step_close(self, mesh):
        params, grads = _zero2_setup(21)
        a = self._step(mesh, params, grads)
        b = self._step(
            mesh, params, grads, bucket_bytes=16 * 1024, compress=True
        )
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), atol=5e-2
            )
            # same values would mean compression never engaged
        assert any(
            np.abs(np.asarray(x) - np.asarray(y)).max() > 0
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
        )


# --------------------------------------------------------------- TP wiring


class TestMappingsChunking:
    def test_chunked_gather_scatter_bitwise(self, mesh):
        from beforeholiday_tpu.transformer.tensor_parallel import mappings as M

        x = _rand((16, 4, 8 * 80), 30)

        def g_fn(v):
            return M.gather_from_tensor_model_parallel_region(v, "data")

        def r_fn(v):
            return M.reduce_scatter_to_sequence_parallel_region(v, "data")

        def run_pair():
            a = jax.jit(shard_map(
                g_fn, mesh=mesh, in_specs=(P(None, None, "data"),),
                out_specs=P(),
            ))(x)
            b = jax.jit(shard_map(
                r_fn, mesh=mesh, in_specs=(P(),), out_specs=P("data"),
            ))(x[: 16])
            return a, b

        base_g, base_r = run_pair()
        prev = M.set_collective_chunk_bytes(2048)
        try:
            comms.reset_comms_ledger()
            chunk_g, chunk_r = run_pair()
            recs = {r["site"]: r for r in comms.comms_records()}
        finally:
            M.set_collective_chunk_bytes(prev)
        assert M.collective_chunk_bytes() is None
        # the chunked trace really split the collectives...
        assert recs["tp.gather_from_region"]["calls"] > 1
        assert recs["sp.reduce_scatter_to_region"]["calls"] > 1
        # ...and stayed bitwise-equal
        np.testing.assert_array_equal(np.asarray(base_g), np.asarray(chunk_g))
        np.testing.assert_array_equal(np.asarray(base_r), np.asarray(chunk_r))


# ------------------------------------------------------------------ ledger


class TestLedgerReporting:
    def test_bucket_count_and_wire_dtype(self, mesh):
        n = 4 * 2048
        x = _rand((WORLD, n), 40)
        comms.reset_comms_ledger()
        _run(
            mesh,
            lambda v: bucketed_psum(
                v[0], "data", site="t.ledger", bucket_bytes=2048 * 4
            ),
            x,
        )
        recs = [
            r for r in comms.comms_records() if r["site"] == "t.ledger"
        ]
        assert len(recs) == 1
        assert recs[0]["calls"] == n_buckets(n, 4, 2048 * 4)
        assert recs[0]["dtype"] == "float32"
        assert recs[0]["bytes"] == recs[0]["logical_bytes"] == n * 4

    def test_compressed_reports_wire_not_logical(self, mesh):
        n = 4096
        x = _rand((WORLD, n), 41)
        comms.reset_comms_ledger()
        _run(
            mesh,
            lambda v: bucketed_psum(
                v[0], "data", site="t.cledger", bucket_bytes=None,
                compress=True,
            ),
            x,
        )
        recs = {
            (r["kind"], r["dtype"]): r
            for r in comms.comms_records()
            if r["site"] == "t.cledger"
        }
        # both phases of the 2-shot exchange ship bf16 on the wire
        assert set(recs) == {
            ("all_to_all", "bfloat16"), ("all_gather", "bfloat16")
        }
        for r in recs.values():
            # wire bytes are HALF the fp32 logical bytes
            assert r["logical_bytes"] == 2 * r["bytes"]
        summ = [
            r for r in comms.comms_summary() if r["subsystem"] == "t"
        ]
        assert summ and all(r["compression_ratio"] == 2.0 for r in summ)


# ----------------------------------------------- fused optimizer view path


class TestViewPathStepFlat:
    """step_flat fed the grad LEAF LIST must match the packed-arena call —
    the treeapi regression fix (no per-step arena pack). Same math, but the
    two programs fuse differently under XLA, so the contract is float32
    ulp-level agreement, not bitwise."""

    def _parity(self, opt, n_steps=2, **step_kw):
        rng = np.random.RandomState(50)
        leaves = [
            jnp.asarray(rng.randn(96, 33), jnp.float32),
            jnp.asarray(rng.randn(257), jnp.float32),
            jnp.asarray(rng.randn(40, 128), jnp.float32),
        ]
        gleaves = [
            jnp.asarray(rng.randn(*l.shape), jnp.float32) for l in leaves
        ]
        pf, spec = flatten(leaves)
        gf, _ = flatten(gleaves)
        st = opt.init_flat(pf)

        @jax.jit
        def arena_run(pf, gf, st):
            p = pf
            for _ in range(n_steps):
                p, st2 = opt.step_flat(p, gf, st, spec=spec, **step_kw)
                st = st2
            return p

        @jax.jit
        def view_run(pf, gl, st):
            p = pf
            for _ in range(n_steps):
                p, st2 = opt.step_flat(p, list(gl), st, **step_kw)
                st = st2
            return p

        a = np.asarray(arena_run(pf, gf, st))
        b = np.asarray(view_run(pf, gleaves, st))
        return a, b

    def test_adam_view_matches_arena(self):
        from beforeholiday_tpu.optimizers import FusedAdam

        a, b = self._parity(FusedAdam(lr=1e-3, weight_decay=0.01))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_sgd_view_matches_arena(self):
        from beforeholiday_tpu.optimizers import FusedSGD

        a, b = self._parity(FusedSGD(lr=0.1, momentum=0.9))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_lamb_view_close(self):
        from beforeholiday_tpu.optimizers import FusedLAMB

        # LAMB's global grad norm reduces in a different association order on
        # the view path (per-leaf partials) — equal to fp32 roundoff
        a, b = self._parity(FusedLAMB(lr=1e-3))
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)


class TestSpecMemoization:
    def test_make_spec_identity(self):
        xs = [jnp.zeros((64, 3)), jnp.zeros((17,))]
        ys = [jnp.ones((64, 3)), jnp.ones((17,))]
        assert make_spec(xs) is make_spec(ys)
