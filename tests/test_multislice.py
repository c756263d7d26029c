"""Two-level hierarchical collectives: parity, ledger, and fallback contracts.

The multi-slice engine's load-bearing promises (ref: apex/parallel/
distributed.py:556-587 ``allreduce_communicators`` — the intra-node
reduce-scatter -> inter-node allreduce -> intra-node all-gather tree,
taken to the TPU slice/DCN topology):

* uncompressed, the hierarchical reduce is BITWISE-equal to the flat
  bucketed reduce over the same two-level axis spec, at every bucket size
  (ragged tails included), through the DDP sweep, the backward-time hook,
  ZeRO-2, and ZeRO-3;
* per-tier compression stays inside the composed analytic bound
  (``bucketing.hierarchical_compression_error_bound``);
* the comms ledger's ``by_tier`` rollup proves the DCN payload is the flat
  payload / slice_size, without changing the summary shape old consumers
  embed;
* degenerate carves (slice_size=1, n_slices=1) collapse to the flat
  path's exact collective sequence — no dead tier collectives in the
  jaxpr.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from beforeholiday_tpu.monitor import comms as mon_comms
from beforeholiday_tpu.optimizers import (
    DistributedFusedAdam,
    ZeRO3FusedAdam,
    zero3,
)
from beforeholiday_tpu.parallel import bucketing, distributed
from beforeholiday_tpu.parallel.parallel_state import (
    HIERARCHICAL_AXES,
    hierarchical_axes,
    make_two_level_mesh,
)

pytestmark = pytest.mark.multislice


def shard_map(f=None, **kw):
    kw.setdefault("check_vma", False)
    if f is None:
        return lambda g: jax.shard_map(g, **kw)
    return jax.shard_map(f, **kw)


# the collective primitives a reduce engine can emit (psum_scatter traces to
# reduce_scatter on some jax versions; monitor/comms.py wraps one of each)
COLLECTIVES = frozenset(
    {"psum", "psum_scatter", "reduce_scatter", "all_gather", "all_to_all"})

AX = HIERARCHICAL_AXES  # ("slice", "intra")
N_SLICES, SLICE_SIZE = 2, 4
BB = 16 * 1024


@pytest.fixture
def two_level_mesh(devices8):
    return make_two_level_mesh(N_SLICES, SLICE_SIZE, devices=devices8)


def _grads(seed=1):
    rng = np.random.RandomState(seed)
    return {
        "w1": jnp.asarray(rng.randn(37, 19).astype(np.float32)),
        "w2": jnp.asarray(rng.randn(128).astype(np.float32)),
        "w3": jnp.asarray(rng.randn(5, 3, 7).astype(np.float32)),
    }


def _params(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w1": jnp.asarray(rng.randn(37, 19).astype(np.float32)),
        "w2": jnp.asarray(rng.randn(128).astype(np.float32)),
        "w3": jnp.asarray(rng.randn(5, 3, 7).astype(np.float32)),
    }


def _run(mesh, fn, *args, out_specs=P()):
    return jax.jit(functools.partial(
        shard_map, mesh=mesh, in_specs=tuple(P() for _ in args),
        out_specs=out_specs)(fn))(*args)


def _tree_eq(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _flat_rank():
    return (jax.lax.axis_index(AX[0]) * SLICE_SIZE
            + jax.lax.axis_index(AX[1]))


def _count_collectives(fn, *args):
    """Collective primitive -> count over the whole (nested) jaxpr."""
    counts = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in COLLECTIVES:
                counts[eqn.primitive.name] = (
                    counts.get(eqn.primitive.name, 0) + 1
                )
            for v in eqn.params.values():
                vs = v if isinstance(v, (tuple, list)) else (v,)
                for item in vs:
                    inner = getattr(item, "jaxpr", None)
                    if inner is None and hasattr(item, "eqns"):
                        inner = item
                    if inner is not None and hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return counts


class TestHierarchicalBitwiseParity:
    @pytest.mark.parametrize("bucket_bytes", [1024, 8192, BB, 1 << 20])
    def test_reduce_gradients_matches_flat(self, two_level_mesh,
                                           bucket_bytes):
        """The acceptance oracle at every bucket geometry: tiny buckets split
        leaves mid-array (ragged scatter tails), the oversized bucket is the
        one-bucket degenerate — all bitwise-equal to the flat chained
        reduce. (``bucket_bytes=None`` without ``hierarchical`` takes the
        legacy per-leaf JOINT-axis psum, whose XLA-chosen reduction order is
        outside the chained-spelling contract — the bucketed flat path is
        the comparison surface.)"""
        grads = _grads()
        flat = _run(two_level_mesh, lambda g: distributed.reduce_gradients(
            g, axis_name=AX, bucket_bytes=bucket_bytes), grads)
        hier = _run(two_level_mesh, lambda g: distributed.reduce_gradients(
            g, axis_name=AX, bucket_bytes=bucket_bytes, hierarchical=True),
            grads)
        _tree_eq(flat, hier)

    def test_per_rank_distinct_grads(self, two_level_mesh):
        """Parity must hold when every rank contributes DIFFERENT data (the
        real data-parallel case), not just replicated grads."""
        grads = _grads()

        def distinct(g):
            r = _flat_rank()
            return jax.tree.map(
                lambda x: x * (1.0 + 0.125 * r.astype(x.dtype)), g)

        flat = _run(two_level_mesh, lambda g: distributed.reduce_gradients(
            distinct(g), axis_name=AX, bucket_bytes=BB), grads)
        hier = _run(two_level_mesh, lambda g: distributed.reduce_gradients(
            distinct(g), axis_name=AX, bucket_bytes=BB, hierarchical=True),
            grads)
        _tree_eq(flat, hier)

    def test_overlap_hook_matches_flat(self, two_level_mesh):
        """The backward-time hook path (overlap_backward=True) reduces the
        cotangent hierarchically with the same bits as the flat sweep."""
        grads, params = _grads(), _params()

        def loss_fn(p, g):
            return sum(jnp.vdot(p[k], g[k]) for k in g)

        ddp_f = distributed.DistributedDataParallel(
            axis_name=AX, bucket_bytes=BB)
        ddp_h = distributed.DistributedDataParallel(
            axis_name=AX, bucket_bytes=BB, hierarchical=True,
            overlap_backward=True)
        _, gf = _run(two_level_mesh,
                     lambda p, g: ddp_f.value_and_grad(loss_fn)(p, g),
                     params, grads, out_specs=(P(), P()))
        _, gh = _run(two_level_mesh,
                     lambda p, g: ddp_h.value_and_grad(loss_fn)(p, g),
                     params, grads, out_specs=(P(), P()))
        _tree_eq(gf, gh)

    def test_zero2_step_matches_flat(self, two_level_mesh):
        """2 hierarchical ZeRO-2 steps == 2 flat steps, bitwise, on params
        AND the fp32 master shard (exercises the scatter + gather legs)."""
        grads, params = _grads(), _params()

        def steps(opt):
            def body(p, g):
                state = opt.init(p)
                for _ in range(2):
                    p, state = opt.step(p, g, state)
                return p, state["master"]

            return _run(two_level_mesh, body, params, grads,
                        out_specs=(P(), P()))

        pf, mf = steps(DistributedFusedAdam(
            lr=1e-2, weight_decay=0.02, impl="jnp", axis_name=AX,
            bucket_bytes=BB))
        ph, mh = steps(DistributedFusedAdam(
            lr=1e-2, weight_decay=0.02, impl="jnp", axis_name=AX,
            bucket_bytes=BB, hierarchical=True))
        np.testing.assert_array_equal(np.asarray(mf), np.asarray(mh))
        _tree_eq(pf, ph)

    def test_zero3_matches_zero2_hierarchical(self, two_level_mesh):
        """ZeRO-3's hierarchical prefetched gather + custom_vjp scatter
        produces the exact bits of the hierarchical ZeRO-2 engine."""
        grads, params = _grads(), _params()
        layout = zero3.layout_of(params)

        z2 = DistributedFusedAdam(
            lr=1e-2, weight_decay=0.02, impl="jnp", axis_name=AX,
            bucket_bytes=BB, hierarchical=True)

        def z2_body(p, g):
            state = z2.init(p)
            for _ in range(2):
                p, state = z2.step(p, g, state)
            return p, state["master"]

        p2, m2 = _run(two_level_mesh, z2_body, params, grads,
                      out_specs=(P(), P()))

        z3 = ZeRO3FusedAdam(
            lr=1e-2, weight_decay=0.02, impl="jnp", axis_name=AX,
            bucket_bytes=BB, hierarchical=True, prefetch=1,
            param_residency="keep")

        def z3_body(p, g):
            state = z3.init(p)
            for _ in range(2):
                def loss_fn(master):
                    leaves = z3.gather_params(master, layout)
                    return sum(
                        jnp.vdot(leaves[k].astype(jnp.float32), g[k])
                        for k in g
                    )

                gs = jax.grad(loss_fn)(state["master"])
                state = z3.step(gs, state)
            return z3.gather_params(state["master"], layout), state["master"]

        p3, m3 = _run(two_level_mesh, z3_body, params, grads,
                      out_specs=(P(), P()))
        np.testing.assert_array_equal(np.asarray(m2), np.asarray(m3))
        _tree_eq(p2, p3)


class TestPerTierCompression:
    @pytest.mark.parametrize("ci,cd", [(True, False), (False, True),
                                       (True, True)])
    def test_within_composed_bound(self, two_level_mesh, ci, cd):
        """Compressing either tier (or both) stays inside the composed
        elementwise bound, with per-rank distinct ragged payloads."""
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(1000).astype(np.float32))

        def body(x):
            r = _flat_rank()
            xl = x * (1.0 + 0.125 * r.astype(x.dtype))
            exact = bucketing.bucketed_psum(
                xl, AX, site="tms.exact", bucket_bytes=1024)
            comp = bucketing.hierarchical_psum(
                xl, AX, site="tms.comp", bucket_bytes=1024,
                compress_intra=ci, compress_dcn=cd)
            sum_abs = jax.lax.psum(jnp.abs(xl), AX)
            bound = bucketing.hierarchical_compression_error_bound(
                sum_abs, compress_intra=ci, compress_dcn=cd)
            return jnp.abs(comp - exact), bound

        err, bound = _run(two_level_mesh, body, x, out_specs=(P(), P()))
        assert bool(jnp.all(err <= bound)), (
            float(jnp.max(err - bound)))

    def test_uncompressed_bound_is_zero_and_bitwise(self, two_level_mesh):
        """Neither tier compressing means a zero bound — and the engines
        deliver it (the parity class proves the bitwise half; this pins the
        bound function's contract end)."""
        b = bucketing.hierarchical_compression_error_bound(
            jnp.float32(100.0))
        assert float(b) == 0.0


class TestLedgerTiers:
    def _dcn_ici_bytes(self, mesh, fn, x, subsystem):
        """Per-tier wire bytes the ledger books for one TRACE of ``fn``
        (records are written while tracing; make_jaxpr never executes)."""
        mon_comms.reset_comms_ledger()
        jax.make_jaxpr(functools.partial(
            shard_map, mesh=mesh, in_specs=(P(),), out_specs=P())(fn))(x)
        row = next(r for r in mon_comms.comms_summary()
                   if r["subsystem"] == subsystem)
        return (row["by_tier"].get("dcn", {}).get("bytes", 0),
                row["by_tier"].get("ici", {}).get("bytes", 0), row)

    def test_dcn_bytes_are_flat_over_slice_size(self, two_level_mesh):
        """The headline claim: on an intra-aligned payload the hierarchical
        reduce's DCN bytes are EXACTLY the flat reduce's / slice_size."""
        n = 128 * 256  # LANES-aligned, divisible by intra=4
        x = jnp.zeros((n,), jnp.float32)
        flat_dcn, _, _ = self._dcn_ici_bytes(
            two_level_mesh,
            lambda a: bucketing.bucketed_psum(
                a, AX, site="tms.flat", bucket_bytes=BB),
            x, "tms")
        hier_dcn, hier_ici, _ = self._dcn_ici_bytes(
            two_level_mesh,
            lambda a: bucketing.hierarchical_psum(
                a, AX, site="tms.hier", bucket_bytes=BB),
            x, "tms")
        assert flat_dcn > 0 and hier_dcn > 0
        assert flat_dcn / hier_dcn == float(SLICE_SIZE)
        # the intra tier moved real scatter/gather traffic
        assert hier_ici > 0

    def test_per_tier_compression_ratio(self, two_level_mesh):
        """compress_dcn=True halves the DCN wire while the ICI tier's ratio
        stays 1.0 — per-tier accounting, not a blended average."""
        x = jnp.zeros((128 * 256,), jnp.float32)
        _, _, row = self._dcn_ici_bytes(
            two_level_mesh,
            lambda a: bucketing.hierarchical_psum(
                a, AX, site="tms.cdcn", bucket_bytes=BB, compress_dcn=True),
            x, "tms")
        assert row["by_tier"]["dcn"]["compression_ratio"] > 1.5
        assert row["by_tier"]["ici"]["compression_ratio"] == 1.0

    def test_summary_shape_backcompat(self):
        """Old consumers index the summary rows by the pre-tier keys; a
        record written with NO tier (a pre-tier call site) must roll up
        under "ici" without changing the row shape."""
        mon_comms.reset_comms_ledger()
        mon_comms.record(
            "psum", "data", jax.ShapeDtypeStruct((16,), jnp.float32),
            site="legacy.site")
        (row,) = mon_comms.comms_summary()
        for k in ("subsystem", "sites", "calls", "bytes", "logical_bytes",
                  "compression_ratio", "by_kind", "by_tier"):
            assert k in row, k
        assert set(row["by_tier"]) == {"ici"}
        assert row["by_tier"]["ici"]["bytes"] == row["bytes"] == 64
        mon_comms.reset_comms_ledger()

    def test_infer_tier(self):
        assert mon_comms.infer_tier("data") == "ici"
        assert mon_comms.infer_tier("slice") == "dcn"
        assert mon_comms.infer_tier(("slice", "intra")) == "dcn"
        assert mon_comms.infer_tier(("data", "tensor")) == "ici"


class TestConsistencyTripwire:
    def test_clean_ranks_pass(self, two_level_mesh):
        grads = _grads()
        _, mm = _run(two_level_mesh, lambda g: distributed.reduce_gradients(
            g, axis_name=AX, hierarchical=True, bucket_bytes=BB,
            check_consistency=True), grads, out_specs=(P(), P()))
        assert not bool(np.asarray(mm).any())

    def test_perturbed_rank_in_other_slice_trips(self, two_level_mesh):
        """A single diverged rank in the SECOND slice must trip the flag on
        every rank — the fingerprint reduction crosses the slice tier."""
        grads = _grads()

        def body(g):
            bad = (_flat_rank() == 2 * SLICE_SIZE - 1)
            g = jax.tree.map(
                lambda x: x + bad.astype(x.dtype) * 0.5, g)
            return distributed.reduce_gradients(
                g, axis_name=AX, hierarchical=True, bucket_bytes=BB,
                check_consistency=True)

        _, mm = _run(two_level_mesh, body, grads, out_specs=(P(), P()))
        assert bool(np.asarray(mm).all())


class TestDegenerateCarves:
    @pytest.mark.parametrize("n_slices,slice_size", [(8, 1), (1, 8)])
    def test_falls_back_to_flat_collectives(self, devices8, n_slices,
                                            slice_size):
        """slice_size=1 and n_slices=1 carves must emit EXACTLY the flat
        path's collective sequence (jaxpr-counted: psums only, same count)
        and the flat path's bits — no dead scatter/gather over a size-1
        axis."""
        mesh = make_two_level_mesh(n_slices, slice_size, devices=devices8)
        x = jnp.asarray(
            np.random.RandomState(0).randn(1000).astype(np.float32))

        def flat_fn(a):
            return bucketing.bucketed_psum(
                a, AX, site="tms.dflat", bucket_bytes=1024)

        def hier_fn(a):
            return bucketing.hierarchical_psum(
                a, AX, site="tms.dhier", bucket_bytes=1024)

        def shmapped(fn):
            return functools.partial(
                shard_map, mesh=mesh, in_specs=(P(),), out_specs=P())(fn)

        c_flat = _count_collectives(shmapped(flat_fn), x)
        c_hier = _count_collectives(shmapped(hier_fn), x)
        assert c_hier == c_flat
        assert set(c_hier) == {"psum"}
        np.testing.assert_array_equal(
            np.asarray(jax.jit(shmapped(flat_fn))(x)),
            np.asarray(jax.jit(shmapped(hier_fn))(x)))

    def test_full_carve_emits_tier_collectives(self, two_level_mesh):
        """Contrast for the fallback test: the real 2x4 carve DOES emit the
        scatter/gather tier ops."""
        x = jnp.zeros((1024,), jnp.float32)
        counts = _count_collectives(functools.partial(
            shard_map, mesh=two_level_mesh, in_specs=(P(),), out_specs=P())(
                lambda a: bucketing.hierarchical_psum(
                    a, AX, site="tms.full", bucket_bytes=None)), x)
        # psum_scatter lowers to the reduce_scatter primitive on some jax
        # versions — either name is the scatter tier
        assert (counts.get("psum_scatter", 0)
                + counts.get("reduce_scatter", 0)) > 0
        assert counts.get("all_gather", 0) > 0
        assert counts.get("psum", 0) > 0


class TestDcnBucketKnob:
    """``bucket_bytes_dcn``: the DCN leg re-buckets independently of ICI
    (DCN wants fewer, bigger collectives). Regrouping an elementwise reduce
    is bitwise-invisible — only the ledger's per-tier call count may move."""

    @pytest.mark.parametrize("n_slices,slice_size",
                             [(2, 4), (4, 2), (8, 1), (1, 8)])
    @pytest.mark.parametrize("dcn_bytes", [512, 1 << 20])
    def test_bitwise_parity_at_mixed_geometries(self, devices8, n_slices,
                                                slice_size, dcn_bytes):
        """Per-rank-distinct ragged payload, every carve (full, wide, tall,
        both degenerates), DCN buckets both smaller and larger than the ICI
        chunks: bits must match the flat chained psum exactly."""
        mesh = make_two_level_mesh(n_slices, slice_size, devices=devices8)
        x = jnp.asarray(
            np.random.RandomState(3).randn(1000).astype(np.float32))

        def body(a):
            r = (jax.lax.axis_index(AX[0]) * slice_size
                 + jax.lax.axis_index(AX[1]))
            al = a * (1.0 + 0.125 * r.astype(a.dtype))
            flat = bucketing.bucketed_psum(
                al, AX, site="tdcn.flat", bucket_bytes=1024)
            hier = bucketing.hierarchical_psum(
                al, AX, site="tdcn.hier", bucket_bytes=1024,
                bucket_bytes_dcn=dcn_bytes)
            return flat, hier

        flat, hier = _run(mesh, body, x, out_specs=(P(), P()))
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(hier))

    def test_regrouping_moves_dcn_call_count_not_bytes(self, two_level_mesh):
        """A large DCN bucket folds the per-ICI-bucket psums into ONE DCN
        collective; the DCN payload bytes stay exactly 1/slice_size of the
        flat payload either way."""
        x = jnp.zeros((1000,), jnp.float32)

        def tier(site, **kw):
            mon_comms.reset_comms_ledger()
            jax.make_jaxpr(functools.partial(
                shard_map, mesh=two_level_mesh, in_specs=(P(),),
                out_specs=P())(
                    lambda a: bucketing.hierarchical_psum(
                        a, AX, site=site, bucket_bytes=1024, **kw)))(x)
            row = next(r for r in mon_comms.comms_summary()
                       if r["subsystem"] == site.split(".")[0])
            return row["by_tier"]["dcn"]

        follow = tier("tdf.follow")  # DCN follows the 4 ICI buckets
        merged = tier("tdm.merged", bucket_bytes_dcn=1 << 20)
        assert follow["calls"] == 4
        assert merged["calls"] == 1
        assert merged["bytes"] == follow["bytes"]

    def test_bucketed_reduce_threads_and_validates(self, two_level_mesh):
        with pytest.raises(ValueError):  # flat policy can't size a DCN tier
            bucketing.BucketedReduce(bucket_bytes_dcn=1 << 20)
        pol = bucketing.BucketedReduce(
            axis_name=AX, hierarchical=True, bucket_bytes=1024,
            bucket_bytes_dcn=1 << 20)
        x = jnp.asarray(
            np.random.RandomState(5).randn(300).astype(np.float32))
        out = _run(two_level_mesh,
                   lambda a: pol.psum(a, site="tdp.psum"), x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(x) * (N_SLICES * SLICE_SIZE),
            rtol=1e-6)


class TestValidation:
    def test_hierarchical_axes_normalization(self):
        assert hierarchical_axes("data") is None
        assert hierarchical_axes(["data"]) is None
        assert hierarchical_axes(("slice", "intra")) == ("slice", "intra")
        with pytest.raises(ValueError):
            hierarchical_axes(("pod", "slice", "intra"))

    def test_make_two_level_mesh_validation(self, devices8):
        mesh = make_two_level_mesh(2, devices=devices8)
        assert mesh.axis_names == AX
        assert mesh.devices.shape == (2, 4)
        # slice-major: flat rank slice*slice_size+intra matches the device
        # order a flat ("data",) mesh over the same list would use
        assert list(mesh.devices.reshape(-1)) == list(devices8)
        with pytest.raises(ValueError):
            make_two_level_mesh(0, devices=devices8)
        with pytest.raises(RuntimeError):
            make_two_level_mesh(3, devices=devices8)  # 8 % 3 != 0
        with pytest.raises(RuntimeError):
            make_two_level_mesh(4, 4, devices=devices8)  # needs 16

    def test_flat_axis_rejected_everywhere(self):
        """hierarchical=True without a two-level spec must fail loudly at
        construction/call time in every engine that grew the knob."""
        with pytest.raises(ValueError):
            distributed.reduce_gradients(
                {}, axis_name="data", hierarchical=True)
        with pytest.raises(ValueError):
            distributed.Reducer(axis_name="data", hierarchical=True)
        with pytest.raises(ValueError):
            distributed.DistributedDataParallel(
                axis_name="data", hierarchical=True)
        with pytest.raises(ValueError):
            DistributedFusedAdam(
                lr=1e-2, impl="jnp", axis_name="data", hierarchical=True)
        with pytest.raises(ValueError):
            ZeRO3FusedAdam(
                lr=1e-2, impl="jnp", axis_name="data", hierarchical=True)
