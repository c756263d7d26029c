"""Overlap-engine contracts on the 8-device CPU mesh.

What this file pins (see beforeholiday_tpu/parallel/overlap.py, the
``overlap_p2p`` engine in transformer/pipeline_parallel/schedules.py, and
``step_in_backward`` in optimizers/fused.py):

* the backward-time reduction hook is BITWISE-identical to the post-backward
  ``reduce_gradients`` sweep (uncompressed) — for plain trees, hooks inside
  a ``lax.scan`` body, every scaling knob, and the DDP/Reducer wiring;
* compressed hooks stay within ``bucketing.compression_error_bound``;
* optimizer-in-backward (``step_in_backward``) is bitwise-equal to phased
  reduce-then-step for Adam/SGD/LAMB, and one overflowing bucket skips the
  WHOLE step — params, every moment, and the step counter;
* the ZeRO-2 per-bucket reduce-scatter-then-update path is bitwise-equal to
  the phased ZeRO-2 step; LAMB refuses ``overlap_backward`` loudly;
* the double-buffered p2p pipeline engine (1F1B and interleaved) matches the
  sequential dense reference and records its phase shift;
* ``_overlap_tables`` satisfies the distance-2 dependency/no-clobber
  invariants and the V=1 closed forms;
* ``reduce_gradients(check_consistency=True)`` composes with the bucketed
  and compressed paths, and the tripwire fires on a perturbed rank.
"""

import functools

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


from beforeholiday_tpu.guard import StepGuard
from beforeholiday_tpu.ops import arena
from beforeholiday_tpu.optimizers.distributed_fused import (
    DistributedFusedAdam,
    DistributedFusedLAMB,
)
from beforeholiday_tpu.optimizers.fused import FusedAdam, FusedLAMB, FusedSGD
from beforeholiday_tpu.parallel import (
    DistributedDataParallel,
    Reducer,
    bucketing,
    reduce_gradients,
)
from beforeholiday_tpu.parallel import overlap
from beforeholiday_tpu.transformer import pipeline_parallel as pp
from beforeholiday_tpu.transformer.pipeline_parallel import schedules as sched
from beforeholiday_tpu.transformer.pipeline_parallel.schedules import (
    _overlap_tables,
)

pytestmark = pytest.mark.overlap_engine

WORLD = 8


@pytest.fixture
def mesh(devices8):
    return Mesh(np.asarray(devices8).reshape(WORLD), ("data",))


def _bitwise(a, b):
    a = np.atleast_1d(np.asarray(a))
    b = np.atleast_1d(np.asarray(b))
    return a.dtype == b.dtype and np.array_equal(
        a.view(np.uint8), b.view(np.uint8)
    )


def _tree_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(_bitwise(x, y) for x, y in zip(la, lb))


def _mlp_params(rng, dim, layers=2):
    p = {}
    for i in range(layers):
        p[f"w{i}"] = jnp.asarray(rng.randn(dim, dim) * 0.3, jnp.float32)
        p[f"b{i}"] = jnp.zeros((dim,), jnp.float32)
    return p


def _mlp_loss(p, x, tgt, layers=2):
    h = x
    for i in range(layers):
        h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
    return jnp.mean((h - tgt) ** 2)


# -------------------------------------------------------------------------------
# rung 1: backward-time reduction hook
# -------------------------------------------------------------------------------


class TestReductionHook:
    DIM = 12

    def _data(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(WORLD, 4, self.DIM), jnp.float32)
        tgt = jnp.asarray(rng.randn(WORLD, 4, self.DIM), jnp.float32)
        return _mlp_params(rng, self.DIM), x, tgt

    @pytest.mark.parametrize(
        "knobs",
        [
            {},
            {"gradient_predivide_factor": 2.0, "allreduce_always_fp32": True},
            {"gradient_average": False, "bucket_bytes": 256},
        ],
        ids=["averaged", "predivide_fp32", "bucketed_sum"],
    )
    def test_ddp_hook_bitwise_vs_post_backward(self, mesh, knobs):
        """DistributedDataParallel(overlap_backward=True) grads (reduced
        inside the backward) are bitwise-identical to the post-backward
        sweep, for every scaling knob — the hook replays the exact
        _pre/psum/_post op sequence."""
        params, x, tgt = self._data()
        hook_ddp = DistributedDataParallel(overlap_backward=True, **knobs)
        post_ddp = DistributedDataParallel(overlap_backward=False, **knobs)

        def run(ddp):
            @jax.jit
            @shard_map(mesh=mesh, in_specs=(P(), P("data"), P("data")),
                       out_specs=(P(), P()))
            def step(p, x, tgt):
                return ddp.value_and_grad(
                    lambda p, x, tgt: _mlp_loss(p, x, tgt))(p, x, tgt)

            return jax.device_get(step(params, x, tgt))

        loss_h, g_h = run(hook_ddp)
        loss_p, g_p = run(post_ddp)
        assert _bitwise(loss_h, loss_p)
        assert _tree_bitwise(g_h, g_p)

    def test_hook_inside_scan_bitwise(self, mesh):
        """A hook on the per-iteration layer slice inside a scan-over-layers
        body reduces each layer's grads mid-backward; the stacked result is
        bitwise-equal to sweeping the stacked grads afterwards."""
        rng = np.random.RandomState(1)
        layers = 3
        stacked = {
            "w": jnp.asarray(rng.randn(layers, self.DIM, self.DIM) * 0.3,
                             jnp.float32),
            "b": jnp.zeros((layers, self.DIM), jnp.float32),
        }
        x = jnp.asarray(rng.randn(WORLD, 4, self.DIM), jnp.float32)
        tgt = jnp.asarray(rng.randn(WORLD, 4, self.DIM), jnp.float32)

        def scan_loss(stacked, x, tgt, *, hook):
            def body(h, lp):
                if hook:
                    lp = overlap.hook_tree(lp, tag="scan_layer",
                                           axis_name="data")
                return jnp.tanh(h @ lp["w"] + lp["b"]), None

            h, _ = jax.lax.scan(body, x, stacked)
            return jnp.mean((h - tgt) ** 2)

        @jax.jit
        @shard_map(mesh=mesh, in_specs=(P(), P("data"), P("data")),
                   out_specs=(P(), P()))
        def hooked(s, x, tgt):
            return jax.value_and_grad(
                lambda s: scan_loss(s, x, tgt, hook=True))(s)

        @jax.jit
        @shard_map(mesh=mesh, in_specs=(P(), P("data"), P("data")),
                   out_specs=(P(), P()))
        def swept(s, x, tgt):
            loss, g = jax.value_and_grad(
                lambda s: scan_loss(s, x, tgt, hook=False))(s)
            return loss, reduce_gradients(g, axis_name="data")

        loss_h, g_h = jax.device_get(hooked(stacked, x, tgt))
        loss_s, g_s = jax.device_get(swept(stacked, x, tgt))
        assert _bitwise(loss_h, loss_s)
        assert _tree_bitwise(g_h, g_s)

    def test_compressed_hook_within_bound(self, mesh):
        """A compressed hook's error vs the raw psum stays within the
        analytic wire bound (bf16 round on the wire, fp32 accumulation)."""
        params, x, tgt = self._data()

        @jax.jit
        @shard_map(mesh=mesh, in_specs=(P(), P("data"), P("data")),
                   out_specs=(P(), P(), P()))
        def step(p, x, tgt):
            _, g_c = jax.value_and_grad(
                lambda p: _mlp_loss(
                    overlap.hook_tree(p, tag="comp", axis_name="data",
                                      gradient_average=False, compress=True),
                    x, tgt))(p)
            _, g_raw = jax.value_and_grad(
                lambda p: _mlp_loss(p, x, tgt))(p)
            g_exact = jax.tree.map(
                lambda g: jax.lax.psum(g, "data"), g_raw)
            bound = jax.tree.map(
                lambda g: bucketing.compression_error_bound(
                    jax.lax.psum(jnp.abs(g), "data")),
                g_raw)
            return g_c, g_exact, bound

        g_c, g_exact, bound = jax.device_get(step(params, x, tgt))
        for c, e, b in zip(jax.tree.leaves(g_c), jax.tree.leaves(g_exact),
                           jax.tree.leaves(bound)):
            np.testing.assert_array_less(
                np.abs(np.asarray(c) - np.asarray(e)),
                np.asarray(b) + 1e-12)

    def test_reducer_hook_matches_reduce(self, mesh):
        """Reducer.hook (backward-time) == vag + Reducer.reduce (bucketed
        sweep), bitwise."""
        params, x, tgt = self._data()
        red = Reducer(bucket_bytes=256)

        @jax.jit
        @shard_map(mesh=mesh, in_specs=(P(), P("data"), P("data")),
                   out_specs=(P(), P()))
        def run(p, x, tgt):
            _, g_h = jax.value_and_grad(
                lambda p: _mlp_loss(red.hook(p), x, tgt))(p)
            _, g = jax.value_and_grad(lambda p: _mlp_loss(p, x, tgt))(p)
            return g_h, red.reduce(g, average=True)

        g_h, g_s = jax.device_get(run(params, x, tgt))
        assert _tree_bitwise(g_h, g_s)


# -------------------------------------------------------------------------------
# rung 2: optimizer-in-backward
# -------------------------------------------------------------------------------


def _flat_setup(rng, dim=8, layers=3):
    leaves = []
    for _ in range(layers):
        leaves.append(jnp.asarray(rng.randn(dim, dim) * 0.3, jnp.float32))
        leaves.append(jnp.zeros((dim,), jnp.float32))
    flat, spec = arena.flatten(leaves)
    return leaves, flat, spec


def _leaves_loss(leaves, x, tgt):
    h = x
    for i in range(len(leaves) // 2):
        h = jnp.tanh(h @ leaves[2 * i] + leaves[2 * i + 1])
    return jnp.mean((h - tgt) ** 2)


class TestOptimizerInBackward:
    @pytest.mark.parametrize(
        "opt",
        [
            FusedAdam(lr=1e-3),
            FusedSGD(lr=1e-2, momentum=0.9),
            FusedLAMB(lr=1e-3),
        ],
        ids=["adam", "sgd", "lamb"],
    )
    def test_bitwise_parity_vs_phased(self, mesh, opt):
        """hooked backward + step_in_backward == plain backward +
        reduce_gradients + step_flat, bitwise on params and every state
        leaf — the fold's found_inf=False select is exact and the grads
        were already proven bitwise-equal."""
        rng = np.random.RandomState(2)
        dim = 8
        leaves, flat, spec = _flat_setup(rng, dim)
        state0 = opt.init_flat(flat)
        x = jnp.asarray(rng.randn(WORLD, 4, dim), jnp.float32)
        tgt = jnp.asarray(rng.randn(WORLD, 4, dim), jnp.float32)

        @jax.jit
        @shard_map(mesh=mesh, in_specs=(P(), P(), P("data"), P("data")),
                   out_specs=(P(), P(), P()))
        def hook_step(flat, state, x, tgt):
            pieces = arena.unflatten(flat, spec)
            _, g = jax.value_and_grad(
                lambda lv: _leaves_loss(
                    overlap.hook_tree(list(lv), tag="oib", axis_name="data"),
                    x, tgt))(pieces)
            new_flat, new_state, flag = opt.step_in_backward(
                flat, list(g), state, spec=spec)
            return new_flat, new_state, flag

        @jax.jit
        @shard_map(mesh=mesh, in_specs=(P(), P(), P("data"), P("data")),
                   out_specs=(P(), P()))
        def phased_step(flat, state, x, tgt):
            pieces = arena.unflatten(flat, spec)
            _, g = jax.value_and_grad(
                lambda lv: _leaves_loss(list(lv), x, tgt))(pieces)
            g = reduce_gradients(list(g), axis_name="data")
            new_flat, new_state = opt.step_flat(flat, list(g), state,
                                                spec=spec)
            return new_flat, new_state

        flat_h, st_h, flag = jax.device_get(hook_step(flat, state0, x, tgt))
        flat_p, st_p = jax.device_get(phased_step(flat, state0, x, tgt))
        assert not bool(np.asarray(flag))
        assert _bitwise(flat_h, flat_p)
        assert _tree_bitwise(st_h, st_p)

    def test_overflow_whole_step_skip(self):
        """One poisoned bucket holds EVERYTHING: params, both moments, and
        the step counter — never a prefix of the buckets."""
        rng = np.random.RandomState(3)
        opt = FusedAdam(lr=1e-3)
        leaves, flat, spec = _flat_setup(rng)
        state0 = opt.init_flat(flat)
        grads = [jnp.full(l.shape, 1e-3, jnp.float32) for l in leaves]
        # poison only the LAST leaf; tiny buckets force several buckets, so
        # a prefix-committing bug would update the early buckets
        grads[-1] = grads[-1].at[0].set(jnp.inf)

        @jax.jit
        def run(flat, grads, state):
            return opt.step_in_backward(flat, grads, state, spec=spec,
                                        bucket_bytes=128)

        flat2, state2, flag = jax.device_get(run(flat, grads, state0))
        assert bool(np.asarray(flag))
        assert _bitwise(flat2, flat)
        assert _bitwise(state2["exp_avg"], state0["exp_avg"])
        assert _bitwise(state2["exp_avg_sq"], state0["exp_avg_sq"])
        assert int(state2["step"]) == int(state0["step"])

        # clean grads with the same geometry DO commit every bucket
        clean = [jnp.full(l.shape, 1e-3, jnp.float32) for l in leaves]
        flat3, state3, flag3 = jax.device_get(run(flat, clean, state0))
        assert not bool(np.asarray(flag3))
        assert not _bitwise(flat3, flat)
        assert int(state3["step"]) == int(state0["step"]) + 1

    def test_per_bucket_flags_and_fold(self):
        """per_bucket_found_inf reports exactly the poisoned bucket;
        fold_found_inf ORs buckets and the external sentinel."""
        leaves = [jnp.ones((64,), jnp.float32) for _ in range(4)]
        leaves[2] = leaves[2].at[5].set(jnp.nan)
        # 256 bytes/leaf -> one bucket per leaf at bucket_bytes=256
        flags = overlap.per_bucket_found_inf(leaves, bucket_bytes=256)
        got = [bool(np.asarray(f)) for f in flags]
        assert got == [False, False, True, False]
        assert bool(np.asarray(overlap.fold_found_inf(flags)))
        clean = overlap.per_bucket_found_inf(
            [jnp.ones((64,), jnp.float32)], bucket_bytes=256)
        assert not bool(np.asarray(overlap.fold_found_inf(clean)))
        assert bool(np.asarray(overlap.fold_found_inf(clean, external=True)))

    def test_step_guard_folds_extra_found_inf(self):
        """StepGuard.apply_update(extra_found_inf=True) skips the step and
        shrinks the scale even though grads are finite — the backward-time
        per-bucket flag lands in the scaler backoff like a phased
        overflow."""
        from beforeholiday_tpu.amp.scaler import LossScaler

        params = {"w": jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)}
        opt = FusedSGD(lr=0.1)
        guard = StepGuard(LossScaler(init_scale=4.0, min_loss_scale=1.0))
        gstate = guard.init(params)
        ostate = opt.init(params)

        def loss(p, x):
            return jnp.sum(p["w"] * x)

        vg = guard.value_and_grad(loss)
        x = jnp.asarray([1.0, -1.0, 2.0, 0.5], jnp.float32)

        @functools.partial(jax.jit, static_argnums=(4,))
        def step(params, ostate, gstate, x, extra):
            _, grads, verdict = vg(params, gstate, x)
            return guard.apply_update(
                opt, params, grads, ostate, gstate, verdict,
                extra_found_inf=jnp.bool_(extra),
            )

        p_skip, o_skip, g_skip = jax.device_get(
            step(params, ostate, gstate, x, True))
        assert _tree_bitwise(p_skip, params)
        assert _tree_bitwise(o_skip, ostate)
        assert int(g_skip["health"]["skipped_total"]) == 1
        assert float(g_skip["scaler"]["scale"]) < 4.0

        p_ok, _, g_ok = jax.device_get(
            step(params, ostate, gstate, x, False))
        assert not _tree_bitwise(p_ok, params)
        assert int(g_ok["health"]["skipped_total"]) == 0


# -------------------------------------------------------------------------------
# ZeRO-2 overlap
# -------------------------------------------------------------------------------


class TestZero2Overlap:
    def _params_grads(self):
        rng = np.random.RandomState(4)
        params = {
            "w": jnp.asarray(rng.randn(24, 16) * 0.3, jnp.float32),
            "b": jnp.asarray(rng.randn(16) * 0.1, jnp.float32),
        }
        grads = jax.tree.map(
            lambda p: jnp.asarray(
                np.sign(np.asarray(p)) * 1e-2, jnp.float32), params)
        return params, grads

    def test_overlap_step_bitwise_vs_phased(self, mesh):
        """Per-bucket reduce-scatter-then-update == phased ZeRO-2 step,
        bitwise on params and the full sharded state — the elementwise
        kernel commutes with arena slicing."""
        params, grads = self._params_grads()

        def run(overlap_backward):
            dopt = DistributedFusedAdam(
                lr=1e-2, weight_decay=0.02, impl="jnp",
                bucket_bytes=512, overlap_backward=overlap_backward,
            )

            @jax.jit
            @shard_map(mesh=mesh, in_specs=P(),
                       out_specs=(P(), P("data"), P()))
            def step(params, grads):
                state = dopt.init(params)
                p2, s2 = dopt.step(params, grads, state)
                shard_state = jnp.concatenate([
                    s2["master"], s2["exp_avg"], s2["exp_avg_sq"]])
                return p2, shard_state[None], s2["step"]

            return jax.device_get(step(params, grads))

        p_o, st_o, step_o = run(True)
        p_p, st_p, step_p = run(False)
        assert _tree_bitwise(p_o, p_p)
        assert _bitwise(st_o, st_p)
        assert int(np.asarray(step_o).ravel()[0]) == int(
            np.asarray(step_p).ravel()[0]) == 1

    def test_overlap_overflow_skips_whole_step(self, mesh):
        """An inf anywhere in the grads holds params and the step counter on
        the overlap path — the per-bucket flags fold to one global pmax."""
        params, grads = self._params_grads()
        grads["w"] = grads["w"].at[0, 0].set(jnp.inf)
        dopt = DistributedFusedAdam(
            lr=1e-2, impl="jnp", bucket_bytes=512, overlap_backward=True)

        @jax.jit
        @shard_map(mesh=mesh, in_specs=P(), out_specs=(P(), P()))
        def step(params, grads):
            state = dopt.init(params)
            p2, s2 = dopt.step(params, grads, state)
            return p2, s2["step"]

        p2, step_no = jax.device_get(step(params, grads))
        assert _tree_bitwise(p2, params)
        assert int(np.asarray(step_no).ravel()[0]) == 0

    def test_lamb_overlap_backward_raises(self):
        with pytest.raises(NotImplementedError, match="overlap_backward"):
            DistributedFusedLAMB(overlap_backward=True)


# -------------------------------------------------------------------------------
# rung 3: double-buffered pipeline engine
# -------------------------------------------------------------------------------

HIDDEN, MICRO = 8, 4


def _stage_fn(sp, x):
    h = x @ sp["w"] + sp["b"]
    return jax.nn.gelu(h) + x


def _pipe_loss(y, tgt):
    return jnp.mean((y - tgt) ** 2)


def _init_stages(key, n):
    ks = jax.random.split(key, n)
    return {
        "w": jnp.stack(
            [jax.random.normal(k, (HIDDEN, HIDDEN)) * 0.3 for k in ks]),
        "b": jnp.zeros((n, HIDDEN)),
    }


def _sequential_reference(stacked, inputs, targets):
    def full(stacked, x):
        def body(h, sp):
            return _stage_fn(sp, h), None

        h, _ = jax.lax.scan(body, x, stacked)
        return h

    def total(stacked):
        return jnp.mean(jax.vmap(
            lambda x, t: _pipe_loss(full(stacked, x), t))(inputs, targets))

    return jax.value_and_grad(total)(stacked)


class TestPipelineOverlap:
    @pytest.mark.parametrize("n_stages,M", [(2, 6), (4, 6), (4, 16)])
    def test_1f1b_overlap_matches_sequential(self, devices8, n_stages, M):
        """overlap_p2p=True 1F1B: loss and grads match the sequential dense
        reference; the schedule report records the double-buffer phase shift
        2*(S-1)."""
        rng = np.random.RandomState(0)
        inputs = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)
        targets = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)
        stacked = _init_stages(jax.random.PRNGKey(1), n_stages)
        ref_loss, ref_grads = _sequential_reference(stacked, inputs, targets)
        mesh = Mesh(np.asarray(devices8[:n_stages]), ("pipe",))

        @jax.jit
        @shard_map(mesh=mesh, in_specs=(P("pipe"), P(), P()),
                   out_specs=(P(), P("pipe")))
        def run(stacked_local, inputs, targets):
            sp = jax.tree.map(lambda v: v[0], stacked_local)
            loss, grads = pp.forward_backward_pipelining_without_interleaving(
                _stage_fn, _pipe_loss, sp, inputs, targets, overlap_p2p=True)
            return loss, jax.tree.map(lambda g: g[None], grads)

        loss, grads = run(stacked, inputs, targets)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(grads[k]), np.asarray(ref_grads[k]),
                rtol=1e-4, atol=1e-5)
        rep = sched.last_schedule_report()
        assert rep["p2p_overlap"] is True
        assert rep["phase_shift_ticks"] == 2 * (n_stages - 1)
        assert rep["overlap_total_ticks"] == (
            M + n_stages - 1 + n_stages) + 2 * (n_stages - 1)

    @pytest.mark.parametrize("S,V", [(2, 2), (2, 3)])
    def test_interleaved_overlap_matches_sequential(self, devices8, S, V):
        M = 4
        rng = np.random.RandomState(5)
        inputs = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)
        targets = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)
        L = S * V
        stacked = _init_stages(jax.random.PRNGKey(4), L)
        ref_loss, ref_grads = _sequential_reference(stacked, inputs, targets)
        perm = np.array([[v * S + s for v in range(V)] for s in range(S)])
        reordered = jax.tree.map(lambda leaf: leaf[perm.ravel()], stacked)
        mesh = Mesh(np.asarray(devices8[:S]), ("pipe",))

        @jax.jit
        @shard_map(mesh=mesh, in_specs=(P("pipe"), P(), P()),
                   out_specs=(P(), P("pipe")))
        def run(chunks_local, inputs, targets):
            loss, grads = pp.forward_backward_pipelining_with_interleaving(
                _stage_fn, _pipe_loss, chunks_local, inputs, targets,
                virtual_pipeline_model_parallel_size=V, overlap_p2p=True)
            return loss, grads

        loss, grads = run(reordered, inputs, targets)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        inv = np.argsort(perm.ravel())
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(grads[k])[inv], np.asarray(ref_grads[k]),
                rtol=1e-4, atol=1e-5)

    def test_overlap_tables_invariants(self):
        """Host-side schedule tables: V=1 closed forms, distance-2
        dependencies, one slot per device per tick, ring-depth no-clobber."""
        for M, S in [(4, 2), (8, 4), (16, 8)]:
            tab = _overlap_tables(M, S, 1)
            t_F, t_B = tab["t_F"], tab["t_B"]
            for m in range(M):
                for s in range(S):
                    assert t_F[(m, s)] == m + 2 * s
                    assert t_B[(m, s)] == 2 * S - 1 + m + 2 * (S - 1 - s)
            assert tab["total_ticks"] == M + 4 * S - 3

        for M, S, V in [(4, 2, 2), (8, 4, 2), (8, 2, 3)]:
            tab = _overlap_tables(M, S, V)
            t_F, t_B = tab["t_F"], tab["t_B"]
            L = V * S
            assert len(t_F) == M * L and len(t_B) == M * L
            for (m, l), t in t_F.items():
                if l > 0:
                    assert t >= t_F[(m, l - 1)] + 2
            for (m, l), t in t_B.items():
                if l == L - 1:
                    assert t >= t_F[(m, l)] + 1
                else:
                    assert t >= t_B[(m, l + 1)] + 2
            from collections import Counter

            cf = Counter((l % S, t) for (m, l), t in t_F.items())
            cb = Counter((l % S, t) for (m, l), t in t_B.items())
            assert max(cf.values()) == 1 and max(cb.values()) == 1
            # reads happen in the compute phase BEFORE the tick's ring
            # write, so a value written at tick w survives reads through
            # w + depth; the act write precedes the same-tick B read, so
            # its clobber is strict
            r_f, r_b, r_act = tab["r_f"], tab["r_b"], tab["r_act"]
            for (m, l), t in t_F.items():
                if l > 0:
                    w = t_F[(m, l - 1)] + 1
                    assert 1 <= t - w <= r_f
            for (m, l), t in t_B.items():
                assert t - t_F[(m, l)] < r_act
                if l < L - 1:
                    w = t_B[(m, l + 1)] + 1
                    assert 1 <= t - w <= r_b


# -------------------------------------------------------------------------------
# consistency tripwire composes with the bucketed path (satellite b)
# -------------------------------------------------------------------------------


class TestConsistencyComposesWithBucketing:
    @pytest.mark.parametrize(
        "knobs",
        [
            {"bucket_bytes": 256},
            {"bucket_bytes": 256, "compress": True},
        ],
        ids=["bucketed", "compressed"],
    )
    def test_clean_and_perturbed(self, mesh, knobs):
        """check_consistency=True composes with bucket_bytes/compress: clean
        replicated grads reduce exactly as without the tripwire and report
        mismatch=False; a perturbed rank fires it."""
        rng = np.random.RandomState(6)
        grads = {
            "w": jnp.asarray(rng.randn(16, 16), jnp.float32),
            "b": jnp.asarray(rng.randn(16), jnp.float32),
        }

        @jax.jit
        @shard_map(mesh=mesh, in_specs=(P(), P("data")),
                   out_specs=(P(), P(), P()))
        def run(grads, perturb):
            local = jax.tree.map(
                lambda g: g + perturb[0] * jax.lax.axis_index(
                    "data").astype(jnp.float32), grads)
            reduced, mismatch = reduce_gradients(
                local, axis_name="data", check_consistency=True, **knobs)
            plain = reduce_gradients(local, axis_name="data", **knobs)
            return reduced, mismatch, plain

        zero = jnp.zeros((WORLD, 1), jnp.float32)
        reduced, mismatch, plain = jax.device_get(run(grads, zero))
        assert not bool(np.asarray(mismatch))
        assert _tree_bitwise(reduced, plain)

        bump = zero.at[3, 0].set(1.0)  # rank 3 diverges
        _, mismatch_bad, _ = jax.device_get(run(grads, bump))
        assert bool(np.asarray(mismatch_bad))
