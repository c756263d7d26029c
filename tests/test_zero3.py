"""ZeRO-3 fully-sharded engine: parity, residency, and resharding contracts.

The engine's load-bearing promises, each pinned bitwise where the design
says bitwise (ref: apex/contrib/optimizers/distributed_fused_adam.py's
pipelined param gather, taken to ZeRO stage 3):

* the prefetched-gather -> custom_vjp scatter -> sharded step pipeline is
  bitwise-equal to ZeRO-2 (``DistributedFusedAdam``) on identical inputs,
  for every prefetch depth and for the per-chunk ``overlap_backward`` step;
* ``param_residency="regather"`` re-runs the bucketed gather in backward
  (ledger-visible: gather traffic doubles) without changing a single bit;
* sharded checkpoints reshard across topology changes (8 -> 4/2/1)
  bitwise, and corrupted/missing shards fail loudly instead of loading.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.monitor import comms as mon_comms
from beforeholiday_tpu.optimizers import (
    DistributedFusedAdam,
    DistributedFusedLAMB,
    ZeRO3FusedAdam,
    ZeRO3FusedLAMB,
    zero3,
)
from beforeholiday_tpu.optimizers.distributed_fused import _shard_len

pytestmark = pytest.mark.zero3


def shard_map(f=None, **kw):
    kw.setdefault("check_vma", False)
    if f is None:
        return lambda g: jax.shard_map(g, **kw)
    return jax.shard_map(f, **kw)


@pytest.fixture
def data_mesh(devices8):
    return Mesh(np.asarray(devices8), ("data",))


# small bucket so the shard spans several buckets and the stripe plan has to
# split leaves across rank and bucket boundaries
BB = 16 * 1024


def _params(seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    return {
        "w1": jnp.asarray(rng.randn(37, 19).astype(dtype)),
        "w2": jnp.asarray(rng.randn(128).astype(dtype)),
        "w3": jnp.asarray(rng.randn(5, 3, 7).astype(dtype)),
    }


def _grads(seed=1):
    rng = np.random.RandomState(seed)
    return {
        "w1": jnp.asarray(rng.randn(37, 19).astype(np.float32)),
        "w2": jnp.asarray(rng.randn(128).astype(np.float32)),
        "w3": jnp.asarray(rng.randn(5, 3, 7).astype(np.float32)),
    }


def _vdot_loss(leaves, grads):
    # linear loss: the cotangent of each leaf is exactly grads[k], so both
    # engines see identical per-rank gradient inputs
    return sum(
        jnp.vdot(leaves[k].astype(jnp.float32), grads[k]) for k in grads
    )


def _tree_eq(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestZeRO3StepParity:
    def test_two_steps_bitwise_equal_zero2(self, data_mesh):
        """The acceptance oracle: 2 ZeRO-3 steps == 2 ZeRO-2 steps, bitwise,
        on params AND the fp32 master shard (uncompressed)."""
        params, grads = _params(), _grads()
        layout = zero3.layout_of(params)
        z2 = DistributedFusedAdam(
            lr=1e-2, weight_decay=0.02, impl="jnp", bucket_bytes=BB)
        z3 = ZeRO3FusedAdam(
            lr=1e-2, weight_decay=0.02, impl="jnp", bucket_bytes=BB,
            prefetch=1, param_residency="keep")

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P()),
            out_specs=(P(), P()))
        def z2_run(p, g):
            state = z2.init(p)
            for _ in range(2):
                p, state = z2.step(p, g, state)
            return p, state["master"]

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P()),
            out_specs=(P(), P()))
        def z3_run(p, g):
            state = z3.init(p)
            for _ in range(2):
                def loss_fn(master):
                    return _vdot_loss(z3.gather_params(master, layout), g)

                gs = jax.grad(loss_fn)(state["master"])
                state = z3.step(gs, state)
            return z3.gather_params(state["master"], layout), state["master"]

        p2, m2 = z2_run(params, grads)
        p3, m3 = z3_run(params, grads)
        _tree_eq(p2, p3)
        np.testing.assert_array_equal(np.asarray(m2), np.asarray(m3))

    @pytest.mark.parametrize("prefetch", [0, 2, 7])
    def test_prefetch_depths_bitwise_identical(self, data_mesh, prefetch):
        """Prefetch only reorders gathers; every depth produces the bits of
        the blocking form."""
        params, grads = _params(), _grads()
        layout = zero3.layout_of(params)

        def run(pf):
            opt = ZeRO3FusedAdam(
                lr=1e-2, impl="jnp", bucket_bytes=BB, prefetch=pf,
                param_residency="keep")

            @jax.jit
            @functools.partial(
                shard_map, mesh=data_mesh, in_specs=(P(), P()), out_specs=P())
            def go(p, g):
                state = opt.init(p)

                def loss_fn(master):
                    return _vdot_loss(opt.gather_params(master, layout), g)

                gs = jax.grad(loss_fn)(state["master"])
                return opt.step(gs, state)["master"]

            return np.asarray(go(params, grads))

        np.testing.assert_array_equal(run(prefetch), run(1))

    def test_overlap_backward_chunked_step_bitwise(self, data_mesh):
        """The per-chunk (``overlap_backward``) sharded update slices the
        same elementwise kernel, so it matches the phased step bitwise."""
        params, grads = _params(), _grads()
        layout = zero3.layout_of(params)

        def run(overlap):
            opt = ZeRO3FusedAdam(
                lr=1e-2, weight_decay=0.02, impl="jnp", bucket_bytes=BB,
                overlap_backward=overlap, param_residency="keep")

            @jax.jit
            @functools.partial(
                shard_map, mesh=data_mesh, in_specs=(P(), P()), out_specs=P())
            def go(p, g):
                state = opt.init(p)

                def loss_fn(master):
                    return _vdot_loss(opt.gather_params(master, layout), g)

                gs = jax.grad(loss_fn)(state["master"])
                return opt.step(gs, state)["master"]

            return np.asarray(go(params, grads))

        np.testing.assert_array_equal(run(True), run(False))

    def test_bf16_uniform_model_gathers_bf16_and_matches_zero2(
            self, data_mesh):
        """A dtype-uniform bf16 model rides the wire in bf16 (cast commutes
        with the gather) and still matches ZeRO-2's trajectory bitwise. The
        grads fed to ZeRO-2 are pre-rounded to bf16: that is what a bf16
        model's backward hands both engines (ZeRO-3's leaf cotangents carry
        the leaf dtype), so the scattered bits match."""
        params = _params(dtype=np.float32)
        params = jax.tree_util.tree_map(
            lambda l: l.astype(jnp.bfloat16), params)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.bfloat16).astype(jnp.float32), _grads())
        layout = zero3.layout_of(params)
        z3 = ZeRO3FusedAdam(
            lr=1e-2, impl="jnp", bucket_bytes=BB, param_residency="keep")
        assert z3._gather_wire(layout) == "bfloat16"
        z2 = DistributedFusedAdam(lr=1e-2, impl="jnp", bucket_bytes=BB)

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P()),
            out_specs=(P(), P()))
        def z2_run(p, g):
            p2, state = z2.step(p, g, z2.init(p))
            return p2, state["master"]

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P()),
            out_specs=(P(), P()))
        def z3_run(p, g):
            state = z3.init(p)

            def loss_fn(master):
                return _vdot_loss(z3.gather_params(master, layout), g)

            gs = jax.grad(loss_fn)(state["master"])
            state = z3.step(gs, state)
            return z3.gather_params(state["master"], layout), state["master"]

        p2, m2 = z2_run(params, grads)
        p3, m3 = z3_run(params, grads)
        assert all(
            l.dtype == jnp.bfloat16 for l in jax.tree_util.tree_leaves(p3))
        _tree_eq(p2, p3)
        np.testing.assert_array_equal(np.asarray(m2), np.asarray(m3))

    def test_overflow_on_one_rank_skips_step_everywhere(self, data_mesh):
        """An inf in a single rank's grad shard must trip the GLOBAL
        found_inf flag: no rank advances step or touches its master."""
        params = _params()
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(lr=1e-2, impl="jnp", bucket_bytes=BB)
        world = 8
        shard = _shard_len(layout.spec.padded_total, world)
        g = np.random.RandomState(0).randn(world, shard).astype(np.float32)
        g[3, 7] = np.inf  # one bad element on one rank

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P("data")),
            out_specs=(P("data"), P("data")))
        def go(p, gs):
            state = opt.init(p)
            state = opt.step(gs.reshape(-1), state)
            return (state["master"][None], state["step"].reshape(1))

        master, step = go(params, jnp.asarray(g))
        assert np.all(np.asarray(step) == 0)
        init_master = np.asarray(jax.jit(functools.partial(
            shard_map(lambda p: opt.init(p)["master"][None],
                      mesh=data_mesh, in_specs=(P(),),
                      out_specs=P("data"))))(params))
        np.testing.assert_array_equal(np.asarray(master), init_master)

    def test_step_rejects_unscattered_grads(self, data_mesh):
        """Passing full-arena (or tree) grads instead of the shard is the
        classic ZeRO-3 wiring bug — pinned to a loud shape error."""
        params = _params()
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(lr=1e-2, impl="jnp", bucket_bytes=BB)
        shard = _shard_len(layout.spec.padded_total, 8)

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(),), out_specs=P())
        def go(p):
            state = opt.init(p)
            bad = jnp.zeros((shard * 8,), jnp.float32)
            return opt.step(bad, state)["master"]

        with pytest.raises(ValueError, match="reduce-scattered grad shard"):
            jax.eval_shape(go, params)


class TestParamResidency:
    def _gather_calls(self, data_mesh, residency):
        params, grads = _params(), _grads()
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(
            lr=1e-2, impl="jnp", bucket_bytes=BB, param_residency=residency)

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P()), out_specs=P())
        def go(p, g):
            state = opt.init(p)

            def loss_fn(master):
                tree = opt.gather_params(master, layout)
                return sum(jnp.sum(jnp.tanh(tree[k])) for k in tree)

            loss_fn = opt.wrap_residency(loss_fn)
            gs = jax.grad(loss_fn)(state["master"])
            return opt.step(gs, state)["master"]

        mon_comms.reset_comms_ledger()
        jax.make_jaxpr(go)(params, grads)
        calls = sum(
            r["calls"] for r in mon_comms.comms_records()
            if r["site"] == "zero3.gather_params"
        )
        out = np.asarray(jax.jit(go)(params, grads))
        return calls, out

    def test_regather_doubles_gather_traffic_bitwise(self, data_mesh):
        """``regather`` makes the gathered arena non-saveable: backward
        re-runs the bucketed gather (2x ledger traffic), bits unchanged."""
        keep_calls, keep_out = self._gather_calls(data_mesh, "keep")
        re_calls, re_out = self._gather_calls(data_mesh, "regather")
        assert keep_calls > 0
        assert re_calls == 2 * keep_calls
        np.testing.assert_array_equal(keep_out, re_out)

    def test_residency_policy_names(self):
        assert ZeRO3FusedAdam(
            param_residency="regather").residency_policy() == "zero3_regather"
        assert ZeRO3FusedAdam(
            param_residency="keep").residency_policy() == "none"


class TestCheckpointing:
    def test_state_dict_roundtrip_resumes_bitwise(self, data_mesh):
        """save -> load reproduces the shard state BITWISE; the continued
        trajectory then matches the unbroken run (allclose, not bitwise: the
        resumed second step is a separately compiled program, and XLA's
        fusion/FMA choices legitimately differ by an ulp across programs —
        the checkpoint itself must not lose a bit)."""
        params = _params()
        g1, g2 = _grads(1), _grads(2)
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(
            lr=1e-2, weight_decay=0.02, impl="jnp", bucket_bytes=BB,
            param_residency="keep")
        tree_specs = {k: P() for k in params}
        sd_specs = {"step": P(), "master": tree_specs, "exp_avg": tree_specs,
                    "exp_avg_sq": tree_specs}

        def one_step(state, g):
            def loss_fn(master):
                return _vdot_loss(opt.gather_params(master, layout), g)

            return opt.step(jax.grad(loss_fn)(state["master"]), state)

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P()),
            out_specs=sd_specs)
        def save_after_one(p, g):
            return opt.state_dict(layout, one_step(opt.init(p), g))

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh,
            in_specs=(sd_specs, P()), out_specs=P())
        def resume_one(sd, g):
            return one_step(opt.load_state_dict(layout, sd), g)["master"]

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P(), P()),
            out_specs=P())
        def continuous(p, ga, gb):
            return one_step(one_step(opt.init(p), ga), gb)["master"]

        stacked = {"master": P("data"), "exp_avg": P("data"),
                   "exp_avg_sq": P("data"), "step": P()}

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P()),
            out_specs=stacked)
        def state_after_one(p, g):
            return one_step(opt.init(p), g)

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(sd_specs,),
            out_specs=stacked)
        def adopt(sd):
            return opt.load_state_dict(layout, sd)

        sd = save_after_one(params, g1)
        assert int(np.asarray(sd["step"])) == 1
        direct = state_after_one(params, g1)
        adopted = adopt(sd)
        for key in ("master", "exp_avg", "exp_avg_sq", "step"):
            np.testing.assert_array_equal(
                np.asarray(direct[key]), np.asarray(adopted[key]))
        resumed = np.asarray(resume_one(sd, g2))
        straight = np.asarray(continuous(params, g1, g2))
        np.testing.assert_allclose(resumed, straight, rtol=2e-6, atol=1e-7)

    def test_load_state_dict_rejects_wrong_shard_shape(self, data_mesh):
        params = _params()
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(lr=1e-2, impl="jnp", bucket_bytes=BB)
        shard = _shard_len(layout.spec.padded_total, 8)
        bad = {"step": 1, "master": np.zeros(shard + 1, np.float32),
               "exp_avg": np.zeros(shard, np.float32),
               "exp_avg_sq": np.zeros(shard, np.float32)}

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(), out_specs=P())
        def go():
            return opt.load_state_dict(layout, bad)["master"]

        with pytest.raises(ValueError, match="reshard with"):
            jax.eval_shape(go)


class TestResharding:
    def _trained_stacked(self, data_mesh, opt, layout, params, grads):
        specs = {"master": P("data"), "exp_avg": P("data"),
                 "exp_avg_sq": P("data"), "step": P()}

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P()), out_specs=specs)
        def go(p, g):
            state = opt.init(p)

            def loss_fn(master):
                return _vdot_loss(opt.gather_params(master, layout), g)

            return opt.step(jax.grad(loss_fn)(state["master"]), state)

        out = go(params, grads)
        shard = _shard_len(layout.spec.padded_total, 8)
        stacked = {
            k: np.asarray(out[k]).reshape(8, shard)
            for k in ("master", "exp_avg", "exp_avg_sq")
        }
        stacked["step"] = np.asarray(out["step"])
        return stacked

    @pytest.mark.parametrize("new_world", [4, 2, 1])
    def test_save_at_8_reshard_bitwise(self, data_mesh, tmp_path, new_world):
        """The acceptance topology change: shards saved at world=8
        re-concatenate bitwise after resharding to 4/2/1."""
        params, grads = _params(), _grads()
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(
            lr=1e-2, impl="jnp", bucket_bytes=BB, param_residency="keep")
        stacked = self._trained_stacked(data_mesh, opt, layout, params, grads)
        manifest = zero3.shard_manifest(layout, 8)
        zero3.save_shard_files(
            tmp_path, zero3.shards_from_stacked(stacked, 8), manifest)
        mf, shards = zero3.load_shard_files(tmp_path)
        assert mf["format"] == "zero3-shard-v1"
        re = zero3.reshard_state(shards, mf, new_world)
        assert len(re) == new_world
        arena_len = mf["arena_len"]
        for key in ("master", "exp_avg", "exp_avg_sq"):
            orig = stacked[key].reshape(-1)[:arena_len]
            back = np.concatenate([r[key] for r in re])[:arena_len]
            np.testing.assert_array_equal(orig, back)
            assert re[0][key].shape == (_shard_len(arena_len, new_world),)

    def test_resharded_shard_loads_into_smaller_mesh(
            self, devices8, data_mesh, tmp_path):
        """End-to-end topology change: train at world=8, reshard to 4, adopt
        the shard via ``load_state_dict`` on a 4-device mesh — the gathered
        params must match the 8-rank gather bitwise."""
        params, grads = _params(), _grads()
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(
            lr=1e-2, impl="jnp", bucket_bytes=BB, param_residency="keep")
        stacked = self._trained_stacked(data_mesh, opt, layout, params, grads)
        manifest = zero3.shard_manifest(layout, 8)
        zero3.save_shard_files(
            tmp_path, zero3.shards_from_stacked(stacked, 8), manifest)
        mf, shards = zero3.load_shard_files(tmp_path)
        re = zero3.reshard_state(shards, mf, 4)
        stacked4 = {
            k: jnp.asarray(np.stack([r[k] for r in re]).reshape(-1))
            for k in ("master", "exp_avg", "exp_avg_sq")
        }
        stacked4["step"] = jnp.asarray(re[0]["step"])
        mesh4 = Mesh(np.asarray(devices8[:4]), ("data",))
        specs = {"master": P("data"), "exp_avg": P("data"),
                 "exp_avg_sq": P("data"), "step": P()}

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh4, in_specs=(specs,), out_specs=P())
        def gather_at_4(sd):
            state = opt.load_state_dict(layout, sd)
            return opt.gather_params(state["master"], layout)

        p4 = gather_at_4(stacked4)
        expect = zero3.layout_of(params)  # structure check via unflatten
        assert jax.tree_util.tree_structure(p4) == expect.treedef
        arena8 = {
            "step": jnp.asarray(stacked["step"]),
            **{k: jnp.asarray(stacked[k].reshape(-1))
               for k in ("master", "exp_avg", "exp_avg_sq")},
        }

        @jax.jit
        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(specs,), out_specs=P())
        def gather_at_8(sd):
            state = opt.load_state_dict(layout, sd)
            return opt.gather_params(state["master"], layout)

        _tree_eq(gather_at_8(arena8), p4)

    def test_missing_shard_fails_loudly(self, data_mesh, tmp_path):
        params, grads = _params(), _grads()
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(lr=1e-2, impl="jnp", bucket_bytes=BB)
        stacked = self._trained_stacked(data_mesh, opt, layout, params, grads)
        zero3.save_shard_files(
            tmp_path, zero3.shards_from_stacked(stacked, 8),
            zero3.shard_manifest(layout, 8))
        os.remove(tmp_path / "shard_00005.npz")
        with pytest.raises(FileNotFoundError, match="shard_00005"):
            zero3.load_shard_files(tmp_path)

    def test_corrupted_shard_fails_loudly(self, data_mesh, tmp_path):
        params, grads = _params(), _grads()
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(lr=1e-2, impl="jnp", bucket_bytes=BB)
        stacked = self._trained_stacked(data_mesh, opt, layout, params, grads)
        zero3.save_shard_files(
            tmp_path, zero3.shards_from_stacked(stacked, 8),
            zero3.shard_manifest(layout, 8))
        with np.load(tmp_path / "shard_00002.npz") as z:
            d = {k: z[k] for k in z.files}
        d["exp_avg"] = d["exp_avg"][:-5]  # truncate one tensor
        np.savez(tmp_path / "shard_00002.npz", **d)
        with pytest.raises(ValueError, match="corrupted or mismatched"):
            zero3.load_shard_files(tmp_path)

    def test_missing_manifest_fails_loudly(self, tmp_path):
        with pytest.raises(FileNotFoundError, match="manifest.json"):
            zero3.load_shard_files(tmp_path)

    def test_manifest_geometry(self):
        layout = zero3.layout_of(_params())
        mf = zero3.shard_manifest(layout, 8)
        assert mf["format"] == "zero3-shard-v1"
        assert mf["shard_len"] == _shard_len(layout.spec.padded_total, 8)
        assert mf["shard_len"] * 8 == mf["arena_len"] + mf["pad"]
        assert mf["state_keys"] == ["master", "exp_avg", "exp_avg_sq"]


class TestConfigSurface:
    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="prefetch"):
            ZeRO3FusedAdam(prefetch=-1)
        with pytest.raises(ValueError, match="param_residency"):
            ZeRO3FusedAdam(param_residency="cached")

    def test_zero3_lamb_fails_loudly(self):
        """ZeRO3FusedLAMB must refuse construction with a message that names
        the architectural conflict AND the supported alternatives."""
        with pytest.raises(NotImplementedError) as e:
            ZeRO3FusedLAMB(lr=1e-3)
        msg = str(e.value)
        assert "trust" in msg and "ZeRO3FusedAdam" in msg
        assert "DistributedFusedLAMB" in msg

    def test_zero2_lamb_rejects_overlap_backward(self):
        """Satellite pin: the ZeRO-2 LAMB's overlap_backward rejection stays
        a loud NotImplementedError with an actionable message."""
        with pytest.raises(NotImplementedError) as e:
            DistributedFusedLAMB(overlap_backward=True)
        msg = str(e.value)
        assert "overlap_backward" in msg
        assert "DistributedFusedAdam" in msg

    def test_state_is_sharded(self, data_mesh):
        """Per-rank ZeRO-3 state is 3 shard arrays — no full-size tensor
        anywhere in the state tree."""
        params = _params()
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(lr=1e-2, impl="jnp", bucket_bytes=BB)
        shard = _shard_len(layout.spec.padded_total, 8)

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(),),
            out_specs={"master": P("data"), "exp_avg": P("data"),
                       "exp_avg_sq": P("data"), "step": P()})
        def init(p):
            return opt.init(p)

        shapes = jax.eval_shape(init, params)
        for key in ("master", "exp_avg", "exp_avg_sq"):
            assert shapes[key].shape == (8 * shard,)  # (shard,) per rank
        assert shard * 8 >= layout.spec.padded_total

    def test_ledger_sites_use_zero3_prefix(self, data_mesh):
        """The subclass inherits ZeRO-2's machinery but its collectives must
        book under ``zero3.*`` so ``comms_summary`` rolls them up apart."""
        params, grads = _params(), _grads()
        layout = zero3.layout_of(params)
        opt = ZeRO3FusedAdam(lr=1e-2, impl="jnp", bucket_bytes=BB)

        @functools.partial(
            shard_map, mesh=data_mesh, in_specs=(P(), P()), out_specs=P())
        def go(p, g):
            state = opt.init(p)

            def loss_fn(master):
                return _vdot_loss(opt.gather_params(master, layout), g)

            return opt.step(jax.grad(loss_fn)(state["master"]), state)["master"]

        mon_comms.reset_comms_ledger()
        jax.make_jaxpr(go)(params, grads)
        sites = {r["site"] for r in mon_comms.comms_records()}
        # gather_state books only on the state_dict path, not the train step
        assert {"zero3.gather_params", "zero3.reduce_scatter_grads",
                "zero3.found_inf"} <= sites
        subs = {r["subsystem"] for r in mon_comms.comms_summary()}
        assert "zero3" in subs and "zero2" not in subs
