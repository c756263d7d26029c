"""Activation-memory engine tests: remat policies, the per-jit memory
ledger, buffer donation, and the CE ``save_softmax`` knob.

The correctness contract of remat is exact: ``jax.checkpoint`` recomputes the
SAME ops on the SAME inputs, so every policy must reproduce the un-remat loss
and gradients to numerical identity (fp32 scan order is preserved — the only
tolerance needed is for CSE-order wiggle, which in practice is zero here).
The memory contract is the compiler's own: ``memory_analysis().temp_bytes``
under ``full`` must not exceed ``none`` (saving nothing can't need more
scratch than saving everything).
"""

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu import monitor, remat
from beforeholiday_tpu.remat import policies as remat_policies
from beforeholiday_tpu.testing import bert, gpt
from beforeholiday_tpu.utils.logging import reset_warn_once

REMAT_POLICIES = ("full", "dots_saveable", "save_boundaries")

_GPT = dict(vocab_size=257, seq_len=32, d_model=32, n_heads=2, n_layers=2,
            dtype=jnp.float32)
_BERT = dict(vocab_size=257, seq_len=32, d_model=32, n_heads=2, n_layers=2,
             dtype=jnp.float32)


# -------------------------------------------------------------------------------
# policy registry
# -------------------------------------------------------------------------------


class TestPolicyRegistry:
    def test_builtins_registered(self):
        names = remat.available_policies()
        for n in ("none", "full", "dots_saveable", "save_boundaries"):
            assert n in names

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError, match="unknown remat policy"):
            remat.resolve("no_such_policy")
        with pytest.raises(ValueError, match="unknown remat policy"):
            remat.apply(lambda x: x, "no_such_policy")

    def test_duplicate_registration_raises(self):
        with pytest.raises(ValueError, match="already registered"):
            remat.register_policy("full", None)
        # overwrite=True is the escape hatch
        remat.register_policy("full", None, overwrite=True)

    def test_none_is_identity_wrap(self):
        fn = lambda x: x * 2
        assert remat.apply(fn, None) is fn
        assert remat.apply(fn, "none") is fn

    def test_custom_policy_round_trips(self):
        name = "test_custom_tags"
        if name not in remat.available_policies():
            remat.register_policy(
                name,
                jax.checkpoint_policies.save_only_these_names(
                    remat.BOUNDARY_TAGS[0]
                ),
            )
        wrapped = remat.apply(lambda x: jnp.sin(x) * 2, name)
        x = jnp.arange(4.0)
        np.testing.assert_allclose(
            jax.grad(lambda x: wrapped(x).sum())(x),
            jax.grad(lambda x: (jnp.sin(x) * 2).sum())(x),
        )

    def test_non_string_policy_passes_through(self):
        pol = jax.checkpoint_policies.dots_saveable
        assert remat.resolve(pol) is pol


# -------------------------------------------------------------------------------
# model parity: every policy reproduces the un-remat loss/grads
# -------------------------------------------------------------------------------


class TestGPTRematParity:
    @pytest.fixture(scope="class")
    def reference(self):
        cfg = gpt.GPTConfig(**_GPT)
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: gpt.loss_fn(p, tokens, targets, cfg)
        ))(params)
        return params, tokens, targets, loss, grads

    @pytest.mark.parametrize("policy", REMAT_POLICIES)
    def test_loss_and_grads_match(self, reference, policy):
        params, tokens, targets, ref_loss, ref_grads = reference
        cfg = gpt.GPTConfig(**_GPT, remat_policy=policy)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: gpt.loss_fn(p, tokens, targets, cfg)
        ))(params)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )

    def test_dropout_path_remat_matches(self):
        """Remat under dropout must replay the SAME mask in the recompute
        (jax.checkpoint preserves the threaded PRNG keys) — loss equality
        with the un-remat dropout forward is the witness."""
        base = dict(_GPT, dropout_rate=0.1, attention_dropout=0.1)
        params = gpt.init(jax.random.PRNGKey(0), gpt.GPTConfig(**base))
        tokens, targets = gpt.synthetic_batch(
            jax.random.PRNGKey(1), gpt.GPTConfig(**base), 2
        )
        dkey = jax.random.PRNGKey(7)

        def loss_for(policy):
            cfg = gpt.GPTConfig(**base, remat_policy=policy)
            return jax.jit(jax.value_and_grad(lambda p: gpt.loss_fn(
                p, tokens, targets, cfg,
                forward_fn=lambda pp, tt, c=cfg: gpt.forward(
                    pp, tt, c, dropout_key=dkey
                ),
            )))(params)

        ref_loss, ref_grads = loss_for(None)
        loss, grads = loss_for("save_boundaries")
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )


class TestBertRematParity:
    @pytest.mark.parametrize("policy", REMAT_POLICIES)
    def test_mlm_logits_grads_match(self, policy):
        cfg0 = bert.BertConfig(**_BERT)
        params = bert.init(jax.random.PRNGKey(0), cfg0)
        tokens, targets, mlm_mask, _ = bert.synthetic_batch(
            jax.random.PRNGKey(1), cfg0, 2
        )

        def masked_loss(p, cfg):
            mlm_logits, nsp_logits = bert.forward(p, tokens, cfg)
            logp = jax.nn.log_softmax(mlm_logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
            return jnp.sum(nll * mlm_mask) / jnp.sum(mlm_mask) + jnp.mean(
                nsp_logits.astype(jnp.float32) ** 2
            )

        ref = jax.jit(jax.value_and_grad(
            functools.partial(masked_loss, cfg=cfg0)))(params)
        got = jax.jit(jax.value_and_grad(functools.partial(
            masked_loss, cfg=bert.BertConfig(**_BERT, remat_policy=policy)
        )))(params)
        np.testing.assert_allclose(float(got[0]), float(ref[0]), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(ref[1])):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )


# -------------------------------------------------------------------------------
# pipeline schedules
# -------------------------------------------------------------------------------

_H, _M, _MICRO, _S = 32, 8, 4, 4


def _stage_fn(sp, x):
    h = jax.nn.gelu(x @ sp["w1"] + sp["b1"])
    return h @ sp["w2"] + sp["b2"] + x


def _mse(y, tgt):
    return jnp.mean((y - tgt) ** 2)


def _toy_stack(key):
    ks = jax.random.split(key, 2)
    s = 1.0 / np.sqrt(_H)
    return {
        "w1": jax.random.normal(ks[0], (_S, _H, 4 * _H)) * s,
        "b1": jnp.zeros((_S, 4 * _H)),
        "w2": jax.random.normal(ks[1], (_S, 4 * _H, _H)) * s,
        "b2": jnp.zeros((_S, _H)),
    }


class TestPipelineRemat:
    @pytest.mark.parametrize("policy", REMAT_POLICIES)
    def test_no_pipelining_remat_parity(self, policy):
        from beforeholiday_tpu.transformer import pipeline_parallel as pp

        stacked = _toy_stack(jax.random.PRNGKey(0))
        rng = np.random.RandomState(1)
        inputs = jnp.asarray(rng.randn(_M, _MICRO, _H), jnp.float32)
        targets = jnp.asarray(rng.randn(_M, _MICRO, _H), jnp.float32)

        def full_model(stacked, x):
            def body(h, sp):
                return _stage_fn(sp, h), None

            return jax.lax.scan(body, x, stacked)[0]

        def run(pol):
            return jax.jit(functools.partial(
                pp.forward_backward_no_pipelining, full_model, _mse,
                remat_policy=pol,
            ))(stacked, inputs, targets)

        ref_loss, ref_grads = run(None)
        loss, grads = run(policy)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )

    @pytest.mark.parametrize("policy", REMAT_POLICIES)
    def test_1f1b_remat_parity(self, devices8, policy):
        """Per-stage remat inside the 1F1B tick loop reproduces the un-remat
        schedule's loss and grads (the stage fn is wrapped once, outside the
        tick loop, so warmup/steady/cooldown all recompute identically)."""
        from jax.sharding import Mesh, PartitionSpec as P

        from beforeholiday_tpu.transformer import pipeline_parallel as pp

        smap = functools.partial(jax.shard_map, check_vma=False)

        stacked = _toy_stack(jax.random.PRNGKey(0))
        rng = np.random.RandomState(1)
        inputs = jnp.asarray(rng.randn(_M, _MICRO, _H), jnp.float32)
        targets = jnp.asarray(rng.randn(_M, _MICRO, _H), jnp.float32)
        mesh = Mesh(np.array(devices8[:_S]), ("pipe",))

        def run(pol):
            @jax.jit
            @functools.partial(
                smap, mesh=mesh, in_specs=(P("pipe"), P(), P()),
                out_specs=(P(), P("pipe")),
            )
            def pipe_step(sp_stacked, inputs, targets):
                sp = jax.tree.map(lambda leaf: leaf[0], sp_stacked)
                loss, grads = pp.forward_backward_pipelining_without_interleaving(
                    _stage_fn, _mse, sp, inputs, targets, axis_name="pipe",
                    remat_policy=pol,
                )
                return loss, jax.tree.map(lambda g: g[None], grads)

            return pipe_step(stacked, inputs, targets)

        ref_loss, ref_grads = run(None)
        loss, grads = run(policy)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )


# -------------------------------------------------------------------------------
# memory ledger
# -------------------------------------------------------------------------------


@pytest.mark.memory
class TestMemoryLedger:
    @pytest.fixture(autouse=True)
    def _clean_ledger(self):
        monitor.reset_memory_ledger()
        yield
        monitor.reset_memory_ledger()

    def _grad_fn(self, policy):
        cfg = gpt.GPTConfig(**_GPT, remat_policy=policy)
        tokens, targets = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, 4)
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        fn = jax.jit(jax.value_and_grad(
            lambda p: gpt.loss_fn(p, tokens, targets, cfg)
        ))
        return fn, params

    def test_full_remat_temp_bytes_not_above_none(self):
        """THE ledger oracle: saving nothing cannot need more scratch than
        saving everything — XLA's own memory_analysis must agree."""
        fn_none, params = self._grad_fn(None)
        fn_full, _ = self._grad_fn("full")
        s_none = monitor.measure_memory(fn_none, params, entry="ledger_none")
        s_full = monitor.measure_memory(fn_full, params, entry="ledger_full")
        if s_none is None or s_full is None:
            pytest.skip("backend offers no memory_analysis")
        assert s_none["temp_bytes"] > 0
        assert s_full["temp_bytes"] <= s_none["temp_bytes"]

    def test_track_memory_records_and_caches(self):
        fn, params = self._grad_fn(None)
        tracked = monitor.track_memory("t_step")(fn)
        l1, g1 = tracked(params)
        l2, g2 = tracked(params)  # same signature: cached executable
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        recs = monitor.memory_records()
        assert recs["t_step"]["calls"] == 2
        assert len(recs["t_step"]["signatures"]) == 1
        stats = recs["t_step"]["signatures"][0]
        if stats is not None:
            assert stats["temp_bytes"] >= 0
            assert stats["argument_bytes"] > 0

    def test_memory_summary_rollup(self):
        fn, params = self._grad_fn(None)
        tracked = monitor.track_memory("t_sum")(fn)
        tracked(params)
        rows = monitor.memory_summary()
        row = next(r for r in rows if r["entry"] == "t_sum")
        assert row["calls"] == 1
        assert row["signatures"] == 1
        for key in ("peak_temp_bytes", "argument_bytes", "output_bytes",
                    "alias_bytes", "generated_code_bytes"):
            assert key in row

    def test_reset_clears_entries(self):
        fn, params = self._grad_fn(None)
        monitor.track_memory("t_reset")(fn)(params)
        assert "t_reset" in monitor.memory_records()
        monitor.reset_memory_ledger()
        assert monitor.memory_records() == {}

    def test_tracked_fn_without_lower_falls_back(self):
        """A plain python fn (no .lower) is still callable under tracking —
        the ledger records a None stats row instead of failing."""
        tracked = monitor.track_memory("t_plain")(lambda x: x + 1)
        assert int(tracked(jnp.int32(1))) == 2
        recs = monitor.memory_records()
        assert recs["t_plain"]["signatures"] == [None]


# -------------------------------------------------------------------------------
# donation
# -------------------------------------------------------------------------------


@pytest.mark.memory
class TestDonation:
    def _sgd(self):
        def step(state, grads_seed):
            params, mom = state
            grads = jax.tree.map(lambda p: p * 0.1 + grads_seed, params)
            mom = jax.tree.map(lambda m, g: 0.9 * m + g, mom, grads)
            params = jax.tree.map(lambda p, m: p - 0.01 * m, params, mom)
            return (params, mom), jax.tree.map(jnp.sum, grads)

        return step

    def _state(self):
        params = {"w": jnp.arange(8.0), "b": jnp.ones((3,))}
        return params, jax.tree.map(jnp.zeros_like, params)

    def test_donated_step_bitwise_matches_undonated(self):
        step = self._sgd()
        plain = jax.jit(step)
        donated = remat.donate_step(step, donate_argnums=(0,))
        s_plain, s_don = self._state(), self._state()
        seed = jnp.float32(0.5)
        for _ in range(3):
            s_plain, out_p = plain(s_plain, seed)
            s_don, out_d = donated(s_don, seed)
        for a, b in zip(jax.tree.leaves(s_plain), jax.tree.leaves(s_don)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_donated_input_is_consumed(self):
        donated = remat.donate_step(self._sgd(), donate_argnums=(0,))
        state = self._state()
        donated(state, jnp.float32(0.5))
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(state))

    def test_aliased_donated_buffers_are_deduped(self):
        """Two donated slots sharing one buffer (the fused optimizers alias
        fp32 masters to the params arena at init) must not trip XLA's
        donate-twice rejection — the wrapper copies the duplicate."""

        def add(a, b):
            return a + b, a - b

        donated = remat.donate_step(add, donate_argnums=(0, 1))
        x = jnp.arange(6.0)
        s, d = donated(x, x)  # same buffer in both donated slots
        np.testing.assert_array_equal(np.asarray(s), np.arange(6.0) * 2)
        np.testing.assert_array_equal(np.asarray(d), np.zeros(6))

    def test_aliased_buffers_replicated_over_a_mesh_are_deduped(self, devices8):
        """``device_put`` of two aliased leaves onto a mesh yields two arrays
        that still share the source's buffer on the source's device (copies
        elsewhere). The alias is per shard, and a whole-array pointer does
        not exist for a multi-shard array — the dedupe must look shard by
        shard or XLA rejects the step (and the other replicas hang)."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.asarray(devices8[:4]), ("data",))
        x = jnp.arange(6.0)
        a, b = jax.device_put((x, x), NamedSharding(mesh, P()))
        donated = remat.donate_step(
            jax.shard_map(
                lambda a, b: (a + b, a - b), mesh=mesh,
                in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False,
            ),
            donate_argnums=(0, 1),
        )
        s, d = donated(a, b)
        np.testing.assert_array_equal(np.asarray(s), np.arange(6.0) * 2)
        np.testing.assert_array_equal(np.asarray(d), np.zeros(6))

    def test_undonated_arena_warns_once(self):
        from beforeholiday_tpu.ops.arena import PackedParams
        from beforeholiday_tpu.remat import donation

        packed = PackedParams.pack({"w": jnp.arange(4.0), "b": jnp.ones((2,))})

        def step(state, arena):
            return state + 1.0, jax.tree.map(lambda a: a * 2.0, arena)

        step.__name__ = "warn_probe_step"
        donated = remat.donate_step(step, donate_argnums=(0,))

        records = []

        class _Cap(logging.Handler):
            def emit(self, record):
                records.append(record)

        h = _Cap()
        donation_logger = logging.getLogger(
            "beforeholiday_tpu.remat.donation"
        )
        root = logging.getLogger("beforeholiday_tpu")
        root.addHandler(h)
        reset_warn_once((donation._WARN_PREFIX, "warn_probe_step", 1))
        try:
            state = jnp.zeros(())
            for _ in range(3):
                state, packed = donated(state, packed)
            msgs = [r.getMessage() for r in records if "PackedParams" in
                    r.getMessage()]
            assert len(msgs) == 1
            assert "undonated argument 1" in msgs[0]
        finally:
            root.removeHandler(h)
            del donation_logger

    def test_donate_optimizer_step_matches_plain(self):
        from beforeholiday_tpu.optimizers import FusedSGD

        opt = FusedSGD(lr=0.1)
        params = {"w": jnp.arange(8.0), "b": jnp.ones((3,))}
        grads = jax.tree.map(lambda p: jnp.full_like(p, 0.25), params)
        plain_p, plain_s = opt.step(params, grads, opt.init(params))
        donated = remat.donate_optimizer_step(opt)
        don_p, don_s = donated(
            {"w": jnp.arange(8.0), "b": jnp.ones((3,))}, grads,
            opt.init({"w": jnp.arange(8.0), "b": jnp.ones((3,))}),
        )
        for a, b in zip(jax.tree.leaves(plain_p), jax.tree.leaves(don_p)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(plain_s), jax.tree.leaves(don_s)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------------------------------
# vocab-parallel CE: save_softmax
# -------------------------------------------------------------------------------


@pytest.mark.memory
class TestCrossEntropySaveSoftmax:
    @pytest.fixture(autouse=True)
    def _single_rank(self, monkeypatch):
        """Run the vocab-parallel CE as world-size 1: full vocab range, the
        collectives become identity. (The real TP path needs jax.shard_map /
        lax.axis_size, absent on older jax — the parity target here is the
        save_softmax residual swap, which is rank-local math.)"""
        from beforeholiday_tpu.transformer.tensor_parallel import (
            cross_entropy as ce,
        )

        monkeypatch.setattr(ce, "vocab_range", lambda v, a: (0, v))

        class _Comms:
            @staticmethod
            def pmax(x, axis_name=None, site=None):
                return x

            @staticmethod
            def psum(x, axis_name=None, site=None):
                return x

        monkeypatch.setattr(ce, "comms", _Comms)
        self.ce = ce

    def _batch(self, dtype=jnp.float32, vocab=64):
        k1, k2 = jax.random.split(jax.random.PRNGKey(3))
        logits = jax.random.normal(k1, (4, 9, vocab), jnp.float32).astype(dtype)
        target = jax.random.randint(k2, (4, 9), 0, vocab)
        return logits, target, vocab

    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    def test_save_softmax_false_bitwise_grads(self, smoothing):
        """Recomputing softmax from (xmax, sum_ex) is the same exp on the
        same inputs — grads must be BITWISE identical, not just close."""
        logits, target, vocab = self._batch()

        def loss(lg, save):
            return jnp.mean(self.ce.vocab_parallel_cross_entropy(
                lg, target, vocab, label_smoothing=smoothing,
                save_softmax=save,
            ))

        l_save, g_save = jax.value_and_grad(functools.partial(
            loss, save=True))(logits)
        l_reco, g_reco = jax.value_and_grad(functools.partial(
            loss, save=False))(logits)
        np.testing.assert_array_equal(np.asarray(l_save), np.asarray(l_reco))
        np.testing.assert_array_equal(np.asarray(g_save), np.asarray(g_reco))

    def test_grad_dtype_follows_logits_without_sentinel(self):
        """The VJP closes over the logits dtype statically (no dtype sentinel
        rides the residuals): bf16 logits get bf16 grads on both residual
        layouts."""
        logits, target, vocab = self._batch(dtype=jnp.bfloat16)
        for save in (True, False):
            g = jax.grad(lambda lg: jnp.mean(
                self.ce.vocab_parallel_cross_entropy(
                    lg, target, vocab, save_softmax=save
                )
            ))(logits)
            assert g.dtype == jnp.bfloat16

    def test_matches_dense_reference(self):
        logits, target, vocab = self._batch()
        for save in (True, False):
            loss = self.ce.vocab_parallel_cross_entropy(
                logits, target, vocab, save_softmax=save
            )
            ref = -jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ref = jnp.take_along_axis(ref, target[..., None], axis=-1)[..., 0]
            np.testing.assert_allclose(
                np.asarray(loss), np.asarray(ref), rtol=1e-6, atol=1e-6
            )

    def test_save_softmax_false_residuals_are_smaller(self):
        """The point of the knob: the saved-residual footprint drops from the
        fp32 (..., V) softmax to the (...,) row stats + original logits."""
        logits, target, vocab = self._batch(dtype=jnp.bfloat16, vocab=512)

        def loss(save):
            def f(lg):
                return jnp.mean(self.ce.vocab_parallel_cross_entropy(
                    lg, target, vocab, save_softmax=save
                ))

            _, vjp = jax.vjp(f, logits)
            return vjp

        def res_bytes(vjp):
            return sum(
                leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(vjp)
                if hasattr(leaf, "dtype")
            )

        assert res_bytes(loss(False)) < res_bytes(loss(True))
