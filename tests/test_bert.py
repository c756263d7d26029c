"""Standalone BERT harness (BASELINE config 4 shape): semantics-preserving
parallelism + FusedLAMB convergence smoke
(ref: apex/transformer/testing/standalone_bert.py:255,
tests/L0/run_transformer/run_bert_minimal_test.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from beforeholiday_tpu.optimizers import FusedLAMB
from beforeholiday_tpu.parallel import parallel_state as ps
from beforeholiday_tpu.testing import bert


def _cfg(**kw):
    base = dict(vocab_size=96, seq_len=128, d_model=64, n_heads=4, n_layers=2)
    base.update(kw)
    return bert.BertConfig(**base)


class TestBertModel:
    def test_shapes_and_finite(self):
        cfg = _cfg()
        params = bert.init(jax.random.PRNGKey(0), cfg)
        tokens, *_ = bert.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
        mlm, nsp = bert.forward(params, tokens, cfg)
        assert mlm.shape == (2, cfg.seq_len, cfg.vocab_size)
        assert nsp.shape == (2, 2)
        assert np.all(np.isfinite(np.asarray(mlm)))

    def test_flash_matches_unfused(self):
        """Bidirectional flash path == materialized scaled-masked softmax,
        including padded sequences."""
        cfg_f = _cfg(use_flash_attention=True, attention_impl="pallas")
        cfg_u = _cfg(use_flash_attention=False)
        params = bert.init(jax.random.PRNGKey(0), cfg_f)
        tokens, *_ = bert.synthetic_batch(jax.random.PRNGKey(1), cfg_f, 2)
        lens = jnp.array([100, 128])
        mlm_f, nsp_f = bert.forward(params, tokens, cfg_f, seq_lens=lens)
        mlm_u, nsp_u = bert.forward(params, tokens, cfg_u, seq_lens=lens)
        np.testing.assert_allclose(mlm_f, mlm_u, atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(nsp_f, nsp_u, atol=2e-4, rtol=2e-4)

    def test_pretrain_loss_grad_finite(self):
        cfg = _cfg()
        params = bert.init(jax.random.PRNGKey(0), cfg)
        batch = bert.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
        loss, grads = jax.value_and_grad(bert.pretrain_loss)(params, *batch, cfg)
        assert np.isfinite(float(loss))
        assert all(np.all(np.isfinite(np.asarray(g))) for g in jax.tree.leaves(grads))


class TestBertTensorParallel:
    @pytest.mark.parametrize("seq_par", [False, True])
    def test_tp2_loss_matches_unsharded(self, devices8, seq_par):
        cfg = _cfg(sequence_parallel=seq_par)
        params = bert.init(jax.random.PRNGKey(0), cfg)
        batch = bert.synthetic_batch(jax.random.PRNGKey(1), cfg, 4)
        loss_ref = float(bert.pretrain_loss(params, *batch, cfg))

        state = ps.initialize_model_parallel(
            tensor_model_parallel_size=2, devices=devices8
        )
        sharded = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(state.mesh, s)),
            params, bert.param_specs(cfg),
        )
        with jax.sharding.set_mesh(state.mesh):
            loss = float(
                jax.jit(lambda p, *b: bert.pretrain_loss(p, *b, cfg))(sharded, *batch)
            )
        np.testing.assert_allclose(loss, loss_ref, rtol=2e-5)


class TestBertLamb:
    def test_lamb_convergence_smoke(self):
        """10 FusedLAMB steps on a fixed batch must cut the MLM+NSP loss —
        the reference's run_bert_minimal_test 'loss goes down' contract."""
        cfg = _cfg(n_layers=2, d_model=64)
        params = bert.init(jax.random.PRNGKey(0), cfg)
        batch = bert.synthetic_batch(jax.random.PRNGKey(1), cfg, 8)
        opt = FusedLAMB(lr=5e-3, weight_decay=0.01, impl="jnp")
        state = opt.init(params)

        @jax.jit
        def step(p, s):
            loss, g = jax.value_and_grad(bert.pretrain_loss)(p, *batch, cfg)
            p, s = opt.step(p, g, s)
            return p, s, loss

        losses = []
        for _ in range(10):
            params, state, loss = step(params, state)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        # LAMB's trust ratio bounds the relative per-layer step to ~lr, so 10
        # steps move the loss steadily but not dramatically: require a strict
        # monotonic decrease with meaningful total progress
        assert all(b < a for a, b in zip(losses, losses[1:])), losses
        assert losses[0] - losses[-1] > 0.1, losses
