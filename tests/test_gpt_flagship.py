"""Flagship GPT: flash-attention path parity and sequence-parallel identity.

The reference's oracle for "parallelism/fusion preserves semantics" is the
identical-losses check (test_pipeline_parallel_fwd_bwd.py and the contrib
attention tests); these are the same checks on the TPU flagship.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from beforeholiday_tpu.parallel import parallel_state as ps
from beforeholiday_tpu.testing import gpt


def _cfg(**kw):
    base = dict(vocab_size=128, seq_len=128, d_model=64, n_heads=4, n_layers=2)
    base.update(kw)
    return gpt.GPTConfig(**base)


class TestFlashPath:
    def test_flash_matches_unfused(self):
        """Pallas flash attention (interpret on CPU) == materialized-scores
        softmax path, forward and gradients."""
        cfg_flash = _cfg(use_flash_attention=True, attention_impl="pallas")
        cfg_plain = _cfg(use_flash_attention=False)
        params = gpt.init(jax.random.PRNGKey(0), cfg_flash)
        tokens, targets = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg_flash, batch=2)

        loss_f, g_f = jax.value_and_grad(gpt.loss_fn)(params, tokens, targets, cfg_flash)
        loss_p, g_p = jax.value_and_grad(gpt.loss_fn)(params, tokens, targets, cfg_plain)
        np.testing.assert_allclose(float(loss_f), float(loss_p), rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4),
            g_f, g_p,
        )

    def test_flash_default_dispatch_runs(self):
        """impl=None resolves by the repo dispatch policy and still runs."""
        cfg = _cfg()
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        tokens, _ = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, batch=2)
        logits = gpt.forward(params, tokens, cfg)
        assert logits.shape == (2, cfg.seq_len, cfg.vocab_size)
        assert np.all(np.isfinite(np.asarray(logits)))


class TestSequenceParallel:
    @pytest.mark.parametrize("seq_par", [False, True])
    def test_tp2_loss_matches_unsharded(self, devices8, seq_par):
        """TP=2 (+ SP on/off) loss and grads == single-device dense run
        (ref: layers.py:293-306 — SP must be semantics-preserving)."""
        cfg = _cfg(sequence_parallel=seq_par)
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, batch=4)

        loss_ref, g_ref = jax.value_and_grad(gpt.loss_fn)(params, tokens, targets, cfg)

        state = ps.initialize_model_parallel(
            tensor_model_parallel_size=2, pipeline_model_parallel_size=1,
            devices=devices8,
        )
        mesh = state.mesh
        specs = gpt.param_specs(cfg)
        sharded = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
        )
        batch_sh = NamedSharding(mesh, P(ps.DATA_AXIS, None))
        with jax.sharding.set_mesh(mesh):
            loss, grads = jax.jit(
                jax.value_and_grad(lambda p, t, y: gpt.loss_fn(p, t, y, cfg))
            )(sharded, jax.device_put(tokens, batch_sh), jax.device_put(targets, batch_sh))
        np.testing.assert_allclose(float(loss), float(loss_ref), rtol=2e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=2e-3
            ),
            grads, g_ref,
        )

    def test_sp_constraint_reaches_residual(self):
        """The lowered TP=2+SP program shards the residual stream along
        sequence: its HLO must contain a reduce-scatter or dynamic-slice on
        the sequence dim (i.e. the knob is not dead)."""
        cfg = _cfg(sequence_parallel=True)
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        tokens, _ = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, batch=4)
        state = ps.initialize_model_parallel(
            tensor_model_parallel_size=2, pipeline_model_parallel_size=1,
        )
        specs = gpt.param_specs(cfg)
        sharded = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(state.mesh, s)), params, specs
        )
        with jax.sharding.set_mesh(state.mesh):
            lowered = jax.jit(
                lambda p, t: gpt.forward(p, t, cfg)
            ).lower(sharded, tokens)
            hlo = lowered.compile().as_text()
        assert ("reduce-scatter" in hlo) or ("collective-permute" in hlo) or (
            "all-gather" in hlo
        ), "SP produced no sequence collectives — knob appears dead"
