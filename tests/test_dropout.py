"""Dropout: oracle parity, TP-rank-distinct masks, recompute-stable masks.

The reference's RNG tracker exists to give dropout exactly these properties
(ref: apex/transformer/tensor_parallel/random.py:124-199 — fork per TP rank,
restore across checkpoint recompute); these tests pin them for the TPU port.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.ops import flash_attention
from beforeholiday_tpu.transformer.tensor_parallel.random import (
    dropout,
    model_parallel_seed,
)


def _smap(f, **kw):
    kw["check_vma"] = False
    return jax.shard_map(f, **kw)


class TestDropoutPrimitive:
    def test_identity_when_deterministic(self):
        x = jnp.ones((8, 16))
        np.testing.assert_array_equal(
            np.asarray(dropout(jax.random.PRNGKey(0), x, 0.5, deterministic=True)),
            np.asarray(x),
        )
        np.testing.assert_array_equal(
            np.asarray(dropout(jax.random.PRNGKey(0), x, 0.0)), np.asarray(x)
        )

    def test_inverted_scaling_and_rate(self):
        x = jnp.ones((64, 256))
        y = np.asarray(dropout(jax.random.PRNGKey(1), x, 0.25))
        kept = y != 0.0
        # survivors scaled by 1/(1-p); drop fraction near p
        np.testing.assert_allclose(y[kept], 1.0 / 0.75, rtol=1e-6)
        assert abs(1.0 - kept.mean() - 0.25) < 0.02
        # unbiased in expectation
        assert abs(y.mean() - 1.0) < 0.02

    def test_same_key_same_mask(self):
        x = jnp.ones((32, 32))
        a = dropout(jax.random.PRNGKey(7), x, 0.5)
        b = dropout(jax.random.PRNGKey(7), x, 0.5)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rejects_bad_rate(self):
        with pytest.raises(ValueError):
            dropout(jax.random.PRNGKey(0), jnp.ones((4,)), 1.0)


class TestTPDistinctMasks:
    def test_tp_ranks_draw_distinct_masks(self, devices8):
        """tp_distinct=True folds the TP rank into the key — each shard of a
        TP region drops different elements (the tracker's model-parallel-rng
        state, ref: random.py:204-234)."""
        mesh = Mesh(np.asarray(devices8[:4]), ("tensor",))
        x = jnp.ones((4, 128))

        @functools.partial(
            _smap, mesh=mesh, in_specs=P("tensor"), out_specs=P("tensor"),
        )
        def f(x_local):
            return dropout(jax.random.PRNGKey(3), x_local, 0.5, tp_distinct=True)

        out = np.asarray(f(x))  # (4, 128): row r = rank r's mask over ones
        masks = out != 0.0
        for a in range(4):
            for b in range(a + 1, 4):
                assert (masks[a] != masks[b]).any(), f"ranks {a},{b} drew identical masks"

    def test_without_tp_distinct_masks_identical(self, devices8):
        mesh = Mesh(np.asarray(devices8[:4]), ("tensor",))
        x = jnp.ones((4, 128))

        @functools.partial(
            _smap, mesh=mesh, in_specs=P("tensor"), out_specs=P("tensor"),
        )
        def f(x_local):
            return dropout(jax.random.PRNGKey(3), x_local, 0.5)

        out = np.asarray(f(x))
        for r in range(1, 4):
            np.testing.assert_array_equal(out[0], out[r])

    def test_model_parallel_seed_distinct(self, devices8):
        mesh = Mesh(np.asarray(devices8[:4]), ("tensor",))

        @functools.partial(
            _smap, mesh=mesh, in_specs=(), out_specs=P("tensor"),
        )
        def f():
            return model_parallel_seed(jax.random.PRNGKey(0))[None]

        keys = np.asarray(jax.random.key_data(f()))
        assert len({tuple(k) for k in keys}) == 4


class TestRecomputeStable:
    def test_checkpoint_recompute_same_mask(self):
        """jax.checkpoint replays the dropout in the backward; gradients must
        match the non-checkpointed version bit-for-bit — the property the
        reference's CheckpointFunction RNG save/restore enforces
        (ref: random.py:237-311)."""
        key = jax.random.PRNGKey(11)
        w = jnp.linspace(0.5, 1.5, 64).reshape(8, 8)
        x = jnp.ones((4, 8))

        def f(w, x):
            h = x @ w
            h = dropout(key, h, 0.5)
            return jnp.sum(jnp.tanh(h) ** 2)

        g_plain = jax.grad(f)(w, x)
        g_remat = jax.grad(jax.checkpoint(f))(w, x)
        np.testing.assert_array_equal(np.asarray(g_plain), np.asarray(g_remat))


class TestAttentionDropout:
    def test_flash_api_dropout_matches_manual_oracle(self):
        """flash_attention(dropout_rate=..) == softmax -> mask -> @v computed
        by hand with the same key (torch's ordering)."""
        B, H, S, D = 2, 2, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v = (jax.random.normal(kk, (B, H, S, D)) for kk in ks[:3])
        dkey = ks[3]
        rate = 0.3
        out = flash_attention(
            q, k, v, causal=True, dropout_rate=rate, dropout_key=dkey, impl="jnp"
        )

        # manual oracle with the identical key/shape draw
        scale = 1.0 / np.sqrt(D)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).reshape(B * H, S, S) * scale
        mask = jnp.triu(jnp.ones((S, S), bool), 1)
        s = jnp.where(mask, -1e30, s)
        p = jax.nn.softmax(s, axis=-1)
        keep = jax.random.bernoulli(dkey, 1.0 - rate, p.shape)
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
        want = jnp.einsum("bqk,bkd->bqd", p, v.reshape(B * H, S, D)).reshape(B, H, S, D)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_dropout_needs_key(self):
        q = jnp.ones((1, 1, 8, 8))
        with pytest.raises(ValueError, match="dropout_key"):
            flash_attention(q, q, q, dropout_rate=0.1)

    def test_forced_pallas_with_dropout_errors_off_tpu(self):
        """In-kernel dropout exists now (r5) but needs the hardware PRNG —
        forcing the kernel in interpret mode (CPU tests) must still error
        rather than silently swap paths. On-chip numerics:
        testing/tpu_checks.py."""
        q = jnp.ones((1, 1, 128, 64), jnp.float32)
        with pytest.raises(ValueError, match="real TPU"):
            flash_attention(
                q, q, q, dropout_rate=0.1,
                dropout_key=jax.random.PRNGKey(0), impl="pallas",
            )

    def test_zero_rate_ignores_key(self):
        B, H, S, D = 1, 2, 32, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (jax.random.normal(kk, (B, H, S, D)) for kk in ks)
        a = flash_attention(q, k, v, causal=True, impl="jnp")
        b = flash_attention(
            q, k, v, causal=True, dropout_rate=0.0,
            dropout_key=jax.random.PRNGKey(9), impl="jnp",
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestModelDropout:
    def test_gpt_dropout_changes_logits_and_is_deterministic(self):
        from beforeholiday_tpu.testing import gpt

        cfg = gpt.GPTConfig(vocab_size=64, seq_len=32, d_model=32, n_heads=2,
                            n_layers=2, dropout_rate=0.2, attention_dropout=0.1)
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        tokens, _ = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
        eval_logits = gpt.forward(params, tokens, cfg)
        k = jax.random.PRNGKey(2)
        train_a = gpt.forward(params, tokens, cfg, dropout_key=k)
        train_b = gpt.forward(params, tokens, cfg, dropout_key=k)
        train_c = gpt.forward(params, tokens, cfg, dropout_key=jax.random.PRNGKey(3))
        np.testing.assert_array_equal(np.asarray(train_a), np.asarray(train_b))
        assert not np.allclose(np.asarray(train_a), np.asarray(eval_logits))
        assert not np.allclose(np.asarray(train_a), np.asarray(train_c))

    def test_bert_dropout_changes_logits_and_is_deterministic(self):
        from beforeholiday_tpu.testing import bert

        cfg = bert.BertConfig(vocab_size=64, seq_len=32, d_model=32, n_heads=2,
                              n_layers=2, dropout_rate=0.2, attention_dropout=0.1)
        params = bert.init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
        mlm_eval, _ = bert.forward(params, tokens, cfg)
        k = jax.random.PRNGKey(2)
        mlm_a, _ = bert.forward(params, tokens, cfg, dropout_key=k)
        mlm_b, _ = bert.forward(params, tokens, cfg, dropout_key=k)
        np.testing.assert_array_equal(np.asarray(mlm_a), np.asarray(mlm_b))
        assert not np.allclose(np.asarray(mlm_a), np.asarray(mlm_eval))

    def test_mha_dropout_smoke(self):
        from beforeholiday_tpu.contrib import multihead_attn as mha

        p = mha.init_self_multihead_attn(jax.random.PRNGKey(0), 32, bias=True)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
        a = mha.self_multihead_attn(p, x, 4, causal=True)
        b = mha.self_multihead_attn(
            p, x, 4, causal=True, dropout_rate=0.3,
            dropout_key=jax.random.PRNGKey(2), impl="jnp",
        )
        assert a.shape == b.shape
        assert not np.allclose(np.asarray(a), np.asarray(b))
