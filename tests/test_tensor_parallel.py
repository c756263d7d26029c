"""TP/SP semantics on an 8-device CPU mesh vs single-device dense math.

Ports of the reference's run_transformer tests: test_mapping.py (conjugate
fwd/bwd of every region function), test_layers.py (Column/Row/Vocab layers
match dense), test_cross_entropy.py (vocab-parallel CE vs full softmax-CE),
test_random.py (per-rank seeds), plus an end-to-end sequence-parallel MLP
block oracle.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.transformer import tensor_parallel as tp


def shard_map(f=None, **kw):
    kw.setdefault("check_vma", False)
    if f is None:
        return lambda g: jax.shard_map(g, **kw)
    return jax.shard_map(f, **kw)


@pytest.fixture
def tp_mesh(devices8):
    # pure TP mesh of 2; remaining devices unused to keep the math obvious
    return Mesh(np.asarray(devices8[:2]).reshape(2), ("tensor",))


def _shard_last(w, world, rank):
    return np.split(w, world, axis=-1)[rank]


class TestMappings:
    def test_copy_region_conjugate(self, tp_mesh):
        """id fwd / psum bwd."""
        x = jnp.asarray(np.random.RandomState(0).randn(4, 6), jnp.float32)

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=(P(), P()))
        def f(x):
            y = tp.copy_to_tensor_model_parallel_region(x, "tensor")
            g = jax.grad(lambda x_: jnp.sum(tp.copy_to_tensor_model_parallel_region(x_, "tensor") ** 2))(x)
            return y, g

        y, g = jax.jit(f)(x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x))
        # bwd psums identical cotangents over 2 ranks → 2 * 2x
        np.testing.assert_allclose(np.asarray(g), 2 * 2 * np.asarray(x), rtol=1e-6)

    def test_reduce_region_conjugate(self, tp_mesh):
        """psum fwd / id bwd."""

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=(P("tensor"), P()))
        def f(x):
            rank = jax.lax.axis_index("tensor")
            local = x * (rank + 1.0)
            y = tp.reduce_from_tensor_model_parallel_region(local, "tensor")
            g = jax.grad(
                lambda v: jnp.sum(tp.reduce_from_tensor_model_parallel_region(v, "tensor"))
            )(local)
            return y[None], g

        x = jnp.ones((3,), jnp.float32)
        y, g = jax.jit(f)(x)
        np.testing.assert_allclose(np.asarray(y)[0], 3.0)  # 1x + 2x
        np.testing.assert_allclose(np.asarray(g), 1.0)  # identity bwd

    def test_scatter_gather_last_dim_roundtrip(self, tp_mesh):
        x = jnp.asarray(np.arange(24).reshape(2, 12), jnp.float32)

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P())
        def f(x):
            local = tp.scatter_to_tensor_model_parallel_region(x, "tensor")
            assert local.shape == (2, 6)
            return tp.gather_from_tensor_model_parallel_region(local, "tensor")

        np.testing.assert_allclose(np.asarray(jax.jit(f)(x)), np.asarray(x))

    def test_sequence_parallel_roundtrip_and_grads(self, tp_mesh):
        x = jnp.asarray(np.random.RandomState(1).randn(8, 3, 4), jnp.float32)

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=(P(), P()))
        def f(x):
            local = tp.scatter_to_sequence_parallel_region(x, "tensor")
            assert local.shape == (4, 3, 4)
            full = tp.gather_from_sequence_parallel_region(x[:4] * 0 + local, "tensor", False)

            def loss(x_):
                l = tp.scatter_to_sequence_parallel_region(x_, "tensor")
                g = tp.gather_from_sequence_parallel_region(l, "tensor", False)
                return jnp.sum(g**2)

            return full, jax.grad(loss)(x)

        full, g = jax.jit(f)(x)
        np.testing.assert_allclose(np.asarray(full), np.asarray(x), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(x), rtol=1e-6)

    def test_reduce_scatter_sp_region(self, tp_mesh):
        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P("tensor"))
        def f(x):
            rank = jax.lax.axis_index("tensor")
            return tp.reduce_scatter_to_sequence_parallel_region(x * (rank + 1.0), "tensor")

        x = jnp.ones((4, 2), jnp.float32)
        out = np.asarray(jax.jit(f)(x))  # (4, 2) gathered back: each half = sum of inputs
        np.testing.assert_allclose(out, 3.0)  # 1+2


class TestLayers:
    def test_column_parallel_matches_dense(self, tp_mesh):
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(4, 8), jnp.float32)
        w = rng.randn(8, 12).astype(np.float32)
        b = rng.randn(12).astype(np.float32)

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P())
        def f(x):
            rank = jax.lax.axis_index("tensor")
            w_l = jax.lax.dynamic_slice_in_dim(jnp.asarray(w), rank * 6, 6, axis=1)
            b_l = jax.lax.dynamic_slice_in_dim(jnp.asarray(b), rank * 6, 6)
            return tp.column_parallel_linear(x, w_l, b_l, gather_output=True,
                                             axis_name="tensor")

        got = jax.jit(f)(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x) @ w + b, rtol=1e-5)

    def test_row_parallel_matches_dense(self, tp_mesh):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(4, 8), jnp.float32)
        w = rng.randn(8, 5).astype(np.float32)
        b = rng.randn(5).astype(np.float32)

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P())
        def f(x):
            rank = jax.lax.axis_index("tensor")
            x_l = jax.lax.dynamic_slice_in_dim(x, rank * 4, 4, axis=1)
            w_l = jax.lax.dynamic_slice_in_dim(jnp.asarray(w), rank * 4, 4, axis=0)
            return tp.row_parallel_linear(x_l, w_l, jnp.asarray(b),
                                          input_is_parallel=True, axis_name="tensor")

        got = jax.jit(f)(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x) @ w + b, rtol=1e-5)

    def test_column_then_row_grads_match_dense(self, tp_mesh):
        """The canonical Megatron MLP pattern: column → gelu → row, with grads."""
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(4, 8), jnp.float32)
        w1 = rng.randn(8, 16).astype(np.float32)
        w2 = rng.randn(16, 8).astype(np.float32)

        def dense_loss(params, x):
            h = jax.nn.gelu(x @ params["w1"])
            return jnp.sum((h @ params["w2"]) ** 2)

        dense_params = {"w1": jnp.asarray(w1), "w2": jnp.asarray(w2)}
        ref_loss, ref_g = jax.value_and_grad(dense_loss)(dense_params, x)

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=(P(), P("tensor"), P("tensor")))
        def f(x):
            rank = jax.lax.axis_index("tensor")
            w1_l = jax.lax.dynamic_slice_in_dim(jnp.asarray(w1), rank * 8, 8, axis=1)
            w2_l = jax.lax.dynamic_slice_in_dim(jnp.asarray(w2), rank * 8, 8, axis=0)

            def tp_loss(p, x):
                h = tp.column_parallel_linear(x, p["w1"], axis_name="tensor")
                h = jax.nn.gelu(h)
                y = tp.row_parallel_linear(h, p["w2"], axis_name="tensor")
                return jnp.sum(y**2)

            loss, g = jax.value_and_grad(tp_loss)({"w1": w1_l, "w2": w2_l}, x)
            return loss, g["w1"][None], g["w2"][None]

        loss, g1, g2 = jax.jit(f)(x)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        # reassemble sharded grads: w1 sharded on cols, w2 on rows
        g1_full = np.concatenate([np.asarray(g1)[0], np.asarray(g1)[1]], axis=-1)
        g2_full = np.concatenate([np.asarray(g2)[0], np.asarray(g2)[1]], axis=0)
        np.testing.assert_allclose(g1_full, np.asarray(ref_g["w1"]), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(g2_full, np.asarray(ref_g["w2"]), rtol=1e-4, atol=1e-4)

    def test_vocab_parallel_embedding_matches_dense(self, tp_mesh):
        rng = np.random.RandomState(5)
        table = rng.randn(16, 6).astype(np.float32)
        tokens = jnp.asarray(rng.randint(0, 16, size=(3, 5)), jnp.int32)

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P())
        def f(tokens):
            rank = jax.lax.axis_index("tensor")
            local = jax.lax.dynamic_slice_in_dim(jnp.asarray(table), rank * 8, 8, axis=0)
            return tp.vocab_parallel_embedding(tokens, local, vocab_size=16,
                                               axis_name="tensor")

        got = jax.jit(f)(tokens)
        np.testing.assert_allclose(np.asarray(got), table[np.asarray(tokens)], rtol=1e-6)

    def test_embedding_grads_scatter_to_owner(self, tp_mesh):
        table = np.ones((8, 4), np.float32)
        tokens = jnp.asarray([1, 6], jnp.int32)  # one token per shard

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P("tensor"))
        def f(tokens):
            rank = jax.lax.axis_index("tensor")
            local = jax.lax.dynamic_slice_in_dim(jnp.asarray(table), rank * 4, 4, axis=0)

            def loss(tbl):
                return jnp.sum(
                    tp.vocab_parallel_embedding(tokens, tbl, vocab_size=8, axis_name="tensor")
                )

            return jax.grad(loss)(local)

        g = np.asarray(jax.jit(f)(tokens))  # (8, 4): both shards stacked
        expect = np.zeros((8, 4))
        expect[1] = 1.0
        expect[6] = 1.0
        np.testing.assert_allclose(g, expect)


class TestVocabParallelCrossEntropy:
    def _dense_ce(self, logits, targets):
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return logz - tgt

    def test_matches_dense(self, tp_mesh):
        rng = np.random.RandomState(6)
        logits = rng.randn(4, 16).astype(np.float32) * 3
        targets = jnp.asarray(rng.randint(0, 16, size=(4,)), jnp.int32)

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P())
        def f(targets):
            rank = jax.lax.axis_index("tensor")
            local = jax.lax.dynamic_slice_in_dim(jnp.asarray(logits), rank * 8, 8, axis=1)
            return tp.vocab_parallel_cross_entropy(local, targets, 16)

        got = jax.jit(f)(targets)
        want = self._dense_ce(jnp.asarray(logits), targets)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)

    def test_grads_match_dense(self, tp_mesh):
        rng = np.random.RandomState(7)
        logits = rng.randn(4, 16).astype(np.float32)
        targets = jnp.asarray(rng.randint(0, 16, size=(4,)), jnp.int32)

        ref_g = jax.grad(
            lambda l: jnp.sum(self._dense_ce(l, targets))
        )(jnp.asarray(logits))

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P("tensor"))
        def f(targets):
            rank = jax.lax.axis_index("tensor")
            local = jax.lax.dynamic_slice_in_dim(jnp.asarray(logits), rank * 8, 8, axis=1)
            return jax.grad(
                lambda l: jnp.sum(tp.vocab_parallel_cross_entropy(l, targets, 16))
            )(local)

        g = np.asarray(jax.jit(f)(targets))  # (8, 8): shards stacked on dim0
        g_full = np.concatenate([g[:4], g[4:]], axis=1)
        np.testing.assert_allclose(g_full, np.asarray(ref_g), rtol=1e-4, atol=1e-6)

    def test_label_smoothing(self, tp_mesh):
        rng = np.random.RandomState(8)
        logits = rng.randn(4, 16).astype(np.float32)
        targets = jnp.asarray(rng.randint(0, 16, size=(4,)), jnp.int32)
        eps = 0.1

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P())
        def f(targets):
            rank = jax.lax.axis_index("tensor")
            local = jax.lax.dynamic_slice_in_dim(jnp.asarray(logits), rank * 8, 8, axis=1)
            return tp.vocab_parallel_cross_entropy(local, targets, 16, eps)

        got = np.asarray(jax.jit(f)(targets))
        lp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
        nll = -jnp.take_along_axis(lp, targets[..., None], -1)[..., 0]
        want = (1 - eps) * nll - eps * jnp.mean(lp, axis=-1)
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4)


class TestSequenceParallelBlock:
    def test_sp_mlp_block_matches_dense(self, tp_mesh):
        """SP end-to-end: sequence-sharded activations in/out of a column→row
        MLP equal the dense computation (the fusion of layers.py:293-306)."""
        rng = np.random.RandomState(9)
        x = jnp.asarray(rng.randn(8, 4, 8), jnp.float32)  # (seq, batch, hidden)
        w1 = rng.randn(8, 16).astype(np.float32)
        w2 = rng.randn(16, 8).astype(np.float32)

        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P())
        def f(x):
            rank = jax.lax.axis_index("tensor")
            w1_l = jax.lax.dynamic_slice_in_dim(jnp.asarray(w1), rank * 8, 8, axis=1)
            w2_l = jax.lax.dynamic_slice_in_dim(jnp.asarray(w2), rank * 8, 8, axis=0)
            xs = tp.scatter_to_sequence_parallel_region(x, "tensor")
            h = tp.column_parallel_linear(xs, w1_l, sequence_parallel=True,
                                          axis_name="tensor")
            h = jax.nn.gelu(h)
            ys = tp.row_parallel_linear(h, w2_l, sequence_parallel=True,
                                        axis_name="tensor")
            return tp.gather_from_sequence_parallel_region(ys, "tensor", False)

        got = jax.jit(f)(x)
        want = jax.nn.gelu(x @ jnp.asarray(w1)) @ jnp.asarray(w2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


class TestRandomAndMemory:
    def test_model_parallel_seed_differs_per_rank(self, tp_mesh):
        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P("tensor"))
        def f(key):
            k = tp.model_parallel_seed(key, "tensor")
            return jax.random.normal(k, (1, 4))

        out = np.asarray(jax.jit(f)(jax.random.PRNGKey(0)))
        assert not np.allclose(out[0], out[1])

    def test_checkpoint_grads_identical(self):
        def fn(x):
            return jnp.sum(jnp.tanh(x @ x.T))

        x = jnp.asarray(np.random.RandomState(10).randn(6, 6), jnp.float32)
        g0 = jax.grad(fn)(x)
        g1 = jax.grad(tp.checkpoint(fn))(x)
        # checkpoint's contract is "same math, re-rounded": the backward pass
        # recomputes tanh(x @ x.T) and XLA fuses the recomputed forward
        # differently from the saved-residual program, so a couple of
        # elements differ in the last ulps (seeded input above: max rel diff
        # 4.5e-6 ~ 2^-18 on the CPU backend). Pin just above the observed
        # artifact rather than at bitwise.
        np.testing.assert_allclose(
            np.asarray(g0), np.asarray(g1), rtol=2e-5, atol=1e-7
        )

    def test_broadcast_data_validates(self):
        data = {"x": jnp.ones((2,), jnp.int32)}
        out = tp.broadcast_data(["x"], data, jnp.int32)
        assert out["x"] is data["x"]
        with pytest.raises(KeyError):
            tp.broadcast_data(["y"], data)
        with pytest.raises(TypeError):
            tp.broadcast_data(["x"], data, jnp.float32)

    def test_broadcast_data_force_selects_rank0(self, tp_mesh):
        @functools.partial(shard_map, mesh=tp_mesh, in_specs=P(), out_specs=P())
        def f(x):
            rank = jax.lax.axis_index("tensor")
            local = {"x": x + rank.astype(x.dtype)}
            return tp.broadcast_data(["x"], local, axis_name="tensor", force=True)["x"]

        out = np.asarray(jax.jit(f)(jnp.zeros((3,), jnp.float32)))
        np.testing.assert_allclose(out, 0.0)  # rank 0's value everywhere
