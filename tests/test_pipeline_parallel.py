"""Pipeline-parallel schedules: the identical-losses-across-layouts oracle.

Port of the reference's key test
(tests/L0/run_transformer/test_pipeline_parallel_fwd_bwd.py:95-238): the same
model run as no-pipelining vs 1F1B (and with TP mixed in) must produce
identical losses and gradients. Plus microbatch-calculator unit tests
(test_microbatches.py) and p2p ring semantics (test_p2p_comm.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.transformer import pipeline_parallel as pp
from beforeholiday_tpu.transformer.pipeline_parallel import p2p_communication as p2p


def shard_map(f=None, **kw):
    kw.setdefault("check_vma", False)
    if f is None:
        return lambda g: jax.shard_map(g, **kw)
    return jax.shard_map(f, **kw)


# --- a toy homogeneous-stage model: each stage is one dense+gelu block ----------
# (the oracle needs stages with identical input/output shapes, the reference's
# fixed tensor_shape contract)

HIDDEN = 8
MICRO = 4  # microbatch rows


def stage_fn(stage_params, x):
    h = x @ stage_params["w"] + stage_params["b"]
    return jax.nn.gelu(h) + x  # residual keeps shapes stable


def loss_fn(y, tgt):
    return jnp.mean((y - tgt) ** 2)


def init_stages(key, n_stages):
    keys = jax.random.split(key, n_stages)
    return {
        "w": jnp.stack(
            [jax.random.normal(k, (HIDDEN, HIDDEN)) * 0.3 for k in keys]
        ),
        "b": jnp.zeros((n_stages, HIDDEN)),
    }


def sequential_reference(stacked, inputs, targets):
    """Ground truth: run all stages sequentially, mean loss over microbatches."""
    M = inputs.shape[0]

    def full_model(stacked, x):
        def body(h, sp):
            return stage_fn(sp, h), None

        h, _ = jax.lax.scan(body, x, stacked)
        return h

    def total_loss(stacked):
        losses = jax.vmap(lambda x, t: loss_fn(full_model(stacked, x), t))(
            inputs, targets
        )
        return jnp.mean(losses)

    return jax.value_and_grad(total_loss)(stacked)


@pytest.fixture
def data(devices8):
    rng = np.random.RandomState(0)
    M = 6
    inputs = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)
    targets = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)
    return inputs, targets


class TestSchedulesOracle:
    @pytest.mark.parametrize("n_stages", [2, 4])
    def test_1f1b_matches_sequential(self, devices8, data, n_stages):
        inputs, targets = data
        stacked = init_stages(jax.random.PRNGKey(1), n_stages)
        ref_loss, ref_grads = sequential_reference(stacked, inputs, targets)

        mesh = Mesh(np.asarray(devices8[:n_stages]), ("pipe",))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P("pipe"), P(), P()), out_specs=(P(), P("pipe")),
        )
        def run(stacked_local, inputs, targets):
            sp = jax.tree.map(lambda v: v[0], stacked_local)  # local stage slice
            loss, grads = pp.forward_backward_pipelining_without_interleaving(
                stage_fn, loss_fn, sp, inputs, targets
            )
            return loss, jax.tree.map(lambda g: g[None], grads)

        loss, grads = run(stacked, inputs, targets)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(grads[k]), np.asarray(ref_grads[k]), rtol=1e-4, atol=1e-5
            )

    def test_no_pipelining_matches_sequential(self, data):
        inputs, targets = data
        stacked = init_stages(jax.random.PRNGKey(2), 3)
        ref_loss, ref_grads = sequential_reference(stacked, inputs, targets)

        def full_model(stacked, x):
            def body(h, sp):
                return stage_fn(sp, h), None

            h, _ = jax.lax.scan(body, x, stacked)
            return h

        loss, grads = pp.forward_backward_no_pipelining(
            full_model, loss_fn, stacked, inputs, targets
        )
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(grads[k]), np.asarray(ref_grads[k]), rtol=1e-5, atol=1e-6
            )

    def test_dispatcher(self):
        f = pp.get_forward_backward_func(None, 1)
        assert f is pp.forward_backward_no_pipelining
        f = pp.get_forward_backward_func(None, 4)
        assert f is pp.forward_backward_pipelining_without_interleaving
        f = pp.get_forward_backward_func(2, 4)
        assert f is pp.forward_backward_pipelining_with_interleaving

    @pytest.mark.parametrize("n_stages,vpp", [(2, 2), (4, 2), (2, 3)])
    def test_interleaved_matches_sequential(self, devices8, data, n_stages, vpp):
        """The interleaved oracle: V chunks per device over S devices == the
        sequential S*V-stage model (ref: test_pipeline_parallel_fwd_bwd.py
        runs the interleaved schedule through the same identical-losses check)."""
        inputs, targets = data
        if inputs.shape[0] % n_stages:  # interleaving needs M % S == 0
            inputs = inputs[: (inputs.shape[0] // n_stages) * n_stages]
            targets = targets[: inputs.shape[0]]
        L = n_stages * vpp
        stacked = init_stages(jax.random.PRNGKey(4), L)
        ref_loss, ref_grads = sequential_reference(stacked, inputs, targets)

        # chunk placement: logical stage v*S + s -> device s, chunk v
        # (Megatron's interleaved layout). Reorder to (device, chunk, ...)
        perm = np.array([[v * n_stages + s for v in range(vpp)] for s in range(n_stages)])
        reordered = jax.tree.map(lambda leaf: leaf[perm.ravel()], stacked)

        mesh = Mesh(np.asarray(devices8[:n_stages]), ("pipe",))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P("pipe"), P(), P()), out_specs=(P(), P("pipe")),
        )
        def run(chunks_local, inputs, targets):
            # P("pipe") on the (S*V, ...) device-major stack leaves each device
            # its (V, ...) chunk slice directly
            loss, grads = pp.forward_backward_pipelining_with_interleaving(
                stage_fn, loss_fn, chunks_local, inputs, targets,
                virtual_pipeline_model_parallel_size=vpp,
            )
            return loss, grads

        loss, grads = run(reordered, inputs, targets)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        inv = np.argsort(perm.ravel())
        for k in ("w", "b"):
            got = np.asarray(grads[k])[inv]
            np.testing.assert_allclose(
                got, np.asarray(ref_grads[k]), rtol=1e-4, atol=1e-5
            )

    def test_act_store_is_m_independent_ring(self, devices8):
        """Activation memory is a 2*V*S ring, NOT (M, ...): a run with
        M >> ring depth must still match the sequential reference (slot reuse
        exercises the ring), and the depth formula is exact."""
        assert pp.activation_ring_depth(1, 2) == 4
        assert pp.activation_ring_depth(2, 4) == 16
        rng = np.random.RandomState(5)
        M = 32  # >> 2*S = 4
        inputs = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)
        targets = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)
        stacked = init_stages(jax.random.PRNGKey(6), 2)
        ref_loss, ref_grads = sequential_reference(stacked, inputs, targets)
        mesh = Mesh(np.asarray(devices8[:2]), ("pipe",))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P("pipe"), P(), P()), out_specs=(P(), P("pipe")),
        )
        def run(stacked_local, inputs, targets):
            sp = jax.tree.map(lambda v: v[0], stacked_local)
            loss, grads = pp.forward_backward_pipelining_without_interleaving(
                stage_fn, loss_fn, sp, inputs, targets
            )
            return loss, jax.tree.map(lambda g: g[None], grads)

        loss, grads = run(stacked, inputs, targets)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(grads["w"]), np.asarray(ref_grads["w"]), rtol=1e-4, atol=1e-5
        )

    def test_interleaved_requires_divisible_microbatches(self, devices8):
        mesh = Mesh(np.asarray(devices8[:2]), ("pipe",))
        stacked = init_stages(jax.random.PRNGKey(7), 4)
        perm = [0, 2, 1, 3]
        reordered = jax.tree.map(lambda leaf: leaf[np.array(perm)], stacked)
        inputs = jnp.zeros((3, MICRO, HIDDEN))  # 3 % 2 != 0
        targets = jnp.zeros((3, MICRO, HIDDEN))

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P("pipe"), P(), P()), out_specs=P(),
        )
        def run(chunks_local, inputs, targets):
            loss, _ = pp.forward_backward_pipelining_with_interleaving(
                stage_fn, loss_fn, chunks_local, inputs, targets,
                virtual_pipeline_model_parallel_size=2,
            )
            return loss

        with pytest.raises(ValueError, match="divisible"):
            run(reordered, inputs, targets)


class TestEmbedHeadDecoupling:
    """Per-stage shapes decoupled: int tokens -> embed -> hidden pipeline ->
    head -> logits -> CE (the reference folds these into first/last stage
    modules, schedules/common.py:30 build_model)."""

    VOCAB = 12

    def _setup(self, n_stages, M=4):
        rng = np.random.RandomState(8)
        key = jax.random.PRNGKey(9)
        stacked = init_stages(key, n_stages)
        embed_params = jnp.asarray(rng.randn(self.VOCAB, HIDDEN) * 0.3, jnp.float32)
        head_params = {
            "w": jnp.asarray(rng.randn(HIDDEN, self.VOCAB) * 0.3, jnp.float32),
            "b": jnp.zeros((self.VOCAB,), jnp.float32),
        }
        tokens = jnp.asarray(rng.randint(0, self.VOCAB, (M, MICRO)), jnp.int32)
        labels = jnp.asarray(rng.randint(0, self.VOCAB, (M, MICRO)), jnp.int32)
        return stacked, embed_params, head_params, tokens, labels

    @staticmethod
    def embed_fn(ep, toks):
        return ep[toks]

    @staticmethod
    def head_fn(hp, h):
        return h @ hp["w"] + hp["b"]

    @staticmethod
    def ce_loss(logits, labels):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))

    def _sequential(self, stacked, ep, hp, tokens, labels):
        def total(stacked, ep, hp):
            def one(toks, labs):
                h = self.embed_fn(ep, toks)

                def body(h, sp):
                    return stage_fn(sp, h), None

                h, _ = jax.lax.scan(body, h, stacked)
                return self.ce_loss(self.head_fn(hp, h), labs)

            return jnp.mean(jax.vmap(one)(tokens, labels))

        return jax.value_and_grad(total, argnums=(0, 1, 2))(stacked, ep, hp)

    def test_tokens_to_loss_matches_sequential(self, devices8):
        n_stages = 4
        stacked, ep, hp, tokens, labels = self._setup(n_stages)
        ref_loss, (ref_gs, ref_ge, ref_gh) = self._sequential(
            stacked, ep, hp, tokens, labels
        )
        mesh = Mesh(np.asarray(devices8[:n_stages]), ("pipe",))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P("pipe"), P(), P(), P(), P()),
            out_specs=(P(), P("pipe"), P(), P()),
        )
        def run(stacked_local, ep, hp, tokens, labels):
            sp = jax.tree.map(lambda v: v[0], stacked_local)
            loss, grads = pp.forward_backward_pipelining_without_interleaving(
                stage_fn, self.ce_loss, sp, tokens, labels,
                embed_fn=self.embed_fn, embed_params=ep,
                head_fn=self.head_fn, head_params=hp,
            )
            return (loss, jax.tree.map(lambda g: g[None], grads.stage),
                    grads.embed, grads.head)

        loss, gs, ge, gh = run(stacked, ep, hp, tokens, labels)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(ref_ge), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(gh["w"]), np.asarray(ref_gh["w"]), rtol=1e-4, atol=1e-5
        )
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(gs[k]), np.asarray(ref_gs[k]), rtol=1e-4, atol=1e-5
            )

    def test_interleaved_with_embed_head(self, devices8):
        S, V = 2, 2
        L = S * V
        stacked, ep, hp, tokens, labels = self._setup(L, M=4)
        ref_loss, (ref_gs, ref_ge, ref_gh) = self._sequential(
            stacked, ep, hp, tokens, labels
        )
        perm = np.array([[v * S + s for v in range(V)] for s in range(S)])
        reordered = jax.tree.map(lambda leaf: leaf[perm.ravel()], stacked)
        mesh = Mesh(np.asarray(devices8[:S]), ("pipe",))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P("pipe"), P(), P(), P(), P()),
            out_specs=(P(), P("pipe"), P(), P()),
        )
        def run(chunks_local, ep, hp, tokens, labels):
            loss, grads = pp.forward_backward_pipelining_with_interleaving(
                stage_fn, self.ce_loss, chunks_local, tokens, labels,
                virtual_pipeline_model_parallel_size=V,
                embed_fn=self.embed_fn, embed_params=ep,
                head_fn=self.head_fn, head_params=hp,
            )
            return loss, grads.stage, grads.embed, grads.head

        loss, gs, ge, gh = run(reordered, ep, hp, tokens, labels)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(ref_ge), rtol=1e-4, atol=1e-5)
        inv = np.argsort(perm.ravel())
        got_w = np.asarray(gs["w"])[inv]
        np.testing.assert_allclose(got_w, np.asarray(ref_gs["w"]), rtol=1e-4, atol=1e-5)

    def test_1f1b_with_tp_inside_stage(self, devices8, data):
        """(tp=2, pp=2): TP column/row linear inside each pipeline stage still
        matches the sequential dense reference — the reference oracle's
        mixed-layout case."""
        from beforeholiday_tpu.transformer import tensor_parallel as tp

        inputs, targets = data
        stacked = init_stages(jax.random.PRNGKey(3), 2)
        ref_loss, ref_grads = sequential_reference(stacked, inputs, targets)

        mesh = Mesh(np.asarray(devices8[:4]).reshape(2, 2), ("pipe", "tensor"))

        def tp_stage_fn(sp, x):
            # column-shard the dense: w local (H, H/2), gather output
            h = tp.column_parallel_linear(
                x, sp["w"], sp["b"], gather_output=True, axis_name="tensor"
            )
            return jax.nn.gelu(h) + x

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P("pipe"), P(), P()), out_specs=(P(), P("pipe", "tensor")),
        )
        def run(stacked_local, inputs, targets):
            tr = jax.lax.axis_index("tensor")
            sp = jax.tree.map(lambda v: v[0], stacked_local)
            half = HIDDEN // 2
            sp_local = {
                "w": jax.lax.dynamic_slice_in_dim(sp["w"], tr * half, half, axis=1),
                "b": jax.lax.dynamic_slice_in_dim(sp["b"], tr * half, half),
            }
            loss, grads = pp.forward_backward_pipelining_without_interleaving(
                tp_stage_fn, loss_fn, sp_local, inputs, targets
            )
            return loss, jax.tree.map(lambda g: g[None, None], grads)

        loss, grads = run(stacked, inputs, targets)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        # grads come back stacked (pipe, tensor, ...): reassemble the col shards
        gw = np.asarray(grads["w"])  # (2, 2, H, H/2)
        gw_full = np.concatenate([gw[:, 0], gw[:, 1]], axis=-1)
        np.testing.assert_allclose(
            gw_full, np.asarray(ref_grads["w"]), rtol=1e-4, atol=1e-5
        )


class TestMicrobatchCalculators:
    def test_constant(self):
        c = pp.build_num_microbatches_calculator(64, 4, 2)
        assert c.get() == 8
        assert c.get_current_global_batch_size() == 64
        c.update(10_000, True)
        assert c.get() == 8

    def test_constant_indivisible_raises(self):
        with pytest.raises(ValueError, match="divisible"):
            pp.build_num_microbatches_calculator(65, 4, 2)

    def test_rampup(self):
        c = pp.build_num_microbatches_calculator(64, 4, 2, rampup_batch_size=[16, 8, 600])
        assert c.get_current_global_batch_size() == 16
        assert c.get() == 2
        c.update(300, True)  # halfway: 16 + 3*8 = 40
        assert c.get_current_global_batch_size() == 40
        c.update(600, True)
        assert c.get_current_global_batch_size() == 64
        c.update(10_000, True)
        assert c.get_current_global_batch_size() == 64
        assert c.get() == 8

    def test_rampup_validation(self):
        with pytest.raises(ValueError, match="rampup_batch_size"):
            pp.build_num_microbatches_calculator(64, 4, 2, rampup_batch_size=[16, 8])


class TestP2P:
    def test_forward_ring(self, devices8):
        mesh = Mesh(np.asarray(devices8[:4]), ("pipe",))

        @functools.partial(shard_map, mesh=mesh, in_specs=P("pipe"), out_specs=P("pipe"))
        def f(x):
            return p2p.send_forward_recv_forward(x, axis_name="pipe")

        out = np.asarray(jax.jit(f)(jnp.arange(4, dtype=jnp.float32)))
        np.testing.assert_allclose(out, [3, 0, 1, 2])  # each got prev stage's value

    def test_backward_ring(self, devices8):
        mesh = Mesh(np.asarray(devices8[:4]), ("pipe",))

        @functools.partial(shard_map, mesh=mesh, in_specs=P("pipe"), out_specs=P("pipe"))
        def f(x):
            return p2p.send_backward_recv_backward(x, axis_name="pipe")

        out = np.asarray(jax.jit(f)(jnp.arange(4, dtype=jnp.float32)))
        np.testing.assert_allclose(out, [1, 2, 3, 0])  # each got next stage's value

    def test_steady_state_pair(self, devices8):
        mesh = Mesh(np.asarray(devices8[:4]), ("pipe",))

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P("pipe"), P("pipe")),
            out_specs=(P("pipe"), P("pipe")),
        )
        def f(y, dy):
            return p2p.send_forward_recv_backward(y, dy, axis_name="pipe")

        y, dy = jax.jit(f)(jnp.arange(4.0), jnp.arange(4.0) * 10)
        np.testing.assert_allclose(np.asarray(y), [3, 0, 1, 2])
        np.testing.assert_allclose(np.asarray(dy), [10, 20, 30, 0])


# --- encoder-decoder (T5-style) schedule: loss/grad identity oracle -------------
# (ref: ModelType.encoder_and_decoder, schedules/common.py:83,312)


def t5_stage_fn(sp, h, mem, is_decoder):
    """Toy enc/dec stage: shared trunk + a cross-attention-ish term gated by
    is_decoder (a traced 0/1 scalar, differentiable where used)."""
    base = jax.nn.gelu(h @ sp["w"] + sp["b"]) + h
    cross = jnp.tanh(mem @ sp["wm"])
    return base + is_decoder * cross


def t5_init_stages(key, n_stages):
    ks = jax.random.split(key, 2)
    return {
        "w": jnp.stack([jax.random.normal(k, (HIDDEN, HIDDEN)) * 0.3
                        for k in jax.random.split(ks[0], n_stages)]),
        "b": jnp.zeros((n_stages, HIDDEN)),
        "wm": jnp.stack([jax.random.normal(k, (HIDDEN, HIDDEN)) * 0.3
                         for k in jax.random.split(ks[1], n_stages)]),
    }


def t5_embed(ep, raw):
    return raw @ ep["we"]


def t5_head(hp, h):
    return h @ hp["wh"]


def t5_sequential_reference(stacked, ee, de, hp, enc_in, dec_in, targets, split):
    """Ground truth: encoder stages then decoder stages, one device."""
    M = enc_in.shape[0]

    def one(stacked, ee, de, hp, e_x, d_x, tgt):
        h = t5_embed(ee, e_x)
        for s in range(split):
            sp = jax.tree.map(lambda v: v[s], stacked)
            h = t5_stage_fn(sp, h, jnp.zeros_like(h), 0.0)
        mem = h
        h = t5_embed(de, d_x)
        for s in range(split, stacked["w"].shape[0]):
            sp = jax.tree.map(lambda v: v[s], stacked)
            h = t5_stage_fn(sp, h, mem, 1.0)
        return loss_fn(t5_head(hp, h), tgt)

    def total(stacked, ee, de, hp):
        losses = jax.vmap(
            lambda e, d, t: one(stacked, ee, de, hp, e, d, t)
        )(enc_in, dec_in, targets)
        return jnp.mean(losses)

    return jax.value_and_grad(total, argnums=(0, 1, 2, 3))(stacked, ee, de, hp)


class TestEncoderDecoderSchedule:
    @pytest.mark.parametrize("split", [1, 2, 3])
    def test_t5_1f1b_matches_sequential(self, devices8, split):
        S = 4
        M = 6
        rng = np.random.RandomState(0)
        stacked = t5_init_stages(jax.random.PRNGKey(1), S)
        ee = {"we": jnp.asarray(rng.randn(HIDDEN, HIDDEN) * 0.3, jnp.float32)}
        de = {"we": jnp.asarray(rng.randn(HIDDEN, HIDDEN) * 0.3, jnp.float32)}
        hp = {"wh": jnp.asarray(rng.randn(HIDDEN, HIDDEN) * 0.3, jnp.float32)}
        enc_in = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)
        dec_in = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)
        targets = jnp.asarray(rng.randn(M, MICRO, HIDDEN), jnp.float32)

        ref_loss, (ref_gs, ref_gee, ref_gde, ref_ghp) = t5_sequential_reference(
            stacked, ee, de, hp, enc_in, dec_in, targets, split
        )

        mesh = Mesh(np.asarray(devices8[:S]), ("pipe",))

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P("pipe"), P(), P(), P(), P(), P(), P()),
            out_specs=(P(), (P("pipe"), P(), P(), P())),
        )
        def run(stacked_local, ee, de, hp, enc_in, dec_in, targets):
            sp = jax.tree.map(lambda v: v[0], stacked_local)
            loss, grads = pp.forward_backward_pipelining_encoder_decoder(
                t5_stage_fn, loss_fn, sp, enc_in, dec_in, targets,
                split_rank=split,
                enc_embed_fn=t5_embed, enc_embed_params=ee,
                dec_embed_fn=t5_embed, dec_embed_params=de,
                head_fn=t5_head, head_params=hp,
            )
            return loss, (
                jax.tree.map(lambda g: g[None], grads.stage),
                grads.enc_embed, grads.dec_embed, grads.head,
            )

        loss, (gs, gee, gde, ghp) = run(
            stacked, ee, de, hp, enc_in, dec_in, targets
        )
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        for k in ("w", "b", "wm"):
            np.testing.assert_allclose(
                np.asarray(gs[k]), np.asarray(ref_gs[k]), rtol=1e-4, atol=1e-5
            )
        np.testing.assert_allclose(
            np.asarray(gee["we"]), np.asarray(ref_gee["we"]), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(gde["we"]), np.asarray(ref_gde["we"]), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(ghp["wh"]), np.asarray(ref_ghp["wh"]), rtol=1e-4, atol=1e-5
        )

    def test_requires_split_rank(self, devices8):
        mesh = Mesh(np.asarray(devices8[:2]), ("pipe",))

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
        )
        def run(x):
            loss, _ = pp.forward_backward_pipelining_encoder_decoder(
                t5_stage_fn, loss_fn, {}, x, x, x,
            )
            return loss

        with pytest.raises(ValueError, match="split_rank"):
            run(jnp.zeros((2, MICRO, HIDDEN)))
