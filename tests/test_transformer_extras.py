"""GradScaler found-inf reduction, FusedScaleMaskSoftmax dispatch, SP layer
norms, virtual-PP / split-rank parallel_state semantics.

Ports: apex/transformer/amp/grad_scaler.py:51 (found-inf over tp+pp),
fused_softmax.py:164-274 (kernel availability + fallback parity),
layers/layer_norm.py:26-99 (SP param-grad allreduce),
parallel_state.py:446-560 (virtual and split-rank predicates).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.parallel import parallel_state as ps
from beforeholiday_tpu.transformer import (
    AttnMaskType,
    GradScaler,
    reduce_found_inf,
)
from beforeholiday_tpu.transformer.functional import FusedScaleMaskSoftmax
from beforeholiday_tpu.transformer.layers import sp_fused_layer_norm


def shard_map(f=None, **kw):
    kw.setdefault("check_vma", False)
    if f is None:
        return lambda g: jax.shard_map(g, **kw)
    return jax.shard_map(f, **kw)


class TestGradScaler:
    def test_found_inf_spreads_across_model_axes(self, devices8):
        mesh = Mesh(np.asarray(devices8[:4]).reshape(2, 2), ("pipe", "tensor"))

        @functools.partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(("pipe", "tensor")))
        def f(_):
            # only (pipe=0, tensor=1) sees a local overflow
            local = (jax.lax.axis_index("pipe") == 0) & (jax.lax.axis_index("tensor") == 1)
            return reduce_found_inf(local)[None]

        out = np.asarray(jax.jit(f)(jnp.zeros(())))
        assert out.all()  # every rank skips

    def test_grad_scaler_unscale_reduces(self, devices8):
        mesh = Mesh(np.asarray(devices8[:4]).reshape(2, 2), ("pipe", "tensor"))
        scaler = GradScaler()

        @functools.partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(("pipe", "tensor")))
        def f(_):
            state = scaler.init()
            bad = jnp.where(
                (jax.lax.axis_index("pipe") == 1) & (jax.lax.axis_index("tensor") == 0),
                jnp.inf,
                1.0,
            )
            grads = {"g": jnp.full((1024,), bad)}
            _, found = scaler.unscale(grads, state, impl="jnp")
            return found[None]

        out = np.asarray(jax.jit(f)(jnp.zeros(())))
        assert out.all()


class TestFusedScaleMaskSoftmax:
    def test_causal_kernel_path_matches_fallback(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 2, 128, 128), jnp.bfloat16)
        fused = FusedScaleMaskSoftmax(
            input_in_bf16=True, attn_mask_type=AttnMaskType.causal, scale=0.5
        )
        eager = FusedScaleMaskSoftmax(
            input_in_bf16=True, attn_mask_type=AttnMaskType.causal,
            scaled_masked_softmax_fusion=False, scale=0.5,
        )
        assert fused.is_kernel_available(None, 2, 2, 128, 128)
        np.testing.assert_allclose(
            np.asarray(fused(x), np.float32), np.asarray(eager(x), np.float32),
            atol=2e-2,
        )

    def test_ragged_causal_falls_back(self):
        fused = FusedScaleMaskSoftmax(input_in_fp16=True, attn_mask_type=AttnMaskType.causal)
        assert not fused.is_kernel_available(None, 2, 2, 96, 96)
        x = jnp.asarray(np.random.RandomState(1).randn(1, 1, 96, 96), jnp.float16)
        out = fused(x)  # dispatches to fallback without error
        assert out.shape == x.shape
        # rows sum to 1
        np.testing.assert_allclose(np.asarray(out.sum(-1), np.float32), 1.0, rtol=1e-2)

    def test_padding_mask_path(self):
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(2, 3, 8, 16), jnp.float16)
        mask = jnp.asarray(rng.rand(2, 1, 8, 16) > 0.5, jnp.int8)
        m = FusedScaleMaskSoftmax(input_in_fp16=True)
        out = np.asarray(m(x, mask), np.float32)
        # masked entries ~0
        masked = np.broadcast_to(np.asarray(mask, bool), out.shape)
        assert out[masked].max() < 1e-3

    def test_fp32_input_goes_eager(self):
        m = FusedScaleMaskSoftmax()
        assert not m.is_kernel_available(None, 1, 1, 128, 128)

    def test_conflicting_dtypes_raise(self):
        with pytest.raises(RuntimeError, match="both fp16 and bf16"):
            FusedScaleMaskSoftmax(input_in_fp16=True, input_in_bf16=True)
        with pytest.raises(RuntimeError, match="fp32 when scaled"):
            FusedScaleMaskSoftmax(softmax_in_fp32=False, scale=2.0)


class TestSPLayerNorm:
    def test_sp_param_grads_are_tp_reduced(self, devices8):
        """Under SP each rank norms its sequence shard; dgamma/dbeta must sum
        across TP to equal the full-sequence grads."""
        mesh = Mesh(np.asarray(devices8[:2]), ("tensor",))
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(8, 4, 16), jnp.float32)  # (seq, b, h)
        scale = jnp.asarray(rng.randn(16), jnp.float32)
        bias = jnp.asarray(rng.randn(16), jnp.float32)

        def full_loss(sb):
            return jnp.sum(sp_fused_layer_norm(x, sb["s"], sb["b"]) ** 2)

        ref = jax.grad(full_loss)({"s": scale, "b": bias})

        @functools.partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P())
        def f(_):
            rank = jax.lax.axis_index("tensor")
            xs = jax.lax.dynamic_slice_in_dim(x, rank * 4, 4, axis=0)

            def loss(sb):
                y = sp_fused_layer_norm(
                    xs, sb["s"], sb["b"], sequence_parallel=True, axis_name="tensor"
                )
                # local sum; param grads must come back globally correct
                return jnp.sum(y**2)

            return jax.grad(loss)({"s": scale, "b": bias})

        g = jax.jit(f)(jnp.zeros(()))
        np.testing.assert_allclose(np.asarray(g["s"]), np.asarray(ref["s"]), rtol=1e-4)
        np.testing.assert_allclose(np.asarray(g["b"]), np.asarray(ref["b"]), rtol=1e-4)


class TestParallelStateDepth:
    def test_virtual_rank_gates_first_last(self, devices8):
        ps.initialize_model_parallel(
            pipeline_model_parallel_size=2,
            virtual_pipeline_model_parallel_size=2,
            devices=devices8,
        )
        try:
            ps.set_virtual_pipeline_model_parallel_rank(0)
            # pipe rank is traced 0 outside shard_map (world>1 warns) — here we
            # only exercise the virtual gating logic
            assert ps.is_pipeline_first_stage() == (ps.get_pipeline_model_parallel_rank() == 0)
            ps.set_virtual_pipeline_model_parallel_rank(1)
            assert ps.is_pipeline_first_stage() is False
            assert ps.is_pipeline_first_stage(ignore_virtual=True) in (True, np.True_)
            # last stage requires last virtual chunk
            ps.set_virtual_pipeline_model_parallel_rank(0)
            assert ps.is_pipeline_last_stage() is False
        finally:
            ps.destroy_model_parallel()
        assert ps.get_virtual_pipeline_model_parallel_rank() is None

    def test_split_rank_predicates(self, devices8):
        ps.initialize_model_parallel(
            pipeline_model_parallel_size=4,
            pipeline_model_parallel_split_rank=2,
            devices=devices8[:4],
        )
        try:
            # outside shard_map the pipe rank resolves to 0 (with a warning)
            import warnings

            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                assert ps.is_pipeline_stage_before_split()
                assert not ps.is_pipeline_stage_after_split()
                assert ps.is_pipeline_stage_before_split(rank=1)
                assert ps.is_pipeline_stage_after_split(rank=2)
                assert ps.is_pipeline_stage_after_split(rank=3)
        finally:
            ps.destroy_model_parallel()

    def test_no_split_is_trivially_true(self, devices8):
        ps.initialize_model_parallel(devices=devices8[:1])
        try:
            assert ps.is_pipeline_stage_before_split()
            assert ps.is_pipeline_stage_after_split()
        finally:
            ps.destroy_model_parallel()


class TestRankLogging:
    def test_layout_in_log_records(self, devices8, capsys):
        from beforeholiday_tpu.utils.logging import get_logger

        ps.initialize_model_parallel(
            tensor_model_parallel_size=2, devices=devices8
        )
        try:
            logger = get_logger("beforeholiday_tpu.test_rank")
            logger.warning("hello")
            err = capsys.readouterr().err
            assert "tp2" in err and "dp4" in err and "pp1" in err
        finally:
            ps.destroy_model_parallel()
