"""Ring attention over the context axis: 8-way sequence sharding must be
semantics-preserving vs full attention (the identical-losses oracle style)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.transformer.context_parallel import ring_attention


def _smap(f, **kw):
    kw["check_vma"] = False
    return jax.shard_map(f, **kw)


def _full_attn(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        S = q.shape[2]
        mask = jnp.arange(S)[None, :] > jnp.arange(S)[:, None]
        s = jnp.where(mask, -1e30, s)
        e = jnp.where(mask, 0.0, jnp.exp(s - jnp.max(s, -1, keepdims=True)))
    else:
        e = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    p = e / jnp.sum(e, -1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _run_ring(mesh, q, k, v, causal, scale, impl=None):
    f = jax.jit(_smap(
        functools.partial(ring_attention, causal=causal, scale=scale,
                          axis_name="context", impl=impl),
        mesh=mesh,
        in_specs=(P(None, None, "context"),) * 3,
        out_specs=P(None, None, "context"),
    ))
    return f(q, k, v)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, devices8, causal):
        mesh = Mesh(np.asarray(devices8), ("context",))
        B, H, S, D = 2, 2, 64, 16  # S sharded 8-way -> S_local 8
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (B, H, S, D)) for kk in ks)
        got = _run_ring(mesh, q, k, v, causal, 0.25)
        want = _full_attn(q, k, v, causal, 0.25)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_full_attention(self, devices8, causal):
        """The ppermute-transposed backward == autodiff through full attn."""
        mesh = Mesh(np.asarray(devices8), ("context",))
        B, H, S, D = 1, 2, 32, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        q, k, v = (jax.random.normal(kk, (B, H, S, D)) for kk in ks[:3])
        w = jax.random.normal(ks[3], q.shape)

        def ring_loss(q, k, v):
            return jnp.sum(_run_ring(mesh, q, k, v, causal, 0.3) * w)

        def full_loss(q, k, v):
            return jnp.sum(_full_attn(q, k, v, causal, 0.3) * w)

        got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
        for g, r, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=2e-5, rtol=2e-5,
                err_msg=f"d{name} diverged",
            )

    def test_bf16_io_fp32_accumulate(self, devices8):
        mesh = Mesh(np.asarray(devices8), ("context",))
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q, k, v = (jax.random.normal(kk, (1, 2, 64, 16), jnp.bfloat16) for kk in ks)
        got = _run_ring(mesh, q, k, v, True, 0.25)
        assert got.dtype == jnp.bfloat16
        want = _full_attn(q, k, v, True, 0.25)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=3e-2, rtol=3e-2,
        )

    def test_shape_validation(self, devices8):
        mesh = Mesh(np.asarray(devices8), ("context",))
        with pytest.raises(ValueError, match="S_local"):
            _smap(
                lambda q: ring_attention(q, q, q, axis_name="context"),
                mesh=mesh, in_specs=P(None, "context"), out_specs=P(None, "context"),
            )(jnp.ones((2, 64, 8)))


class TestRingAttentionFlashHops:
    """impl='pallas': each hop runs the flash kernel (interpret mode on CPU)
    and hops merge by (o, lse) — must match full attention exactly, forward
    and backward (the backward exercises the kernel's dlse cotangent)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, devices8, causal):
        mesh = Mesh(np.asarray(devices8), ("context",))
        B, H, S, D = 1, 2, 1024, 8  # S_local = 128: the kernel's min block
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q, k, v = (jax.random.normal(kk, (B, H, S, D)) for kk in ks)
        got = _run_ring(mesh, q, k, v, causal, 0.35, impl="pallas")
        want = _full_attn(q, k, v, causal, 0.35)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)

    def test_grads_match_full_attention(self, devices8):
        mesh = Mesh(np.asarray(devices8), ("context",))
        B, H, S, D = 1, 1, 1024, 8
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q, k, v = (jax.random.normal(kk, (B, H, S, D)) for kk in ks[:3])
        w = jax.random.normal(ks[3], q.shape)

        def ring_loss(q, k, v):
            return jnp.sum(_run_ring(mesh, q, k, v, True, 0.3, impl="pallas") * w)

        def full_loss(q, k, v):
            return jnp.sum(_full_attn(q, k, v, True, 0.3) * w)

        got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
        for g, r, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=3e-5, rtol=3e-5,
                err_msg=f"d{name} diverged",
            )
