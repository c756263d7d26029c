"""Unified Pallas dispatch policy (ref: the per-extension availability gates,
apex/transformer/functional/fused_softmax.py:164 ``is_kernel_available``).

One rule for every fused op: pallas iff the traced program owns one device per
shard (no ambient mesh, or inside shard_map over all mesh axes) — however many
chips the host has; jnp under GSPMD/auto sharding and off-TPU. Verified here by (a) a decision-table unit
test with the backend patched, and (b) actually running Pallas kernels inside
an 8-device shard_map (interpret mode on CPU) for the multi-tensor and
normalization families.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.ops import _pallas_util
from beforeholiday_tpu.ops import multi_tensor as mt
from beforeholiday_tpu.ops.normalization import fused_layer_norm
from beforeholiday_tpu.ops.softmax import scaled_softmax


def _smap(f, **kw):
    kw["check_vma"] = False
    return jax.shard_map(f, **kw)


class TestResolvePolicy:
    def test_explicit_always_honored(self):
        assert _pallas_util.resolve_impl("pallas") == "pallas"
        assert _pallas_util.resolve_impl("jnp") == "jnp"
        with pytest.raises(ValueError):
            _pallas_util.resolve_impl("cuda")

    def test_off_tpu_defaults_jnp(self):
        assert jax.default_backend() != "tpu"
        assert _pallas_util.resolve_impl(None) == "jnp"

    def test_tpu_gspmd_mesh_defaults_jnp(self, monkeypatch, devices8):
        """``set_mesh`` with auto axes is how GSPMD programs run here: the
        partitioner owns the body -> jnp, at top level and under jit."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        mesh = Mesh(np.asarray(devices8).reshape(4, 2), ("data", "tensor"))
        seen = []
        with jax.sharding.set_mesh(mesh):
            seen.append(_pallas_util.resolve_impl(None))
            jax.eval_shape(
                jax.jit(lambda x: (seen.append(_pallas_util.resolve_impl(None)), x)[1]),
                jax.ShapeDtypeStruct((8, 4), jnp.float32),
            )
        assert seen == ["jnp", "jnp"]

    def test_tpu_no_mesh_defaults_pallas_on_multichip_host(self, monkeypatch):
        """No ambient mesh = the program owns the one device its inputs are
        committed to, so a plain jit gets the kernels however many chips the
        host has (the old ``device_count() == 1`` gate sent a four-chip
        host's one-chip training to the unfused path)."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert jax.device_count() > 1
        seen = [_pallas_util.resolve_impl(None)]
        jax.eval_shape(
            jax.jit(lambda x: (seen.append(_pallas_util.resolve_impl(None)), x)[1]),
            jax.ShapeDtypeStruct((8, 4), jnp.float32),
        )
        assert seen == ["pallas", "pallas"]

    def test_tpu_inside_shard_map_defaults_pallas(self, monkeypatch, devices8):
        """Fully-manual context (check_vma=False): every shard is one device
        -> pallas."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        mesh = Mesh(np.asarray(devices8).reshape(8), ("data",))
        seen = []

        @functools.partial(
            _smap, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        )
        def f(x):
            seen.append(_pallas_util.resolve_impl(None))
            return x

        jax.eval_shape(f, jax.ShapeDtypeStruct((8, 4), jnp.float32))
        assert seen == ["pallas"]

    def test_shard_map_with_vma_tracking_defaults_jnp(self, monkeypatch, devices8):
        """Under check_vma=True (jax's default) pallas_call is rejected at
        trace time, so the default must stay jnp — no regression for vanilla
        shard_map users."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        mesh = Mesh(np.asarray(devices8).reshape(8), ("data",))
        seen = []

        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        )
        def f(x):
            seen.append(_pallas_util.resolve_impl(None))
            return x

        jax.eval_shape(f, jax.ShapeDtypeStruct((8, 4), jnp.float32))
        assert seen == ["jnp"]

    def test_partially_manual_context_defaults_jnp(self, monkeypatch, devices8):
        """shard_map over a strict subset of axes leaves Auto axes -> GSPMD
        still partitions the body -> jnp."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        mesh = jax.make_mesh(
            (4, 2), ("data", "tensor"),
            axis_types=(jax.sharding.AxisType.Explicit,) * 2,
            devices=devices8,
        )
        seen = []

        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            axis_names={"data"},
        )
        def f(x):
            seen.append(_pallas_util.resolve_impl(None))
            return x

        jax.eval_shape(f, jax.ShapeDtypeStruct((8, 4), jnp.float32))
        assert seen == ["jnp"]

    def test_check_vma_attribute_error_fails_safe_to_jnp(self, monkeypatch):
        """Regression: the vma probe reaches into jax internals
        (get_abstract_mesh, AxisType, jax._src.config._check_vma). If any of
        them survives as a name but loses its shape (API drift — e.g.
        ``_check_vma`` without ``.value``), the manual-context probe must fail
        safe (False -> jnp), not raise from inside every op dispatch."""
        import jax._src.config as jax_config

        class FakeMesh:
            axis_names = ("data",)
            # empty axis_types: vacuously all-Manual, so the probe reaches the
            # _check_vma peek on every jax version without needing AxisType
            axis_types = ()

        monkeypatch.setattr(
            jax.sharding, "get_abstract_mesh", lambda: FakeMesh(),
            raising=False,
        )

        class FakeVma:
            value = False  # check_vma off, the pallas-safe mode

        monkeypatch.setattr(jax_config, "_check_vma", FakeVma, raising=False)
        assert _pallas_util.in_fully_manual_context() is True  # control

        monkeypatch.setattr(
            jax_config, "_check_vma", object(), raising=False  # no .value
        )
        assert _pallas_util.in_fully_manual_context() is False

    def test_multi_tensor_uses_streaming_policy(self):
        """The mt family defaults to the XLA-fused path EVERYWHERE (r5
        measurement: 46M Adam jnp 1.5 ms vs pallas 1.8 ms aliased — see
        resolve_impl_streaming); the fusion-impossible kernels (attention,
        softmax, layernorm) keep the pallas-on-TPU policy."""
        assert mt._resolve is _pallas_util.resolve_impl_streaming
        assert mt._resolve(None) == "jnp"
        assert mt._resolve("pallas") == "pallas"  # explicit always honored


class TestPallasInsideShardMap:
    """The kernels themselves must run under manual partitioning — the policy
    would be moot if pallas_call broke inside shard_map."""

    def test_multi_tensor_scale_pallas_under_shard_map(self, devices8):
        mesh = Mesh(np.asarray(devices8).reshape(8), ("data",))
        src = np.random.RandomState(0).randn(8, 64).astype(np.float32)

        @functools.partial(
            _smap, mesh=mesh, in_specs=P("data"), out_specs=(P("data"), P()),
        )
        def f(x):
            outs, found_inf = mt.multi_tensor_scale([x[0]], 2.0, impl="pallas")
            return outs[0][None], jax.lax.pmax(found_inf, "data")

        y, found_inf = jax.jit(f)(jnp.asarray(src))
        np.testing.assert_allclose(np.asarray(y), src * 2.0, rtol=1e-6)
        assert not bool(found_inf)

    def test_layer_norm_pallas_under_shard_map(self, devices8):
        mesh = Mesh(np.asarray(devices8).reshape(8), ("data",))
        rng = np.random.RandomState(1)
        x = rng.randn(8, 4, 128).astype(np.float32)
        g = rng.randn(128).astype(np.float32)
        b = rng.randn(128).astype(np.float32)

        @functools.partial(
            _smap, mesh=mesh, in_specs=(P("data"), P(), P()), out_specs=P("data"),
        )
        def f(xs, g, b):
            return fused_layer_norm(xs, g, b, impl="pallas")

        y = jax.jit(f)(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
        want = fused_layer_norm(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b), impl="jnp")
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-5)

    def test_softmax_pallas_under_shard_map(self, devices8):
        mesh = Mesh(np.asarray(devices8).reshape(8), ("data",))
        x = np.random.RandomState(2).randn(8, 128, 64).astype(np.float32)

        @functools.partial(
            _smap, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        )
        def f(xs):
            return scaled_softmax(xs, 0.5, impl="pallas")

        y = jax.jit(f)(jnp.asarray(x))
        want = scaled_softmax(jnp.asarray(x), 0.5, impl="jnp")
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5, atol=2e-6)
