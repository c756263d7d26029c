"""Collective matmul: the ppermute-ring gather+GEMM overlap for SP TP layers.

The decomposition changes the schedule, never the numbers — so the contract
tests are bitwise: forward AND all three grads of the sequence-parallel
ColumnParallel layer must match the monolithic gather-then-matmul exactly.
Plus the knob semantics (default OFF, module-wide + per-call override) and
the per-hop comms-ledger sites.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from beforeholiday_tpu.monitor import comms as mon_comms
from beforeholiday_tpu.transformer import tensor_parallel as tp
from beforeholiday_tpu.transformer.tensor_parallel import collective as cm

pytestmark = pytest.mark.quantized


def _smap(f, **kw):
    kw["check_vma"] = False
    return jax.shard_map(f, **kw)


WORLD = 8
IN_SPECS = (P("tensor"), P(None, "tensor"), P("tensor"), P(None, "tensor"))
OUT_SPECS = (P(None, "tensor"), P("tensor"), P(None, "tensor"), P("tensor"))


def _operands(S=64, K=16, N=64, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return (
        jnp.asarray(rng.randn(S, K), dtype),
        jnp.asarray(rng.randn(K, N) / np.sqrt(K), dtype),
        jnp.asarray(rng.randn(N), dtype),
        jnp.asarray(rng.randn(S, N), dtype),
    )


def _fwdbwd(mesh, collective):
    def body(xs, ws, bs, dys):
        def f(args):
            xl, wl, bl = args
            return tp.column_parallel_linear(
                xl, wl, bl, sequence_parallel=True,
                collective_matmul=collective,
            )

        y, pull = jax.vjp(f, (xs, ws, bs))
        dx, dw, db = pull(dys)[0]
        return y, dx, dw, db

    return _smap(body, mesh=mesh, in_specs=IN_SPECS, out_specs=OUT_SPECS)


class TestBitwiseParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_fwd_and_bwd_match_monolithic(self, devices8, dtype):
        mesh = Mesh(np.asarray(devices8), ("tensor",))
        args = _operands(dtype=dtype)
        ref = jax.jit(_fwdbwd(mesh, False))(*args)
        got = jax.jit(_fwdbwd(mesh, True))(*args)
        for name, a, b in zip(("y", "dx", "dw", "db"), ref, got):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{name} diverged from the monolithic path",
            )

    def test_3d_activations(self, devices8):
        """(s_local, B, K) activations — the layer's batched-sequence shape."""
        mesh = Mesh(np.asarray(devices8), ("tensor",))
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(16, 4, 8).astype(np.float32))
        w = jnp.asarray(rng.randn(8, 32).astype(np.float32))

        def run(collective):
            body = _smap(
                lambda xs, ws: cm.all_gather_matmul(xs, ws, "tensor")
                if collective
                else tp.column_parallel_linear(
                    xs, ws, sequence_parallel=True, collective_matmul=False,
                ),
                mesh=mesh,
                in_specs=(P("tensor"), P(None, "tensor")),
                out_specs=P(None, None, "tensor"),
            )
            return jax.jit(body)(x, w)

        np.testing.assert_array_equal(
            np.asarray(run(True)), np.asarray(run(False))
        )


class TestKnob:
    def test_default_off_and_set_returns_prev(self):
        assert cm.collective_matmul_enabled() is False
        prev = cm.set_collective_matmul(True)
        try:
            assert prev is False
            assert cm.collective_matmul_enabled() is True
        finally:
            assert cm.set_collective_matmul(False) is True

    def test_default_path_has_no_ppermute(self, devices8):
        """With the knob OFF and no per-call override the traced program must
        be the monolithic gather — zero ppermute ring hops."""
        mesh = Mesh(np.asarray(devices8), ("tensor",))
        x, w, b, _ = _operands()

        def trace(collective):
            body = _smap(
                lambda xs, ws, bs: tp.column_parallel_linear(
                    xs, ws, bs, sequence_parallel=True,
                    collective_matmul=collective,
                ),
                mesh=mesh, in_specs=IN_SPECS[:3], out_specs=P(None, "tensor"),
            )
            return str(jax.make_jaxpr(body)(x, w, b))

        assert "ppermute" not in trace(None)  # module default: OFF
        assert "ppermute" in trace(True)

    def test_module_default_drives_none(self, devices8):
        mesh = Mesh(np.asarray(devices8), ("tensor",))
        x, w, b, _ = _operands()
        body = _smap(
            lambda xs, ws, bs: tp.column_parallel_linear(
                xs, ws, bs, sequence_parallel=True,
            ),
            mesh=mesh, in_specs=IN_SPECS[:3], out_specs=P(None, "tensor"),
        )
        prev = cm.set_collective_matmul(True)
        try:
            assert "ppermute" in str(jax.make_jaxpr(body)(x, w, b))
        finally:
            cm.set_collective_matmul(prev)


class TestLedger:
    def test_every_hop_booked(self, devices8):
        mesh = Mesh(np.asarray(devices8), ("tensor",))
        args = _operands()
        mon_comms.reset_comms_ledger()
        jax.block_until_ready(jax.jit(_fwdbwd(mesh, True))(*args))
        sites = {
            r["site"] for r in mon_comms.comms_records()
            if r["site"].startswith("tp.collective_matmul")
        }
        want = {f"tp.collective_matmul:hop{t}" for t in range(1, WORLD)}
        want.add("tp.collective_matmul.bwd_dx")
        assert want <= sites
