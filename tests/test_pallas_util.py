"""``ops._pallas_util.dispatch``: the one choice of kernel of the ops that have a
shape of their own, held to through each of them.

Off its kernels' shapes an op asked for ``pallas`` by name raises its own
sentence, and left to the policy (``impl=None`` on a TPU) it takes the ``jnp``
path, booked once by ``guard.dispatch`` — the cases the kernel files' own tests
each held before the policy had one home.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.guard import dispatch
from beforeholiday_tpu.ops import _pallas_util, deltanet, gated_delta, grouped_matmul
from beforeholiday_tpu.ops import short_conv, ssd


def _normal(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _deltanet_qkv(impl):
    # head dims of 16 and 40 rows: the small model of tests/test_qwen3_next.py
    return deltanet.deltanet_qkv(_normal(0, 2, 40, 128), _normal(1, 128, 4), key_heads=2,
                                 value_heads=4, d_k=16, d_v=16, impl=impl)


def _deltanet_gate(impl):
    return deltanet.deltanet_gate(_normal(0, 2, 4, 40, 16), _normal(1, 2, 40, 64),
                                  jnp.ones((16,)), eps=1e-6, impl=impl)


def _short_conv(impl):
    # 64 channels: the small model of tests/test_lfm2_moe.py
    return short_conv.gated_short_conv(_normal(0, 1, 48, 3 * 64), _normal(1, 64, 3), impl=impl)


def _gated_delta(impl):
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k, v = unit(_normal(0, 1, 128, 1, 64)), unit(_normal(1, 1, 128, 1, 64)), \
        _normal(2, 1, 128, 1, 128)
    g = -jax.random.uniform(jax.random.PRNGKey(3), (1, 128, 1))
    beta = jax.nn.sigmoid(_normal(4, 1, 128, 1))
    return gated_delta.gated_delta_rule(q * 0.125, k, v, g, beta, chunk=64, impl=impl)


def _ssd(impl):
    dt = jax.nn.softplus(_normal(1, 1, 64, 2))
    A = -jnp.exp(_normal(2, 2))
    return ssd.ssd(_normal(0, 1, 64, 2, 8), dt, A, _normal(3, 1, 64, 1, 16),
                   _normal(4, 1, 64, 1, 16), jnp.ones((2,)), chunk=32, impl=impl)


def _grouped_matmul(impl):
    return grouped_matmul.grouped_matmul(jnp.ones((256, 96)), jnp.ones((2, 96, 24)),
                                         jnp.asarray((100, 156), jnp.int32), impl=impl)


# op (guard.dispatch's key) -> (a call off the kernels' shapes, the op's own sentence)
_OFF_SHAPE = {
    "deltanet_qkv": (_deltanet_qkv, r"S 40 is not whole tiles of \d+ rows, d_k 16 / d_v 16 not"),
    "deltanet_gate": (_deltanet_gate, r"S 40 is not whole tiles of \d+ rows or d_v 16 not"),
    "short_conv": (_short_conv, r"S 48 is not whole tiles of \d+ rows, D 64 not a multiple"),
    "gated_delta_rule": (_gated_delta, r"chunk 64 is not a multiple of 64 or d_k 64 / d_v 128"),
    "ssd": (_ssd, r"chunk 32 / state 16 is not a multiple of 128, or 2 heads a group of 8"),
    "grouped_matmul": (_grouped_matmul, r"lhs \(256, 96\) float32 x rhs \(2, 96, 24\) float32 "
                                        r"is off the kernels' shapes"),
}


@pytest.mark.parametrize("op", sorted(_OFF_SHAPE))
def test_a_kernel_forced_off_its_shapes_raises_the_ops_own_sentence(op):
    call, why = _OFF_SHAPE[op]
    with pytest.raises(ValueError, match=rf"impl='pallas' forced but {why}.*; pass impl=None "
                                         r"for the automatic fallback"):
        call("pallas")


@pytest.mark.parametrize("op", sorted(_OFF_SHAPE))
def test_the_default_off_the_kernels_shapes_is_jnp_booked_once(op, monkeypatch):
    call, _ = _OFF_SHAPE[op]
    want = call("jnp")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # resolve_impl -> pallas
    dispatch.reset_dispatch_counters()
    got = call(None)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    counted = {k[0]: v for k, v in dispatch.dispatch_counters().items()}
    assert counted[op]["jnp"] == 1 and counted[op]["pallas"] == 0, counted


def test_dispatch_books_nothing_where_the_kernels_are_taken_or_jnp_is_asked_for(monkeypatch):
    dispatch.reset_dispatch_counters()
    x = jnp.zeros((8, 128))
    assert _pallas_util.dispatch("probe_op", "jnp", False, "never said", x, statics=()) == \
        ("jnp", True)
    assert _pallas_util.dispatch("probe_op", "pallas", True, "never said", x, statics=()) == \
        ("pallas", True)
    assert _pallas_util.dispatch("probe_op", None, True, "never said", x, statics=()) == \
        ("jnp", False)                                              # the CPU's policy
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _pallas_util.dispatch("probe_op", None, True, "never said", x, statics=()) == \
        ("pallas", False)                # unforced: the caller still probes and counts it
    assert not [k for k in dispatch.dispatch_counters() if k[0] == "probe_op"]
