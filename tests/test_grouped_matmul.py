"""The grouped matmul (``ops/grouped_matmul.py``) against ``jax.lax.ragged_dot``.

The kernels run in the Pallas interpreter on the CPU. Forward, ``d lhs`` and
``d rhs`` are compared group layout by group layout: even groups, skewed ones,
an empty group, groups smaller than a row tile, edges inside a tile, and a
buffer whose tail belongs to no group. The tail is poisoned with NaN on the way
in (operand and cotangent): a tail row read into any result shows. Float32 at
``highest`` matmul precision differs from the oracle by the order of the sums
(1e-5 of the largest value); bfloat16 operands by the rounding of a float32
accumulator (one bfloat16 ulp of the largest value, 2 ** -7)."""

import jax
import jax.numpy as jnp
import pytest

from beforeholiday_tpu.guard import dispatch
from beforeholiday_tpu.ops import grouped_matmul as gm

_TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -7}

# (R, E, K, N, group sizes): the row tile of these shapes is 128 (R / E < 512)
LAYOUTS = {
    "even": (1024, 4, 128, 256, (256, 256, 256, 256)),
    "skewed": (1024, 4, 256, 128, (700, 100, 24, 100)),
    "an_empty_group": (1024, 4, 128, 128, (300, 0, 200, 100)),
    "groups_smaller_than_a_tile": (512, 8, 128, 128, (3, 60, 1, 17, 0, 90, 40, 5)),
    "edges_inside_a_tile": (768, 3, 128, 256, (200, 250, 190)),
    "a_tail_of_no_group": (1000, 4, 128, 128, (3, 0, 500, 301)),
    "first_and_last_groups_empty": (512, 4, 128, 128, (0, 130, 126, 0)),
    "no_rows_at_all": (256, 2, 128, 128, (0, 0)),
}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def operands(layout, dtype, seed=0):
    R, E, K, N, sizes = LAYOUTS[layout]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    sizes = jnp.asarray(sizes, jnp.int32)
    valid = (jnp.arange(R) < jnp.sum(sizes))[:, None]
    lhs = jax.random.normal(ks[0], (R, K)).astype(dtype)
    rhs = (jax.random.normal(ks[1], (E, K, N)) * 0.1).astype(dtype)
    ct = jax.random.normal(ks[2], (R, N))
    return lhs, rhs, sizes, ct, valid


def results(lhs, rhs, sizes, ct, valid, impl):
    """``(out, d lhs, d rhs)`` with the tail rows NaN in the operand and in the
    cotangent, and cut from what is compared."""
    nan = jnp.asarray(jnp.nan, lhs.dtype)

    def poisoned(a, b):
        return gm.grouped_matmul(jnp.where(valid, a, nan), b, sizes,
                                 preferred_element_type=jnp.float32, impl=impl)

    out, pull = jax.vjp(poisoned, lhs, rhs)
    dlhs, drhs = pull(jnp.where(valid, ct, jnp.nan))
    return (jnp.where(valid, out, 0.0), jnp.where(valid, dlhs.astype(jnp.float32), 0.0),
            drhs.astype(jnp.float32))


@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16), ids=("float32", "bfloat16"))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_forward_and_both_cotangents_match_ragged_dot(layout, dtype):
    args = operands(layout, dtype)
    got, want = results(*args, "pallas"), results(*args, "jnp")
    for g, w, what in zip(got, want, ("out", "d lhs", "d rhs")):
        assert bool(jnp.all(jnp.isfinite(g))), f"{what}: a tail row reached a result"
        scale = max(float(jnp.max(jnp.abs(w))), 1e-30)
        assert float(jnp.max(jnp.abs(g - w))) <= _TOL[dtype] * scale, (layout, what)


def test_results_keep_the_dtypes_ragged_dot_gives_them():
    lhs, rhs, sizes, ct, _ = operands("even", jnp.bfloat16)
    for out in (jnp.float32, jnp.bfloat16, None):
        y, pull = jax.vjp(lambda a, b: gm.grouped_matmul(
            a, b, sizes, preferred_element_type=out, impl="pallas"), lhs, rhs)
        assert y.dtype == (out or jnp.bfloat16)
        dlhs, drhs = pull(ct.astype(y.dtype))
        assert dlhs.dtype == drhs.dtype == jnp.bfloat16
        assert dlhs.shape == lhs.shape and drhs.shape == rhs.shape


def test_under_jit_with_traced_group_sizes():
    lhs, rhs, sizes, _, valid = operands("skewed", jnp.float32)
    f = jax.jit(lambda a, b, s: gm.grouped_matmul(a, b, s, impl="pallas"))
    for s in (sizes, sizes[::-1], jnp.zeros_like(sizes)):
        keep = (jnp.arange(lhs.shape[0]) < jnp.sum(s))[:, None]
        want = jax.lax.ragged_dot(lhs, rhs, s)
        assert float(jnp.max(jnp.abs(jnp.where(keep, f(lhs, rhs, s) - want, 0.0)))) <= 1e-4


# -- the plan: a function of what the call can see ---------------------------------


@pytest.mark.parametrize("R,E,K,N,out,tm,steps", (
    (24576, 16, 2304, 896, jnp.float32, 128, 192 + 15),     # the Mellum cell, gate / up
    (24576, 16, 896, 2304, jnp.bfloat16, 128, 192 + 15),    # ... down
    (16384, 32, 2048, 512, jnp.float32, 128, 128 + 31),     # the Qwen cell, gate / up
    (16384, 32, 512, 2048, jnp.bfloat16, 128, 128 + 31),    # ... down
    (384, 16, 128, 128, jnp.float32, 128, 3 + 15),          # a buffer smaller than a tile a group
    (65536, 8, 1024, 1024, jnp.bfloat16, 512, 128 + 7),     # many rows an expert: the widest tile
))
def test_the_plan_follows_the_rows_an_expert_expects(R, E, K, N, out, tm, steps):
    for kernel in ("fwd", "dlhs", "drhs"):
        p = gm.plan(kernel, R, E, K, N, jnp.bfloat16, out if kernel == "fwd" else jnp.bfloat16)
        assert (p.tm, p.steps) == (tm, steps), (kernel, p)
        width = K if kernel == "dlhs" else N
        assert p.tn * p.splits == width and p.tn % 128 == 0
        assert gm._vmem_bytes(kernel, p.tm, p.tn, N if kernel == "dlhs" else K,
                              jnp.bfloat16, out if kernel == "fwd" else jnp.bfloat16) \
            <= gm._VMEM_BUDGET


def test_a_panel_too_large_for_the_budget_is_split_along_its_output():
    p = gm.plan("fwd", 8192, 4, 8192, 4096, jnp.bfloat16, jnp.float32)
    assert p.splits > 1 and p.tn * p.splits == 4096 and p.tn % 128 == 0
    q = gm.plan("drhs", 8192, 4, 8192, 4096, jnp.bfloat16, jnp.bfloat16)
    assert q.splits > 1 and q.tn * q.splits == 4096


def test_a_split_panel_gives_the_same_results(monkeypatch):
    """The outer grid axis walks the parts of the panel: forced here by a budget
    that the whole panel does not fit."""
    args = operands("edges_inside_a_tile", jnp.float32)
    want = results(*args, "jnp")
    monkeypatch.setattr(gm, "_VMEM_BUDGET", 750 * 1024)
    R, E, K, N, _ = LAYOUTS["edges_inside_a_tile"]
    assert gm.plan("fwd", R, E, K, N, jnp.float32, jnp.float32).splits == 2
    assert gm.plan("drhs", R, E, K, N, jnp.float32, jnp.float32).splits == 2
    for g, w, what in zip(results(*args, "pallas"), want, ("out", "d lhs", "d rhs")):
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-5 * float(jnp.max(jnp.abs(w))), what


def test_the_visit_table():
    """Rows 0..200 | 200..450 | 450..640 in tiles of 128: tile 1 is shared by
    groups 0 and 1, tile 3 by 1 and 2; the dead step repeats the last visit and
    does nothing."""
    R_, O, C = gm._ROWS, gm._OPENS, gm._CLOSES
    sizes = jnp.asarray((200, 250, 190), jnp.int32)
    flags, lo, hi, group, tile = (x.tolist() for x in gm._visits(sizes, 768, 128, 8, visit_empty=False))
    assert group == [0, 0, 1, 1, 1, 2, 2, 2]
    assert tile == [0, 1, 1, 2, 3, 3, 4, 4]
    assert flags == [R_ | O, R_ | C, R_ | O, R_, R_ | C, R_ | O, R_ | C, 0]
    assert (lo, hi) == ([0, 0, 200, 200, 200, 450, 450, 450], [200, 200, 450, 450, 450, 640, 640, 640])
    # an empty group has no visit, or one where its zeros must be written
    sizes = jnp.asarray((130, 0, 126), jnp.int32)
    flags, _, _, group, tile = (x.tolist() for x in gm._visits(sizes, 512, 128, 6, visit_empty=False))
    assert (group, tile) == ([0, 0, 2, 2, 2, 2], [0, 1, 1, 1, 1, 1])
    assert flags == [R_ | O, R_ | C, R_ | O | C, 0, 0, 0]
    flags, _, _, group, tile = (x.tolist() for x in gm._visits(sizes, 512, 128, 6, visit_empty=True))
    assert (group, tile) == ([0, 0, 1, 2, 2, 2], [0, 1, 1, 1, 1, 1])
    assert flags == [R_ | O, R_ | C, O | C, R_ | O | C, 0, 0]
    # no rows at all: nothing is live for the rows x panel kernels
    flags, *_ = gm._visits(jnp.zeros((3,), jnp.int32), 512, 128, 6, visit_empty=False)
    assert flags.tolist() == [0] * 6


# -- dispatch ------------------------------------------------------------------------


@pytest.mark.parametrize("R,K,N,dtype,rhs_dtype", (
    (256, 96, 128, jnp.float32, jnp.float32),         # K not a multiple of 128
    (256, 128, 24, jnp.float32, jnp.float32),         # N not a multiple of 128
    (256, 128, 128, jnp.float16, jnp.float16),        # a dtype the kernels do not take
    (256, 128, 128, jnp.bfloat16, jnp.float32),       # operands of two dtypes
))
def test_forced_pallas_off_the_kernels_shapes_raises(R, K, N, dtype, rhs_dtype):
    assert not gm.is_kernel_available(R, 2, K, N, dtype, rhs_dtype)
    lhs, rhs = jnp.zeros((R, K), dtype), jnp.zeros((2, K, N), rhs_dtype)
    sizes = jnp.asarray((100, 100), jnp.int32)
    with pytest.raises(ValueError, match="impl='pallas' forced"):
        gm.grouped_matmul(lhs, rhs, sizes, impl="pallas")


def test_mismatched_shapes_are_refused():
    sizes = jnp.asarray((100, 100), jnp.int32)
    with pytest.raises(ValueError, match="shapes mismatch"):
        gm.grouped_matmul(jnp.zeros((256, 128)), jnp.zeros((2, 256, 128)), sizes)
    with pytest.raises(ValueError, match="shapes mismatch"):
        gm.grouped_matmul(jnp.zeros((256, 128)), jnp.zeros((3, 128, 128)), sizes)


def _as_on_the_chip(monkeypatch):
    dispatch.reset_dispatch_counters()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # resolve_impl -> pallas
    monkeypatch.setattr(gm, "_interpret_default", lambda: True)


def _counted():
    return {k[0]: v for k, v in dispatch.dispatch_counters().items()}["grouped_matmul"]


def test_dispatch_is_guarded_and_counted(monkeypatch):
    _as_on_the_chip(monkeypatch)
    lhs, rhs, sizes, _, valid = operands("skewed", jnp.float32)
    got = gm.grouped_matmul(lhs, rhs, sizes)
    assert _counted()["pallas"] == 1 and _counted()["jnp"] == 0
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    assert float(jnp.max(jnp.abs(jnp.where(valid, got - want, 0.0)))) <= 1e-4


def test_a_failed_probe_degrades_to_ragged_dot(monkeypatch):
    from beforeholiday_tpu.testing import faults

    _as_on_the_chip(monkeypatch)
    dispatch.clear_probe_cache("grouped_matmul")
    lhs, rhs, sizes, _, _ = operands("even", jnp.float32)
    with faults.force_probe_failure("grouped_matmul"):
        got = gm.grouped_matmul(lhs, rhs, sizes)
    dispatch.clear_probe_cache("grouped_matmul")
    assert _counted()["jnp"] == 1 and _counted()["pallas"] == 0
    assert bool(jnp.array_equal(got, jax.lax.ragged_dot(lhs, rhs, sizes)))


def test_each_traced_kernel_books_its_plan():
    from beforeholiday_tpu import monitor

    dispatch.reset_dispatch_counters()
    jax.clear_caches()      # a kernel call is a jit function: booked when traced, not when hit
    R, E, K, N, _ = LAYOUTS["skewed"]
    lhs, rhs, sizes, ct, _ = operands("skewed", jnp.bfloat16)
    jax.grad(lambda a, b: jnp.sum(gm.grouped_matmul(
        a, b, sizes, preferred_element_type=jnp.float32, impl="pallas") * ct), argnums=(0, 1))(
            lhs, rhs)
    rows = {r["kernel"]: r for r in monitor.tile_records() if r["op"] == "grouped_matmul"}
    assert set(rows) == {"fwd", "dlhs", "drhs"}
    for kernel, row in rows.items():
        p = gm.plan(kernel, R, E, K, N, jnp.bfloat16,
                    jnp.float32 if kernel == "fwd" else jnp.bfloat16)
        assert row["traces"] == 1
        # grid steps of the worst-case table, row tiles of the buffer, visits that can be shared
        assert (row["total"], row["live"], row["masked"]) == (
            p.splits * p.steps, p.splits * (R // p.tm), p.splits * (E - 1)) == (11, 8, 3)
        assert str((R, E, K, N)).strip("()") in row["key"]


def test_the_kernels_are_named_for_the_trace():
    """``grouped_matmul_ms[.mellum]`` reads ``^%grouped_matmul``: the chip
    prints a kernel under its ``name=``."""
    from jax._src import core

    def kernels(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in core.jaxprs_in_params(eqn.params):
                yield from kernels(sub)

    lhs, rhs, sizes, ct, _ = operands("even", jnp.float32)
    grad = jax.grad(lambda a, b: jnp.sum(gm.grouped_matmul(a, b, sizes, impl="pallas") * ct),
                    argnums=(0, 1))
    assert sorted(kernels(jax.make_jaxpr(grad)(lhs, rhs).jaxpr)) == [
        "grouped_matmul_dlhs", "grouped_matmul_drhs", "grouped_matmul_fwd"]
