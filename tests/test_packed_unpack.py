"""``PackedParams.unpack`` and its transpose (PR 25).

``unpack`` gives ``unflatten``'s slices a ``custom_vjp`` whose backward is one
``concatenate`` per dtype bucket: the gradient arena is packed once, where the
slices' own transpose is ``add_any(pad(g0), …, pad(gn))``, a sum XLA:TPU
re-evaluated inside every consumer. The reference here is that transpose:
``jax.grad`` through ``ops.arena.unflatten``.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.ops.arena import TILE, PackedParams, unflatten

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


def _tree(case, seed=0):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def leaf(shape, dtype):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    bf16, f32 = jnp.bfloat16, jnp.float32
    if case == "two_buckets":  # leaf sizes that are no multiples of 128, two dtypes
        return {"w": leaf((33, 7), bf16), "b": leaf((7,), f32),
                "emb": leaf((5, 3, 11), bf16), "g": leaf((129,), f32)}
    if case == "scalar_leaf":
        return {"w": leaf((130,), bf16), "t": leaf((), bf16), "v": leaf((3, 3), bf16)}
    if case == "unused_leaf":
        return {"w": leaf((17, 5), f32), "unused": leaf((300,), f32), "v": leaf((9,), f32)}
    if case == "one_leaf":
        return {"w": leaf((1000,), bf16)}
    if case == "exact_tile":  # total == padded_total: no tail piece
        return {"a": leaf((TILE - 5,), f32), "b": leaf((5,), f32)}
    raise ValueError(case)


CASES = ["two_buckets", "scalar_leaf", "unused_leaf", "one_leaf", "exact_tile"]


def _loss(tree):
    """Another weight on every element, so that no two cotangents are equal.
    A cotangent is ``w + 2 x``: one rounding however the compiler fuses it, so
    two programs that differ only in the pack give the same bits."""
    total = jnp.float32(0.0)
    for i, (name, x) in enumerate(sorted(tree.items())):
        if name == "unused":
            continue
        x32 = x.astype(jnp.float32)
        w = np.linspace(0.5, 1.5 + i, x.size, dtype=np.float32).reshape(x.shape)
        total = total + jnp.sum(x32 * w) + jnp.sum(x32 * x32)
    return total


def _unpack_by_slices(packed):
    """The leaves as plain slices of the arenas, with the slices' own transpose."""
    lay = packed.layout
    leaves = [None] * lay.n_leaves
    for arena, idx, spec in zip(packed.arenas, lay.indices, lay.specs):
        for i, piece in zip(idx, unflatten(arena, spec)):
            leaves[i] = piece
    return jax.tree_util.tree_unflatten(lay.treedef, leaves)


def _bits(x):
    return np.asarray(x).tobytes()


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", CASES)
def test_forward_is_the_slices(case, jit):
    packed = PackedParams.pack(_tree(case))
    new, old = packed.unpack, lambda: _unpack_by_slices(packed)
    if jit:
        new, old = jax.jit(new), jax.jit(old)
    got, want = new(), old()
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert _bits(g) == _bits(w)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", CASES)
def test_gradient_arenas_equal_the_old_transpose_bitwise(case, jit):
    packed = PackedParams.pack(_tree(case))
    new = jax.grad(lambda pk: _loss(pk.unpack()))
    old = jax.grad(lambda pk: _loss(_unpack_by_slices(pk)))
    if jit:
        new, old = jax.jit(new), jax.jit(old)
    g_new, g_old = new(packed), old(packed)
    assert isinstance(g_new, PackedParams) and g_new.layout == packed.layout
    for a_new, a_old, arena in zip(g_new.arenas, g_old.arenas, packed.arenas):
        assert a_new.shape == arena.shape and a_new.dtype == arena.dtype
        assert _bits(a_new) == _bits(a_old)
        assert np.any(np.asarray(a_new, np.float32) != 0.0)


def test_tail_and_unused_leaf_get_zero_cotangent():
    packed = PackedParams.pack(_tree("unused_leaf"))
    (spec,) = packed.layout.specs
    assert spec.padded_total > spec.total
    (g,) = jax.grad(lambda pk: _loss(pk.unpack()))(packed).arenas
    g = np.asarray(g)
    assert not g[spec.total:].any()
    names = sorted(_tree("unused_leaf"))  # dict leaves flatten in sorted order
    off = spec.offsets[names.index("unused")]
    assert not g[off: off + 300].any()
    assert g[:off].all() and g[off + 300: spec.total].all()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _arena_wide(jaxpr, packed):
    """Names of the primitives whose result has an arena's element count (in
    any shape: ``unflatten`` slices through the arena's ``(rows, 128)`` view)."""
    sizes = {a.size for a in packed.arenas}
    return [
        eqn.primitive.name for eqn in _eqns(jaxpr)
        if any(getattr(v.aval, "size", None) in sizes for v in eqn.outvars)
    ]


@pytest.mark.parametrize("case", CASES)
def test_backward_packs_each_bucket_once(case):
    """The engagement counter of PR 25: one ``concatenate`` per dtype bucket
    produces the gradient arena, and nothing arena-sized is padded or summed."""
    packed = PackedParams.pack(_tree(case))
    jaxpr = jax.make_jaxpr(jax.grad(lambda pk: _loss(pk.unpack())))(packed).jaxpr
    names = _arena_wide(jaxpr, packed)
    n_single = sum(len(s.shapes) == 1 and s.total == s.padded_total
                   for s in packed.layout.specs)  # a one-piece arena needs no concat
    assert names.count("concatenate") == len(packed.arenas) - n_single
    assert not set(names) & {"pad", "add_any", "add", "dynamic_update_slice"}, names
    assert all(
        v.aval.shape == a.shape and v.aval.dtype == a.dtype
        for v, a in zip(jaxpr.outvars, packed.arenas)
    )


def test_old_transpose_is_what_the_counter_would_catch():
    """The same count on the slicing ``unflatten`` finds the padded sum, so the
    test above is able to fail. ``unflatten`` itself is unchanged: it serves
    the list-API optimizers' outputs, which nothing differentiates."""
    packed = PackedParams.pack(_tree("two_buckets"))
    jaxpr = jax.make_jaxpr(jax.grad(lambda pk: _loss(_unpack_by_slices(pk))))(packed).jaxpr
    names = _arena_wide(jaxpr, packed)
    assert {"add_any", "dynamic_update_slice"} <= set(names) and "concatenate" not in names
    fwd = jax.make_jaxpr(lambda a: unflatten(a, packed.layout.specs[0]))(packed.arenas[0])
    assert {e.primitive.name for e in fwd.jaxpr.eqns} <= {"reshape", "dynamic_slice"}


@pytest.mark.parametrize("case", ["two_buckets", "scalar_leaf"])
def test_second_order_through_unpack(case):
    """``jax.grad`` of ``jax.grad``: a Hessian-vector product at the arenas
    equals the one through the slices."""
    packed = PackedParams.pack(_tree(case))
    direction = PackedParams.pack(_tree(case, seed=1))

    def hvp(unpack):
        def directional(pk):
            g = jax.grad(lambda q: _loss(unpack(q)))(pk)
            return sum(jnp.vdot(a.astype(jnp.float32), d.astype(jnp.float32))
                       for a, d in zip(g.arenas, direction.arenas))
        return jax.grad(directional)(packed)

    new, old = hvp(lambda q: q.unpack()), hvp(_unpack_by_slices)
    for a_new, a_old in zip(new.arenas, old.arenas):
        assert np.any(np.asarray(a_new, np.float32) != 0.0)
        np.testing.assert_array_equal(np.asarray(a_new, np.float32),
                                      np.asarray(a_old, np.float32))


@pytest.mark.parametrize("case", ["two_buckets", "unused_leaf"])
def test_forward_mode_through_unpack_raises_the_documented_error(case):
    """The pack is a ``custom_vjp`` (the variant the chip picked): forward mode
    through ``unpack`` is given up, and says so."""
    packed = PackedParams.pack(_tree(case))
    tangent = PackedParams.pack(_tree(case, seed=1))
    with pytest.raises(TypeError, match="forward-mode autodiff.*custom_vjp"):
        jax.jvp(lambda pk: _loss(pk.unpack()), (packed,), (tangent,))
    # nothing else of forward mode is lost: the leaves themselves still take jvp
    leaves, t_leaves = packed.unpack(), tangent.unpack()
    out, t_out = jax.jvp(_loss, (leaves,), (t_leaves,))
    g = jax.grad(lambda pk: _loss(pk.unpack()))(packed)
    by_grad = sum(jnp.vdot(a.astype(jnp.float32), t.astype(jnp.float32))
                  for a, t in zip(g.arenas, tangent.arenas))
    np.testing.assert_allclose(t_out, by_grad, rtol=2e-2)


def test_unpack_rejects_an_arena_shorter_than_its_leaves():
    packed = PackedParams.pack(_tree("two_buckets"))
    short = packed.replace_arenas([a[:10] for a in packed.arenas])
    with pytest.raises((TypeError, ValueError)):
        short.unpack()


_HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation.7 (param_0.1: bf16[300], param_1.2: bf16[65536]) -> bf16[65536] {
  %param_1.2 = bf16[65536]{0:T(1024)(128)(2,1)} parameter(1)
  %param_0.1 = bf16[300]{0:T(512)(128)(2,1)} parameter(0)
  %constant.3 = s32[]{:T(128)} constant(40)
  ROOT %dynamic-update-slice.1 = bf16[65536]{0:T(1024)(128)(2,1)} dynamic-update-slice(%param_1.2, %param_0.1, %constant.3)
}

%fused_computation.9 (param_0.4: bf16[300], param_1.5: bf16[40]) -> pred[] {
  %param_0.4 = bf16[300]{0:T(512)(128)(2,1)} parameter(0)
  %param_1.5 = bf16[40]{0:T(512)(128)(2,1)} parameter(1)
  %pad.1 = bf16[512,128]{1,0:T(8,128)(2,1)} pad(%param_0.4, %param_1.5), padding=0_0
  ROOT %reduce.2 = pred[]{:T(512)} reduce(%pad.1, %param_1.5), dimensions={0,1}, to_apply=%or
}

ENTRY %main.1 (Arg_0.1: bf16[300], Arg_1.2: bf16[40]) -> (bf16[65536], pred[]) {
  %Arg_0.1 = bf16[300]{0:T(512)(128)(2,1)} parameter(0)
  %Arg_1.2 = bf16[40]{0:T(512)(128)(2,1)} parameter(1)
  %buf = bf16[65536]{0:T(1024)(128)(2,1)} custom-call(), custom_call_target="AllocateBuffer"
  %dus_fusion.1 = bf16[65536]{0:T(1024)(128)(2,1)} fusion(%Arg_0.1, %buf), kind=kLoop, calls=%fused_computation.7, backend_config={"window_config":{"estimated_cycles":"1234"}}
  %small.2 = bf16[300]{0:T(512)(128)(2,1)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(step)/mul"}
  %check.3 = pred[]{:T(512)} fusion(%Arg_0.1, %Arg_1.2), kind=kInput, calls=%fused_computation.9, metadata={op_name="jit(step)/amp_unscale/reduce_or"}, backend_config={"window_config":{"estimated_cycles":"99"}}
  ROOT %tuple.4 = (bf16[65536]{0:T(1024)(128)(2,1)}, pred[]{:T(512)}) tuple(%dus_fusion.1, %check.3)
}
"""


def test_offline_step_lists_the_arena_wide_fusions():
    """``tools/offline_step.py``'s reading of an optimized HLO text: a fusion
    that writes the arena, one that rebuilds it inside itself from the leaf
    cotangents (the fault of PR 25, by its lane view), and one that is neither."""
    import offline_step

    found = offline_step.arena_wide_fusions(_HLO, {65536})
    assert found == [
        ("%dus_fusion.1", 2, 1234, "(no op_name)"),
        ("%check.3", 2, 99, "jit(step)/amp_unscale/reduce_or"),
    ]
    assert offline_step.arena_wide_fusions(_HLO, {32768}) == []
