"""Shared Pallas dispatch policy and padding helpers for the fused-op kernels."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from beforeholiday_tpu.guard.dispatch import count_forced as _count_forced


def interpret_default() -> bool:
    """Pallas compiles natively on TPU; elsewhere the interpreter runs."""
    return jax.default_backend() != "tpu"


def in_fully_manual_context() -> bool:
    """True when tracing inside ``shard_map`` over every mesh axis with vma
    tracking off (``check_vma=False``, the repo convention).

    There the per-shard program sees exactly one device, so an opaque
    ``pallas_call`` needs no GSPMD partitioning — the safe (and fast) place
    for fused kernels on a pod. Under ``check_vma=True`` (jax's default) a
    pallas_call is rejected at trace time because its out_shapes carry no
    ``vma``; the default must stay jnp there rather than regress working
    user code."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return False
    if not all(t == jax.sharding.AxisType.Manual for t in mesh.axis_types):
        return False
    try:
        from jax._src.config import _check_vma

        return not _check_vma.value
    except (ImportError, AttributeError):
        # _check_vma is private jax state with no public spelling: if it
        # moves (ImportError) or changes shape (AttributeError on the name or
        # on ``.value``) the default stays jnp rather than tracing a
        # pallas_call the vma checker may reject. chip_smoke.py fails on the
        # resulting missing pallas dispatches, so this cannot go unnoticed.
        return False


def resolve_impl(impl: Optional[str]) -> str:
    """ONE dispatch policy for every fused op (multi_tensor / normalization /
    softmax — the reference's per-extension availability checks,
    e.g. fused_softmax.py:164 ``is_kernel_available``).

    ``pallas_call`` is an opaque custom call to the GSPMD partitioner: under a
    >1-device auto-sharded program it would force replication/all-gathers on
    sharded operands. Default to pallas only where the traced program owns a
    single device per shard — decided from the trace's ambient mesh, never
    from how many chips the host happens to have:

    * no ambient mesh (plain ``jit``/eager on the one device the inputs are
      committed to — also on a multi-chip host), or
    * inside ``shard_map`` over ALL mesh axes (fully-manual context).

    Anywhere else (a ``jax.sharding.set_mesh`` scope with auto/explicit axes,
    which is how every GSPMD program in this repo runs; CPU/GPU) the jnp path
    partitions transparently. Explicit ``impl=`` is always honored.

    One GSPMD case is invisible at trace time: a plain ``jit`` with no
    ``set_mesh`` whose INPUTS are sharded over several devices. It resolves
    to pallas, and Mosaic then refuses to lower ("Mosaic kernels cannot be
    automatically partitioned") — loud, not a quiet slow path. Run such a
    program under ``set_mesh`` or pass ``impl="jnp"``.

    Note: inside shard_map the kernels require ``check_vma=False`` (the
    repo-wide convention, see parallel/distributed.py) — jax's interpret-mode
    vma tracking rejects pallas_call bodies (jax#: "pass check_vma=False").
    """
    if impl is None:
        on_tpu = jax.default_backend() == "tpu"
        one_device_per_shard = (
            not jax.sharding.get_abstract_mesh().axis_names
            or in_fully_manual_context()
        )
        impl = "pallas" if on_tpu and one_device_per_shard else "jnp"
    if impl not in ("pallas", "jnp"):
        raise ValueError(f"impl must be 'pallas' or 'jnp', got {impl!r}")
    return impl


def dispatch(op: str, impl: Optional[str], available: bool, why: str, *arrays, statics):
    """``(impl, forced)`` by the one policy of the kernels with a shape of their
    own (``deltanet``, ``short_conv``, ``gated_delta``, ``ssd``,
    ``grouped_matmul``): :func:`resolve_impl`, then ``jnp`` where the call is off
    the kernels' shapes (``available`` false) — booked once under ``op`` with
    ``arrays`` and ``statics`` as ``guard.dispatch`` keys a probe — unless
    ``pallas`` was asked for by name, which raises with the op's own ``why``."""
    forced = impl is not None
    impl = resolve_impl(impl)
    if impl == "pallas" and not available:
        if forced:
            raise ValueError(f"impl='pallas' forced but {why}; pass impl=None for the "
                             "automatic fallback")
        impl = "jnp"
        _count_forced(op, impl, *arrays, statics=statics)
    return impl, forced


def resolve_impl_streaming(impl: Optional[str]) -> str:
    """Dispatch for the BANDWIDTH-BOUND elementwise/reduction arena family
    (multi_tensor adam/sgd/lamb/scale/axpby/l2norm...): default ``jnp``
    everywhere, including single-device TPU.

    Measurement-driven (r5, v5-lite chip, 46M fp32 Adam arena, fori_loop
    meter): XLA fuses the straight-line update into one near-roofline pass —
    ~1.5 ms vs the Pallas kernel's ~1.8 ms (with input_output_aliasing; 4.2 ms
    without). Single-buffer streaming on this chip caps at ~670 GB/s while
    many-small-buffer elementwise reaches ~1.4 TB/s aggregate, and XLA's
    fusion machinery sits closer to that limit than a hand-tiled grid for
    pure streaming work. Pallas earns its keep where XLA CANNOT fuse (flash
    attention, row-softmax, layernorm custom VJPs) — for streaming math the
    TPU-native answer is the compiler, with the kernels kept as a verified,
    selectable alternate (``impl="pallas"``). This mirrors ops/dense.py's
    XLA-fused-by-contract argument; the reference needed amp_C because torch
    eager CANNOT fuse (csrc/amp_C_frontend.cpp) — under XLA that premise
    inverts. Explicit ``impl=`` is always honored.
    """
    if impl is None:
        return "jnp"
    if impl not in ("pallas", "jnp"):
        raise ValueError(f"impl must be 'pallas' or 'jnp', got {impl!r}")
    return impl


def pad_rows(x: jax.Array, block_rows: int):
    """Pad the leading dim to a multiple of block_rows (any rank).
    Returns (padded, rows)."""
    rows = x.shape[0]
    padded = ((rows + block_rows - 1) // block_rows) * block_rows
    if padded != rows:
        x = jnp.pad(x, ((0, padded - rows),) + ((0, 0),) * (x.ndim - 1))
    return x, rows
