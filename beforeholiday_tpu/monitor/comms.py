"""Collective-traffic ledger — trace-time accounting of every collective the
library issues.

The reference answers "where do the bytes go" with NCCL debug logs and nsight
timelines; under jit neither exists, but something better does: every
``lax`` collective passes through Python exactly once per compilation, when
the step is TRACED. Recording there costs ZERO device time and ZERO host
syncs — the ledger is a host-side dict updated while XLA builds the program,
never while it runs (``tests/test_no_host_sync.py`` proves the module adds no
readback idioms).

Contract — what a record means:

* Each wrapper (``psum``/``pmax``/``pmin``/``all_gather``/``psum_scatter``/
  ``ppermute``/``all_to_all``) records the op kind, axis name, dtype, the
  PER-RANK local input payload bytes (``size * itemsize`` of the local
  operand — the quantity each rank hands to the interconnect), and a
  call-site tag, then delegates to the identical ``jax.lax`` op.
* Accounting is PER TRACE: one compiled step records each collective once,
  however many steps later execute from the cache. A collective inside a
  ``lax.scan``/``fori_loop`` BODY records once but executes once per
  iteration — multiply by the trip count when converting to wire bytes (the
  ring-attention k/v permutes and the pipeline tick rings are the two such
  sites here, both tagged so the caveat is findable).
* ``ledger_scope`` pushes a caller label (e.g. the TP layer name) onto a
  per-thread stack; records carry the joined stack, so mapping-level
  collectives attribute to the layer that issued them.

Query like ``dispatch_summary()``: ``comms_records()`` is the per-key
snapshot, ``comms_summary()`` rolls up by subsystem (the site tag's prefix
before the first ``.`` — ``ddp``/``tp``/``sp``/``pp``/``cp``/``zero2``/
``zero3``/``sync_bn``), ``reset_comms_ledger()`` clears between entry points.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "all_gather",
    "all_to_all",
    "comms_records",
    "comms_summary",
    "infer_tier",
    "ledger_scope",
    "pmax",
    "pmin",
    "ppermute",
    "psum",
    "psum_scatter",
    "record",
    "reset_comms_ledger",
]

_LOCK = threading.Lock()
# (kind, axis, dtype, site, scope, tier) -> {"calls": n, "bytes": b}
_RECORDS: Dict[Tuple[str, str, str, str, str, str], Dict[str, int]] = {}
_TLS = threading.local()

# Mesh axes that cross the slow inter-slice (DCN) tier. A collective whose
# axis spec touches any of these is booked as "dcn" — its slowest hop sets its
# cost — everything else is on-slice ICI. Matches parallel_state.SLICE_AXIS
# (string literal here to keep monitor/ free of parallel/ imports).
DCN_AXES = frozenset({"slice"})


def _axis_names(axis_name: Any) -> Tuple[str, ...]:
    """Axis spec → tuple of axis-name strings (handles single names and the
    tuple specs jax collectives accept)."""
    if isinstance(axis_name, (tuple, list)):
        return tuple(str(a) for a in axis_name)
    return (str(axis_name),)


def infer_tier(axis_name: Any) -> str:
    """Default tier for a collective: "dcn" if its axis spec crosses a
    slice boundary, else "ici"."""
    return "dcn" if any(a in DCN_AXES for a in _axis_names(axis_name)) else "ici"


def _scope_stack() -> List[str]:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


@contextlib.contextmanager
def ledger_scope(name: str):
    """Label every collective recorded inside the block (nests; per-thread).
    The TP/SP layers wrap their bodies so mapping-level collectives attribute
    to ``column_parallel_linear`` etc. rather than to the shared helpers."""
    st = _scope_stack()
    st.append(name)
    try:
        yield
    finally:
        st.pop()


def _payload_bytes(tree: Any) -> Dict[str, int]:
    """Per-dtype local input payload bytes over the pytree's leaves. Works on
    tracers (shape/dtype are static), ``jax.ShapeDtypeStruct`` stand-ins, and
    plain Python scalars."""
    out: Dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        dtype = getattr(leaf, "dtype", None)
        dt = np.dtype(dtype) if dtype is not None else np.dtype(
            jnp.result_type(leaf)
        )
        shape = getattr(leaf, "shape", None)
        if shape is None:
            shape = jnp.shape(leaf)
        n = math.prod(shape)
        out[dt.name] = out.get(dt.name, 0) + n * dt.itemsize
    return out


def record(
    kind: str, axis_name: Any, tree: Any, *, site: str, logical: Any = None,
    tier: str = None,
) -> None:
    """Account one collective call (host-side, trace-time). Wrappers call
    this; call it directly only for a collective with no wrapper here.

    ``tree`` is the operand actually handed to the interconnect, so ``bytes``
    is always the WIRE payload. A compressed collective (bf16-on-the-wire over
    a logically-fp32 gradient) passes the uncompressed stand-in via
    ``logical`` — pass ``jax.ShapeDtypeStruct``s to avoid building dead cast
    ops — and the row's ``logical_bytes`` then records what the payload WOULD
    have cost uncompressed. For ordinary collectives
    ``logical_bytes == bytes``.

    ``tier`` books the record against an interconnect tier ("ici" on-slice,
    "dcn" inter-slice); when omitted it is inferred from the axis spec via
    ``infer_tier`` — pre-tier call sites keep summarizing unchanged."""
    scope = ".".join(_scope_stack())
    if tier is None:
        tier = infer_tier(axis_name)
    payload = _payload_bytes(tree)
    wire_total = sum(payload.values())
    logical_total = (
        sum(_payload_bytes(logical).values())
        if logical is not None
        else wire_total
    )
    with _LOCK:
        for dtype_name, nbytes in payload.items():
            key = (kind, str(axis_name), dtype_name, site, scope, tier)
            row = _RECORDS.setdefault(
                key, {"calls": 0, "bytes": 0, "logical_bytes": 0}
            )
            row["calls"] += 1
            row["bytes"] += nbytes
            # multi-dtype wire payloads split the logical total
            # proportionally; the single-dtype case (every compressed call
            # site here) is exact
            row["logical_bytes"] += (
                logical_total * nbytes // wire_total if wire_total else nbytes
            )
    # mirror into the active timeline (if one is recording) as an instant
    # marker, so the Perfetto view shows WHICH collectives a traced region
    # issued; deferred full-dotted-path import — the package attribute
    # ``trace`` is the spans profiler function, not the submodule
    from beforeholiday_tpu.monitor.trace import active_recorder

    rec = active_recorder()
    if rec is not None:
        rec.instant(
            f"{kind}:{site}",
            args={"axis": str(axis_name), "scope": scope, "tier": tier,
                  **payload},
        )


# ------------------------------------------------------------------ wrappers
# Each is signature-compatible with its jax.lax namesake plus a required
# keyword ``site`` tag; the ledger sees the LOCAL input operand.


def psum(x, axis_name, *, site: str, axis_index_groups=None, logical=None,
         tier=None):
    record("psum", axis_name, x, site=site, logical=logical, tier=tier)
    return jax.lax.psum(x, axis_name, axis_index_groups=axis_index_groups)


def pmax(x, axis_name, *, site: str, axis_index_groups=None, tier=None):
    record("pmax", axis_name, x, site=site, tier=tier)
    return jax.lax.pmax(x, axis_name, axis_index_groups=axis_index_groups)


def pmin(x, axis_name, *, site: str, axis_index_groups=None, tier=None):
    record("pmin", axis_name, x, site=site, tier=tier)
    return jax.lax.pmin(x, axis_name, axis_index_groups=axis_index_groups)


def all_gather(
    x, axis_name, *, site: str, axis: int = 0, tiled: bool = False,
    logical=None, tier=None,
):
    record("all_gather", axis_name, x, site=site, logical=logical, tier=tier)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def psum_scatter(
    x, axis_name, *, site: str, scatter_dimension: int = 0,
    tiled: bool = False, logical=None, tier=None,
):
    record("psum_scatter", axis_name, x, site=site, logical=logical,
           tier=tier)
    return jax.lax.psum_scatter(
        x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled
    )


def ppermute(x, axis_name, perm, *, site: str, tier=None):
    record("ppermute", axis_name, x, site=site, tier=tier)
    return jax.lax.ppermute(x, axis_name, perm)


def all_to_all(
    x, axis_name, split_axis, concat_axis, *, site: str, tiled: bool = False,
    logical=None, tier=None,
):
    record("all_to_all", axis_name, x, site=site, logical=logical, tier=tier)
    return jax.lax.all_to_all(
        x, axis_name, split_axis, concat_axis, tiled=tiled
    )


# ------------------------------------------------------------------- queries


def comms_records() -> List[Dict[str, object]]:
    """Per-key snapshot, one JSON-ready row per distinct
    (kind, axis, dtype, site, scope, tier): ``{"kind", "axis", "dtype",
    "site", "scope", "tier", "calls", "bytes", "logical_bytes"}``.
    ``calls``/``bytes`` count trace-time issues (see the module contract for
    the scan-body multiplier caveat); ``bytes`` is the WIRE payload,
    ``logical_bytes`` the uncompressed equivalent (equal unless the site
    compresses); ``tier`` is the interconnect tier the payload crossed
    ("ici" on-slice, "dcn" inter-slice)."""
    with _LOCK:
        items = [(k, dict(v)) for k, v in _RECORDS.items()]
    return sorted(
        (
            {
                "kind": kind,
                "axis": axis,
                "dtype": dtype,
                "site": site,
                "scope": scope,
                "tier": tier,
                "calls": c["calls"],
                "bytes": c["bytes"],
                "logical_bytes": c.get("logical_bytes", c["bytes"]),
            }
            for (kind, axis, dtype, site, scope, tier), c in items
        ),
        key=lambda r: (r["site"], r["kind"], r["dtype"], r["scope"],
                       r["tier"]),
    )


def comms_summary() -> List[Dict[str, object]]:
    """Subsystem rollup, one row per site-tag prefix (the segment before the
    first ``.``): ``{"subsystem", "sites", "calls", "bytes", "logical_bytes",
    "compression_ratio", "by_kind", "by_tier"}`` — the shape
    ``__graft_entry__`` prints, mirroring ``dispatch_summary``. ``bytes``
    totals are WIRE traffic (actual interconnect cost);
    ``compression_ratio = logical_bytes / bytes`` is 1.0 for uncompressed
    subsystems and ~2.0 for bf16-on-the-wire over fp32. ``by_tier`` splits
    the same totals per interconnect tier ("ici"/"dcn"), each with its own
    ``compression_ratio`` — the oracle surface for proving a hierarchical
    reduce moved 1/slice_size of the flat payload over DCN. Records written
    before the tier field existed roll up under "ici" (every pre-tier call
    site was single-slice)."""
    rows = comms_records()
    by_sub: Dict[str, Dict[str, object]] = {}
    sites_seen: Dict[str, set] = {}
    for r in rows:
        sub = str(r["site"]).split(".", 1)[0]
        row = by_sub.setdefault(
            sub, {"subsystem": sub, "sites": 0, "calls": 0, "bytes": 0,
                  "logical_bytes": 0, "by_kind": {}, "by_tier": {}}
        )
        sites_seen.setdefault(sub, set()).add(r["site"])
        row["calls"] += r["calls"]
        row["bytes"] += r["bytes"]
        row["logical_bytes"] += r["logical_bytes"]
        kind_row = row["by_kind"].setdefault(
            r["kind"], {"calls": 0, "bytes": 0}
        )
        kind_row["calls"] += r["calls"]
        kind_row["bytes"] += r["bytes"]
        tier_row = row["by_tier"].setdefault(
            r.get("tier", "ici"),
            {"calls": 0, "bytes": 0, "logical_bytes": 0},
        )
        tier_row["calls"] += r["calls"]
        tier_row["bytes"] += r["bytes"]
        tier_row["logical_bytes"] += r["logical_bytes"]
    for sub, row in by_sub.items():
        row["sites"] = len(sites_seen[sub])
        row["compression_ratio"] = (
            round(row["logical_bytes"] / row["bytes"], 4)
            if row["bytes"] else 1.0
        )
        for tier_row in row["by_tier"].values():
            tier_row["compression_ratio"] = (
                round(tier_row["logical_bytes"] / tier_row["bytes"], 4)
                if tier_row["bytes"] else 1.0
            )
    return sorted(by_sub.values(), key=lambda r: r["subsystem"])


def reset_comms_ledger() -> None:
    """Clear the ledger (call between entry points to scope a query; jit
    caching means an already-compiled step will NOT re-record on re-run)."""
    with _LOCK:
        _RECORDS.clear()
