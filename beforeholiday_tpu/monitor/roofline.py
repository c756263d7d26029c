"""Roofline / MFU ledger — analytic FLOPs & bytes per jitted entry, joined
with measured wall time into attribution numbers.

The memory ledger (``monitor/memory.py``) answers "how much HBM does each
executable need"; this module answers the campaign question ROADMAP item 5
opens: *which entries burn the gap to the roofline*. XLA already counts the
work — every compiled executable carries a cost analysis (flops, bytes
accessed) reachable through the AOT API — so ``track_costs`` records it with
the same registry pattern as ``track_memory``: wrap ABOVE ``jax.jit``, one
AOT compile per new abstract signature, the executable cached and reused.
When the backend omits cost keys (CPU builds and some XLA versions do), a
jaxpr-walking fallback computes the closed-form counts instead:
``dot_general`` is 2·M·N·K, convs count 2·out·kernel, reductions count their
input, elementwise ops one flop per output element.

FLOPs alone are not attribution — they need wall time. Timing is the
CALLER's job (this module must stay free of device syncs; the no-host-sync
scan covers it with zero sanctions): measure a step however you already do
(fenced steps, span wall-times) and hand the seconds to
:func:`record_wall_time`, or let :func:`join_spans` pull durations for spans
named after tracked entries off a trace recorder. ``roofline_summary`` then
joins analytic work with measured time against a registrable
:class:`ChipSpec`:

* ``mfu``       — flops / second / peak_tflops (model-flops utilization);
* ``bw_util``   — bytes / second / hbm_gbs (HBM bandwidth utilization);
* ``bound``     — compute / memory (arithmetic intensity vs the ridge
  point) or comms (recorded comms time dominates the step).

:func:`perf_report` is the one-call rollup ``__graft_entry__`` prints:
per-entry ``<entry>_mfu`` / ``<entry>_bw_util`` keys plus the
dispatch/comms/compile summaries.

Usage::

    monitor.register_chip_spec(name="v5p", peak_tflops=459.0, hbm_gbs=2765.0)

    @monitor.track_costs("train_step")
    @jax.jit
    def train_step(params, batch): ...

    t0 = time.perf_counter(); train_step(...); jax.block_until_ready(...)
    monitor.record_wall_time("train_step", time.perf_counter() - t0)
    monitor.perf_report(chip="v5p")
    # {"train_step_mfu": 0.41, "train_step_bw_util": 0.63, ...}
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import numpy as np

from beforeholiday_tpu.monitor.compile import _sig_of

__all__ = [
    "ChipSpec",
    "chip_specs",
    "estimate_costs",
    "get_chip_spec",
    "join_spans",
    "measure_costs",
    "perf_report",
    "record_wall_time",
    "register_chip_spec",
    "reset_roofline_ledger",
    "roofline_records",
    "roofline_summary",
    "track_costs",
]


# ------------------------------------------------------------------ chip spec
@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Peak numbers utilization is measured against. ``peak_tflops`` is the
    dense-matmul peak for the dtype you train in (bf16 for the TPU rows);
    ``hbm_gbs`` is peak memory bandwidth in GB/s. ``fp8_peak_tflops`` is the
    quantized-matmul peak that FLOPs booked as ``fp8_flops`` (O6 GEMMs) are
    measured against; None means the part publishes no fp8 rate, and an
    entry that books fp8 FLOPs against it gets no MFU rather than an assumed
    multiple of the dense peak."""

    name: str
    peak_tflops: float
    hbm_gbs: float
    fp8_peak_tflops: Optional[float] = None

    @property
    def ridge_flops_per_byte(self) -> float:
        """Arithmetic intensity at which the roofline bends: entries above it
        are compute-bound, below it memory-bound."""
        return (self.peak_tflops * 1e12) / (self.hbm_gbs * 1e9)


_SPECS_LOCK = threading.Lock()
_CHIP_SPECS: Dict[str, ChipSpec] = {}

# PUBLISHED peaks, keyed by the string ``jax.devices()[0].device_kind`` prints
# on that part. Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
# bf16, 819 GB/s HBM; it lists 393 TOP/s int8 and NO fp8 rate. A self-measured
# probe is an "achievable" number, never the utilization denominator.
_PUBLISHED_TPU = (
    ChipSpec("TPU v5 lite", peak_tflops=197.0, hbm_gbs=819.0),
)
# CPU proxy so the 8-device host mesh produces finite ratios instead of ~0
# against a TPU peak (counts-only runs; not a device metric).
_CPU_PROXY = ChipSpec("cpu_proxy", peak_tflops=0.2, hbm_gbs=40.0)


def register_chip_spec(
    spec: Optional[ChipSpec] = None,
    *,
    name: Optional[str] = None,
    peak_tflops: Optional[float] = None,
    hbm_gbs: Optional[float] = None,
    fp8_peak_tflops: Optional[float] = None,
) -> ChipSpec:
    """Register (or overwrite) a chip spec by name. Pass a :class:`ChipSpec`
    or the fields as keywords. Returns the registered spec."""
    if spec is None:
        if name is None or peak_tflops is None or hbm_gbs is None:
            raise ValueError(
                "register_chip_spec needs a ChipSpec or all of "
                "name/peak_tflops/hbm_gbs"
            )
        spec = ChipSpec(
            str(name), float(peak_tflops), float(hbm_gbs),
            float(fp8_peak_tflops) if fp8_peak_tflops is not None else None,
        )
    if spec.peak_tflops <= 0 or spec.hbm_gbs <= 0 or (
        spec.fp8_peak_tflops is not None and spec.fp8_peak_tflops <= 0
    ):
        raise ValueError(f"chip peaks must be positive, got {spec}")
    with _SPECS_LOCK:
        _CHIP_SPECS[spec.name] = spec
    return spec


def get_chip_spec(name: str) -> ChipSpec:
    with _SPECS_LOCK:
        if name not in _CHIP_SPECS:
            raise KeyError(
                f"unknown chip spec {name!r}; registered: "
                f"{sorted(_CHIP_SPECS)} (add via register_chip_spec)"
            )
        return _CHIP_SPECS[name]


def chip_specs() -> Dict[str, ChipSpec]:
    with _SPECS_LOCK:
        return dict(_CHIP_SPECS)


def _resolve_chip(chip: Union[ChipSpec, str, None]) -> ChipSpec:
    if isinstance(chip, ChipSpec):
        return chip
    if isinstance(chip, str):
        return get_chip_spec(chip)
    # default: the published peaks of the device_kind this process runs on
    # (an unknown kind raises — no default TPU), the CPU proxy off-TPU —
    # never silently compare a host run to a TPU peak
    if jax.default_backend() != "tpu":
        return _CPU_PROXY
    return get_chip_spec(jax.devices()[0].device_kind)


for _spec in _PUBLISHED_TPU + (_CPU_PROXY,):
    register_chip_spec(_spec)


# ------------------------------------------------------- XLA cost extraction
def _xla_costs(compiled: Any) -> Optional[Dict[str, float]]:
    """``Compiled.cost_analysis()`` → ``{"flops", "bytes_accessed"}`` with
    missing keys as None. Returns None when the backend offers no analysis.
    The dict-vs-[dict] return shape varies across jax versions."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend without cost analysis
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed", ca.get("bytes_accessed"))
    out: Dict[str, float] = {}
    # XLA reports -1 (or 0 on some CPU builds) when it did not count
    out["flops"] = float(flops) if flops is not None and flops > 0 else None
    out["bytes_accessed"] = (
        float(nbytes) if nbytes is not None and nbytes > 0 else None
    )
    return out


def _aot_compile(fn: Callable, args, kwargs):
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    try:
        return lower(*args, **kwargs).compile()
    except Exception:  # noqa: BLE001 — backend without AOT support
        return None


# --------------------------------------------------------- jaxpr-walk fallback
# One flop per output element; comparisons/selects count like arithmetic.
_ELTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "atan2",
    "max", "min", "and", "or", "xor", "not", "neg", "sign", "abs",
    "exp", "exp2", "expm1", "log", "log1p", "sqrt", "rsqrt", "cbrt",
    "sin", "cos", "tan", "asin", "acos", "atan",
    "sinh", "cosh", "tanh", "erf", "erfc", "erf_inv", "logistic",
    "floor", "ceil", "round", "clamp", "select_n", "nextafter",
    "eq", "ne", "lt", "le", "gt", "ge", "square",
})

_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
})


def _jaxprs_in(v: Any):
    """Yield every jaxpr reachable inside an eqn param value (duck-typed so
    it survives jax.core relocations across versions)."""
    if hasattr(v, "eqns") and hasattr(v, "invars"):
        yield v
    elif hasattr(v, "jaxpr"):
        yield from _jaxprs_in(v.jaxpr)
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _jaxprs_in(x)


def _shape_of(var: Any):
    aval = getattr(var, "aval", None)
    return getattr(aval, "shape", None)


def _out_elems(eqn) -> float:
    return float(max(
        (math.prod(s) for s in map(_shape_of, eqn.outvars) if s is not None),
        default=0,
    ))


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        lhs_shape = _shape_of(eqn.invars[0]) or ()
        k = math.prod(lhs_shape[d] for d in lhs_contract) if lhs_contract else 1
        return 2.0 * _out_elems(eqn) * float(k)
    if name == "conv_general_dilated":
        rhs_shape = _shape_of(eqn.invars[1]) or ()
        dn = eqn.params["dimension_numbers"]
        out_ch = rhs_shape[dn.rhs_spec[0]] if rhs_shape else 1
        per_out = math.prod(rhs_shape) / max(out_ch, 1)
        return 2.0 * _out_elems(eqn) * per_out
    if name in _REDUCE:
        return float(sum(
            math.prod(s) for s in map(_shape_of, eqn.invars) if s is not None
        ))
    if name in _ELTWISE:
        return _out_elems(eqn)
    return 0.0


def _walk_flops(jaxpr, mult: float, by_prim: Dict[str, float]) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        subs = [j for v in eqn.params.values() for j in _jaxprs_in(v)]
        if subs:
            sub_mult = mult
            if name == "scan":
                sub_mult = mult * float(eqn.params.get("length", 1) or 1)
            if name == "cond":
                # branches are alternatives — charge the most expensive one
                branch_costs = [
                    _walk_flops(j, sub_mult, by_prim) for j in subs
                ]
                total += max(branch_costs, default=0.0)
            else:
                for j in subs:
                    total += _walk_flops(j, sub_mult, by_prim)
            continue
        f = _eqn_flops(eqn) * mult
        if f:
            by_prim[name] = by_prim.get(name, 0.0) + f
            total += f
    return total


def _aval_bytes(var: Any) -> int:
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return math.prod(shape) * np.dtype(dtype).itemsize


def estimate_costs(fn: Callable, *args, **kwargs) -> Dict[str, Any]:
    """The jaxpr-walking fallback, directly callable: trace ``fn`` abstractly
    and return ``{"flops", "bytes_accessed", "by_primitive", "method"}``.
    FLOPs follow the closed forms (dot_general = 2·out·K, conv = 2·out·kernel,
    reductions = input elements, elementwise = 1/element); bytes are the
    jaxpr's input + output aval sizes (a lower bound — XLA temps are not
    visible at this level). Scan bodies multiply by trip count; cond charges
    its most expensive branch. Host-only: nothing executes on device."""
    # unwrap tracking decorators (track_costs/track_memory dispatch to a
    # cached compiled executable, which cannot be re-traced) down to the
    # first function with an AOT surface — or the bare python callable
    while hasattr(fn, "__wrapped__") and not hasattr(fn, "lower"):
        fn = fn.__wrapped__
    closed = jax.make_jaxpr(lambda *a, **k: fn(*a, **k))(*args, **kwargs)
    by_prim: Dict[str, float] = {}
    flops = _walk_flops(closed.jaxpr, 1.0, by_prim)
    nbytes = sum(_aval_bytes(v) for v in closed.jaxpr.invars)
    nbytes += sum(_aval_bytes(v) for v in closed.jaxpr.outvars)
    return {
        "flops": flops,
        "bytes_accessed": float(nbytes),
        "by_primitive": {
            k: v for k, v in sorted(by_prim.items(), key=lambda kv: -kv[1])
        },
        "method": "jaxpr",
    }


def _cost_record(fn: Callable, args, kwargs, compiled: Any) -> Optional[Dict]:
    """Best-available analytic costs: XLA's own numbers when the compiled
    executable reports them, the jaxpr walk for whatever it omits."""
    rec: Dict[str, Any] = {
        "flops": None, "bytes_accessed": None,
        "method": None, "by_primitive": None,
    }
    xla = _xla_costs(compiled) if compiled is not None else None
    if xla is not None:
        if xla["flops"] is not None:
            rec["flops"] = xla["flops"]
            rec["method"] = "xla"
        if xla["bytes_accessed"] is not None:
            rec["bytes_accessed"] = xla["bytes_accessed"]
    if rec["flops"] is None or rec["bytes_accessed"] is None:
        try:
            est = estimate_costs(fn, *args, **kwargs)
        except Exception:  # noqa: BLE001 — untraceable fn: record what we have
            est = None
        if est is not None:
            if rec["flops"] is None:
                rec["flops"] = est["flops"]
                rec["method"] = "jaxpr"
            if rec["bytes_accessed"] is None:
                rec["bytes_accessed"] = est["bytes_accessed"]
            rec["by_primitive"] = est["by_primitive"]
    if rec["flops"] is None and rec["bytes_accessed"] is None:
        return None
    return rec


# ------------------------------------------------------------------- ledger
_LOCK = threading.Lock()
# entry -> {"signatures": {sig: {"costs": dict|None, "compiled": obj|None,
#                                "first_call": int}},
#           "calls": int, "seconds": float, "timed_steps": int,
#           "comms_seconds": float, "flops_override": float|None,
#           "fp8_flops_override": float|None, "bytes_override": float|None}
_ENTRIES: Dict[str, Dict[str, Any]] = {}


def _entry_row(entry: str) -> Dict[str, Any]:
    # caller holds _LOCK
    return _ENTRIES.setdefault(entry, {
        "signatures": {}, "calls": 0,
        "seconds": 0.0, "timed_steps": 0, "comms_seconds": 0.0,
        "flops_override": None, "fp8_flops_override": None,
        "bytes_override": None,
    })


def _mirror_to_trace(entry: str, costs: Optional[Dict[str, Any]]) -> None:
    if costs is None:
        return
    from beforeholiday_tpu.monitor.trace import active_recorder

    rec = active_recorder()
    if rec is not None:
        rec.instant(f"costs:{entry}", args={
            "flops": costs["flops"],
            "bytes_accessed": costs["bytes_accessed"],
            "method": costs["method"],
        })


def track_costs(entry: str):
    """Decorator: record analytic FLOPs/bytes per abstract signature.

    Apply OUTSIDE ``jax.jit`` (same contract and caveats as
    ``track_memory`` — the cached AOT executable is called directly, so
    arguments must be arrays/pytrees, not Python scalars needing weak-type
    handling)."""

    def deco(fn):
        def wrapper(*args, **kwargs):
            sig = _sig_of(args, kwargs)
            with _LOCK:
                row = _entry_row(entry)
                row["calls"] += 1
                rec = row["signatures"].get(sig)
                calls = row["calls"]
            if rec is None:
                compiled = _aot_compile(fn, args, kwargs)
                costs = _cost_record(fn, args, kwargs, compiled)
                with _LOCK:
                    rec = _entry_row(entry)["signatures"].setdefault(
                        sig,
                        {"costs": costs, "compiled": compiled,
                         "first_call": calls},
                    )
                _mirror_to_trace(entry, rec["costs"])
            compiled = rec["compiled"]
            if compiled is not None:
                return compiled(*args, **kwargs)
            return fn(*args, **kwargs)

        wrapper.__name__ = getattr(fn, "__name__", "wrapper")
        wrapper.__wrapped__ = fn
        return wrapper

    return deco


def measure_costs(
    fn: Callable, *args, entry: Optional[str] = None, **kwargs
) -> Optional[Dict[str, Any]]:
    """One-off analytic measurement: compile/trace ``fn`` for these arguments
    and return its cost dict (``flops``/``bytes_accessed``/``method``).
    With ``entry`` the costs also land in the ledger (calls stay 0 — the
    function is analyzed, not executed)."""
    compiled = _aot_compile(fn, args, kwargs)
    costs = _cost_record(fn, args, kwargs, compiled)
    if entry is not None:
        sig = _sig_of(args, kwargs)
        with _LOCK:
            _entry_row(entry)["signatures"].setdefault(
                sig, {"costs": costs, "compiled": None, "first_call": 0}
            )
        _mirror_to_trace(entry, costs)
    return costs


def record_wall_time(
    entry: str,
    seconds: float,
    *,
    steps: int = 1,
    flops: Optional[float] = None,
    fp8_flops: Optional[float] = None,
    bytes_accessed: Optional[float] = None,
    comms_seconds: float = 0.0,
) -> None:
    """Attribute measured wall time to an entry — the join point between the
    caller's timing (fenced steps, span durations) and the analytic costs.

    ``seconds`` covers ``steps`` executions. ``flops``/``bytes_accessed``
    are optional PER-STEP overrides for callers that know the analytic count
    in closed form (6·N·tokens); they take precedence over the tracked
    costs so the headline MFU matches the caller's own arithmetic.
    ``fp8_flops`` is the per-step share of ``flops``-class work executed as
    quantized (fp8) matmuls — it is measured against the chip's fp8 peak in
    the MFU, so pass the SPLIT (``flops`` excluding the fp8 share), not the
    total twice. ``comms_seconds`` (also per the whole measurement) feeds the
    comms-bound classification. Host floats in, host floats stored — no
    device work."""
    if seconds < 0 or steps < 1:
        raise ValueError(f"need seconds >= 0 and steps >= 1, got "
                         f"{seconds}/{steps}")
    with _LOCK:
        row = _entry_row(entry)
        row["seconds"] += float(seconds)
        row["timed_steps"] += int(steps)
        row["comms_seconds"] += float(comms_seconds)
        if flops is not None:
            row["flops_override"] = float(flops)
        if fp8_flops is not None:
            row["fp8_flops_override"] = float(fp8_flops)
        if bytes_accessed is not None:
            row["bytes_override"] = float(bytes_accessed)


def join_spans(events: Optional[List[Dict[str, Any]]] = None) -> int:
    """Pull wall time off a trace timeline: every complete ``B``/``E`` span
    whose name matches a tracked entry contributes its duration (one step
    per span) via :func:`record_wall_time`. ``events`` defaults to the active
    recorder's. Returns the number of spans joined. Call once per timeline —
    durations accumulate."""
    if events is None:
        from beforeholiday_tpu.monitor.trace import active_recorder

        rec = active_recorder()
        if rec is None:
            return 0
        events = rec.events()
    with _LOCK:
        tracked = set(_ENTRIES)
    from beforeholiday_tpu.monitor.trace import span_intervals

    joined = 0
    for iv in span_intervals(events):
        if iv["name"] in tracked:
            record_wall_time(
                iv["name"], (iv["end"] - iv["start"]) / 1e6, steps=1
            )
            joined += 1
    return joined


# ------------------------------------------------------------------- queries
def roofline_records() -> Dict[str, Dict[str, Any]]:
    """Raw ledger snapshot (JSON-ready; cached executables omitted)."""
    with _LOCK:
        out = {}
        for name, row in _ENTRIES.items():
            out[name] = {
                "calls": row["calls"],
                "seconds": row["seconds"],
                "timed_steps": row["timed_steps"],
                "comms_seconds": row["comms_seconds"],
                "flops_override": row["flops_override"],
                "fp8_flops_override": row["fp8_flops_override"],
                "bytes_override": row["bytes_override"],
                "signatures": [
                    dict(r["costs"]) if r["costs"] is not None else None
                    for r in row["signatures"].values()
                ],
            }
        return out


def roofline_summary(
    chip: Union[ChipSpec, str, None] = None,
) -> List[Dict[str, Any]]:
    """One row per entry: analytic work joined with recorded wall time
    against ``chip`` (default: the published peaks of this TPU's
    ``device_kind``, CPU proxy elsewhere). Entries without recorded time
    still classify by arithmetic intensity but carry ``mfu``/``bw_util`` of
    None; so does the ``mfu`` of an entry that books fp8 FLOPs against a chip
    with no fp8 rate."""
    spec = _resolve_chip(chip)
    ridge = spec.ridge_flops_per_byte
    rows = []
    for name, row in sorted(roofline_records().items()):
        costs = [c for c in row["signatures"] if c is not None]
        flops = row["flops_override"]
        method = "override" if flops is not None else None
        if flops is None:
            sig_flops = [c["flops"] for c in costs if c["flops"] is not None]
            flops = max(sig_flops, default=None)
            if flops is not None:
                method = next(
                    c["method"] for c in costs if c["flops"] is not None
                )
        nbytes = row["bytes_override"]
        if nbytes is None:
            sig_bytes = [
                c["bytes_accessed"] for c in costs
                if c["bytes_accessed"] is not None
            ]
            nbytes = max(sig_bytes, default=None)

        fp8_flops = row["fp8_flops_override"]

        steps = row["timed_steps"]
        sec = row["seconds"] / steps if steps else None
        comms_frac = (
            row["comms_seconds"] / row["seconds"] if row["seconds"] else None
        )
        mfu = None
        bw_util = None
        fp8_priced = not fp8_flops or spec.fp8_peak_tflops is not None
        if sec and fp8_priced and (flops is not None or fp8_flops is not None):
            # each precision class utilizes its own peak: bf16-class flops
            # against peak_tflops, quantized-GEMM flops against the fp8 peak
            mfu = (flops or 0.0) / spec.peak_tflops
            if fp8_flops:
                mfu += fp8_flops / spec.fp8_peak_tflops
            mfu = mfu / sec / 1e12
        if sec and nbytes is not None:
            bw_util = nbytes / sec / 1e9 / spec.hbm_gbs
        total_flops = (
            (flops or 0.0) + (fp8_flops or 0.0)
            if flops is not None or fp8_flops is not None
            else None
        )
        intensity = (
            total_flops / nbytes if total_flops is not None and nbytes else None
        )
        if comms_frac is not None and comms_frac >= 0.5:
            bound = "comms"
        elif intensity is not None:
            bound = "compute" if intensity >= ridge else "memory"
        else:
            bound = "unknown"
        rows.append({
            "entry": name,
            "calls": row["calls"],
            "signatures": len(row["signatures"]),
            "method": method,
            "flops_per_step": flops,
            "fp8_flops_per_step": fp8_flops,
            "bytes_per_step": nbytes,
            "seconds_per_step": sec,
            "timed_steps": steps,
            "comms_fraction": comms_frac,
            "mfu": mfu,
            "bw_util": bw_util,
            "intensity_flops_per_byte": intensity,
            "ridge_flops_per_byte": ridge,
            "bound": bound,
        })
    return rows


def reset_roofline_ledger() -> None:
    """Forget all entries (costs, cached executables, and recorded times).
    Tracked functions re-analyze on their next call."""
    with _LOCK:
        _ENTRIES.clear()


# ---------------------------------------------------------------- the report
def perf_report(*, chip: Union[ChipSpec, str, None] = None) -> Dict[str, Any]:
    """The one-call attribution rollup: roofline rows flattened into
    ``<entry>_mfu`` / ``<entry>_bw_util`` keys, and the dispatch/comms/compile
    summaries (``tests/test_perf_attr.py`` pins the shape)."""
    from beforeholiday_tpu.monitor.comms import comms_summary
    from beforeholiday_tpu.monitor.compile import compile_summary
    from beforeholiday_tpu.monitor.counters import dispatch_summary

    spec = _resolve_chip(chip)
    rows = roofline_summary(chip=spec)
    report: Dict[str, Any] = {
        "chip": dataclasses.asdict(spec),
        "entries": rows,
    }
    for r in rows:
        if r["mfu"] is not None:
            report[f"{r['entry']}_mfu"] = round(r["mfu"], 6)
        if r["bw_util"] is not None:
            report[f"{r['entry']}_bw_util"] = round(r["bw_util"], 6)

    report["dispatch"] = dispatch_summary()
    report["comms"] = comms_summary()
    report["compile"] = compile_summary()
    return report
