"""Chip peaks — the published numbers a utilization is measured against.

A :class:`ChipSpec` holds a part's dense-matmul peak and its HBM bandwidth;
the registry is keyed by the string ``jax.devices()[0].device_kind`` prints on
that part, and a kind that is not in it is an error, never a default TPU.
``testing/tpu_checks.py`` divides its kernels' required operations by these
peaks; the benchmark keeps its own table (``benchmark/peaks.json``).

(The cost ledger that lived here — ``track_costs``, ``perf_report``: XLA's
``cost_analysis()`` FLOPs joined with host wall time — had no caller since the
device trace superseded it, and went in PR 52. What an entry compiled to is
on two ledgers: ``monitor/memory.py``, the bytes, and ``monitor/program.py``,
the instructions.)
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Union

import jax

__all__ = [
    "ChipSpec",
    "chip_specs",
    "get_chip_spec",
    "register_chip_spec",
]


# ------------------------------------------------------------------ chip spec
@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Peak numbers utilization is measured against. ``peak_tflops`` is the
    dense-matmul peak for the dtype you train in (bf16 for the TPU rows);
    ``hbm_gbs`` is peak memory bandwidth in GB/s. ``fp8_peak_tflops`` is the
    quantized-matmul peak for O6 GEMMs; None means the part publishes no fp8
    rate: divide nothing by an assumed multiple of the dense peak."""

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
