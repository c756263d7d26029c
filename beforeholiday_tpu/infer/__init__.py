"""Serving — the second workload front: AOT continuous-batching inference.

Built entirely on machinery the training stack already ships:

* :mod:`beforeholiday_tpu.infer.kvcache`  — paged KV-cache (fixed pages +
  per-request page tables on one arena allocation; page 0 reserved as the
  null page absorbing padded writes) and the host-side page allocator.
* :mod:`beforeholiday_tpu.infer.engine`   — bucketed, AOT-compiled prefill
  and single-token decode (``jit(...).lower().compile()`` per declared
  signature; the paged cache donated through every step; the recompile
  sentinel promoted to a hard bucket gate; optional one-time bf16 weight
  cast via the amp stack).
* :mod:`beforeholiday_tpu.infer.batching` — Orca-style continuous batching:
  admit/evict at decode-step granularity against the page budget, preempt
  by recompute on famine, plus the static-batching baseline the tests pair
  it with.
* :mod:`beforeholiday_tpu.infer.radix`    — host-side radix tree over
  page-aligned token prefixes: shared prompt prefixes alias shared KV pages
  (refcounted, copy-on-write tails), so repeat prefixes skip prefill.
* :mod:`beforeholiday_tpu.infer.disagg`   — prefill/decode disaggregation:
  separate AOT bucket sets per regime, zero-copy page-table handoff,
  decode-priority scheduling.
* :mod:`beforeholiday_tpu.infer.telemetry` — per-request lifecycle records,
  mergeable latency histograms (TTFT / inter-token / e2e), Perfetto
  request+counter tracks, and SLO burn-rate gates wired to the flight
  recorder.

The async open-loop request driver (with the crash flight recorder wired
in) lives in ``examples/serve/``. Serving has no cell in the benchmark yet
(ROADMAP B2); ``tests/test_infer.py`` and ``tests/test_serving.py`` hold the
decode oracles and the closed signature set.
"""

from beforeholiday_tpu.infer.batching import (  # noqa: F401
    ContinuousBatcher,
    Request,
    static_batched_generate,
)
from beforeholiday_tpu.infer.disagg import (  # noqa: F401
    DisaggregatedBatcher,
)
from beforeholiday_tpu.infer.engine import (  # noqa: F401
    EngineConfig,
    InferenceEngine,
    pick_bucket,
)
from beforeholiday_tpu.infer.radix import (  # noqa: F401
    RadixCache,
)
from beforeholiday_tpu.infer.telemetry import (  # noqa: F401
    RequestRecord,
    ServingTelemetry,
    SLOPolicy,
)
from beforeholiday_tpu.infer.kvcache import (  # noqa: F401
    KVCache,
    NULL_PAGE,
    PageAllocator,
    PagedLayout,
    alloc_cache,
    gather_pages,
    gather_pages_quantized,
    kv_dequant_error_bound,
    kv_logit_error_bound,
    pages_for,
    write_prefill,
    write_prefill_quantized,
    write_token,
    write_token_quantized,
)

__all__ = [
    "ContinuousBatcher",
    "DisaggregatedBatcher",
    "EngineConfig",
    "InferenceEngine",
    "KVCache",
    "NULL_PAGE",
    "PageAllocator",
    "PagedLayout",
    "RadixCache",
    "Request",
    "RequestRecord",
    "SLOPolicy",
    "ServingTelemetry",
    "alloc_cache",
    "gather_pages",
    "gather_pages_quantized",
    "kv_dequant_error_bound",
    "kv_logit_error_bound",
    "pages_for",
    "pick_bucket",
    "static_batched_generate",
    "write_prefill",
    "write_prefill_quantized",
    "write_token",
    "write_token_quantized",
]
