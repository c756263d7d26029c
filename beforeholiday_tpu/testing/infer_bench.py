"""Serving-path bench — continuous vs static batching under open-loop load.

One synthetic request trace (seeded Poisson arrivals, uniform prompt and
generation lengths with generation dominating) is replayed twice through the
SAME engine at the SAME page budget: once under the continuous batcher
(decode-step admission, Orca) and once under classic static batching (a
batch holds its slots until the longest member drains). The headline
``continuous_vs_static_batching`` tokens/s ratio is therefore a pure
scheduling win — model, buckets, executables, and pages are all shared.

Numbers are CPU proxies (the decode step times an XLA CPU executable, not a
TPU), useful as a regression trend; the RATIO and the latency percentiles
are the gated signal. Before timing anything the child asserts the decode
path against the full-forward greedy oracle — a fast paged-KV engine that
emits different tokens is not a result.

Also attributed here: decode MFU through the roofline ledger (analytic FLOPs
from ``measure_costs`` joined with the measured decode wall time against the
``cpu_proxy`` chip), and the compiled-signature count against the engine's
DECLARED bucket budget — the strict-gate contract, checked end-to-end.

Run as ``python -m beforeholiday_tpu.testing.infer_bench`` with
``JAX_PLATFORMS=cpu``; prints one JSON line.
"""

from __future__ import annotations

import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

# model proxy: tiny GPT, decode-dominated load
VOCAB, POS, D_MODEL, HEADS, LAYERS = 512, 128, 128, 4, 2
# engine geometry: one batch bucket (so static and continuous pay identical
# per-step padding) and two prefill buckets (cheap fresh admission vs
# worst-case re-prefill)
MAX_SEQ, PAGE_SIZE, NUM_PAGES = 64, 8, 65
BATCH_BUCKETS, SEQ_BUCKETS = (8,), (8, 64)
# open-loop trace: arrivals far faster than service, and BIMODAL generation
# lengths — mostly short answers with a long tail, the mix where static
# batching hurts most (every batch drains at the pace of its longest member)
N_REQUESTS, RATE_HZ = 160, 400.0
PROMPT_RANGE = (4, 9)          # np.randint half-open
SHORT_NEW, LONG_NEW, LONG_FRAC = (4, 13), (40, 58), 0.3
MFU_DECODE_STEPS = 24
MEASURE_REPEATS = 5  # interleaved rounds × 2 passes × 2 schedulers


def _trace(seed: int):
    from beforeholiday_tpu.infer import Request

    rng = np.random.RandomState(seed)
    t, out = 0.0, []
    for i in range(N_REQUESTS):
        t += float(rng.exponential(1.0 / RATE_HZ))
        new_range = LONG_NEW if rng.random_sample() < LONG_FRAC else SHORT_NEW
        out.append(Request(
            rid=i,
            prompt=list(map(int, rng.randint(1, VOCAB,
                                             rng.randint(*PROMPT_RANGE)))),
            max_new_tokens=int(rng.randint(*new_range)),
            arrival=t,
        ))
    return out


def _rebase(trace, base: float):
    for r in trace:
        r.arrival = base + r.arrival
    return trace


def _measure(finished, base: float, end: float):
    tokens = sum(len(r.out) for r in finished)
    lat = sorted(r.finish_time - r.arrival for r in finished)
    return {
        "tokens": tokens,
        "tokens_per_s": tokens / (end - base),
        "p50_ms": 1e3 * lat[len(lat) // 2],
        "p99_ms": 1e3 * lat[min(len(lat) - 1, round(0.99 * (len(lat) - 1)))],
    }


def _timed(run_fn, engine):
    """One wall-timed run with the GC parked — the schedulers churn Python
    lists, and a mid-run collection is a double-digit swing on a ~1s run."""
    gc.collect()
    gc.disable()
    try:
        return run_fn(engine, seed=0)
    finally:
        gc.enable()


def _extreme(runs):
    """Per-key best-of-N — max throughput, min latency percentiles: the
    extreme over N runs estimates the unperturbed machine. Additive keys
    (tokens, preemptions) are identical across runs (seeded trace, greedy
    decode) — asserted."""
    assert len({r["tokens"] for r in runs}) == 1
    best = dict(runs[0])
    best["tokens_per_s"] = max(r["tokens_per_s"] for r in runs)
    best["p50_ms"] = min(r["p50_ms"] for r in runs)
    best["p99_ms"] = min(r["p99_ms"] for r in runs)
    return best


def _run_continuous(engine, seed: int):
    from beforeholiday_tpu.infer import ContinuousBatcher

    engine.reset_cache()
    bat = ContinuousBatcher(engine)
    base = time.perf_counter()
    for r in _rebase(_trace(seed), base):
        bat.submit(r)
    fin = bat.run()
    res = _measure(fin, base, time.perf_counter())
    res["preemptions"] = sum(r.preemptions for r in fin)
    assert all(len(r.out) == r.max_new_tokens for r in fin)
    return res


def _run_static(engine, seed: int):
    from beforeholiday_tpu.infer import static_batched_generate

    engine.reset_cache()
    base = time.perf_counter()
    trace = _rebase(_trace(seed), base)
    fin = static_batched_generate(engine, trace)
    res = _measure(fin, base, time.perf_counter())
    assert all(len(r.out) == r.max_new_tokens for r in fin)
    return res


def _assert_greedy_parity(engine, gpt, params, cfg):
    """Decode oracle: paged incremental decode must replay the full-forward
    greedy trajectory token-for-token (cheap — two short requests)."""
    from beforeholiday_tpu.infer import PageAllocator, pages_for

    engine.reset_cache()
    alloc = PageAllocator(engine.cfg.num_pages)
    prompts = [[5, 9, 2, 7, 1, 3], [11, 4, 8]]
    tables = [alloc.alloc(pages_for(len(p), PAGE_SIZE)) for p in prompts]
    seqs = [list(p) for p in prompts]
    toks = engine.prefill(prompts, tables).tolist()
    lens = [len(p) for p in prompts]
    for i, t in enumerate(toks):
        seqs[i].append(t)
    for _ in range(5):
        for i in range(len(prompts)):
            while len(tables[i]) * PAGE_SIZE <= lens[i]:
                tables[i] += alloc.alloc(1)
        toks = engine.decode(toks, lens, tables).tolist()
        for i, t in enumerate(toks):
            seqs[i].append(t)
            lens[i] += 1
    for i, p in enumerate(prompts):
        ref = list(p)
        for _ in range(6):
            lg = gpt.forward(params, jnp.asarray([ref], jnp.int32), cfg)
            ref.append(int(np.argmax(np.asarray(lg[0, len(ref) - 1]))))
        assert ref == seqs[i], (
            f"paged decode diverged from full-forward greedy: {ref} vs {seqs[i]}"
        )


def _warm_executables(engine):
    """Touch every declared signature once so the measured passes never pay a
    compile: both prefill seq buckets and the decode bucket."""
    from beforeholiday_tpu.infer import PageAllocator, pages_for

    for s in SEQ_BUCKETS:
        engine.reset_cache()
        alloc = PageAllocator(engine.cfg.num_pages)
        plen = s - 1
        prompts = [[1 + i] * plen for i in range(2)]
        tables = [alloc.alloc(pages_for(plen, PAGE_SIZE)) for _ in prompts]
        toks = engine.prefill(prompts, tables).tolist()
        if plen < MAX_SEQ:
            for i in range(len(prompts)):
                while len(tables[i]) * PAGE_SIZE <= plen:
                    tables[i] += alloc.alloc(1)
            engine.decode(toks, [plen] * len(prompts), tables)
    engine.reset_cache()


def _decode_mfu(engine):
    """Analytic decode FLOPs joined with measured decode wall time — the
    roofline ledger's serving entry."""
    from beforeholiday_tpu import monitor
    from beforeholiday_tpu.infer import PageAllocator, pages_for

    engine.reset_cache()
    alloc = PageAllocator(engine.cfg.num_pages)
    B = BATCH_BUCKETS[-1]
    plen = 8
    prompts = [[1 + i] * plen for i in range(B)]
    tables = [alloc.alloc(pages_for(plen, PAGE_SIZE)) for _ in prompts]
    toks = engine.prefill(prompts, tables).tolist()
    lens = [plen] * B
    # analytic FLOPs of ONE decode step, from the traced jaxpr (host-only)
    argv = (
        engine._params, engine._cache, jnp.asarray(toks, jnp.int32),
        jnp.asarray(lens, jnp.int32),
        jnp.asarray(engine._pad_tables(tables, B)),
    )
    monitor.measure_costs(engine._decode_fn, *argv, entry="infer_decode")
    # timed steps (each engine.decode blocks on the token readback)
    for i in range(B):
        while len(tables[i]) * PAGE_SIZE <= lens[i] + MFU_DECODE_STEPS:
            tables[i] += alloc.alloc(1)
    t0 = time.perf_counter()
    for _ in range(MFU_DECODE_STEPS):
        toks = engine.decode(toks, lens, tables).tolist()
        lens = [n + 1 for n in lens]
    secs = time.perf_counter() - t0
    monitor.record_wall_time("infer_decode", secs, steps=MFU_DECODE_STEPS)
    row = next(
        r for r in monitor.roofline_summary(chip="cpu_proxy")
        if r["entry"] == "infer_decode"
    )
    return row["mfu"], secs / MFU_DECODE_STEPS


def main():
    from beforeholiday_tpu import monitor
    from beforeholiday_tpu.infer import EngineConfig, InferenceEngine
    from beforeholiday_tpu.testing import gpt

    if jax.default_backend() != "cpu":
        # a scheduler-logic proxy; bench.py runs it as a CPU child
        raise RuntimeError(
            f"infer_bench expects the CPU backend, got {jax.default_backend()}"
        )

    cfg = gpt.GPTConfig(
        vocab_size=VOCAB, seq_len=POS, d_model=D_MODEL, n_heads=HEADS,
        n_layers=LAYERS, dtype=jnp.float32,
    )
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(
        max_seq_len=MAX_SEQ, page_size=PAGE_SIZE, num_pages=NUM_PAGES,
        batch_buckets=BATCH_BUCKETS, prefill_seq_buckets=SEQ_BUCKETS,
    )
    engine = InferenceEngine(params, cfg, ecfg)

    # correctness before speed, then compile everything out of the timed path
    _assert_greedy_parity(engine, gpt, params, cfg)
    _warm_executables(engine)
    _run_continuous(engine, seed=0)  # scheduler warmup (allocator churn, GC)

    # both passes sample the SAME time window, interleaved round-robin
    # (bench.py's _round_robin trick) — minute-scale machine drift lands on
    # pass 1 and pass 2 alike instead of skewing their ratio
    samples = {(s, p): [] for s in ("cont", "stat") for p in (0, 1)}
    for _ in range(MEASURE_REPEATS):
        for p in (0, 1):
            samples[("cont", p)].append(_timed(_run_continuous, engine))
            samples[("stat", p)].append(_timed(_run_static, engine))

    out, pass2 = {}, {}
    for p, sink in ((0, out), (1, pass2)):
        cont = _extreme(samples[("cont", p)])
        stat = _extreme(samples[("stat", p)])
        sink["infer_tokens_per_s"] = round(cont["tokens_per_s"], 2)
        sink["infer_p50_ms"] = round(cont["p50_ms"], 2)
        sink["infer_p99_ms"] = round(cont["p99_ms"], 2)
        sink["continuous_vs_static_batching"] = round(
            cont["tokens_per_s"] / stat["tokens_per_s"], 3
        )
        if sink is out:
            out["infer_static_tokens_per_s"] = round(stat["tokens_per_s"], 2)
            out["infer_static_p99_ms"] = round(stat["p99_ms"], 2)
            out["infer_preemptions"] = cont["preemptions"]
            out["infer_tokens"] = cont["tokens"]

    mfu, step_s = _decode_mfu(engine)
    out["infer_decode_mfu"] = round(mfu, 5) if mfu is not None else None
    out["infer_decode_step_ms"] = round(step_s * 1e3, 3)

    # the strict-gate contract, end to end: everything above ran through the
    # gated entries and the executable cache must not exceed the declaration
    counts = monitor.compile_counts()
    gate_sigs = sum(
        c["signatures"] for name, c in counts.items()
        if name.startswith(ecfg.entry_prefix + ".")
    )
    assert engine.compiled_signatures <= ecfg.declared_signatures, (
        engine.compiled_signatures, ecfg.declared_signatures)
    assert gate_sigs <= ecfg.declared_signatures, (
        gate_sigs, ecfg.declared_signatures)
    out["infer_compiled_signatures"] = engine.compiled_signatures
    out["infer_declared_signatures"] = ecfg.declared_signatures

    out["pass2"] = pass2
    out["config"] = (
        f"V={VOCAB} D={D_MODEL} H={HEADS} L={LAYERS} max_seq={MAX_SEQ} "
        f"page={PAGE_SIZE} pages={NUM_PAGES} batch={BATCH_BUCKETS} "
        f"seq={SEQ_BUCKETS} n_req={N_REQUESTS} rate={RATE_HZ}/s fp32"
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
