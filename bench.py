"""Benchmark driver — prints ONE JSON line with the headline metric.

Headline (BASELINE.md configs 1-2, the north-star path): ResNet-50 synthetic
ImageNet training throughput on the TPU chip, amp O5 (bf16 + fp32 masters,
the TPU-native default) vs the self-generated O0 fp32 baseline on the same
hardware — the reference publishes no numbers (BASELINE.md), so the baseline
is config 1 run here. vs_baseline > 1.0 = amp wins.

Meter (v2; whether end-to-end cells keep the chained meter or move to the real
fenced step loop is to be re-decided on the chip: ROADMAP A0(vi)):

* EVERY timed quantity is N steps of a state-carrying ``lax.fori_loop``
  inside ONE jitted dispatch, fenced by a single 4-byte scalar readback —
  the ``bench_chip_peak`` pattern applied everywhere. N is calibrated so
  device work per sample is ~``target_s`` (default 0.8 s). The trip count is
  a TRACED argument, so calibration never recompiles.
* Loop carries are arranged so no measured work is loop-invariant (XLA's
  while-loop LICM hoists anything provably constant): attention chains feed
  the output back as the next query; optimizer rungs refresh the gradients
  in-loop from the carried gradient buffer (one elementwise pass) and a
  separate gen-only loop of exactly that pass is timed and SUBTRACTED from
  both sides, so ratios compare optimizer work only.
* Every A-vs-B ratio is the median of per-pair (A_i - gen_i)/(B_i - gen_i)
  with A/B/gen timed back-to-back per pair (the chip's shared-tenancy drift
  is minute-scale, +-20-30%).
* The whole measurement runs TWICE with the same compiled chains; the JSON
  carries both passes and ``meter.stable`` = every ratio agreeing within
  +-10% across passes. An unstable bench is flagged, not trusted.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# r04 recorded values for the keys that survive into r05, so round-over-round
# deltas are readable straight from the bench tail (VERDICT r4 next #1). The
# r04 ms-scale entries were measured with the noise-prone chained-dispatch
# meter and are listed for the delta table, not as a trusted baseline.
R04_RECORDED = {
    "resnet_o5_mfu": 0.1608, "o5_step_ms": 56.73, "o0_fp32_step_ms": 104.41,
    "fused_adam_46M_ms": 5.683, "fused_adam_vs_optax": 0.756,
    "fused_adam_kernel_ms": 5.599, "fused_adam_kernel_vs_optax": 0.76,
    "fused_adam_o5_ms": 5.77, "fused_adam_o5_vs_optax": 0.98,
    "flash_attn_s8192_fwd_ms": 15.42, "flash_attn_vs_unfused_fwd": 2.246,
    "ring_hop_flash_vs_jnp": 1.183, "ring_hop_flash_ms": 6.172,
    "bert_lamb_step_ms": 51.28, "bert_lamb_mfu": 0.0932,
    "gpt_o5_step_ms": 30.26, "gpt_o5_mfu": 0.337,
}

# ONE-OFF r5 decomposition of the GPT O5 step (d512/6L/s1024 b32, paired
# fori_loop probes, 2026-07-30 on the build chip) — a dated RECORD.
R05_GPT_ANALYSIS = (
    "[measured on gpt_512x8_6layer_s1024_b32] fwd 30 ms (0.42 6ND-MFU), "
    "bwd 80 ms (0.32), optimizer+scaler 4.6 ms. "
    "The vocab head matmul runs AT chip peak (5.6 ms for 1.07 TFLOP, both "
    "fp32 and bf16-acc). Binding constraints: K=512 matmul efficiency (the "
    "d_model) and flash-attention backward recompute, which 6ND accounting "
    "ignores entirely (attention adds ~33% fwd FLOPs at S=1024, its flash "
    "bwd ~2.5x that) — counting real FLOPs the step runs ~0.45-0.55 of "
    "peak. The d_model=1024 candidate exists because wider matmuls are the "
    "legitimate lever, not because the 512 config is fixable."
)

# ONE-OFF r5 measurement of the LAMB optimizer's share of the BERT rung
# (bert_large_8layer b64, 134M params, paired full-vs-fwd+bwd chains,
# 2026-07-30) — a dated RECORD (VERDICT r4 next #5 asked for the share).
R05_BERT_LAMB_SHARE = (
    "[measured on bert_large_8layer_b64] full step ~95-98 ms, fwd+bwd "
    "~81 ms, packed LAMB step 14-17 ms (~15% of step) at 134M params — "
    "stage1 + per-tensor trust-ratio norms + stage2 over fp32 master "
    "arenas, ~60% of streaming roofline (the per-tensor norm machinery "
    "adds ~2 GB of traffic beyond the Adam-like 3.8 GB)."
)

# ONE-OFF r5 decomposition of the ResNet-50 O5 step (b128, paired fori_loop
# probes, 2026-07-30 on the build chip) — a dated RECORD like R04_RECORDED,
# not something this meter re-measures each run. The attribution came from
# paired sub-step chains, not a device trace (ROADMAP A0(v)).
R05_RESNET_ANALYSIS = (
    "step decomposition at b128: fwd 15 ms (BN batch stats ~6), bwd ~35 ms, "
    "optimizer+scaler ~7 ms. ISOLATED convs run at 150-190 TF/s fwd AND "
    "backward (80-100% of chip peak; stem conv1 81 TF/s) - the convs are "
    "NOT the bound. The bound is the elementwise traffic BETWEEN convs: "
    "fp32 BN normalize/backward + residual chains over ~0.7 GB of bf16 "
    "activations x several passes each direction, HBM-bound at the chip's "
    "~680 GB/s single-buffer streaming rate (conv compute is ~3 ms of the "
    "8.9 ms eval fwd; the rest is elementwise). r5 fixes: arena-native "
    "optimizer step + one-pass-shifted BN stats (~5-7 ms combined); batch "
    "256/512 gave no further throughput. Closing the gap to the 2600 "
    "img/s north star means cutting elementwise passes (BN-bwd refactoring "
    "or activation-layout changes), not faster convs."
)


def _force(tree):
    """Fence device execution: reduce ONE leaf to a scalar on device and fetch
    4 bytes. Execution is in-order, so the last result's readback fences all.
    Never device_get a full array here (see module docstring)."""
    leaf = jax.tree.leaves(tree)[-1]
    return float(jax.device_get(jnp.sum(leaf.astype(jnp.float32))))


_LATENCY = None


def _readback_latency() -> float:
    """The one-scalar device->host round trip, measured once and subtracted
    from every sample (re-decide on the chip: ROADMAP A0(vi))."""
    global _LATENCY
    if _LATENCY is None:
        f = jax.jit(lambda x: x + 1)
        x = jnp.float32(1.0)
        _force(f(x))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            _force(f(x))
            ts.append(time.perf_counter() - t0)
        _LATENCY = float(np.median(ts))
    return _LATENCY


_CHAIN_SEQ = 0
_CALIBRATED_CHAINS = []


class Chain:
    """One measurable unit: a jitted dynamic-trip-count fori_loop over
    ``step_fn(state, *invariants) -> state``. The jitted runner is tracked by
    the recompile sentinel under ``bench.chain.<label>`` (the trip count is a
    traced arg, so a sentinel hit here means the meter's no-recompile
    contract broke)."""

    def __init__(self, step_fn, state, invariants=(), label=None):
        global _CHAIN_SEQ
        _CHAIN_SEQ += 1
        self.label = label or f"chain{_CHAIN_SEQ}"
        self.state = state
        self.inv = tuple(invariants)

        from beforeholiday_tpu.monitor import track_compiles

        @jax.jit
        def _jitted(n, state, *inv):
            return jax.lax.fori_loop(0, n, lambda i, s: step_fn(s, *inv), state)

        run = track_compiles(f"bench.chain.{self.label}")(_jitted)
        # the sentinel wrapper hides jit's cache introspection; keep it
        # reachable — the meter test pins _cache_size() == 1
        run._cache_size = _jitted._cache_size
        self.run = run
        self.n = None
        self.per_iter_est = None
        self.undersized_sample = False

    def compile(self):
        out = self.run(jnp.int32(1), self.state, *self.inv)
        if not np.isfinite(_force(out)):
            raise RuntimeError("chain produced non-finite state on warmup")
        return self

    def calibrate(self, target_s=0.8, n_cap=200000):
        """Pick N so one sample is ~target_s of device work."""
        lat = _readback_latency()
        self.compile()
        n = 4
        while True:
            t0 = time.perf_counter()
            _force(self.run(jnp.int32(n), self.state, *self.inv))
            t = time.perf_counter() - t0 - lat
            if t > 0.25 or n >= n_cap:
                break
            n = min(n * min(16, max(2, int(0.3 / max(t, 1e-3)))), n_cap)
        per = max(t / n, 1e-9)
        self.n = max(1, min(int(target_s / per), n_cap))
        self.per_iter_est = per
        # a chain so cheap that even n_cap iterations fall under half the
        # sample budget never escapes readback jitter — flag it so the JSON
        # reader knows the number is noise-prone, don't silently trust it
        self.undersized_sample = bool(
            self.n >= n_cap and per * self.n < target_s / 2
        )
        _CALIBRATED_CHAINS.append(self)
        return self

    def sample(self) -> float:
        """One timed sample: per-iteration seconds over self.n loop steps."""
        lat = _readback_latency()
        t0 = time.perf_counter()
        out = self.run(jnp.int32(self.n), self.state, *self.inv)
        val = _force(out)
        dt = time.perf_counter() - t0 - lat
        if not np.isfinite(val):
            raise RuntimeError("chain state went non-finite during timing")
        return max(dt, 1e-9) / self.n

    def samples(self, reps=3):
        return [self.sample() for _ in range(reps)]


def _round_robin(chains: dict, pairs=3) -> dict:
    """Time several chains back-to-back per pair (defeats minute-scale chip
    drift in ratios). Returns name -> [per-iter seconds] * pairs."""
    out = {k: [] for k in chains}
    for _ in range(pairs):
        for k, c in chains.items():
            out[k].append(c.sample())
    return out


def _sub_ratio(times, a, b, gen_a=None, gen_b=None):
    """Median over pairs of (a_i - gen_a_i) / (b_i - gen_b_i)."""
    ratios = []
    for i in range(len(times[a])):
        ta = times[a][i] - (times[gen_a][i] if gen_a else 0.0)
        tb = times[b][i] - (times[gen_b][i] if gen_b else 0.0)
        if tb > 1e-9:
            ratios.append(ta / tb)
    return float(np.median(ratios)) if ratios else float("nan")


def _unstable_keys(detail: dict, pass2: dict, tol: float = 0.10) -> list:
    """THE stability gate: keys whose pass-2 value disagrees with pass 1 by
    more than ``tol`` relative. Missing or zero pass-1 entries are skipped
    (a zero would make the relative test meaningless). main() calls this;
    tests/test_bench_meter.py pins it."""
    out = []
    for k, v2 in pass2.items():
        v1 = detail.get(k)
        if v1 is None or v1 == 0 or not np.isfinite(v2):
            continue
        if abs(v2 - v1) > tol * abs(v1):
            out.append(k)
    return out


def _med_sub(times, a, gen=None):
    vals = [
        times[a][i] - (times[gen][i] if gen else 0.0)
        for i in range(len(times[a]))
    ]
    return float(np.median(vals))


# ---------------------------------------------------------------------------------
# chip peak
# ---------------------------------------------------------------------------------


def bench_chip_peak(n: int = 16384):
    """Achievable bf16 matmul TFLOP/s: a dependent matmul chain inside one
    jitted fori_loop (one dispatch), scalar-fenced. At n=16384 this reads
    ~165 TFLOP/s on an idle v5e (nominal ~197) — the MFU denominator.
    Also probes effective HBM GB/s with a 1-GiB triad loop."""
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16)

    # 1/sqrt(n) keeps the chained product's magnitude stationary (a random
    # matmul grows norms by ~sqrt(n) per hop; the old *0.999 overflowed bf16
    # once the calibrated loop ran hundreds of iterations)
    mm = Chain(lambda o, a: (a @ o) * (1.0 / 128.0), b, (a,)).calibrate(target_s=1.5)
    dt = min(mm.samples(3))
    tflops = 2 * n**3 / dt / 1e12

    n_el = 192 * 1024 * 1024
    x = jnp.ones((n_el,), jnp.float32)
    y = jnp.ones((n_el,), jnp.float32)
    triad = Chain(lambda y, x: y * 0.999 + x, y, (x,)).calibrate(target_s=1.5)
    dt = min(triad.samples(3))
    gbs = 3 * n_el * 4 / dt / 1e9
    return tflops, gbs


# ---------------------------------------------------------------------------------
# ResNet-50 (headline)
# ---------------------------------------------------------------------------------


def make_resnet_rung(opt_level: str, batch: int = 128):
    """Chain over one synthetic ImageNet train step."""
    import os
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "examples", "imagenet")
    )
    import main_amp

    trainer = main_amp.build_trainer(
        "resnet50", opt_level=opt_level, global_batch=batch, distributed=False,
    )
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randint(0, 256, (batch, 224, 224, 3), np.uint8))
    labels = jnp.asarray(rng.randint(0, 1000, (batch,), np.int64))
    lr = jnp.float32(0.1)

    state = (trainer.params, trainer.opt_state, trainer.scaler_state, trainer.bn_state)

    def step(s, images, labels, lr):
        return trainer.train_step(*s, images, labels, lr)[:4]

    return Chain(step, state, (images, labels, lr)).calibrate(target_s=2.0)


# ---------------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------------


def make_flash_fwd_rungs(S: int = 8192):
    """Forward-only chains at long sequence: Pallas flash vs the materialized
    (B*H, S, S) softmax path (~13 GB of HBM traffic/step vs flash's ~0.2 GB
    at S=8192; the unfused backward does not even compile there). The output
    feeds back as the next query — a dependent chain XLA cannot hoist."""
    from beforeholiday_tpu.ops import attention as A
    from beforeholiday_tpu.ops import scaled_upper_triang_masked_softmax

    B, H, D = 2, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16) for kk in ks)
    sc = 1.0 / np.sqrt(D)

    def flash_step(q, k, v):
        return A.flash_attention(q, k, v, causal=True, scale=sc, impl="pallas")

    def unfused_step(q, k, v):
        scores = (q @ k.transpose(0, 1, 3, 2)).reshape(B * H, S, S)
        probs = scaled_upper_triang_masked_softmax(scores, sc)
        return probs.astype(q.dtype).reshape(B, H, S, S) @ v

    return {
        "flash": Chain(flash_step, q, (k, v)).calibrate(),
        "unfused": Chain(unfused_step, q, (k, v)).calibrate(),
    }


def _fwdbwd_step_of(loss):
    """Chain step timing the FULL backward: grads wrt q AND k AND v (grad wrt
    q alone would let XLA dead-code-eliminate the dkv kernel / the unfused
    dk-dv matmuls), all folded into the carried query so nothing is
    eliminable. The damped update keeps values bounded over thousands of
    iterations."""

    def step(q, k, v):
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        upd = dq + 1e-3 * (dk + dv)
        return jnp.clip(q * 0.999 + upd.astype(q.dtype) * 1e-3, -3, 3)

    return step


def make_flash_fwdbwd_rungs(S: int = 4096):
    """fwd+bwd chains (VERDICT r4 next #8): time the full training-path
    attention at a length where BOTH backwards compile."""
    from beforeholiday_tpu.ops import attention as A
    from beforeholiday_tpu.ops import scaled_upper_triang_masked_softmax

    B, H, D = 2, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16) for kk in ks)
    sc = 1.0 / np.sqrt(D)

    def flash_loss(q, k, v):
        return A.flash_attention(
            q, k, v, causal=True, scale=sc, impl="pallas"
        ).astype(jnp.float32).sum()

    def unfused_loss(q, k, v):
        scores = (q @ k.transpose(0, 1, 3, 2)).reshape(B * H, S, S)
        probs = scaled_upper_triang_masked_softmax(scores, sc)
        out = probs.astype(q.dtype).reshape(B, H, S, S) @ v
        return out.astype(jnp.float32).sum()

    return {
        "flash": Chain(_fwdbwd_step_of(flash_loss), q, (k, v)).calibrate(),
        "unfused": Chain(_fwdbwd_step_of(unfused_loss), q, (k, v)).calibrate(),
    }


def make_flash_bwd_rung(S: int = 8192):
    """Training-path flash attention at S=8192 under DEFAULT dispatch
    (``impl=None``): at this shape the materialized-scores jnp oracle is over
    the viability budget (the unfused backward does not even compile — the
    r04 note), so the guarded dispatch books the kernel via ``count_forced``
    and flash is the ONLY path. main() asserts the counters afterwards: zero
    jnp dispatches for any S=8192 flash key, or the rung lied about what it
    timed."""
    from beforeholiday_tpu.ops import attention as A

    B, H, D = 2, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16) for kk in ks)
    sc = 1.0 / np.sqrt(D)

    def flash_loss(q, k, v):
        return A.flash_attention(
            q, k, v, causal=True, scale=sc,  # impl=None: guarded default
        ).astype(jnp.float32).sum()

    return Chain(_fwdbwd_step_of(flash_loss), q, (k, v)).calibrate(), S


def _flash_jnp_dispatches(S: int) -> int:
    """Total jnp-oracle dispatches booked for flash_attention keys whose
    operand signatures carry sequence length S."""
    from beforeholiday_tpu.guard.dispatch import dispatch_counters

    total = 0
    for key, c in dispatch_counters().items():
        if key[0] != "flash_attention":
            continue
        if any(
            isinstance(sig, (tuple, list)) and S in tuple(sig[0])
            for sig in key[2]
        ):
            total += c["jnp"]
    return total


def make_flash_dropout_rungs(S: int = 4096):
    """Training-path attention WITH attention-probability dropout — the exact
    configuration the reference's fused kernels exist for (dropout.cuh):
    in-kernel PRNG flash vs the materialized-scores jnp dropout path,
    fwd+bwd. r04 had to route any dropout request to the O(S^2) path; this
    rung prices the fix."""
    from beforeholiday_tpu.ops import attention as A

    B, H, D = 2, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16) for kk in ks)
    sc = 1.0 / np.sqrt(D)
    dkey = jax.random.PRNGKey(11)

    def loss_of(impl):
        def loss(q, k, v):
            return A.flash_attention(
                q, k, v, causal=True, scale=sc, impl=impl,
                dropout_rate=0.1, dropout_key=dkey,
            ).astype(jnp.float32).sum()

        return loss

    return {
        "flash": Chain(_fwdbwd_step_of(loss_of("pallas")), q, (k, v)).calibrate(),
        "unfused": Chain(_fwdbwd_step_of(loss_of("jnp")), q, (k, v)).calibrate(),
    }


def make_ring_hop_rungs(BH: int = 32, Sl: int = 2048):
    """One ring-attention hop (the per-step block compute ring attention
    repeats cp times): Pallas flash-with-lse kernel vs the jnp online-softmax
    hop at a long-context shard shape. The fp32 accumulator output (with a
    vanishing lse coupling so neither output can be dead-code-eliminated)
    feeds back as the next query."""
    from beforeholiday_tpu.ops.attention import flash_attention_with_lse

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (BH, Sl, D), jnp.bfloat16)
               for kk, D in zip(ks, (64, 64, 64)))
    sc = 1.0 / np.sqrt(64)

    def flash_step(q, k, v):
        acc, lse = flash_attention_with_lse(q, k, v, causal=False, scale=sc)
        return (acc + 1e-30 * lse[..., None]).astype(jnp.bfloat16)

    def jnp_step(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * sc
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)) / l
        lse = m[..., 0] + jnp.log(l[..., 0])
        return (acc + 1e-30 * lse[..., None]).astype(jnp.bfloat16)

    return {
        "flash": Chain(flash_step, q, (k, v)).calibrate(),
        "jnp": Chain(jnp_step, q, (k, v)).calibrate(),
    }


# ---------------------------------------------------------------------------------
# fused Adam rungs (gen-subtraction scheme, see module docstring)
# ---------------------------------------------------------------------------------


def _param_set(key, dtype=jnp.float32):
    shapes = (
        [(1024, 1024)] * 12 + [(4096, 1024)] * 3 + [(1024, 4096)] * 3
        + [(30522, 256)] + [(1024,)] * 48
    )
    keys = jax.random.split(key, len(shapes))
    return {f"p{i}": jax.random.normal(k, s, dtype) * 0.02
            for i, (k, s) in enumerate(zip(keys, shapes))}


def _gen_tree(g):
    """The in-loop gradient refresh: one fused elementwise pass (decay toward
    a small fixed point so values never drift). Identical work on every side
    of a comparison AND timed alone for subtraction."""
    return jax.tree.map(lambda x: x * 0.999 + jnp.asarray(1e-6, x.dtype), g)


def make_fused_adam_rungs():
    """Fused arena-resident Adam vs unfused optax.adamw.

    Rungs (every one a gen-refreshed fori_loop chain; gen loops timed and
    subtracted so the ratios compare optimizer work only):

    * ``dropin``:  FusedAdam.step_flat fed the grad LEAF LIST — the view path
      (per-leaf update against arena views, outputs reassembled by one concat
      pass; no materialized grad arena) — what a tree-based training loop
      pays — vs tree optax.adamw.
    * ``kernel``:  step_flat on pre-flattened grads — the arena-NATIVE cost
      (grads born flat via PackedParams; see fused_adam_kernel_ms).
    * ``o5``:      the shipped amp O5 packed master-weight step
      (PackedParams + MasterWeights(arena) — one fused kernel pass emits fp32
      masters AND the bf16 model copy) vs the equivalent optax chain (cast
      grads up, adamw on masters, cast params back down).
    """
    import optax
    from beforeholiday_tpu.optimizers import FusedAdam, MasterWeights
    from beforeholiday_tpu.ops.arena import PackedParams, flatten

    hp = dict(lr=1e-3, weight_decay=0.01)
    opt = optax.adamw(learning_rate=hp["lr"], b1=0.9, b2=0.999, eps=1e-8,
                      weight_decay=hp["weight_decay"])

    params = _param_set(jax.random.PRNGKey(0))
    grads = _param_set(jax.random.PRNGKey(1))
    n_params = sum(x.size for x in jax.tree.leaves(params))

    fused = FusedAdam(**hp)
    pf, _ = flatten(list(params.values()))
    gf, _ = flatten(list(grads.values()))
    fstate = fused.init_flat(pf)
    ost = opt.init(params)

    # --- fp32 drop-in (leaf-list view path, no in-step arena pack) vs tree
    # optax ---
    def dropin_step(s):
        p, st, g = s
        g = _gen_tree(g)
        p, st = fused.step_flat(p, list(g.values()), st)
        return (p, st, g)

    def optax_step(s):
        p, o, g = s
        g = _gen_tree(g)
        updates, o = opt.update(g, o, p)
        return (optax.apply_updates(p, updates), o, g)

    def gen_tree_only(g):
        return _gen_tree(g)

    # --- kernel (grads already flat — the arena-native cost) ---
    def kernel_step(s):
        p, st, g = s
        g = g * 0.999 + 1e-6
        p, st = fused.step_flat(p, g, st)
        return (p, st, g)

    def gen_flat_only(g):
        return g * 0.999 + 1e-6

    # --- shipped O5: PackedParams master-weights vs optax chain ---
    model_tree = _param_set(jax.random.PRNGKey(0), jnp.bfloat16)
    g_bf_tree = _param_set(jax.random.PRNGKey(1), jnp.bfloat16)
    pk_model = PackedParams.pack(model_tree)
    pk_grads = PackedParams.pack(g_bf_tree)
    mw = MasterWeights(FusedAdam(**hp), arena=True)
    mw_state = mw.init(pk_model)
    fi = jnp.float32(0.0)
    inv_scale = 1.0 / 65536

    def gen_packed(g):
        return g.replace_arenas(
            [a * 0.999 + jnp.asarray(1e-6, a.dtype) for a in g.arenas]
        )

    def mw_step(s):
        pk, st, g = s
        g = gen_packed(g)
        pk, st = mw.step(pk, g, st, found_inf=fi, grad_scale=inv_scale)
        return (pk, st, g)

    master32 = _param_set(jax.random.PRNGKey(0))
    ost5 = opt.init(master32)
    modelp0 = jax.tree.map(lambda p: p.astype(jnp.bfloat16), master32)

    def optax_o5_step(s):
        master, o, modelp, g = s
        g = _gen_tree(g)
        g32 = jax.tree.map(lambda x: x.astype(jnp.float32) * inv_scale, g)
        updates, o = opt.update(g32, o, master)
        master = optax.apply_updates(master, updates)
        modelp = jax.tree.map(lambda p: p.astype(jnp.bfloat16), master)
        return (master, o, modelp, g)

    def gen16_only(g):
        return _gen_tree(g)

    target = 0.6
    chains = {
        "gen_tree": Chain(gen_tree_only, grads).calibrate(target),
        "optax": Chain(optax_step, (params, ost, grads)).calibrate(target),
        "dropin": Chain(dropin_step, (pf, fstate, grads)).calibrate(target),
        "gen_flat": Chain(gen_flat_only, gf).calibrate(target),
        "kernel": Chain(kernel_step, (pf, fstate, gf)).calibrate(target),
        "gen16": Chain(gen16_only, g_bf_tree).calibrate(target),
        # the o5 chain refreshes PACKED grads (one bf16 arena pass) — its
        # subtraction baseline must be that same pass, not the 67-leaf tree
        # refresh (single- vs multi-buffer streaming differ ~2x on this chip)
        "gen_pack": Chain(gen_packed, pk_grads).calibrate(target),
        "o5": Chain(mw_step, (pk_model, mw_state, pk_grads)).calibrate(target),
        "optax_o5": Chain(
            optax_o5_step, (master32, ost5, modelp0, g_bf_tree)
        ).calibrate(target),
    }
    return chains, n_params


def measure_fused_adam(chains, pairs=3):
    t = _round_robin(chains, pairs=pairs)
    return {
        # the SHIPPED path (amp arena_native: grads born flat) vs tree optax —
        # r04's "fused_adam_kernel_*"
        "fused_adam_native_ms": _med_sub(t, "kernel", "gen_flat") * 1e3,
        "fused_adam_native_vs_optax": _sub_ratio(t, "optax", "kernel", "gen_tree", "gen_flat"),
        # tree-grads step_flat interface, now the VIEW path (per-leaf updates
        # into arena views, one concat write-back) — r04's
        # "fused_adam_46M_ms"/"fused_adam_vs_optax"; r05 measured the old
        # in-step concat pack at 0.54x optax, which the view path removes
        "fused_adam_treeapi_ms": _med_sub(t, "dropin", "gen_tree") * 1e3,
        "fused_adam_treeapi_vs_optax": _sub_ratio(t, "optax", "dropin", "gen_tree", "gen_tree"),
        # shipped amp O5 packed master-weights step vs the optax O5 chain;
        # each side subtracts ITS OWN grad-refresh baseline
        "fused_adam_o5_ms": _med_sub(t, "o5", "gen_pack") * 1e3,
        "fused_adam_o5_vs_optax": _sub_ratio(t, "optax_o5", "o5", "gen16", "gen_pack"),
    }


# ---------------------------------------------------------------------------------
# model rungs: BERT + LAMB, GPT O5
# ---------------------------------------------------------------------------------


def _first_candidate(candidates, run_one, label):
    """Try (tag, cfg) candidates largest-first; return (result, tag) from the
    first that runs, logging each failure's class AND message to stderr (a
    real bug in the stage wiring must stay diagnosable). To be replaced by
    one fixed configuration per cell: ROADMAP A0(ii)."""
    import sys

    for tag, cfg in candidates:
        try:
            return run_one(cfg), tag
        except Exception as e:
            print(f"# {label} bench {tag} failed: {type(e).__name__}: "
                  f"{str(e)[:120]}", file=sys.stderr, flush=True)
    return None, "all_failed"


def make_bert_rung():
    """BERT + FusedLAMB pretraining step (BASELINE config 4; ref:
    apex/transformer/testing/standalone_bert.py:255 + DistributedFusedLAMB's
    MLPerf recipe) on the shipped fast path: bf16 model via amp O5,
    arena-NATIVE PackedParams masters, LAMB step_flat with born-flat grads,
    flash attention engaged, batch raised to the HBM-bound regime (VERDICT
    r4 next #5 — r04 timed the list-path step at a toy batch 8).
    Returns ((chain, flops_per_step), tag)."""
    from beforeholiday_tpu import amp
    from beforeholiday_tpu.optimizers import FusedLAMB
    from beforeholiday_tpu.testing import bert

    large8 = bert.bert_large(seq_len=128, n_layers=8, dtype=jnp.bfloat16)
    candidates = [
        # b128 measured MFU 0.40 vs 0.385 at b64 (r5); b256 fails at compile
        ("bert_large_8layer_b128", (large8, 128)),
        ("bert_large_8layer_b64", (large8, 64)),
        ("bert_large_8layer_b32", (large8, 32)),
        ("bert_large_4layer_b64", (bert.bert_large(
            seq_len=128, n_layers=4, dtype=jnp.bfloat16), 64)),
        ("bert_512x8_4layer_b64", (bert.BertConfig(
            vocab_size=30522, seq_len=128, d_model=512, n_heads=8, n_layers=4,
            dtype=jnp.bfloat16), 64)),
    ]

    def run_one(cfg_batch):
        cfg, batch = cfg_batch
        params = bert.init(jax.random.PRNGKey(0), cfg)
        batch_data = bert.synthetic_batch(jax.random.PRNGKey(1), cfg, batch)
        m = amp.initialize(
            lambda p, tok: bert.forward(p, tok, cfg), params,
            FusedLAMB(lr=1e-3, weight_decay=0.01), "O5", arena_native=True,
        )

        def loss(pk):
            return bert.pretrain_loss(pk.unpack(), *batch_data, cfg)

        opt_state = m.optimizer.init(m.params)

        def step(s):
            pk, o = s
            _, g = jax.value_and_grad(loss)(pk)
            pk, o = m.optimizer.step(pk, g, o)
            return (pk, o)

        n_params = sum(x.size for x in jax.tree.leaves(params))
        chain = Chain(step, (m.params, opt_state)).calibrate(target_s=1.5)
        return chain, 6.0 * n_params * batch * cfg.seq_len

    return _first_candidate(candidates, run_one, "bert")


def make_gpt_rung(opt_level: str = "O5"):
    """Flagship GPT training step (BASELINE config 5 shape): amp O5 with
    arena-NATIVE PackedParams (fp32 masters + model copy in one kernel pass,
    grads born flat) + flash attention + FusedAdam, single chip. Batch
    pushed toward the HBM limit (VERDICT r4 next #7). ``opt_level="O6"``
    swaps the block GEMMs onto the quantized (fp8-style) tier — same storage
    semantics, only the matmul arithmetic changes.
    Returns ((chain, tokens, flops_per_step, fp8_flops_per_step), tag);
    ``fp8_flops_per_step`` is the share of the 6·N·tokens model flops whose
    GEMMs run quantized (the block dense weights) — 0.0 for O5."""
    from beforeholiday_tpu import amp
    from beforeholiday_tpu.optimizers import FusedAdam
    from beforeholiday_tpu.testing import gpt

    # d_model=1024 first: K=512 matmuls cap the MXU near 0.42 fwd MFU (the
    # r5 decomposition note below); the 1024-wide model is the honest
    # config-5-scale flagship AND the better hardware fit
    xl = gpt.GPTConfig(
        vocab_size=32000, seq_len=1024, d_model=1024, n_heads=16, n_layers=8,
        dtype=jnp.bfloat16)
    big = gpt.GPTConfig(
        vocab_size=32000, seq_len=1024, d_model=512, n_heads=8, n_layers=6,
        dtype=jnp.bfloat16)
    small = gpt.GPTConfig(
        vocab_size=8192, seq_len=512, d_model=256, n_heads=4, n_layers=4,
        dtype=jnp.bfloat16)
    # no b32 for the xl config: the fp32 logits alone are 4.2 GB there and
    # the attempt reliably exceeds the 16 GB chip
    candidates = [
        ("gpt_1024x16_8layer_s1024_b16", (xl, 16)),
        ("gpt_1024x16_8layer_s1024_b8", (xl, 8)),
        ("gpt_512x8_6layer_s1024_b32", (big, 32)),
        ("gpt_512x8_6layer_s1024_b16", (big, 16)),
        ("gpt_512x8_6layer_s1024_b8", (big, 8)),
        ("gpt_256x4_4layer_s512_b8", (small, 8)),
    ]

    def run_one(cfg_batch):
        cfg, batch = cfg_batch
        params = gpt.init(jax.random.PRNGKey(0), cfg)
        tokens, targets = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, batch)
        m = amp.initialize(
            lambda p, t: gpt.forward(p, t, cfg), params,
            FusedAdam(lr=1e-4), opt_level, arena_native=True,
        )

        def loss_fn(p, tok, tgt):
            return gpt.loss_fn(p, tok, tgt, cfg, forward_fn=m.apply)

        svag = amp.scaled_value_and_grad(loss_fn, m.scaler)
        opt_state = m.optimizer.init(m.params)
        sstate = m.scaler.init()

        def step(s, tokens, targets):
            p, o, sc = s
            loss, g, fi, sc = svag(p, sc, tokens, targets)
            p, o = m.optimizer.step(p, g, o, found_inf=fi)
            return (p, o, sc)

        n_params = sum(x.size for x in jax.tree.leaves(params))
        tokens_per = batch * cfg.seq_len
        fp8_flops = 0.0
        if opt_level == "O6":
            # the quantized tier routes exactly the block dense GEMMs
            # (wqkv/wo/wi/wo2 via fused_dense); embedding/vocab-head stay bf16
            n_dense = sum(
                params["blocks"][k].size
                for k in ("wqkv", "wo", "wi", "wo2")
            )
            fp8_flops = 6.0 * n_dense * tokens_per
        chain = Chain(
            step, (m.params, opt_state, sstate), (tokens, targets)
        ).calibrate(target_s=1.5)
        return (chain, tokens_per,
                6.0 * n_params * tokens_per - fp8_flops, fp8_flops)

    return _first_candidate(candidates, run_one, f"gpt_{opt_level.lower()}")


# ---------------------------------------------------------------------------------
# monitor substrate (observability overhead + metrics snapshot)
# ---------------------------------------------------------------------------------


def make_monitor_rungs():
    """Identical toy train step with and without the monitor metrics fold —
    prices the pure-jnp observability substrate (a handful of norm reductions
    per step; the contract is zero extra host syncs, so the only cost is
    device FLOPs). Returns (chains, TrainMonitor)."""
    from beforeholiday_tpu.monitor import TrainMonitor

    mon = TrainMonitor()
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    params = {
        "w1": jax.random.normal(ks[0], (1024, 1024), jnp.float32) * 0.02,
        "w2": jax.random.normal(ks[1], (1024, 1024), jnp.float32) * 0.02,
    }
    x = jax.random.normal(ks[2], (256, 1024), jnp.float32)
    lr = 1e-3

    def loss_fn(p, x):
        h = jnp.tanh(x @ p["w1"])
        return jnp.mean(jnp.square(h @ p["w2"]))

    def plain_step(p, x):
        _, g = jax.value_and_grad(loss_fn)(p, x)
        return jax.tree.map(lambda a, b: a - lr * b, p, g)

    def monitored_step(s, x):
        p, m = s
        loss, g = jax.value_and_grad(loss_fn)(p, x)
        p2 = jax.tree.map(lambda a, b: a - lr * b, p, g)
        m = mon.update(m, loss=loss, grads=g, params=p, new_params=p2)
        return (p2, m)

    chains = {
        "plain": Chain(plain_step, params, (x,)).calibrate(0.6),
        "monitored": Chain(
            monitored_step, (params, mon.init()), (x,)
        ).calibrate(0.6),
    }
    return chains, mon


def _drain_metrics(mon, metrics):
    """One-fetch drain of a metrics pytree into a JSON-ready row (no file,
    no overflow warning — the bench only wants the values)."""
    from beforeholiday_tpu.monitor import MetricsLogger

    return MetricsLogger(mon, warn_overflow_streak=0).drain(metrics, step=0)


def _monitor_snapshot(mon, chain, n=16):
    """Advance the monitored chain ``n`` steps OUTSIDE timing and drain the
    final metrics pytree — the emitted line carries real trajectory values
    (loss/grad-norm EMAs after n steps), not init-state zeros."""
    out = chain.run(jnp.int32(n), chain.state, *chain.inv)
    return _drain_metrics(mon, out[1])


# ---------------------------------------------------------------------------------
# pipeline overhead (CPU-mesh proxy)
# ---------------------------------------------------------------------------------


def _cpu_child_env(devices=None):
    """Environment for a stage subprocess: the CPU backend (the parent holds
    the chip — a child must never ask for it), with ``devices`` virtual host
    devices when the stage needs a mesh."""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if devices:
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    return env


def _run_cpu_child(module, devices=None):
    """Run ``python -m beforeholiday_tpu.testing.<module>`` on the CPU
    backend and parse the JSON object on its last stdout line."""
    import os
    import subprocess

    out = subprocess.run(
        [sys.executable, "-m", f"beforeholiday_tpu.testing.{module}"],
        env=_cpu_child_env(devices), capture_output=True, text=True,
        timeout=600, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if out.returncode != 0:
        raise RuntimeError(f"{module} failed: {out.stderr[-200:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_pp_overhead():
    """1F1B schedule overhead vs sequential grad accumulation, measured on a
    virtual 8-CPU mesh in a subprocess — a SCHEDULE-LOGIC PROXY, not a TPU
    number (ICI ring latency and bf16 compute ratios differ)."""
    return _run_cpu_child("pp_bench", devices=8)


def bench_comms_overhead():
    """Bucketed-collective overhead on the same virtual 8-CPU mesh subprocess
    as ``bench_pp_overhead`` — a DISPATCH-COST PROXY, not a TPU number (the
    CPU 'wire' is memcpy, so bucketing/compression wins from overlap and
    halved ICI bytes are invisible; what this catches is the bucketing layer
    itself getting expensive)."""
    return _run_cpu_child("comms_bench", devices=8)


def bench_remat_sweep():
    """Remat-policy sweep (temp bytes + step time per checkpoint policy) on a
    CPU subprocess — the temp-byte numbers are XLA's own static
    ``memory_analysis()`` and therefore exact; the step times are CPU
    proxies."""
    return _run_cpu_child("remat_bench")


def bench_overlap_skew():
    """Measured compute/comms overlap fraction + device-side rank skew on the
    same virtual 8-CPU mesh subprocess — a SCHEDULE-LOGIC PROXY (the CPU
    backend serializes compute and collectives, so the honest fraction here
    is ~0; what this gates is the overlap/skew MEASUREMENT machinery: the
    child asserts the perf_report fraction against a closed-form timeline
    oracle and the skew against numpy before printing)."""
    return _run_cpu_child("overlap_bench", devices=8)


def bench_overlap_engine():
    """Overlap-engine paired rungs on the same virtual 8-CPU mesh subprocess
    — a PROGRAM-POSITION PROXY: the child traces each paired variant to a
    jaxpr and replays it through a deterministic dual-engine cost model, so
    the gated ratios measure where the collectives sit in the program, not
    wall clock. The child pins numerics first (hook bitwise vs post-backward,
    compressed within the analytic bound) and asserts the hook variant's
    replayed overlap_fraction is strictly higher before printing."""
    return _run_cpu_child("overlap_engine_bench", devices=8)


def bench_zero3():
    """ZeRO-3 engine rungs on the same virtual 8-CPU mesh subprocess. The
    child pins the 2-step ZeRO-3 run bitwise against ZeRO-2 and the 8->{4,2,1}
    shard resharding round-trip before printing; the gated keys are the
    per-rank persistent-state ratio (memory-ledger AOT argument bytes:
    shard-only vs full-params + shard) and the replayed overlap fraction of
    the prefetched bucket gather (strictly above the blocking prefetch=0
    form, which the child asserts)."""
    return _run_cpu_child("zero3_bench", devices=8)


def bench_multislice():
    """Two-level hierarchical collectives on the 2-slice x 4-rank carve of
    the virtual 8-CPU mesh. The child pins the hierarchical DDP reduce and a
    2-step hierarchical ZeRO-2 run bitwise against the flat engines, then
    derives the gated keys from measurements: ``hier_dcn_bytes_ratio`` is
    the ledger-booked flat/hierarchical DCN byte quotient (must equal the
    slice size exactly on the aligned payload) and ``hier_vs_flat_makespan``
    the dual-engine replay ratio with the slice axis taxed at DCN rates
    (strictly below 1, asserted in the child)."""
    return _run_cpu_child("multislice_bench", devices=8)


def bench_elastic():
    """Elastic-training rungs on the virtual 8-CPU mesh subprocess. The
    child runs the full preemption drill (a grandchild SIGKILLs itself
    mid-run; resume at world=4 from the last durable generation must match
    an independent uninterrupted reference bitwise — trajectory AND master
    arena) and asserts the async checkpoint stall meter before printing:
    ``ckpt_stall_hidden_fraction`` strictly positive and strictly above the
    synchronous submit+wait baseline."""
    return _run_cpu_child("elastic_bench", devices=8)


def bench_chaos():
    """Chaos soak on the virtual 8-CPU mesh subprocess. The child runs six
    seeded multi-fault schedules (SIGKILL'd and SIGTERM-drained training
    subprocesses, injected shrinks, real SIGUSR1 preemption notices,
    torn-host generations, watchdog-flagged hung ranks, capacity grow-back)
    plus the dedicated 4->8 grow-back drill; EVERY schedule is asserted
    bitwise against a fault-free lineage-replay reference before the child
    prints."""
    return _run_cpu_child("chaos_bench", devices=8)


def bench_moe():
    """Mixture-of-Experts rungs on a 16-device virtual CPU mesh subprocess
    (the only stage that needs the full pipe=2 x data=2 x expert=2 x
    tensor=2 carve). The child pins the 4D-mesh MoE stack bitwise against
    its single-device reference, the dispatch/combine all_to_all ledger
    bytes against the exact analytic payload, the two-level hierarchical
    routing (bitwise vs joint, per-tier DCN/ICI booking), and an executed
    ring-attention + expert-parallel long-context rung (S=8192, plus an
    eval_shape-traced S=32768 byte oracle) — ALL before deriving the gated
    keys: ``moe_vs_dense_step`` is the dual-engine replay makespan ratio of
    the capacity-factor-1.25 MoE layer vs the dense every-expert oracle
    (strictly below 1, asserted in the child)."""
    return _run_cpu_child("moe_bench", devices=16)


def bench_telemetry():
    """Telemetry rungs on the virtual 8-CPU mesh subprocess. The child
    gates the serving observer's cost with paired telemetry-on/off replays
    (``telemetry_overhead_vs_plain <= 1.05`` asserted in the child, token
    streams identical both sides), trips the SLO burn-rate gate under an
    injected prefill latency fault (flight dump with offender records
    asserted on disk), and runs the seeded elastic fault schedule (preempt
    8->4, grow back 4->8) under a live timeline, asserting the goodput
    breakdown sums to wall time exactly before deriving
    ``elastic_goodput_fraction``."""
    return _run_cpu_child("telemetry_bench", devices=8)


def bench_quantized():
    """O6 quantized-tier rungs on a CPU subprocess. The child pins the
    per-matmul quantized_matmul error inside its analytic bound, steps O5 and
    O6 GPT runs >= 50 steps from identical init and asserts EVERY step's loss
    deviation inside ``loss_parity_bound``, and requires the dispatch
    counters to show the native-fp8 fast path with zero oracle downgrades —
    all before printing. Deterministic end to end, so the gated keys
    re-derive exactly."""
    return _run_cpu_child("quantized_bench")


def bench_collective_matmul():
    """Collective-matmul rungs on the virtual 8-CPU mesh subprocess. The
    child pins the ppermute-ring SP ColumnParallel forward and full backward
    BITWISE against the monolithic gather-then-matmul (fp32 and bf16), checks
    every ring hop books into the comms ledger at ``tp.collective_matmul:*``,
    and asserts the ring's replayed overlap_fraction strictly above both the
    monolithic and chunked-gather forms before printing."""
    return _run_cpu_child("collective_matmul_bench", devices=8)


def bench_infer():
    """Serving rungs (CPU subprocess): continuous vs static batching tokens/s
    at the same page budget, decode latency percentiles under a seeded
    open-loop trace, decode MFU through the roofline ledger, and the
    compiled-signature count against the engine's declared bucket budget.
    The child asserts the paged decode path against the full-forward greedy
    oracle before timing anything."""
    return _run_cpu_child("infer_bench")


def bench_serving():
    """Serving-perf rungs (CPU subprocess): fp8 KV pages (greedy parity +
    per-step logit deviation inside the exported analytic bound, capacity
    ratio gated >= 1.8x), radix prefix caching (byte-identical streams, p99
    TTFT gated strictly below the no-cache arm on the prefix-heavy Zipf
    trace), and prefill/decode disaggregation (identical streams, closed
    signature sets, goodput gated >= the unified baseline, roofline ledger
    classifying prefill compute-bound / decode memory-bound). All oracles
    assert in the child before anything prints."""
    return _run_cpu_child("serving_bench")


def bench_autotune():
    """Knob-autotuner rung (CPU subprocess): bounded successive-halving
    search over the CPU-proxy GPT knob space (attention schedule, opt
    level, remat policy), manifest cache-hit re-run asserted at ZERO
    trials in the child, then a paired min-of-iters gate: the tuned config
    must beat the all-defaults step (``tuned_vs_default_step`` < 1.0) and
    match the best single-knob hand config
    (``tuned_vs_best_hand_config`` <= 1.05)."""
    return _run_cpu_child("autotune_bench")


# ---------------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------------


# subprocess-isolated stages runnable standalone via ``bench.py --only <name>``
STAGES = {
    "pp_overhead": bench_pp_overhead,
    "comms_overhead": bench_comms_overhead,
    "remat_sweep": bench_remat_sweep,
    "overlap_skew": bench_overlap_skew,
    "overlap_engine": bench_overlap_engine,
    "zero3": bench_zero3,
    "multislice": bench_multislice,
    "elastic": bench_elastic,
    "chaos": bench_chaos,
    "moe": bench_moe,
    "telemetry": bench_telemetry,
    "quantized": bench_quantized,
    "collective_matmul": bench_collective_matmul,
    "infer": bench_infer,
    "serving": bench_serving,
    "autotune": bench_autotune,
}


def run_only(stage):
    """``--only <stage>``: run ONE registered stage in isolation and print
    its JSON line. Returns a process exit code — 0 on success, 1 when the
    stage errored (the error is folded the same way main() folds it), 2 for
    an unknown stage name."""
    if stage not in STAGES:
        print(json.dumps(
            {"error": f"unknown stage {stage!r}",
             "stages": sorted(STAGES)}))
        return 2
    detail = {}
    out = _stage(detail, STAGES[stage])
    print(json.dumps({"stage": stage, "result": out, "detail": detail}))
    return 0 if out is not None else 1


def _stage(detail, fn, *args):
    """Run one bench stage, folding failures into the detail dict instead of
    killing the whole bench. To be replaced by a per-cell failure that
    fails the run: ROADMAP A0(ii)."""
    try:
        return fn(*args)
    except Exception as e:
        detail[f"{fn.__name__}_error"] = f"{type(e).__name__}: {str(e)[:160]}"
        return None


def _fold_bench_diff(detail, result, root=None, tol=0.10):
    """CI drift hook: compare this run's metric tree against the most recent
    ``BENCH_r*.json`` (highest run number) via ``tools/bench_diff.diff_runs``
    and fold the verdict into ``detail["bench_drift"]`` before the metric
    line prints. A missing baseline, an unparsed baseline (``parsed: null``),
    or any tooling error degrades to a note — the drift check must never
    kill the bench run it is auditing."""
    import glob
    import importlib.util
    import os
    import re

    here = root or os.path.dirname(os.path.abspath(__file__))
    try:
        runs = sorted(
            glob.glob(os.path.join(here, "BENCH_r*.json")),
            key=lambda p: (
                int(m.group(1))
                if (m := re.search(r"BENCH_r(\d+)", p)) else -1
            ),
        )
        if not runs:
            detail["bench_drift"] = {
                "baseline": None, "note": "no prior BENCH_r*.json"}
            return
        spec = importlib.util.spec_from_file_location(
            "bench_diff",
            os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "tools", "bench_diff.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        with open(runs[-1]) as f:
            old = json.load(f)
        res = mod.diff_runs(old, {"parsed": result}, tol)
        detail["bench_drift"] = {
            "baseline": os.path.basename(runs[-1]),
            "tol": tol,
            "compared": res["compared"],
            "regressions_total": len(res["regressions"]),
            "regressions": res["regressions"][:20],
            "added": len(res["added"]),
            "removed": len(res["removed"]),
            "baseline_unparsed": res["missing_old"],
            "stable": not res["regressions"] and not res["missing_old"],
        }
    except Exception as e:  # never fail the run over its own audit
        detail["bench_drift"] = {
            "error": f"{type(e).__name__}: {str(e)[:160]}"}


def _free(*_):
    """Named-reference sink: callers assign their rung vars to None and call
    this; gc then lets the chip free the buffers (BERT-large b64 + masters
    holds ~2.5 GB — without this the GPT rung OOMs on a 16 GB chip)."""
    import gc

    gc.collect()


def main(strict_drift=False):
    backend = jax.default_backend()
    if backend != "tpu":
        # a measurement path that finds no chip fails; it never records
        # "backend": "cpu" and carries on (ROADMAP A0(iii))
        print(f"bench.py measures the TPU; found backend {backend!r}",
              file=sys.stderr)
        return 1
    from beforeholiday_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    batch = 128
    detail = {"backend": backend, "global_batch": batch}
    # ratio/one-number keys measured twice for the stability gate
    pass2 = {}

    peak = _stage(detail, bench_chip_peak)
    peak_tflops = None
    if peak:
        peak_tflops, hbm_gbs = peak
        detail["chip_peak_bf16_tflops"] = round(peak_tflops, 1)
        detail["chip_hbm_gbs"] = round(hbm_gbs, 0)
    else:
        # MFU numbers must not silently vanish with a flaky peak probe; fall
        # back to the r04 measured peak, loudly labeled
        peak_tflops, hbm_gbs = 172.6, 680.0
        detail["chip_peak_note"] = "probe failed; MFU uses r04 peak 172.6"

    # the measured peak becomes the roofline denominator: every rung below
    # records its wall time into the roofline ledger and the perf_report
    # telemetry at the end re-derives each rung's MFU against this spec
    from beforeholiday_tpu import monitor as _monitor

    # fp8 peak: the MXU's quantized-matmul rate is 2x the bf16 dense peak on
    # every TPU generation with native fp8 — the O6 rung's MFU books its
    # quantized GEMM share against this denominator (roofline.ChipSpec's own
    # default, made explicit here so the JSON records the assumption)
    _monitor.register_chip_spec(
        name="bench_chip", peak_tflops=peak_tflops, hbm_gbs=hbm_gbs,
        fp8_peak_tflops=2.0 * peak_tflops)

    def mfu(model_flops, dt, fp8_flops=0.0):
        if not (peak_tflops and dt):
            return None
        return round(
            (model_flops / peak_tflops + fp8_flops / (2.0 * peak_tflops))
            / dt / 1e12, 4)

    # Rung order is memory-aware: the big-model rungs run FIRST on a clean
    # chip (the d1024 GPT flagship at b16 peaks ~7 GB transient — fp32
    # logits 2.1 GB plus dlogits and 8 layers of activations — and
    # BERT-large b64 holds ~2 GB of state), and EVERY rung's arrays are
    # dropped before the next.

    # --- GPT flagship (arena-native O5) ---
    o5_step_s = o5_tag = None
    gpt_res = _stage(detail, make_gpt_rung)
    if gpt_res and gpt_res[0]:
        (chain, tokens, flops, _), tag = gpt_res
        t = min(chain.samples(3))
        t2 = min(chain.samples(2))
        o5_step_s, o5_tag = t, tag
        pass2["gpt_o5_step_ms"] = t2 * 1e3
        detail["gpt_o5_step_ms"] = round(t * 1e3, 2)
        detail["gpt_o5_tokens_per_s"] = round(tokens / t, 1)
        detail["gpt_config"] = tag
        m = mfu(flops, t)
        if m:
            detail["gpt_o5_mfu"] = m
        # roofline join: perf_report re-derives this rung's MFU from the
        # ledger at the end; the pass-2 counterpart rides the ±10% gate
        _monitor.record_wall_time("gpt_o5", t, flops=flops)
        pass2["perf_gpt_o5_mfu"] = mfu(flops, t2)
        detail["gpt_d512_analysis_r5_recorded"] = R05_GPT_ANALYSIS
        chain = None
    gpt_res = None
    _free()

    # --- GPT flagship on the quantized tier (arena-native O6) ---
    gpt6_res = _stage(detail, make_gpt_rung, "O6")
    if gpt6_res and gpt6_res[0]:
        (chain, tokens, flops, fp8_flops), tag = gpt6_res
        t = min(chain.samples(3))
        t2 = min(chain.samples(2))
        pass2["gpt_o6_step_ms"] = t2 * 1e3
        detail["gpt_o6_step_ms"] = round(t * 1e3, 2)
        detail["gpt_o6_tokens_per_s"] = round(tokens / t, 1)
        detail["gpt_o6_config"] = tag
        detail["gpt_o6_fp8_flops_share"] = round(
            fp8_flops / (flops + fp8_flops), 4)
        m = mfu(flops, t, fp8_flops)
        if m:
            # fp8-aware MFU: bf16-class flops against the dense peak, the
            # quantized GEMM share against the 2x fp8 peak
            detail["gpt_o6_mfu"] = m
        _monitor.record_wall_time("gpt_o6", t, flops=flops,
                                  fp8_flops=fp8_flops)
        pass2["perf_gpt_o6_mfu"] = mfu(flops, t2, fp8_flops)
        if o5_step_s and tag == o5_tag:
            # same winning config on both tiers -> the step ratio is a real
            # O6-vs-O5 number, not a config artifact
            detail["o6_vs_o5_step"] = round(t / o5_step_s, 3)
            pass2["o6_vs_o5_step"] = t2 / o5_step_s
        chain = None
    gpt6_res = None
    _free()

    # --- BERT + LAMB (arena-native O5, step_flat, batch >= 64) ---
    bert_res = _stage(detail, make_bert_rung)
    if bert_res and bert_res[0]:
        (chain, flops), tag = bert_res
        t = min(chain.samples(3))
        t2 = min(chain.samples(2))
        pass2["bert_lamb_step_ms"] = t2 * 1e3
        detail["bert_lamb_step_ms"] = round(t * 1e3, 2)
        detail["bert_lamb_config"] = tag
        m = mfu(flops, t)
        if m:
            detail["bert_lamb_mfu"] = m
        _monitor.record_wall_time("bert_lamb", t, flops=flops)
        pass2["perf_bert_lamb_mfu"] = mfu(flops, t2)
        detail["bert_lamb_share_r5_recorded"] = R05_BERT_LAMB_SHARE
        chain = None
    bert_res = None
    _free()

    # --- ResNet headline ---
    o5 = _stage(detail, make_resnet_rung, "O5", batch)
    o5_s = o0_s = None
    if o5:
        o5_s = min(o5.samples(3))
        o5_s2 = min(o5.samples(2))
        pass2["o5_step_ms"] = o5_s2 * 1e3
        detail["o5_step_ms"] = round(o5_s * 1e3, 2)
        rn_flops = 3 * 4.1e9 * batch  # fwd+bwd ~ 3x 4.1 GFLOP/img
        detail["resnet_o5_model_tflops"] = round(rn_flops / o5_s / 1e12, 2)
        m = mfu(rn_flops, o5_s)
        if m:
            detail["resnet_o5_mfu"] = m
        _monitor.record_wall_time("resnet_o5", o5_s, flops=rn_flops)
        pass2["perf_resnet_o5_mfu"] = mfu(rn_flops, o5_s2)
        detail["resnet_analysis_r5_recorded"] = R05_RESNET_ANALYSIS
    o5 = None
    _free()
    o0 = _stage(detail, make_resnet_rung, "O0", batch)
    if o0:
        o0_s = min(o0.samples(3))
        detail["o0_fp32_step_ms"] = round(o0_s * 1e3, 2)
        detail["o0_img_per_s"] = round(batch / o0_s, 1)
    o0 = None
    _free()

    # --- fused Adam family ---
    adam = _stage(detail, make_fused_adam_rungs)
    if adam:
        chains, n_params = adam
        r1 = measure_fused_adam(chains)
        r2 = measure_fused_adam(chains)
        for k, val in r1.items():
            detail[k] = round(val, 3)
        detail["fused_adam_n_params"] = n_params
        # the r05 regression gate: the tree-grads interface must at least
        # match optax now that it takes the view path instead of packing an
        # arena per step
        detail["fused_adam_treeapi_ok"] = (
            r1["fused_adam_treeapi_vs_optax"] >= 1.0
        )
        pass2.update(r2)
        detail["fused_adam_note"] = (
            "gen-subtracted fori_loop meter; native = shipped arena_native "
            "path (grads born flat, maps to r04 fused_adam_kernel_*); "
            "treeapi = tree-grads interface on the VIEW path (per-leaf "
            "updates into arena views, no in-step pack — fixes r05's 0.54x); "
            "single-buffer streaming caps at ~670 GB/s on this chip (7-pass "
            "floor 1.95 ms), multi-buffer concurrency takes the fused step "
            "below it"
        )
        chains = None
    adam = None
    _free()

    # --- flash attention family ---
    fa = _stage(detail, make_flash_fwd_rungs)
    if fa:
        t1 = _round_robin(fa, pairs=3)
        t2 = _round_robin(fa, pairs=2)
        detail["flash_attn_s8192_fwd_ms"] = round(_med_sub(t1, "flash") * 1e3, 2)
        detail["flash_attn_vs_unfused_fwd"] = round(_sub_ratio(t1, "unfused", "flash"), 3)
        pass2["flash_attn_vs_unfused_fwd"] = _sub_ratio(t2, "unfused", "flash")
        detail["flash_attn_note"] = (
            "unfused bwd uncompilable at S=8192; fwd+bwd compared at S=4096"
        )
    fa = None
    _free()

    fab = _stage(detail, make_flash_fwdbwd_rungs)
    if fab:
        t1 = _round_robin(fab, pairs=3)
        t2 = _round_robin(fab, pairs=2)
        detail["flash_attn_s4096_fwdbwd_ms"] = round(_med_sub(t1, "flash") * 1e3, 2)
        detail["flash_attn_fwdbwd_vs_unfused"] = round(
            _sub_ratio(t1, "unfused", "flash"), 3)
        pass2["flash_attn_fwdbwd_vs_unfused"] = _sub_ratio(t2, "unfused", "flash")
    fab = None
    _free()

    # --- flash bwd at S=8192: flash-only guarded dispatch ---
    fb = _stage(detail, make_flash_bwd_rung)
    if fb and fb[0]:
        chain, S8 = fb
        t = min(chain.samples(3))
        t2 = min(chain.samples(2))
        detail["flash_bwd_s8192_ms"] = round(t * 1e3, 2)
        pass2["flash_bwd_s8192_ms"] = t2 * 1e3
        jnp_hits = _stage(detail, _flash_jnp_dispatches, S8)
        detail["flash_bwd_s8192_jnp_dispatches"] = jnp_hits
        if jnp_hits:
            detail["flash_bwd_s8192_error"] = (
                f"{jnp_hits} dispatches took the jnp oracle at S=8192 — the "
                "flash-only path broke; the timing above is not flash"
            )
        chain = None
    fb = None
    _free()

    fdr = _stage(detail, make_flash_dropout_rungs)
    if fdr:
        t1 = _round_robin(fdr, pairs=3)
        t2 = _round_robin(fdr, pairs=2)
        detail["flash_dropout_s4096_fwdbwd_ms"] = round(
            _med_sub(t1, "flash") * 1e3, 2)
        detail["flash_dropout_vs_unfused"] = round(
            _sub_ratio(t1, "unfused", "flash"), 3)
        pass2["flash_dropout_vs_unfused"] = _sub_ratio(t2, "unfused", "flash")
    fdr = None
    _free()

    # --- ring hop ---
    ring = _stage(detail, make_ring_hop_rungs)
    if ring:
        t1 = _round_robin(ring, pairs=3)
        t2 = _round_robin(ring, pairs=2)
        detail["ring_hop_flash_ms"] = round(_med_sub(t1, "flash") * 1e3, 3)
        detail["ring_hop_flash_vs_jnp"] = round(_sub_ratio(t1, "jnp", "flash"), 3)
        pass2["ring_hop_flash_vs_jnp"] = _sub_ratio(t2, "jnp", "flash")
    ring = None
    _free()

    # --- monitor substrate: overhead ratio + drained metrics snapshot ---
    monr = _stage(detail, make_monitor_rungs)
    if monr:
        mchains, mon = monr
        t1 = _round_robin(mchains, pairs=3)
        t2 = _round_robin(mchains, pairs=2)
        detail["monitor_overhead_vs_plain"] = round(
            _sub_ratio(t1, "monitored", "plain"), 3)
        pass2["monitor_overhead_vs_plain"] = _sub_ratio(t2, "monitored", "plain")
        snap = _stage(detail, _monitor_snapshot, mon, mchains["monitored"])
        if snap:
            detail["monitor_metrics"] = snap
        mchains = None
    monr = None
    _free()

    # --- PP overhead (CPU proxy, subprocess) ---
    pp_res = _stage(detail, bench_pp_overhead)
    if pp_res:
        detail["pp_overhead_vs_sequential_cpu8proxy"] = pp_res[
            "pp_overhead_vs_sequential"]
        detail["pp_1f1b_ms_cpu8"] = pp_res["pp_1f1b_ms"]
        for k in ("bubble_fraction", "engine_bubble_fraction",
                  "total_ticks", "phase_counts"):
            if k in pp_res:
                detail[f"pp_{k}"] = pp_res[k]
        detail["pp_note"] = "schedule-logic proxy on an 8-CPU mesh, not a TPU number"

    # --- bucketed collectives (CPU proxy, subprocess) ---
    comms_res = _stage(detail, bench_comms_overhead)
    if comms_res:
        for k in ("ddp_bucketed_vs_monolithic", "zero2_compressed_vs_fp32"):
            detail[k] = comms_res[k]
        detail["comms_bucket_bytes"] = comms_res["bucket_bytes"]
        detail["comms_n_buckets"] = comms_res["n_buckets"]
        detail["comms_note"] = (
            "dispatch-cost proxy on an 8-CPU mesh: bucketed reduce is "
            "bitwise-checked vs monolithic in-process; overlap and wire-byte "
            "wins need real ICI"
        )

    # --- remat-policy sweep (CPU proxy, subprocess) ---
    remat_res = _stage(detail, bench_remat_sweep)
    if remat_res:
        for k, v in remat_res.items():
            if k.startswith(("peak_temp_bytes_", "remat_")):
                detail[k] = v
        detail["remat_memory_summary"] = remat_res.get("memory_summary")
        detail["remat_config"] = remat_res.get("config")
        detail["remat_note"] = (
            "remat sweep on a CPU subprocess: temp bytes are XLA "
            "memory_analysis() (exact, backend-static); step times are CPU "
            "proxies for the recompute tax, not TPU numbers"
        )
        # the child's second-pass timings ride the same stability gate as
        # every other measured-twice key
        pass2.update(remat_res.get("pass2") or {})

    # --- measured overlap + rank skew (CPU proxy, subprocess) ---
    ov = _stage(detail, bench_overlap_skew)
    if ov:
        detail["overlap_fraction"] = ov.get("overlap_fraction")
        detail["rank_skew_rel"] = ov.get("rank_skew_rel")
        detail["overlap_bench"] = {
            k: v for k, v in ov.items()
            if k not in ("pass2", "compile_counters")
        }
        detail["overlap_note"] = (
            "8-CPU-mesh schedule proxy: the CPU backend serializes compute "
            "and collectives so ~0 is honest; the child oracle-checks the "
            "measurement path (perf_report fraction vs constructed timeline, "
            "rank_skew vs numpy) before printing"
        )
        pass2.update(ov.get("pass2") or {})

    # --- overlap-engine replay rungs (CPU proxy, subprocess) ---
    oe = _stage(detail, bench_overlap_engine)
    if oe:
        for k in ("ddp_overlap_vs_post_backward", "opt_in_backward_vs_phased",
                  "ddp_hook_overlap_fraction", "ddp_post_overlap_fraction",
                  "opt_hook_overlap_fraction", "opt_phased_overlap_fraction"):
            detail[k] = oe.get(k)
        detail["overlap_engine_bench"] = {
            k: v for k, v in oe.items()
            if k not in ("pass2", "compile_counters")
        }
        detail["overlap_engine_note"] = (
            "deterministic jaxpr-replay proxy on an 8-CPU mesh: ratios gate "
            "collective ISSUE POSITION (backward-time vs post-backward), "
            "numerics pinned bitwise / within compression_error_bound in the "
            "child; the overlap claim is the strict fraction inequality the "
            "child asserts, wall clock means nothing on this host"
        )
        pass2.update(oe.get("pass2") or {})

    # --- ZeRO-3 fully-sharded rungs (CPU proxy, subprocess) ---
    z3 = _stage(detail, bench_zero3)
    if z3:
        for k in ("zero3_peak_state_bytes_vs_zero2",
                  "zero3_prefetch_overlap_fraction",
                  "zero3_noprefetch_overlap_fraction",
                  "zero3_prefetch_makespan_ratio",
                  "zero2_state_bytes_per_rank", "zero3_state_bytes_per_rank"):
            detail[k] = z3.get(k)
        detail["zero3_bench"] = {
            k: v for k, v in z3.items()
            if k not in ("pass2", "compile_counters")
        }
        detail["zero3_note"] = (
            "8-CPU-mesh proxy: the state-bytes ratio is exact AOT argument "
            "accounting (what a rank holds between steps), the overlap "
            "fraction a deterministic jaxpr replay of the prefetched bucket "
            "gather; numerics are pinned bitwise vs ZeRO-2 and the sharded "
            "checkpoint resharding round-trip is asserted in the child "
            "before anything prints"
        )
        pass2.update(z3.get("pass2") or {})

    # --- two-level hierarchical collectives (2x4 slice carve, subprocess) ---
    ms = _stage(detail, bench_multislice)
    if ms:
        for k in ("hier_dcn_bytes_ratio", "hier_vs_flat_makespan",
                  "hier_dcn_bytes", "flat_dcn_bytes",
                  "hier_dcn_compression_ratio", "hier_ici_compression_ratio"):
            detail[k] = ms.get(k)
        detail["multislice_bench"] = {
            k: v for k, v in ms.items()
            if k not in ("pass2", "compile_counters")
        }
        detail["multislice_note"] = (
            "2-slice x 4-rank carve of the 8-CPU mesh: the DCN byte ratio is "
            "the ledger-booked flat/hierarchical quotient on the slow tier "
            "(== slice_size exactly on the aligned payload), the makespan "
            "ratio a deterministic dual-engine replay with the slice axis "
            "taxed at 10x ICI rates; numerics are pinned bitwise against the "
            "flat DDP reduce and a 2-step flat ZeRO-2 run in the child "
            "before anything prints"
        )
        pass2.update(ms.get("pass2") or {})

    # --- O6 quantized-tier parity + dispatch honesty (CPU subprocess) ---
    qz = _stage(detail, bench_quantized)
    if qz:
        for k in ("o6_loss_parity_margin", "o6_vs_o5_final_loss_dev",
                  "o6_parity_steps", "quantized_matmul_err",
                  "quantized_matmul_bound"):
            detail[k] = qz.get(k)
        detail["quantized_bench"] = {
            k: v for k, v in qz.items() if k != "pass2"
        }
        detail["quantized_note"] = (
            "CPU-subprocess parity rung: O6 vs O5 losses over >= 50 steps "
            "from identical init, every step asserted inside the analytic "
            "loss_parity_bound; quantized_matmul dispatches must all take "
            "the native-fp8 path (zero oracle downgrades) — deterministic, "
            "so the gated keys re-derive exactly"
        )
        pass2.update(qz.get("pass2") or {})

    # --- collective matmul: ring-overlapped SP gather+GEMM (CPU subprocess) ---
    cmm = _stage(detail, bench_collective_matmul)
    if cmm:
        for k in ("collective_matmul_overlap_fraction",
                  "tp_monolithic_overlap_fraction",
                  "tp_chunked_overlap_fraction",
                  "tp_collective_matmul_vs_chunked",
                  "tp_collective_matmul_vs_mono_makespan"):
            detail[k] = cmm.get(k)
        detail["collective_matmul_bench"] = {
            k: v for k, v in cmm.items() if k != "pass2"
        }
        detail["collective_matmul_note"] = (
            "8-CPU-mesh jaxpr-replay proxy: numerics pinned bitwise vs the "
            "monolithic gather-then-matmul (fwd + dx/dw/db, fp32 and bf16) "
            "in the child; the gated claim is the strict overlap-fraction "
            "inequality (ring hops hide under chunk GEMMs), makespans are "
            "program-position facts, not TPU wall clock"
        )
        pass2.update(cmm.get("pass2") or {})

    # --- serving rungs: continuous vs static batching (CPU proxy, subprocess) ---
    inf = _stage(detail, bench_infer)
    if inf:
        for k in ("infer_tokens_per_s", "infer_p50_ms", "infer_p99_ms",
                  "continuous_vs_static_batching", "infer_decode_mfu",
                  "infer_compiled_signatures", "infer_declared_signatures"):
            detail[k] = inf.get(k)
        detail["infer_bench"] = {
            k: v for k, v in inf.items() if k != "pass2"
        }
        detail["infer_note"] = (
            "open-loop serving proxy on a CPU subprocess: the batching ratio "
            "and latency percentiles are scheduling wins at an equal page "
            "budget (same engine, same executables both sides); tokens/s is "
            "a CPU trend number, not a TPU rate; the child pins paged decode "
            "against the full-forward greedy oracle and the compiled "
            "signature count against the declared bucket budget before "
            "printing"
        )
        pass2.update(inf.get("pass2") or {})

    # --- serving perf: fp8 KV pages, prefix cache, disaggregation ---
    sv = _stage(detail, bench_serving)
    if sv:
        for k in ("kv_fp8_capacity_ratio", "kv_fp8_logit_dev",
                  "kv_fp8_logit_bound_frac", "serving_prefix_p99_ttft_ms",
                  "prefix_vs_nocache_ttft", "prefix_hit_rate",
                  "serving_disagg_goodput_tokens_per_s",
                  "disagg_vs_unified_goodput", "serving_disagg_p99_ttft_ms",
                  "serving_prefill_bound", "serving_decode_bound"):
            detail[k] = sv.get(k)
        detail["serving_bench"] = {
            k: v for k, v in sv.items() if k != "pass2"
        }
        detail["serving_note"] = (
            "CPU-subprocess serving rungs: fp8 KV pages pinned to the fp32 "
            "greedy trajectory with the per-step logit deviation inside the "
            "exported analytic bound and the capacity ratio gated >= 1.8x; "
            "the radix prefix cache replays the Zipf prefix-heavy trace "
            "byte-identical to the no-cache arm with p99 TTFT gated "
            "strictly below it; disaggregation replays the mixed bimodal "
            "trace stream-identical to the unified engine with goodput "
            "gated >= baseline, both signature sets closed, and the "
            "roofline ledger classifying prefill compute-bound / decode "
            "memory-bound — TTFT/goodput are CPU trend values, the gated "
            "inequalities and ratios are the signal"
        )
        pass2.update(sv.get("pass2") or {})

    # --- elastic training: preemption drill + checkpoint stall meter ---
    el = _stage(detail, bench_elastic)
    if el:
        for k in ("elastic_resume_bitwise", "ckpt_stall_hidden_fraction",
                  "ckpt_timeline_overlap_fraction",
                  "ckpt_sync_hidden_fraction", "ckpt_exposed_s",
                  "ckpt_background_s", "ckpt_generations",
                  "resumed_from_step", "killed_rc"):
            detail[k] = el.get(k)
        detail["elastic_bench"] = {
            k: v for k, v in el.items() if k != "pass2"
        }
        detail["elastic_note"] = (
            "8-CPU-mesh subprocess: the drill SIGKILLs a training child "
            "mid-run and resumes at world=4 from the last durable async "
            "generation — trajectory and master arena asserted bitwise "
            "against an independent uninterrupted reference in the child "
            "before anything prints; the stall meter's hidden fraction is "
            "ckpt-ledger accounting (writer-thread work minus "
            "training-thread blocked time), strictly positive and above "
            "the synchronous baseline by child assert"
        )
        pass2.update(el.get("pass2") or {})

    # --- chaos soak: randomized multi-fault schedules, all bitwise ---
    ch = _stage(detail, bench_chaos)
    if ch:
        for k in ("chaos_schedules_survived", "chaos_schedules_total",
                  "chaos_total_events", "chaos_sigkill_rc",
                  "chaos_sigterm_drain_rc", "chaos_sigterm_dump_written",
                  "growback_resume_bitwise", "growback_stall_s",
                  "growback_stall_mean_s"):
            detail[k] = ch.get(k)
        detail["chaos_bench"] = {
            k: v for k, v in ch.items() if k != "pass2"
        }
        detail["chaos_note"] = (
            "8-CPU-mesh subprocess: six seeded fault schedules composing "
            "{SIGKILL, SIGTERM drain, shrink, grow-back, torn host "
            "generation, hung rank}, each bitwise vs a fault-free "
            "lineage-replay reference, plus the dedicated 4->8 grow-back "
            "drill; survived counts and the grow drill verdict are gated, "
            "the grow-back stall meter is wall-clock and reported ungated"
        )
        pass2.update(ch.get("pass2") or {})

    # --- Mixture-of-Experts: 4D-mesh parity + routing traffic (subprocess) ---
    mo = _stage(detail, bench_moe)
    if mo:
        for k in ("moe_4d_mesh_parity", "moe_dispatch_bytes_ratio",
                  "moe_vs_dense_step", "moe_a2a_bytes", "moe_hier_dcn_bytes",
                  "long_context_tokens", "long_context_analytic_tokens"):
            detail[k] = mo.get(k)
        detail["moe_bench"] = {
            k: v for k, v in mo.items()
            if k not in ("pass2", "compile_counters")
        }
        detail["moe_note"] = (
            "16-device virtual CPU mesh: the 4D pipe x data x expert x "
            "tensor MoE stack is pinned bitwise against its single-device "
            "reference and the dispatch/combine all_to_all ledger bytes "
            "against the exact analytic payload before anything prints; "
            "moe_vs_dense_step is a deterministic dual-engine replay ratio "
            "(conditional compute vs the every-expert dense oracle at "
            "capacity factor 1.25), not TPU wall clock; the long-context "
            "rung composes ring attention with expert-parallel MoE over "
            "the same 8 ranks at S=8192 executed / S=32768 traced"
        )
        pass2.update(mo.get("pass2") or {})

    # --- telemetry: serving SLO numbers, observer overhead, goodput ledger ---
    tl = _stage(detail, bench_telemetry)
    if tl:
        for k in ("telemetry_overhead_vs_plain", "serving_p99_ttft_ms",
                  "serving_goodput_tokens_per_s", "elastic_goodput_fraction",
                  "slo_breach_dump", "serving_preemptions",
                  "serving_quantile_error_bound"):
            detail[k] = tl.get(k)
        detail["telemetry_bench"] = {
            k: v for k, v in tl.items() if k != "pass2"
        }
        detail["telemetry_note"] = (
            "8-CPU-mesh subprocess: the serving observer's cost is a "
            "paired on/off replay ratio (child-asserted <= 1.05 with "
            "bitwise-identical token streams), the SLO drill injects a "
            "prefill latency fault and asserts the burn-rate breach wrote "
            "a flight dump carrying the offending request records, and "
            "the goodput leg replays the seeded preempt+grow-back "
            "schedule under a live timeline with the breakdown asserted "
            "to sum to wall time exactly; serving numbers are CPU trend "
            "values, not TPU rates"
        )
        pass2.update(tl.get("pass2") or {})

    # --- autotune: the knob search must turn shipped mechanisms into speed ---
    at = _stage(detail, bench_autotune)
    if at:
        for k in ("tuned_vs_default_step", "tuned_vs_best_hand_config",
                  "autotune_trials", "autotune_cache_hit_trials",
                  "autotune_best_config", "autotune_pruned"):
            detail[k] = at.get(k)
        detail["autotune_bench"] = {
            k: v for k, v in at.items() if k != "pass2"
        }
        detail["autotune_note"] = (
            "CPU subprocess: bounded successive-halving over the proxy GPT "
            "knob space (attention schedule / opt level / remat policy) "
            "with ledger-costed trials and per-trial compile+probe-cache "
            "isolation; the child asserts the manifest cache-hit re-run "
            "took 0 trials, and the gate ratios are paired min-of-iters — "
            "tuned_vs_default_step < 1.0 means the search beat the shipped "
            "defaults on THIS chip (dense attention beats the chunked "
            "flash schedule on CPU; the same search on TPU keeps flash)"
        )
        pass2.update(at.get("pass2") or {})

    # --- guard dispatch + comms + compile counters: what every rung above
    # actually dispatched/communicated/compiled (collected LAST so the
    # telemetry covers the whole bench) ---
    from beforeholiday_tpu.monitor import (
        comms_summary,
        compile_summary,
        dispatch_summary,
    )

    counters = _stage(detail, dispatch_summary)
    if counters is not None:
        detail["dispatch_counters"] = counters
    comms = _stage(detail, comms_summary)
    if comms:
        detail["comms_summary"] = comms
    compiles = _stage(detail, compile_summary)
    if compiles is not None:
        detail["compile_counters"] = compiles

    # --- perf attribution: one perf_report over the roofline ledger the
    # rungs above populated; each entry's MFU lands as perf_<entry>_mfu and
    # must agree with that rung's directly-computed *_mfu (same flops, same
    # clock — this is a consistency check on the ledger join, and the pass-2
    # counterparts recorded per-rung ride the ±10% gate) ---
    def bench_perf_report():
        return _monitor.perf_report(chip="bench_chip")

    rep = _stage(detail, bench_perf_report)
    if rep:
        for row in rep.get("entries") or []:
            if row.get("mfu") is not None:
                detail[f"perf_{row['entry']}_mfu"] = row["mfu"]
            if row.get("bw_util") is not None:
                detail[f"perf_{row['entry']}_bw_util"] = row["bw_util"]
        detail["perf_chip"] = rep.get("chip")
        direct = detail.get("gpt_o5_mfu")
        joined = detail.get("perf_gpt_o5_mfu")
        if direct and joined:
            detail["perf_mfu_agrees_5pct"] = (
                abs(joined - direct) <= 0.05 * direct
            )

    # --- stability gate: pass-2 must agree within 10% on every ratio ---
    unstable = _unstable_keys(detail, pass2)
    detail["meter"] = {
        "method": "fori_loop-chained, gen-subtracted, paired; two passes",
        "stable": not unstable,
        "unstable_keys": unstable,
        "undersized_chains": sorted(
            c.label for c in _CALIBRATED_CHAINS if c.undersized_sample
        ),
        "pass2": {k: round(float(v), 3) for k, v in pass2.items()},
    }
    detail["r04_recorded"] = R04_RECORDED

    result = {
        "metric": "resnet50_amp_O5_train",
        "value": round(batch / o5_s, 1) if o5_s else 0.0,
        "unit": "img/s",
        "vs_baseline": round(o0_s / o5_s, 3) if (o5_s and o0_s) else 0.0,
        "detail": detail,
    }
    # CI drift audit LAST: the verdict rides inside detail but compares the
    # tree as it stood above (bench_drift itself is excluded by ordering)
    _fold_bench_diff(detail, result)
    print(json.dumps(result))
    if strict_drift and _drift_fatal(detail):
        return 1
    return 0


def _drift_fatal(detail):
    """``--strict-drift`` verdict: fatal when a baseline existed and the
    folded drift audit is not stable (metric regressions beyond tol, or a
    baseline that failed to parse). A missing baseline or a tooling error
    in the audit itself stays non-fatal — there is nothing to regress
    against."""
    drift = detail.get("bench_drift") or {}
    if not drift.get("baseline"):
        return False
    return not drift.get("stable", True)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description="beforeholiday_tpu bench driver")
    ap.add_argument(
        "--only", metavar="STAGE",
        help="run a single subprocess bench stage and exit "
             f"(one of: {', '.join(sorted(STAGES))})")
    ap.add_argument(
        "--strict-drift", action="store_true",
        help="exit nonzero when the folded bench_drift verdict is not "
             "stable (CI mode; default keeps drift advisory)")
    args = ap.parse_args()
    if args.only:
        sys.exit(run_only(args.only))
    sys.exit(main(strict_drift=args.strict_drift))
