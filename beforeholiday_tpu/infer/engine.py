"""AOT inference engine — bucketed prefill/decode executables over paged KV.

The serving path inverts the training loop's tolerance for compilation:
a trainer amortizes one trace over thousands of identical steps, but a
server sees a new (batch, seq) shape on every request mix — left alone,
jit turns traffic shape into a recompilation storm. The engine closes that
hole with three interlocking pieces:

* **buckets** — :class:`EngineConfig` declares the finite set of batch sizes
  and prefill sequence lengths; every call is padded UP to the smallest
  bucket that fits (padding rides the null page + ``kv_lens`` masking, see
  ``infer/kvcache.py``), so the set of abstract signatures is closed;
* **AOT compilation** — each (bucket) signature is lowered and compiled
  explicitly (``jit(...).lower(...).compile()``) on first use and cached in
  a host dict keyed by the same abstract signature the recompile sentinel
  computes (the ``monitor/memory.py:track_memory`` executable-cache idiom),
  so steady-state dispatch never re-enters tracing;
* **the hard gate** — ``monitor.track_compiles(strict=True,
  max_signatures=...)`` wraps both entry points with the DECLARED bucket
  count as the budget: a signature outside the bucket set raises
  :class:`~beforeholiday_tpu.monitor.compile.BucketGateError` instead of
  warn-once. In serving, an undeclared shape is a bug upstream (a bucket
  table and a scheduler disagreeing), not a performance footnote.

The decode step consumes and returns the paged cache, wired through
``remat/donation.py`` so XLA aliases the pools in place — the cache is the
largest live buffer in a serving process and must not double-buffer.
Weights optionally cast once to bf16 at construction via the amp stack's
``cast_floats`` (the serving analogue of O2 master-weight casting: fp32
masters stay with the trainer; the server keeps only the low-precision
copy).

The model contract is the repo's stacked-block GPT parameter layout
(``testing/gpt.py``): the engine mirrors that forward exactly — same fused
ops, same dtype convention, same scan-over-layers — but re-derived for
incremental decode (single-token queries against the gathered page view).
The engine lives below ``testing/`` and imports only library code.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from beforeholiday_tpu.infer import kvcache
from beforeholiday_tpu.monitor.compile import _sig_of, track_compiles
from beforeholiday_tpu.monitor.trace import active_recorder
from beforeholiday_tpu.ops import flash_attention, fused_dense, fused_layer_norm
from beforeholiday_tpu.ops._autocast import cast_floats
from beforeholiday_tpu.remat.donation import donate_step

__all__ = ["EngineConfig", "InferenceEngine", "pick_bucket"]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static serving geometry — buckets, pages, dtypes.

    ``batch_buckets`` / ``prefill_seq_buckets`` define the CLOSED signature
    set: decode compiles one executable per batch bucket, prefill one per
    (batch bucket, seq bucket) pair, and the strict gate holds both entry
    points to exactly those budgets. Prefill buckets must be page-aligned
    (the bulk KV scatter is a reshape, not a gather) and fit ``max_seq_len``.
    """

    max_seq_len: int = 128
    page_size: int = 16
    num_pages: int = 65  # physical pages per layer, incl. the null page
    batch_buckets: Tuple[int, ...] = (4, 8)
    prefill_seq_buckets: Tuple[int, ...] = (32, 64, 128)
    # decode-side batch buckets; None shares ``batch_buckets`` (the unified
    # engine). A distinct set is the prefill/decode disaggregation knob: the
    # two phases live in different roofline regimes (prefill compute-bound,
    # decode bandwidth-bound), so prefill wants small admission chunks while
    # decode wants one deep resident batch — see infer/disagg.py.
    decode_batch_buckets: Optional[Tuple[int, ...]] = None
    # one-time weight cast at construction (e.g. "bfloat16"); None keeps the
    # checkpoint dtype. compute dtype follows the weights unless forced.
    weights_dtype: Optional[str] = None
    compute_dtype: Optional[str] = None
    # "float32" (default) or "e4m3": fp8 pages under per-(layer, page)
    # scales — see infer/kvcache.py's quantized variants
    cache_dtype: str = "float32"
    # strict=True promotes the recompile sentinel to the hard bucket gate
    strict_buckets: bool = True
    entry_prefix: str = "infer"

    def __post_init__(self):
        if self.max_seq_len % self.page_size:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} must be a multiple of "
                f"page_size {self.page_size}"
            )
        if tuple(sorted(self.batch_buckets)) != tuple(self.batch_buckets):
            raise ValueError(f"batch_buckets must ascend: {self.batch_buckets}")
        if self.decode_batch_buckets is not None and tuple(
            sorted(self.decode_batch_buckets)
        ) != tuple(self.decode_batch_buckets):
            raise ValueError(
                f"decode_batch_buckets must ascend: {self.decode_batch_buckets}"
            )
        if tuple(sorted(self.prefill_seq_buckets)) != tuple(
            self.prefill_seq_buckets
        ):
            raise ValueError(
                f"prefill_seq_buckets must ascend: {self.prefill_seq_buckets}"
            )
        for s in self.prefill_seq_buckets:
            if s % self.page_size:
                raise ValueError(
                    f"prefill bucket {s} not page-aligned "
                    f"(page_size {self.page_size})"
                )
            if s > self.max_seq_len:
                raise ValueError(
                    f"prefill bucket {s} exceeds max_seq_len {self.max_seq_len}"
                )

    @property
    def n_slots(self) -> int:
        """Page-table width: logical slots per request."""
        return self.max_seq_len // self.page_size

    @property
    def decode_buckets(self) -> Tuple[int, ...]:
        """The decode entry point's batch buckets (``batch_buckets`` unless
        disaggregated)."""
        return self.decode_batch_buckets or self.batch_buckets

    @property
    def max_batch(self) -> int:
        """Active-set capacity — how many requests decode can carry."""
        return self.decode_buckets[-1]

    @property
    def max_prefill_batch(self) -> int:
        """Largest batch one prefill call admits."""
        return self.batch_buckets[-1]

    @property
    def declared_prefill_signatures(self) -> int:
        return len(self.batch_buckets) * len(self.prefill_seq_buckets)

    @property
    def declared_decode_signatures(self) -> int:
        return len(self.decode_buckets)

    @property
    def declared_copy_signatures(self) -> int:
        """The COW tail-page copy is ONE fixed-shape executable (indices pad
        to ``max_batch`` with the null page) — a single extra signature."""
        return 1

    @property
    def declared_signatures(self) -> int:
        """Total compiled-signature budget — the bound the tests hold."""
        return (
            self.declared_prefill_signatures
            + self.declared_decode_signatures
            + self.declared_copy_signatures
        )


def pick_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest declared bucket >= n. Out of range raises — feeding an
    over-bucket size through anyway would hit the strict gate one layer down
    with a less actionable message."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds the largest declared bucket {buckets[-1]}")


def _vocab_head(x: jax.Array, embedding: jax.Array) -> jax.Array:
    """Tied-embedding logits in compute dtype with fp32 accumulation — the
    same contract as ``testing/_model_utils.vocab_head_matmul``."""
    return jax.lax.dot_general(
        x, embedding.astype(x.dtype),
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


class InferenceEngine:
    """Bucketed AOT prefill/decode over one resident paged cache.

    Host surface (used by the scheduler; everything device-shaped is padded
    to buckets internally):

    * ``prefill(prompts, page_tables) -> next_tokens`` — run full prompts,
      populate their pages, return the first generated token per request;
    * ``decode(tokens, lens, page_tables) -> next_tokens`` — one token for
      every active request: writes the fed token's K/V at position ``len``
      and samples greedily from the resulting logits.

    The cache is engine state, rebound after every (donated) step; callers
    never hold a reference to it.
    """

    def __init__(self, params: Any, model_cfg: Any, cfg: EngineConfig):
        if cfg.max_seq_len > model_cfg.seq_len:
            raise ValueError(
                f"max_seq_len {cfg.max_seq_len} exceeds the model's position "
                f"table ({model_cfg.seq_len})"
            )
        self.cfg = cfg
        self.model_cfg = model_cfg
        compute = cfg.compute_dtype or cfg.weights_dtype
        self._compute_dtype = (
            jnp.dtype(compute) if compute is not None else model_cfg.dtype
        )
        if cfg.weights_dtype is not None:
            params = cast_floats(params, jnp.dtype(cfg.weights_dtype))
        self._params = params
        self.layout = kvcache.PagedLayout(
            n_layers=model_cfg.n_layers,
            n_pages=cfg.num_pages,
            page_size=cfg.page_size,
            kv_dim=model_cfg.n_heads * model_cfg.head_dim,
            dtype_name=cfg.cache_dtype,
        )
        self._cache = kvcache.alloc_cache(self.layout)
        # donated step fns: the cache (arg 1 / arg 0) is consumed and
        # re-emitted
        self._prefill_step = donate_step(self._prefill_fn, donate_argnums=(1,))
        self._decode_step = donate_step(self._decode_fn, donate_argnums=(1,))
        self._copy_step = donate_step(self._copy_fn, donate_argnums=(0,))
        # AOT executable cache, keyed by the sentinel's abstract signature
        # (the monitor/memory.py idiom: one .lower().compile() per signature,
        # plain dict dispatch after)
        self._exec: Dict[Any, Any] = {}
        # the hard gate: every entry strict against its DECLARED budget
        self._prefill_gated = track_compiles(
            f"{cfg.entry_prefix}.prefill",
            strict=cfg.strict_buckets,
            max_signatures=cfg.declared_prefill_signatures,
        )(functools.partial(self._dispatch, "prefill"))
        self._decode_gated = track_compiles(
            f"{cfg.entry_prefix}.decode",
            strict=cfg.strict_buckets,
            max_signatures=cfg.declared_decode_signatures,
        )(functools.partial(self._dispatch, "decode"))
        self._copy_gated = track_compiles(
            f"{cfg.entry_prefix}.copy",
            strict=cfg.strict_buckets,
            max_signatures=cfg.declared_copy_signatures,
        )(functools.partial(self._dispatch, "copy"))

    # -- device-side step functions (traced; closures over static config) ----

    def _embed(self, params, tokens, pos):
        x = params["tok_embed"][tokens] + params["pos_embed"][pos]
        return x.astype(self._compute_dtype)

    def _block_mlp(self, lp, x):
        h = fused_layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
        h = jax.nn.gelu(
            fused_dense(h, lp["wi"].astype(h.dtype), lp["bi"].astype(h.dtype))
        )
        return x + fused_dense(
            h, lp["wo2"].astype(x.dtype), lp["bo2"].astype(x.dtype)
        )

    def _qkv(self, lp, x):
        h = fused_layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
        qkv = fused_dense(
            h, lp["wqkv"].astype(h.dtype), lp["bqkv"].astype(h.dtype)
        )
        return jnp.split(qkv, 3, axis=-1)

    def _heads(self, t):
        B, S, _ = t.shape
        mc = self.model_cfg
        return t.reshape(B, S, mc.n_heads, mc.head_dim).transpose(0, 2, 1, 3)

    def _merge_heads(self, t):
        B, H, S, hd = t.shape
        return t.transpose(0, 2, 1, 3).reshape(B, S, H * hd)

    def _attn_out(self, lp, x, ctx):
        out = fused_dense(
            ctx, lp["wo"].astype(x.dtype), lp["bo"].astype(x.dtype)
        )
        return x + out

    def _final_logits(self, params, x_last):
        x_last = fused_layer_norm(
            x_last, params["lnf_scale"], params["lnf_bias"]
        )
        return _vocab_head(x_last, params["tok_embed"])[:, 0, :]

    def _scan_xs(self, params, cache):
        """Per-layer scan slices: blocks + page pools (+ scale planes on
        quantized layouts)."""
        if self.layout.quantized:
            return (params["blocks"], cache.k, cache.v,
                    cache.k_scale, cache.v_scale)
        return (params["blocks"], cache.k, cache.v)

    def _rebuild(self, cache, ys):
        """Reassemble the cache from the scan's stacked per-layer outputs."""
        if self.layout.quantized:
            k_new, v_new, ks_new, vs_new = ys
            return cache.replace(k_new, v_new, ks_new, vs_new)
        k_new, v_new = ys
        return cache.replace(k_new, v_new)

    def _prefill_fn(self, params, cache, tokens, lens, page_table):
        """tokens (B, S_bucket) int32, lens (B,), page_table (B, n_slots).
        Returns (next_tokens (B,), last_logits (B, V) fp32, cache).

        Attention runs on the EXACT k/v just computed (not a quantized
        round-trip) — prefill compute is full-precision either way; fp8
        pages only affect later decode reads."""
        B, S = tokens.shape
        mc = self.model_cfg
        scale = 1.0 / np.sqrt(mc.head_dim)
        x = self._embed(params, tokens, jnp.arange(S))
        quant = self.layout.quantized

        def body(carry, xs):
            if quant:
                lp, kp, vp, ks, vs = xs
            else:
                lp, kp, vp = xs
            q, k, v = self._qkv(lp, carry)
            if quant:
                kp, ks = kvcache.write_prefill_quantized(
                    kp, ks, page_table, k
                )
                vp, vs = kvcache.write_prefill_quantized(
                    vp, vs, page_table, v
                )
            else:
                kp = kvcache.write_prefill(kp, page_table, k)
                vp = kvcache.write_prefill(vp, page_table, v)
            ctx = flash_attention(
                self._heads(q), self._heads(k), self._heads(v),
                causal=True, scale=scale, kv_lens=lens,
                impl=getattr(mc, "attention_impl", None),
            )
            carry = self._attn_out(lp, carry, self._merge_heads(ctx))
            carry = self._block_mlp(lp, carry)
            return carry, ((kp, vp, ks, vs) if quant else (kp, vp))

        x, ys = jax.lax.scan(body, x, self._scan_xs(params, cache))
        last = jnp.clip(lens - 1, 0, S - 1).astype(jnp.int32)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
        logits = self._final_logits(params, x_last)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, \
            self._rebuild(cache, ys)

    def _decode_fn(self, params, cache, tokens, lens, page_table):
        """One incremental token. tokens (B,) = the last sampled token per
        row, lens (B,) = tokens already cached (the fed token's position);
        inactive rows carry lens == 0 + a null page table and are fully
        masked. Returns (next_tokens (B,), logits (B, V) fp32, cache).

        On quantized layouts the fed token quantizes under its page's scale
        (fresh scale when it OPENS the page) and the gather dequantizes
        in-place to fp32 — the same tensor an fp32-cache engine feeds the
        masked flash call."""
        B = tokens.shape[0]
        mc = self.model_cfg
        scale = 1.0 / np.sqrt(mc.head_dim)
        x = self._embed(params, tokens, lens)[:, None, :]  # (B, 1, D)
        kv_lens = jnp.where(lens > 0, lens + 1, 0)
        quant = self.layout.quantized

        def body(carry, xs):
            if quant:
                lp, kp, vp, ks, vs = xs
            else:
                lp, kp, vp = xs
            q, k, v = self._qkv(lp, carry)
            if quant:
                kp, ks = kvcache.write_token_quantized(
                    kp, ks, page_table, lens, k[:, 0, :]
                )
                vp, vs = kvcache.write_token_quantized(
                    vp, vs, page_table, lens, v[:, 0, :]
                )
                kc = kvcache.gather_pages_quantized(kp, ks, page_table)
                vc = kvcache.gather_pages_quantized(vp, vs, page_table)
            else:
                kp = kvcache.write_token(kp, page_table, lens, k[:, 0, :])
                vp = kvcache.write_token(vp, page_table, lens, v[:, 0, :])
                kc = kvcache.gather_pages(kp, page_table)
                vc = kvcache.gather_pages(vp, page_table)
            ctx = flash_attention(
                self._heads(q), self._heads(kc), self._heads(vc),
                causal=False, scale=scale, kv_lens=kv_lens,
                impl=getattr(mc, "attention_impl", None),
            )
            carry = self._attn_out(lp, carry, self._merge_heads(ctx))
            carry = self._block_mlp(lp, carry)
            return carry, ((kp, vp, ks, vs) if quant else (kp, vp))

        x, ys = jax.lax.scan(body, x, self._scan_xs(params, cache))
        logits = self._final_logits(params, x)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, \
            self._rebuild(cache, ys)

    def _copy_fn(self, cache, src, dst):
        """Whole-page duplication ``dst[i] <- src[i]`` across all layers,
        k+v pools (and scale planes): the copy-on-write tail copy of the
        prefix cache. ``src``/``dst`` are (max_batch,) int32, padded with
        the null page — a 0→0 copy is a no-op by construction. One fixed
        shape, hence exactly one declared signature."""
        k = cache.k.at[:, dst].set(cache.k[:, src])
        v = cache.v.at[:, dst].set(cache.v[:, src])
        if self.layout.quantized:
            ks = cache.k_scale.at[:, dst].set(cache.k_scale[:, src])
            vs = cache.v_scale.at[:, dst].set(cache.v_scale[:, src])
            return cache.replace(k, v, ks, vs)
        return cache.replace(k, v)

    # -- AOT dispatch --------------------------------------------------------

    def _dispatch(self, kind, *argv):
        step = {
            "prefill": self._prefill_step,
            "decode": self._decode_step,
            "copy": self._copy_step,
        }[kind]
        key = (kind, _sig_of(argv, {}))
        compiled = self._exec.get(key)
        if compiled is None:
            compiled = step.jitted.lower(*argv).compile()
            self._exec[key] = compiled
        return compiled(*argv)

    @property
    def compiled_signatures(self) -> int:
        """Executables resident in the AOT cache — never more than
        ``cfg.declared_signatures`` (``tests/test_infer.py``)."""
        return len(self._exec)

    def reset_cache(self) -> None:
        """Fresh zeroed pools (test isolation; reused pages don't need
        this — prefill rewrites every slot it claims and kv_lens masks the
        rest)."""
        self._cache = kvcache.alloc_cache(self.layout)

    # -- host surface --------------------------------------------------------

    def _host_span(self, kind: str, **args):
        """Span the host dispatch of one engine call on the active timeline
        recorder (``infer.prefill`` / ``infer.decode`` with the chosen
        bucket as args) — the serving telemetry's engine-side track. No-op
        when no recorder is active."""
        rec = active_recorder()
        if rec is None:
            return contextlib.nullcontext()
        return rec.span(f"{self.cfg.entry_prefix}.{kind}", args=args)

    def _pad_tables(self, page_tables: Sequence[Sequence[int]], B: int):
        pt = np.zeros((B, self.cfg.n_slots), np.int32)
        for i, row in enumerate(page_tables):
            if len(row) > self.cfg.n_slots:
                raise ValueError(
                    f"request {i}: {len(row)} pages > {self.cfg.n_slots} slots"
                )
            pt[i, : len(row)] = row
        return pt

    def prefill(self, prompts: Sequence[Sequence[int]],
                page_tables: Sequence[Sequence[int]]) -> np.ndarray:
        """Run ``n`` prompts through the bucketed prefill; returns the first
        generated token per request, (n,) int32 on host."""
        n = len(prompts)
        if n == 0:
            return np.zeros((0,), np.int32)
        if n != len(page_tables):
            raise ValueError(f"{n} prompts vs {len(page_tables)} page tables")
        B = pick_bucket(n, self.cfg.batch_buckets)
        longest = max(len(p) for p in prompts)
        if longest < 1:
            raise ValueError("empty prompt")
        S = pick_bucket(longest, self.cfg.prefill_seq_buckets)
        tokens = np.zeros((B, S), np.int32)
        lens = np.zeros((B,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, : len(p)] = p
            lens[i] = len(p)
        pt = self._pad_tables(page_tables, B)
        with self._host_span("prefill", batch=B, seq=S):
            nxt, _, self._cache = self._prefill_gated(
                self._params, self._cache, jnp.asarray(tokens),
                jnp.asarray(lens), jnp.asarray(pt),
            )
            return np.asarray(jax.device_get(nxt))[:n]

    def decode(self, tokens: Sequence[int], lens: Sequence[int],
               page_tables: Sequence[Sequence[int]]) -> np.ndarray:
        """One decode step for ``n`` active requests; returns (n,) int32."""
        n = len(tokens)
        if n == 0:
            return np.zeros((0,), np.int32)
        if not (n == len(lens) == len(page_tables)):
            raise ValueError("tokens/lens/page_tables length mismatch")
        B = pick_bucket(n, self.cfg.decode_buckets)
        tok = np.zeros((B,), np.int32)
        ln = np.zeros((B,), np.int32)
        tok[:n] = tokens
        ln[:n] = lens
        if ln[:n].max() >= self.cfg.max_seq_len:
            raise ValueError(
                f"decode past max_seq_len {self.cfg.max_seq_len}"
            )
        pt = self._pad_tables(page_tables, B)
        with self._host_span("decode", batch=B):
            nxt, _, self._cache = self._decode_gated(
                self._params, self._cache, jnp.asarray(tok),
                jnp.asarray(ln), jnp.asarray(pt),
            )
            return np.asarray(jax.device_get(nxt))[:n]

    def decode_logits(self, tokens: Sequence[int], lens: Sequence[int],
                      page_tables: Sequence[Sequence[int]]) -> np.ndarray:
        """Decode step that ALSO returns the (n, V) fp32 logits — the
        correctness-oracle surface (tests compare these against a contiguous
        reference); shares executables with :meth:`decode`."""
        n = len(tokens)
        B = pick_bucket(n, self.cfg.decode_buckets)
        tok = np.zeros((B,), np.int32)
        ln = np.zeros((B,), np.int32)
        tok[:n] = tokens
        ln[:n] = lens
        pt = self._pad_tables(page_tables, B)
        _, logits, self._cache = self._decode_gated(
            self._params, self._cache, jnp.asarray(tok),
            jnp.asarray(ln), jnp.asarray(pt),
        )
        return np.asarray(jax.device_get(logits))[:n]

    def copy_pages(self, src: Sequence[int], dst: Sequence[int]) -> None:
        """Duplicate whole pages ``src[i] → dst[i]`` inside the resident
        arena — the prefix cache's copy-on-write: a fully-cached prompt
        aliases every page but its tail, which is copied onto a fresh page
        the request may then overwrite. Pads to ``max_batch`` with the null
        page (0→0 is a no-op), so the call is one declared signature."""
        n = len(src)
        if n == 0:
            return
        if n != len(dst):
            raise ValueError(f"{n} src pages vs {len(dst)} dst pages")
        if n > self.cfg.max_batch:
            raise ValueError(
                f"copy_pages({n}) exceeds max_batch {self.cfg.max_batch}"
            )
        s = np.zeros((self.cfg.max_batch,), np.int32)
        d = np.zeros((self.cfg.max_batch,), np.int32)
        s[:n] = src
        d[:n] = dst
        with self._host_span("copy", pages=n):
            self._cache = self._copy_gated(
                self._cache, jnp.asarray(s), jnp.asarray(d)
            )
