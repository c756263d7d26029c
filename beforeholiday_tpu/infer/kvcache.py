"""Paged KV-cache — vLLM-style PagedAttention storage on the flat-arena stack.

Serving batches are ragged: every request holds a different number of cached
key/value tokens and grows by one token per decode step. A contiguous
(B, max_seq, KV) cache wastes HBM on the gap between each request's length
and the max, and admitting/evicting a request would reshape the buffer — a
recompile. The paged layout (Kwon et al., arXiv:2309.06180 — vLLM's
PagedAttention) fixes both: the cache is a fixed pool of fixed-size pages,
and each request owns a *page table* — an int32 row mapping its logical
slots to physical pages. Admission allocates pages from a host-side free
list; eviction returns them. The device arrays never change shape, so the
decode executable compiles once per batch bucket.

Layout choices, in the repo's idiom:

* one HBM allocation: k-pages and v-pages for ALL layers are carved out of a
  single flat arena buffer (``ops/arena.py``'s ``make_spec``/``unflatten``),
  allocated once at engine construction and donated through every decode
  step (``remat/donation.py``) so XLA updates it in place;
* pages are stacked per layer — ``(n_layers, n_pages, page_size, kv_dim)``
  — so the engine's ``lax.scan`` over layers consumes one page-pool slice
  per step, matching the stacked-block parameter layout of the test models;
* **page 0 is the reserved null page**: page-table rows are padded with 0,
  so writes from padding slots (inactive batch rows, prompt padding past a
  request's last real page) land harmlessly in page 0, and reads of padded
  slots are masked by ``kv_lens`` in the attention kernel — no dynamic
  shapes, no host-side masking, no ``where`` over the whole pool.

**fp8 pages** (``dtype_name="e4m3"``): pages store saturating e4m3 values
under one fp32 scale per (layer, page), riding a parallel ``(n_layers,
n_pages)`` array outside the arena (the arena is single-dtype). A page's
scale is fixed at its FIRST write — prefill from the page chunk's amax with
headroom ``margin`` (the ``scales_from_history`` pattern), decode from the
first token's amax — and later tokens saturate at that scale rather than
requantizing the page (requantization compounds rounding error and breaks
the analytic bound). Dequantization is fused into :func:`gather_pages`
(one gather of pages, one gather of scales, one multiply), and the error
model is exported as :func:`kv_dequant_error_bound` (tight, per element)
plus :func:`kv_logit_error_bound` (the loose end-to-end envelope the parity
drill gates on, ``loss_parity_bound``-shaped). A page's bytes are a pure
function of its token prefix (per-page amax, causal attention), which is
what lets the radix cache (``infer/radix.py``, RadixAttention — Zheng et
al., arXiv:2312.07104) alias full pages between requests byte-identically.

Everything here is either pure device math on statically-shaped arrays (the
write/gather helpers, called inside the engine's jitted steps) or pure host
bookkeeping over Python ints (the allocator, called between steps by the
scheduler). Nothing syncs.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from beforeholiday_tpu.ops import arena
from beforeholiday_tpu.ops.quantized import E4M3_MAX, E4M3_REL, E4M3_TINY

__all__ = [
    "KVCache",
    "NULL_PAGE",
    "PageAllocator",
    "PagedLayout",
    "alloc_cache",
    "gather_pages",
    "gather_pages_quantized",
    "kv_dequant_error_bound",
    "kv_logit_error_bound",
    "pages_for",
    "write_prefill",
    "write_prefill_quantized",
    "write_token",
    "write_token_quantized",
]

# physical page 0 absorbs writes from padded page-table slots; the allocator
# never hands it out and kv_lens masking hides whatever lands there
NULL_PAGE = 0

# quantized page formats: dtype_name -> storage dtype. Scales ride a parallel
# (n_layers, n_pages) fp32 array; see the module docstring.
_KV_QUANT_DTYPES = {"e4m3": jnp.float8_e4m3fn}

# first-write scale headroom: amax maps to E4M3_MAX / margin so tokens
# written later under the frozen scale have 2x growth room before they
# saturate — the same margin default as ``scales_from_history``
KV_SCALE_MARGIN = 2.0


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static geometry of a paged cache (hashable: rides jit static args)."""

    n_layers: int
    n_pages: int  # physical pages per layer, INCLUDING the reserved null page
    page_size: int  # tokens per page
    kv_dim: int  # n_heads * head_dim
    dtype_name: str = "float32"

    def __post_init__(self):
        if self.n_pages < 2:
            raise ValueError(
                f"n_pages={self.n_pages}: need >= 2 (page 0 is reserved)"
            )
        if self.page_size < 1 or self.kv_dim < 1 or self.n_layers < 1:
            raise ValueError(f"degenerate layout: {self}")
        jnp.dtype(self.dtype)  # reject unknown dtype names loudly

    @property
    def quantized(self) -> bool:
        """True when pages store a sub-byte-precision format under scales."""
        return self.dtype_name in _KV_QUANT_DTYPES

    @property
    def dtype(self):
        alias = _KV_QUANT_DTYPES.get(self.dtype_name)
        return jnp.dtype(alias) if alias is not None else jnp.dtype(
            self.dtype_name
        )

    @property
    def usable_pages(self) -> int:
        return self.n_pages - 1

    @property
    def tokens_per_layer(self) -> int:
        return self.usable_pages * self.page_size

    @property
    def page_bytes(self) -> int:
        """HBM bytes of ONE page across k+v and all layers, scales included
        — the per-page capacity currency the fp8 ratio gate divides."""
        per = self.page_size * self.kv_dim * self.dtype.itemsize
        scale = 4 if self.quantized else 0  # one fp32 scale per (layer, page)
        return self.n_layers * 2 * (per + scale)


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` (ceil division)."""
    return -(-n_tokens // page_size)


@jax.tree_util.register_pytree_node_class
class KVCache:
    """The paged pools as a pytree: ``k``/``v`` are traced children shaped
    ``(n_layers, n_pages, page_size, kv_dim)``, the layout is static aux
    data — so a ``KVCache`` passes through jit/donate transparently.

    Quantized layouts add ``k_scale``/``v_scale`` children shaped
    ``(n_layers, n_pages)`` fp32 (``None`` on full-precision layouts — None
    subtrees flatten away, so the fp32 pytree is unchanged)."""

    __slots__ = ("k", "v", "k_scale", "v_scale", "layout")

    def __init__(self, k: jax.Array, v: jax.Array, layout: PagedLayout,
                 k_scale: Optional[jax.Array] = None,
                 v_scale: Optional[jax.Array] = None):
        self.k = k
        self.v = v
        self.k_scale = k_scale
        self.v_scale = v_scale
        self.layout = layout

    def tree_flatten(self):
        return (self.k, self.v, self.k_scale, self.v_scale), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        k, v, k_scale, v_scale = children
        return cls(k, v, layout, k_scale, v_scale)

    def replace(self, k: jax.Array, v: jax.Array,
                k_scale: Optional[jax.Array] = None,
                v_scale: Optional[jax.Array] = None) -> "KVCache":
        return KVCache(
            k, v, self.layout,
            self.k_scale if k_scale is None else k_scale,
            self.v_scale if v_scale is None else v_scale,
        )


def alloc_cache(layout: PagedLayout) -> KVCache:
    """Allocate the k/v page pools out of ONE flat arena buffer.

    A single zeros allocation padded to the arena tile is carved into the two
    pools with static slices (``arena.unflatten``) — the same one-buffer
    discipline as the fused optimizers' parameter arenas, so the whole cache
    is one donation unit and one HBM region for the life of the engine.
    Quantized layouts add the per-(layer, page) fp32 scale planes beside the
    arena (the arena is single-dtype); scales start at 1.0, under which the
    zeroed null page dequantizes to exactly 0."""
    shape = (layout.n_layers, layout.n_pages, layout.page_size, layout.kv_dim)
    spec = arena.make_spec(
        [jax.ShapeDtypeStruct(shape, layout.dtype)] * 2
    )
    flat = jnp.zeros((spec.padded_total,), layout.dtype)
    k, v = arena.unflatten(flat, spec)
    if not layout.quantized:
        return KVCache(k, v, layout)
    # two separate allocations — a shared buffer would be donated twice
    k_scale = jnp.ones((layout.n_layers, layout.n_pages), jnp.float32)
    v_scale = jnp.ones((layout.n_layers, layout.n_pages), jnp.float32)
    return KVCache(k, v, layout, k_scale, v_scale)


# ---------------------------------------------------------------------------------
# device-side page ops — called inside the engine's jitted steps, per layer
# ---------------------------------------------------------------------------------


def write_token(pages: jax.Array, page_table: jax.Array, pos: jax.Array,
                val: jax.Array) -> jax.Array:
    """Scatter one new token per sequence into its page.

    ``pages``: (n_pages, page_size, kv_dim) — ONE layer's pool.
    ``page_table``: (B, n_slots) int32. ``pos``: (B,) int32 — the logical
    position being written (== tokens already cached). ``val``: (B, kv_dim).

    Inactive batch rows carry an all-null page table, so their write lands in
    page 0 (duplicate scatter indices there are fine — the null page's
    content is never read unmasked)."""
    ps = pages.shape[1]
    batch = jnp.arange(pos.shape[0])
    phys = page_table[batch, pos // ps]
    return pages.at[phys, pos % ps].set(val.astype(pages.dtype))


def write_prefill(pages: jax.Array, page_table: jax.Array,
                  vals: jax.Array) -> jax.Array:
    """Bulk-scatter a whole prompt's K or V into its pages.

    ``vals``: (B, S, kv_dim) with ``S % page_size == 0`` — the prefill seq
    bucket is page-aligned by construction, so the scatter is a reshape to
    (B * n_slots, page_size, kv_dim) chunks indexed by the table's first
    ``S / page_size`` slots. Positions past a request's real length either
    fall in null-page slots (masked forever) or in the tail of its last real
    page (masked by ``kv_lens`` until the decode loop overwrites them —
    decode token ``t`` lands at exactly offset ``t % page_size``)."""
    B, S, kv = vals.shape
    ps = pages.shape[1]
    if S % ps:
        raise ValueError(
            f"prefill length {S} must be a multiple of page_size {ps}"
        )
    n_slots = S // ps
    phys = page_table[:, :n_slots].reshape(-1)
    chunks = vals.astype(pages.dtype).reshape(B * n_slots, ps, kv)
    return pages.at[phys].set(chunks)


def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize each sequence's logically-contiguous K or V view.

    (n_pages, page_size, kv_dim) gathered by (B, n_slots) → (B, n_slots *
    page_size, kv_dim). Token at logical position ``p`` sits at row ``p`` of
    the view; junk past each request's length is masked by ``kv_lens`` in
    the attention call, never inspected."""
    B, n_slots = page_table.shape
    ps, kv = pages.shape[1], pages.shape[2]
    return pages[page_table].reshape(B, n_slots * ps, kv)


# -- fp8 (e4m3) page variants -----------------------------------------------------


def _page_scale(amax: jax.Array, margin: float) -> jax.Array:
    """amax -> e4m3 scale with saturation headroom; 1.0 for an all-zero
    chunk (under which zeros quantize and dequantize to exactly 0 — the
    null-page invariant)."""
    return jnp.where(
        amax > 0.0, (E4M3_MAX / margin) / amax, jnp.float32(1.0)
    )


def _q_pages(vals: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    # SATURATING cast — the forward-operand contract of ops/quantized.py:
    # a frozen page scale must clip late-arriving outliers, never inf/NaN
    return jnp.clip(
        vals.astype(jnp.float32) * scale, -E4M3_MAX, E4M3_MAX
    ).astype(dtype)


def write_token_quantized(
    pages: jax.Array, scales: jax.Array, page_table: jax.Array,
    pos: jax.Array, val: jax.Array, *, margin: float = KV_SCALE_MARGIN,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`write_token` for e4m3 pages: quantize one token per sequence
    under its page's scale, fixing the scale from the token's own amax when
    the write OPENS the page (``pos % page_size == 0``) — later tokens on
    the page saturate at the frozen scale. ``scales``: (n_pages,) fp32 for
    this layer. Returns (pages, scales)."""
    ps = pages.shape[1]
    batch = jnp.arange(pos.shape[0])
    phys = page_table[batch, pos // ps]
    off = pos % ps
    amax = jnp.max(jnp.abs(val.astype(jnp.float32)), axis=-1)  # (B,)
    fresh = _page_scale(amax, margin)
    # rows mid-page keep the page's existing scale (gather-then-rescatter of
    # the same value is a no-op; duplicate indices only collide on page 0,
    # whose scale is never meaningful — null dequant is 0 under any scale)
    row_scale = jnp.where(off == 0, fresh, scales[phys])
    scales = scales.at[phys].set(row_scale)
    q = _q_pages(val, row_scale[:, None], pages.dtype)
    return pages.at[phys, off].set(q), scales


def write_prefill_quantized(
    pages: jax.Array, scales: jax.Array, page_table: jax.Array,
    vals: jax.Array, *, margin: float = KV_SCALE_MARGIN,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`write_prefill` for e4m3 pages: one scale per page from that
    page's OWN chunk amax (first write of every page it touches). Because
    attention is causal, a page's chunk — and therefore its scale and its
    quantized bytes — is a pure function of the token prefix through that
    page, which is what makes radix-aliased pages byte-identical across
    requests. Returns (pages, scales)."""
    B, S, kv = vals.shape
    ps = pages.shape[1]
    if S % ps:
        raise ValueError(
            f"prefill length {S} must be a multiple of page_size {ps}"
        )
    n_slots = S // ps
    phys = page_table[:, :n_slots].reshape(-1)
    chunks = vals.astype(jnp.float32).reshape(B * n_slots, ps, kv)
    amax = jnp.max(jnp.abs(chunks), axis=(1, 2))  # (B * n_slots,)
    scale = _page_scale(amax, margin)
    scales = scales.at[phys].set(scale)
    q = _q_pages(chunks, scale[:, None, None], pages.dtype)
    return pages.at[phys].set(q), scales


def gather_pages_quantized(
    pages: jax.Array, scales: jax.Array, page_table: jax.Array,
) -> jax.Array:
    """:func:`gather_pages` with the dequant fused in: gather pages AND their
    scales by the same table, divide once — fp32 out (what an fp32-cache
    engine would feed the flash ``kv_lens`` path). The null page holds zeros,
    which dequantize to zeros under any positive scale, so padded slots stay
    exactly as masked-harmless as in the fp32 layout."""
    B, n_slots = page_table.shape
    ps, kv = pages.shape[1], pages.shape[2]
    deq = pages[page_table].astype(jnp.float32) * (
        1.0 / scales[page_table]
    )[:, :, None, None]
    return deq.reshape(B, n_slots * ps, kv)


# -- analytic error bounds ---------------------------------------------------------


def kv_dequant_error_bound(values, scales) -> jax.Array:
    """Tight per-element bound on ``|dequant(quant(v)) - v|`` for e4m3 pages
    under ``scales`` (broadcastable against ``values``).

    Same decomposition as ``quantized_matmul_error_bound``'s per-operand
    term: round-to-nearest relative error ``E4M3_REL · |v|``, the subnormal
    absolute floor ``E4M3_TINY / s`` (divided back by the scale), plus the
    explicit saturation excess ``max(0, |v| - E4M3_MAX / s)`` charged when a
    frozen page scale clips a late outlier."""
    v = jnp.abs(jnp.asarray(values, jnp.float32))
    s = jnp.asarray(scales, jnp.float32)
    clip = jnp.maximum(0.0, v - E4M3_MAX / s)
    return E4M3_REL * v + E4M3_TINY / s + clip


def kv_logit_error_bound(
    step,
    *,
    n_layers: int,
    logit_ceiling: float,
    margin: float = KV_SCALE_MARGIN,
    growth: float = 1.5,
) -> float:
    """Envelope for ``max|logits_fp8kv(t) - logits_fp32kv(t)|`` at decode
    step ``t`` — what the greedy-parity drill asserts against (the serving
    analogue of O6's ``loss_parity_bound``).

    Form: ``logit_ceiling · ((1 + 4·eps)**n_layers - 1) · growth**step``
    where ``eps = E4M3_REL + margin · E4M3_TINY / E4M3_MAX`` is the
    worst-case RELATIVE dequant error of a page element whose scale was set
    at first write with ``margin`` headroom (so ``TINY/s <= amax · margin ·
    TINY / E4M3_MAX``; in-range elements don't clip). Per layer, attention
    output is a softmax-convex combination of V rows (≤ eps relative error)
    steered by perturbed K logits (the factor-4 slack covers the K-side
    softmax sensitivity and the residual path), layers compound
    geometrically, ``logit_ceiling`` (the fp32 run's max |logit|) converts
    relative to absolute, and ``growth`` majorizes the per-step accumulation
    as more quantized history enters each read. Worst-case-over-everything,
    hence loose (``tests/test_serving.py`` holds a run inside it)."""
    if n_layers < 1:
        raise ValueError(f"n_layers must be >= 1, got {n_layers}")
    eps = E4M3_REL + margin * E4M3_TINY / E4M3_MAX
    compounded = (1.0 + 4.0 * eps) ** n_layers - 1.0
    return float(logit_ceiling) * compounded * float(growth) ** float(step)


# ---------------------------------------------------------------------------------
# host-side page accounting — scheduler territory, plain ints, zero device work
# ---------------------------------------------------------------------------------


class PageAllocator:
    """Refcounted free-list over physical pages ``1 .. n_pages-1`` (page 0
    reserved).

    All-or-nothing allocation: the continuous batcher admits a request only
    if its whole ask fits, and preempts (rather than partially allocating)
    when the pool runs dry mid-decode. Double-free and foreign-page frees
    raise — an accounting bug here silently corrupts another request's cache,
    so it must be loud.

    Refcounts are the prefix cache's sharing currency: :meth:`alloc` hands
    out pages at refcount 1, :meth:`ref` lets another holder (a radix-tree
    node, a prefix-matched request) pin an already-live page, and
    :meth:`free` decrements — the page returns to the free list only when
    the LAST holder releases it. Copy-on-write discipline is structural,
    not enforced here: schedulers only ever WRITE pages they allocated
    fresh (a shared page is always a full, read-only prefix page), and
    :meth:`refcount` is the assertion surface tests pin that invariant on.
    """

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError(f"n_pages={n_pages}: need >= 2 (page 0 reserved)")
        self.n_pages = n_pages
        self._free = deque(range(1, n_pages))
        self._refs: Dict[int, int] = {}

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        """Pages currently held by at least one owner."""
        return len(self._refs)

    def refcount(self, page: int) -> int:
        """Current holders of ``page`` (0 for free/never-allocated pages)."""
        return self._refs.get(page, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh pages at refcount 1 each, or None if the pool can't
        cover the whole ask."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def ref(self, pages: Sequence[int]) -> None:
        """Add one reference to each page — aliasing an already-live page
        (radix hit, tree adoption). Referencing a free page raises: a ref
        can only extend a live lineage, never resurrect a recycled page."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(
                    f"ref on page {p} not currently allocated "
                    f"(stale alias — the page was recycled)"
                )
        for p in pages:
            self._refs[p] += 1

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; a page rejoins the free list when
        its count hits zero."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(
                    f"freeing page {p} not currently allocated "
                    f"(double free or foreign page)"
                )
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)
