"""Flash attention parity: Pallas kernel (interpret mode on CPU) vs the
unfused jnp oracle and vs the repo's existing unfused softmax path.

Mirrors the reference's contrib tests (apex/contrib/test/fmha/test_fmha.py,
multihead_attn/) which compare each fused op against a pure-PyTorch module.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from beforeholiday_tpu.ops import attention as A


def _ref_attn(q, k, v, causal, scale, kv_lens=None):
    """Materialized-scores oracle in fp64-ish fp32."""
    B, H, S, D = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    kj = jnp.arange(S)
    masked = jnp.zeros((B, 1, S, S), bool)
    if kv_lens is not None:
        masked = masked | (kj[None, None, None, :] >= kv_lens[:, None, None, None])
    if causal:
        masked = masked | (kj[None, None, None, :] > jnp.arange(S)[None, None, :, None])
    s = jnp.where(masked, -1e30, s)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(masked, 0.0, jnp.exp(s - m))  # exact zero on masked slots
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = jnp.where(l > 0, e / jnp.where(l > 0, l, 1.0), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _qkv(key, B=2, H=2, S=256, D=64, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (B, H, S, D), dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


class TestFlashForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_oracle(self, causal):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        got = A.flash_attention(q, k, v, causal=causal, impl="pallas")
        want = _ref_attn(q, k, v, causal, 1.0 / np.sqrt(64))
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_jnp_impl_matches_oracle(self):
        q, k, v = _qkv(jax.random.PRNGKey(1))
        got = A.flash_attention(q, k, v, causal=True, impl="jnp")
        want = _ref_attn(q, k, v, True, 1.0 / np.sqrt(64))
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_kv_lens_padding(self):
        q, k, v = _qkv(jax.random.PRNGKey(2))
        lens = jnp.array([128, 200])
        got = A.flash_attention(q, k, v, causal=False, kv_lens=lens, impl="pallas")
        want = _ref_attn(q, k, v, False, 1.0 / np.sqrt(64), lens)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("impl", ["pallas", "jnp"])
    def test_fully_masked_rows_zero(self, impl):
        """kv_len == 0: 'pay attention to nothing' → zero output, no NaN, on
        BOTH impls (the generic softmax kernel's fully-masked convention)."""
        q, k, v = _qkv(jax.random.PRNGKey(3))
        lens = jnp.array([0, 256])
        got = A.flash_attention(q, k, v, causal=False, kv_lens=lens, impl=impl)
        assert not np.any(np.isnan(np.asarray(got)))
        np.testing.assert_allclose(got[0], np.zeros_like(got[0]), atol=0)

    def test_custom_scale_and_bf16(self):
        q, k, v = _qkv(jax.random.PRNGKey(4), dtype=jnp.bfloat16)
        got = A.flash_attention(q, k, v, causal=True, scale=0.1, impl="pallas")
        want = _ref_attn(q, k, v, True, 0.1)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            got.astype(np.float32), want.astype(np.float32), atol=2e-2, rtol=2e-2
        )

    def test_availability_gate(self):
        assert A.is_flash_available(256, 64)
        assert not A.is_flash_available(200, 64)  # ragged seq
        assert not A.is_flash_available(256, 1024)  # head too wide
        # ragged shapes silently take the jnp path rather than erroring
        B, H, S, D = 1, 2, 96, 32
        q = jax.random.normal(jax.random.PRNGKey(5), (B, H, S, D))
        out = A.flash_attention(q, q, q, causal=True, impl=None)
        np.testing.assert_allclose(
            out, _ref_attn(q, q, q, True, 1.0 / np.sqrt(D)), atol=2e-5, rtol=2e-5
        )


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_oracle(self, causal):
        q, k, v = _qkv(jax.random.PRNGKey(10), B=1, H=2, S=256, D=64)
        w = jax.random.normal(jax.random.PRNGKey(11), q.shape)

        def f(impl):
            def loss(q, k, v):
                o = A.flash_attention(q, k, v, causal=causal, impl=impl)
                return jnp.sum(o * w)

            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        dq_p, dk_p, dv_p = f("pallas")
        def loss_ref(q, k, v):
            return jnp.sum(_ref_attn(q, k, v, causal, 1.0 / np.sqrt(64)) * w)

        dq_r, dk_r, dv_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(dq_p, dq_r, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(dk_p, dk_r, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(dv_p, dv_r, atol=1e-4, rtol=1e-4)

    def test_grads_with_kv_lens(self):
        q, k, v = _qkv(jax.random.PRNGKey(12), B=2, H=1, S=256, D=32)
        lens = jnp.array([100, 256])
        w = jax.random.normal(jax.random.PRNGKey(13), q.shape)

        def loss_flash(q, k, v):
            return jnp.sum(
                A.flash_attention(q, k, v, causal=True, kv_lens=lens, impl="pallas") * w
            )

        def loss_ref(q, k, v):
            return jnp.sum(_ref_attn(q, k, v, True, 1.0 / np.sqrt(32), lens) * w)

        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(got, want):
            np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


class TestSelfAttention:
    def test_fused_block_matches_manual(self):
        B, S, D, H = 2, 128, 64, 4
        key = jax.random.PRNGKey(20)
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (B, S, D))
        w_qkv = jax.random.normal(ks[1], (D, 3 * D)) * 0.05
        b_qkv = jax.random.normal(ks[2], (3 * D,)) * 0.01
        w_out = jax.random.normal(ks[3], (D, D)) * 0.05

        got = A.self_attention(x, w_qkv, b_qkv, w_out, None, H, causal=True, impl="pallas")

        qkv = x @ w_qkv + b_qkv
        q, k, v = jnp.split(qkv, 3, axis=-1)
        hs = lambda t: t.reshape(B, S, H, D // H).transpose(0, 2, 1, 3)
        ctx = _ref_attn(hs(q), hs(k), hs(v), True, 1.0 / np.sqrt(D // H))
        want = ctx.transpose(0, 2, 1, 3).reshape(B, S, D) @ w_out
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)


class TestDropoutDispatch:
    """CPU-side dispatch contract for in-kernel dropout. The kernel itself
    needs the hardware PRNG (no interpret-mode lowering), so its numerics —
    determinism, variance law, same-mask gradient parity, S=8192 fwd+bwd —
    are verified on a real chip by ``testing/tpu_checks.py`` (all-PASS r5)."""

    def test_dropout_falls_back_to_jnp_off_tpu(self):
        q, k, v = _qkv(jax.random.PRNGKey(0), S=128)
        auto = A.flash_attention(
            q, k, v, dropout_rate=0.25, dropout_key=jax.random.PRNGKey(1))
        ref = A.flash_attention(
            q, k, v, impl="jnp", dropout_rate=0.25,
            dropout_key=jax.random.PRNGKey(1))
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))

    def test_forced_pallas_dropout_raises_off_tpu(self):
        q, k, v = _qkv(jax.random.PRNGKey(0), S=128)
        with pytest.raises(ValueError, match="real TPU"):
            A.flash_attention(q, k, v, impl="pallas", dropout_rate=0.25,
                              dropout_key=jax.random.PRNGKey(1))

    def test_dropout_requires_key(self):
        q, k, v = _qkv(jax.random.PRNGKey(0), S=128)
        with pytest.raises(ValueError, match="dropout_key"):
            A.flash_attention(q, k, v, dropout_rate=0.25)

    def test_jnp_dropout_statistics(self):
        """Inverted-scaling contract on the oracle path: mean preserved,
        variance follows (rate/keep) * sum p^2."""
        B, H, S, D = 2, 2, 128, 32
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q, k, _ = (jax.random.normal(kk, (B, H, S, D)) for kk in ks)
        out = A.flash_attention(
            q, k, jnp.ones((B, H, S, D)), impl="jnp",
            dropout_rate=0.25, dropout_key=jax.random.PRNGKey(7))
        arr = np.asarray(out, np.float64)
        assert abs(arr.mean() - 1.0) < 0.02, arr.mean()
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (1.0 / np.sqrt(D))
        p = jax.nn.softmax(s, axis=-1)
        pred = (0.25 / 0.75) * float(jnp.mean(jnp.sum(p * p, axis=-1)))
        assert 0.5 < arr.var() / pred < 2.0, (arr.var(), pred)

    def test_rate0_identical_to_plain(self):
        q, k, v = _qkv(jax.random.PRNGKey(0), S=128)
        plain = A.flash_attention(q, k, v, causal=True)
        rate0 = A.flash_attention(q, k, v, causal=True, dropout_rate=0.0,
                                  dropout_key=jax.random.PRNGKey(1))
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(rate0))


class TestFlashOnlyDispatch:
    """Above the oracle-score budget the jnp fallback is not a viable
    degradation target (it materializes O(S^2) fp32 scores through autodiff),
    so dispatch must become flash-ONLY: no probe, no downgrade, the dispatch
    booked via ``count_forced`` — the contract of an S=8192 backward,
    pinned here at unit size by shrinking the budget instead of the shape."""

    def _booked(self):
        from beforeholiday_tpu.guard import dispatch as gd

        out = {"pallas": 0, "jnp": 0, "probes": 0}
        for key, c in gd.dispatch_counters().items():
            if key[0] == "flash_attention":
                for f in out:
                    out[f] += c[f]
        return out

    def test_over_budget_books_forced_flash_no_probe(self, monkeypatch):
        from beforeholiday_tpu.guard import dispatch as gd

        # CPU resolves the default to jnp; force the TPU-side "pallas"
        # resolution (interpret-mode kernel) so the budget branch is reachable
        monkeypatch.setattr(A, "_resolve_impl", lambda impl: "pallas")
        q, k, v = _qkv(jax.random.PRNGKey(11), B=1, H=1, S=128, D=32)
        gd.reset_dispatch_counters()
        prev = A.set_oracle_score_budget(1)  # 4*B*H*S*Sk >> 1: flash-only
        try:
            # forward AND backward ride the forced dispatch
            g = jax.grad(lambda a: jnp.sum(A.flash_attention(a, k, v)))(q)
        finally:
            assert A.set_oracle_score_budget(prev) == 1
        assert np.isfinite(np.asarray(g)).all()
        booked = self._booked()
        assert booked["pallas"] >= 1  # the flash-only dispatch is visible
        assert booked["probes"] == 0  # probe skipped: nothing to degrade to
        assert booked["jnp"] == 0  # the oracle is never taken

    def test_under_budget_keeps_guarded_probe(self, monkeypatch):
        from beforeholiday_tpu.guard import dispatch as gd

        monkeypatch.setattr(A, "_resolve_impl", lambda impl: "pallas")
        q, k, v = _qkv(jax.random.PRNGKey(12), B=1, H=1, S=128, D=32)
        gd.clear_probe_cache("flash_attention")
        gd.reset_dispatch_counters()
        assert 4 * 1 * 1 * 128 * 128 <= A.oracle_score_budget()
        out = A.flash_attention(q, k, v)
        assert np.isfinite(np.asarray(out)).all()
        booked = self._booked()
        assert booked["pallas"] >= 1
        assert booked["probes"] >= 1  # the guard probed as usual


# ---------------------------------------------------------------------------------
# the two-level tile plan (PR 28): grid blocks as before, tiles inside the
# blocks the causal diagonal crosses
# ---------------------------------------------------------------------------------


def _walk_tiles(plan, by_cols):
    """Every (tile row, tile col, through a causal mask) the kernel whose
    strips run along ``by_cols`` computes, from the plan's own walk over the
    live blocks: what the kernels unroll, flattened."""
    out = []
    for i in range(plan.nq):
        for j in range(plan.nk):
            if plan.causal and j > i:
                continue
            for fixed, pieces in plan.walk(by_cols, plan.causal and i == j):
                for moving, on_diag in pieces:
                    rows, cols = (moving, fixed) if by_cols else (fixed, moving)
                    out += [((i * plan.bq + r) // plan.tq, (j * plan.bk + c) // plan.tk, on_diag)
                            for r in range(rows.start, rows.stop, plan.tq)
                            for c in range(cols.start, cols.stop, plan.tk)]
    return out


class TestTilePlan:
    # (Sq, Sk, D, causal) -> grid block, tile, live / total tiles, masked
    TABLE = [
        ((1024, 1024, 64, True), (1024, 1024), (256, 256), (10, 16, 4)),   # the GPT cells
        ((8192, 8192, 64, True), (1024, 1024), (256, 256), (528, 1024, 32)),
        ((8192, 8192, 256, True), (512, 512), (128, 128), (2080, 4096, 64)),  # the Qwen cell
        ((1536, 1536, 64, True), (512, 512), (128, 128), (78, 144, 12)),
        ((384, 384, 64, True), (128, 128), (128, 128), (6, 9, 3)),
        ((128, 128, 64, True), (128, 128), (128, 128), (1, 1, 1)),
        ((1024, 1024, 64, False), (1024, 1024), (1024, 1024), (1, 1, 1)),  # one tile a block
        ((256, 384, 64, False), (256, 128), (256, 128), (3, 3, 3)),
    ]

    @pytest.mark.parametrize("key,block,tile,counts", TABLE,
                             ids=[f"S{k[0]}x{k[1]}-D{k[2]}-{'causal' if k[3] else 'full'}"
                                  for k, *_ in TABLE])
    def test_plan_table(self, key, block, tile, counts):
        plan = A._tile_plan(*key)
        assert (plan.bq, plan.bk) == block
        assert (plan.bq, plan.bk) == (A._block_size(key[0], key[2]), A._block_size(key[1], key[2]))
        assert (plan.tq, plan.tk) == tile
        live, total, masked = counts
        assert plan.counts(False) == {"total": total, "live": live, "masked": masked}
        # with kv_lens every computed tile takes the length test
        assert plan.counts(True)["masked"] == live

    @pytest.mark.parametrize("key", [k for k, *_ in TABLE if k[0] <= 1536],
                             ids=lambda k: f"S{k[0]}x{k[1]}-D{k[2]}-{'causal' if k[3] else 'full'}")
    @pytest.mark.parametrize("by_cols", [False, True], ids=["rows", "cols"])
    def test_walk_covers_exactly_the_live_tiles(self, key, by_cols):
        """Brute force against the mask: a tile is computed iff some score in
        it is live, exactly once, and only a tile the diagonal crosses goes
        through the causal mask — for the row walk (fwd, dq) and the column
        walk (dkv) alike."""
        plan = A._tile_plan(*key)
        tiles = _walk_tiles(plan, by_cols)
        assert len({t[:2] for t in tiles}) == len(tiles)
        want = {}
        for r in range(plan.sq // plan.tq):
            for c in range(plan.sk // plan.tk):
                first_col, last_col = c * plan.tk, (c + 1) * plan.tk - 1
                first_row, last_row = r * plan.tq, (r + 1) * plan.tq - 1
                if not plan.causal:
                    want[(r, c)] = False
                elif first_col <= last_row:                # some key <= some query
                    want[(r, c)] = last_col > first_row    # some key > some query
        assert {t[:2]: t[2] for t in tiles} == want
        counts = plan.counts(False)
        assert counts["live"] == len(tiles)
        if plan.causal:
            assert counts["masked"] == sum(t[2] for t in tiles)

    def test_counter_books_each_traced_kernel(self):
        from beforeholiday_tpu import monitor
        from beforeholiday_tpu.guard import dispatch

        dispatch.reset_dispatch_counters()
        q, k, v = _qkv(jax.random.PRNGKey(7), B=1, H=1, S=512)
        jax.grad(lambda q: jnp.sum(A.flash_attention(q, k, v, causal=True, impl="pallas")))(q)
        rows = {r["kernel"]: r for r in monitor.tile_records() if r["op"] == "flash_attention"}
        assert sorted(rows) == ["dqkv", "fwd"]      # one block a head: one backward kernel
        for r in rows.values():   # block 512 in four strips of 128
            assert (r["live"], r["total"], r["masked"]) == (10, 16, 4)
            assert r["traces"] >= 1
        dispatch.reset_dispatch_counters()
        assert monitor.tile_records() == []


def _kernel_primitives(fn, *args):
    """Per pallas_call of ``fn``'s jaxpr, the sorted primitives of its kernel
    body (nested bodies included, jit wrappers not)."""
    found = []

    def body(jaxpr, out):
        for e in jaxpr.eqns:
            if e.primitive.name not in ("pjit", "jit", "closed_call"):
                out.append(e.primitive.name)
            for v in e.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        body(getattr(sub, "jaxpr", sub), out)
        return out

    def find(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                found.append(sorted(body(e.params["jaxpr"], [])))
                continue
            for v in e.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        find(getattr(sub, "jaxpr", sub))

    find(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


class TestNonCausalBodyUnchanged:
    """A non-causal call is one tile a block: the kernel bodies PR 28 found,
    operation for operation. The counts were read off the three kernels of
    the commit before the tile plan (352c098) at Sq=256, Sk=384, without and
    with the ``dlse`` operand (one more read and one more lane slice) — less
    two ``convert_element_type`` a kernel: the casts of the Python scalars
    ``_NEG`` and ``0.0`` that ``jnp.where`` made and ``lax.select`` does not
    (scalar constants, no vector work)."""

    FWD = {"add": 4, "broadcast_in_dim": 14, "cond": 3, "convert_element_type": 9,
           "div": 1, "dot_general": 2, "eq": 2, "exp": 2, "ge": 2, "get": 11, "gt": 1,
           "iota": 1, "log": 1, "max": 1, "mul": 4, "program_id": 3, "reduce_max": 1,
           "reduce_sum": 1, "select_n": 6, "slice": 2, "sub": 2, "swap": 8}
    DQ = {"add": 3, "broadcast_in_dim": 4, "cond": 3, "convert_element_type": 7,
          "dot_general": 3, "eq": 2, "exp": 1, "ge": 2, "get": 10, "iota": 1, "mul": 5,
          "program_id": 3, "reduce_sum": 1, "select_n": 2, "slice": 1, "sub": 2, "swap": 3}
    DKV = {"add": 4, "broadcast_in_dim": 5, "cond": 3, "convert_element_type": 9,
           "dot_general": 4, "eq": 2, "exp": 1, "ge": 2, "get": 13, "iota": 1, "mul": 5,
           "program_id": 3, "reduce_sum": 1, "select_n": 2, "slice": 1, "sub": 2, "swap": 6}

    @staticmethod
    def _grads(with_lse):
        BH, Sq, Sk, D = 2, 256, 384, 64
        q = jnp.zeros((BH, Sq, D), jnp.bfloat16)
        k = jnp.zeros((BH, Sk, D), jnp.bfloat16)
        lens = jnp.full((BH,), float(Sk))
        seed = jnp.zeros((1,), jnp.int32)

        def loss(q, k, v):
            if with_lse:
                o, lse = A._flash3_lse(q, k, v, lens, False, 0.125)
                return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse)
            return jnp.sum(A._flash3(q, k, v, lens, seed, False, 0.125, 0.0).astype(jnp.float32))

        return _kernel_primitives(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)

    @pytest.mark.parametrize("with_lse", [False, True], ids=["plain", "lse"])
    def test_same_operations(self, with_lse):
        import collections

        fwd, dq, dkv = (collections.Counter(p) for p in self._grads(with_lse))
        dlse = {"get": 1, "slice": 1} if with_lse else {}
        assert fwd == self.FWD
        assert dq == collections.Counter(self.DQ) + collections.Counter(dlse)
        assert dkv == collections.Counter(self.DKV) + collections.Counter(dlse)


def _oracle_grads(q, k, v, lens, causal, scale, w, wl=None):
    """(o, dq, dk, dv) of the jnp oracle for the loss sum(o * w) [+ sum(lse * wl)]."""
    def loss(q, k, v):
        o = A._attn_jnp(q, k, v, lens, causal, scale)
        out = jnp.sum(o * w)
        if wl is not None:
            s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
            kj = jnp.arange(k.shape[1])
            masked = kj[None, None, :] >= lens[:, None, None]
            if causal:
                masked |= kj[None, :] > jnp.arange(q.shape[1])[:, None]
            lse = jax.nn.logsumexp(jnp.where(masked, -jnp.inf, s), axis=-1)
            out = out + jnp.sum(jnp.where(jnp.isfinite(lse), lse, 0.0) * wl)
        return out
    o = A._attn_jnp(q, k, v, lens, causal, scale)
    return (o,) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


class TestTiledParity:
    """Interpret-mode parity of forward, dq, dk, dv against ``_attn_jnp`` where
    the diagonal walk has all four strips (S=512: block 512, tiles of 128)."""

    S, D, BH = 512, 64, 2

    def _inputs(self, seed, sk=None):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        sk = sk or self.S
        q = jax.random.normal(ks[0], (self.BH, self.S, self.D), jnp.float32)
        k = jax.random.normal(ks[1], (self.BH, sk, self.D), jnp.float32)
        v = jax.random.normal(ks[2], (self.BH, sk, self.D), jnp.float32)
        w = jax.random.normal(ks[3], (self.BH, self.S, self.D), jnp.float32)
        wl = jax.random.normal(ks[4], (self.BH, self.S), jnp.float32)
        return q, k, v, w, wl

    @pytest.mark.parametrize("lens", [None, (300, 512), (256, 384), (0, 130)],
                             ids=["no-lens", "inside-a-tile", "on-a-tile-edge", "zero"])
    def test_causal_matches_oracle(self, lens):
        q, k, v, w, _ = self._inputs(11)
        scale = 1.0 / np.sqrt(self.D)
        kv = None if lens is None else jnp.asarray(lens, jnp.float32)
        seed = jnp.zeros((1,), jnp.int32)

        def loss(q, k, v):
            return jnp.sum(A._flash3(q, k, v, kv, seed, True, scale, 0.0) * w)

        got = (A._flash3(q, k, v, kv, seed, True, scale, 0.0),) + jax.grad(
            loss, argnums=(0, 1, 2))(q, k, v)
        full = jnp.full((self.BH,), float(self.S)) if kv is None else kv
        want = _oracle_grads(q, k, v, full, True, scale, w)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            assert not np.any(np.isnan(np.asarray(a))), name
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)

    @pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
    def test_lse_variant_with_dlse(self, causal):
        q, k, v, w, wl = self._inputs(12)
        scale = 1.0 / np.sqrt(self.D)
        lens = jnp.asarray((300.0, 512.0))

        def loss(q, k, v):
            o, lse = A.flash_attention_with_lse(q, k, v, causal=causal, scale=scale,
                                                kv_lens=lens)
            return jnp.sum(o * w) + jnp.sum(lse * wl)

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = _oracle_grads(q, k, v, lens, causal, scale, w, wl)[1:]
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)

    def test_unequal_lengths_non_causal(self):
        q, k, v, w, _ = self._inputs(13, sk=384)
        scale = 1.0 / np.sqrt(self.D)
        lens = jnp.asarray((384.0, 200.0))
        seed = jnp.zeros((1,), jnp.int32)

        def loss(q, k, v):
            return jnp.sum(A._flash3(q, k, v, lens, seed, False, scale, 0.0) * w)

        got = (A._flash3(q, k, v, lens, seed, False, scale, 0.0),) + jax.grad(
            loss, argnums=(0, 1, 2))(q, k, v)
        want = _oracle_grads(q, k, v, lens, False, scale, w)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)

    def test_multi_block_causal_bf16(self):
        """Blocks above the diagonal skipped, below it unmasked, on it walked:
        S=1024 at D=256 is 2 x 2 blocks of 512."""
        ks = jax.random.split(jax.random.PRNGKey(14), 4)
        q, k, v, w = (jax.random.normal(kk, (1, 1024, 256), jnp.float32) for kk in ks)
        scale = 1.0 / 16.0
        seed = jnp.zeros((1,), jnp.int32)
        assert A._tile_plan(1024, 1024, 256, True).nq == 2
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

        def loss(q, k, v):
            return jnp.sum(A._flash3(q, k, v, None, seed, True, scale, 0.0).astype(jnp.float32) * w)

        got = jax.grad(loss, argnums=(0, 1, 2))(qb, kb, vb)
        full = jnp.full((1,), 1024.0)
        want = _oracle_grads(*(x.astype(jnp.float32) for x in (qb, kb, vb)), full, True, scale, w)[1:]
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a.astype(np.float32), b, atol=3e-2, rtol=3e-2, err_msg=name)


# ---------------------------------------------------------------------------------
# the window (PR 31): causal attention over the last W keys, the grid the band
# ---------------------------------------------------------------------------------


def _window_oracle(q, k, v, window, scale, kv_lens=None):
    """Materialised mask, straight from the definition: key j of query i is
    kept where ``i - window < j <= i`` (and ``j < len``)."""
    S = q.shape[1]
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    keep = (j <= i) & (j > i - window)
    keep = keep[None] if kv_lens is None else keep[None] & (j[None] < kv_lens[:, None, None])
    s = jnp.where(keep, jnp.einsum("bqd,bkd->bqk", q, k) * scale, -jnp.inf)
    p = jnp.where(keep, jnp.exp(s - jnp.max(jnp.where(keep, s, -1e30), -1, keepdims=True)), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    return jnp.einsum("bqk,bkd->bqd", jnp.where(l > 0, p / jnp.where(l > 0, l, 1.0), 0.0), v)


# (S, W, D): where the window's lower edge falls against the plan's blocks
WINDOWED = {
    "edge-inside-a-block": (512, 200, 64),          # block 256 > W, tiles of 128
    "edge-inside-a-tile-D128": (1024, 300, 128),    # block 512, the edge crosses two tiles
    "edge-on-a-block-boundary": (1024, 512, 64),    # W == block
    "window-is-one-tile": (512, 128, 64),           # W == block == tile
    "across-several-blocks": (2560, 2 * 512 + 128, 64),   # W = 2 blocks + 128: band of 4
    "one-block-a-head": (128, 50, 64),              # the one-pass body, both edges in it
    "own-key-only": (256, 1, 64),
}


class TestWindow:
    @staticmethod
    def _inputs(S, D, seed, BH=2):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        return tuple(jax.random.normal(kk, (BH, S, D), jnp.float32) for kk in ks)

    def test_plans_put_the_edge_where_the_cases_say(self):
        plans = {name: A._tile_plan(S, S, D, True, W) for name, (S, W, D) in WINDOWED.items()}
        got = {name: (p.bq, p.tq, p.band) for name, p in plans.items()}
        assert got == {
            "edge-inside-a-block": (256, 128, 2), "edge-inside-a-tile-D128": (512, 128, 2),
            "edge-on-a-block-boundary": (512, 128, 2), "window-is-one-tile": (128, 128, 2),
            "across-several-blocks": (512, 128, 4), "one-block-a-head": (128, 128, 1),
            "own-key-only": (128, 128, 1)}
        assert plans["one-block-a-head"].one_pass

    @pytest.mark.parametrize("lens", [None, (0.62, 1.0)], ids=["no-lens", "kv-lens"])
    @pytest.mark.parametrize("case", WINDOWED)
    def test_matches_the_materialised_mask(self, case, lens):
        """Forward and the three gradients, Pallas in interpret mode, against
        the mask written out (tolerances: the causal path's, TestTiledParity)."""
        S, W, D = WINDOWED[case]
        q, k, v, w = self._inputs(S, D, 31)
        scale = 1.0 / np.sqrt(D)
        kv = None if lens is None else jnp.asarray([int(f * S) for f in lens], jnp.float32)
        seed = jnp.zeros((1,), jnp.int32)

        def flash(q, k, v):
            return A._flash3(q, k, v, kv, seed, True, scale, 0.0, W)

        got = (flash(q, k, v),) + jax.grad(
            lambda q, k, v: jnp.sum(flash(q, k, v) * w), argnums=(0, 1, 2))(q, k, v)
        want = (_window_oracle(q, k, v, W, scale, kv),) + jax.grad(
            lambda q, k, v: jnp.sum(_window_oracle(q, k, v, W, scale, kv) * w),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            assert not np.any(np.isnan(np.asarray(a))), name
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)

    @pytest.mark.parametrize("impl", ["pallas", "jnp"])
    def test_public_call_and_jnp_path(self, impl):
        """(B, H, S, D) through ``flash_attention`` on both paths, gradients
        through the custom VJP / autodiff, bf16 on the Pallas path's terms."""
        S, W, D = 512, 200, 64
        q, k, v, w = (x.reshape(1, 2, S, D) for x in self._inputs(S, D, 32))
        lens = jnp.asarray([400])
        f = lambda q, k, v: jnp.sum(A.flash_attention(
            q, k, v, causal=True, window=W, kv_lens=lens, impl=impl) * w)
        g = lambda q, k, v: jnp.sum(_window_oracle(
            q[0], k[0], v[0], W, D ** -0.5, jnp.asarray([400.0, 400.0])) * w[0])
        for a, b in zip(jax.grad(f, (0, 1, 2))(q, k, v), jax.grad(g, (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)

    def test_lse_variant_and_self_attention_pass_the_window_on(self):
        S, W, D = 512, 200, 64
        q, k, v, w = self._inputs(S, D, 33)
        o, lse = A.flash_attention_with_lse(q, k, v, causal=True, scale=0.125, window=W)
        np.testing.assert_allclose(o, _window_oracle(q, k, v, W, 0.125), atol=5e-5, rtol=5e-5)
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        s = jnp.where((j <= i) & (j > i - W), jnp.einsum("bqd,bkd->bqk", q, k) * 0.125, -jnp.inf)
        np.testing.assert_allclose(lse, jax.nn.logsumexp(s, -1), atol=5e-5, rtol=5e-5)
        x = jax.random.normal(jax.random.PRNGKey(3), (1, S, 128), jnp.float32)
        w_qkv = jax.random.normal(jax.random.PRNGKey(4), (128, 384), jnp.float32) * 0.05
        w_out = jnp.eye(128)
        a = A.self_attention(x, w_qkv, None, w_out, None, 2, causal=True, window=W, impl="pallas")
        b = A.self_attention(x, w_qkv, None, w_out, None, 2, causal=True, window=W, impl="jnp")
        c = A.self_attention(x, w_qkv, None, w_out, None, 2, causal=True, impl="jnp")
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)
        assert float(jnp.max(jnp.abs(b - c))) > 1e-3          # the window cuts

    @pytest.mark.parametrize("by_cols", [False, True], ids=["rows", "cols"])
    @pytest.mark.parametrize("case", [c for c in WINDOWED if WINDOWED[c][0] <= 2560])
    def test_band_walk_covers_exactly_the_live_tiles(self, case, by_cols):
        """Brute force against the mask: over the band's blocks a tile is
        computed iff some score in it is live, once; it goes through the causal
        test iff the diagonal crosses it and through the window test iff the
        lower edge does — for the row walk (fwd, dq) and the column walk (dkv)."""
        S, W, D = WINDOWED[case]
        plan = A._tile_plan(S, S, D, True, W)
        b, t, seen = plan.bq, plan.tq, {}
        for i in range(plan.nq):
            for d in range(min(plan.band, i + 1)):
                for fixed, pieces in plan.band_walk(by_cols, d):
                    for moving, edge in pieces:
                        rows, cols = (moving, fixed) if by_cols else (fixed, moving)
                        for r in range(rows.start, rows.stop, t):
                            for c in range(cols.start, cols.stop, t):
                                key = ((i * b + r) // t, ((i - d) * b + c) // t)
                                assert key not in seen
                                seen[key] = edge
        want = {}
        for r in range(S // t):
            for c in range(S // t):
                lo, hi = (r - c) * t - (t - 1), (r - c) * t + (t - 1)     # q - k over the tile
                if hi >= 0 and lo < W:
                    want[(r, c)] = (1 if lo < 0 else 0) | (2 if hi >= W else 0)
        assert seen == want
        counts = plan.counts(False)
        assert counts["live"] == len(want) and counts["masked"] == sum(map(bool, want.values()))
        assert plan.counts(True)["masked"] == len(want)

    def test_the_mellum_cells_plan(self):
        """(S 8192, W 1024, D 128): blocks of 1024 in four strips, a band of two
        blocks a query block, and no grid step on a block outside the band."""
        plan = A._tile_plan(8192, 8192, 128, True, 1024)
        assert (plan.bq, plan.bk, plan.tq, plan.tk, plan.band) == (1024, 1024, 256, 256, 2)
        # 15 live blocks of the 64: the diagonal's 10 tiles + 10 of the block before it
        assert plan.counts(False) == {"total": 1024, "live": 150, "masked": 60}
        own, keys, queries = A._block_maps(plan)
        visited, clamped = set(), 0
        for i in range(plan.nq):
            steps = [int(keys(0, i, s)[1]) for s in range(plan.band)]
            assert len(steps) <= 2 and own(0, i, 0) == (0, i, 0)
            for s, j in enumerate(steps):
                if i + s - (plan.band - 1) < 0:      # before the sequence's start:
                    clamped += 1                     # clamped onto the next step's block,
                    assert j == steps[s + 1]         # so nothing new is copied
                else:
                    assert 0 <= i - j < plan.band    # inside the band
                    visited.add((i, j))
        assert len(visited) == 15 and clamped == 1
        assert {(int(queries(0, j, s)[1]), j) for j in range(plan.nk)
                for s in range(plan.band) if j + s < plan.nq} == visited
        assert int(queries(0, plan.nk - 1, 1)[1]) == plan.nq - 1      # dkv's one clamped step

    def test_tile_counter_and_kernel_names(self):
        from beforeholiday_tpu import monitor
        from beforeholiday_tpu.guard import dispatch

        dispatch.reset_dispatch_counters()
        q, k, v = _qkv(jax.random.PRNGKey(7), B=1, H=1, S=512)
        f = lambda q: jnp.sum(A.flash_attention(q, k, v, causal=True, window=200, impl="pallas"))
        jax.grad(f)(q)
        rows = {r["kernel"]: r for r in monitor.tile_records() if r["op"] == "flash_attention"}
        assert sorted(rows) == ["dqkv_blocks", "fwd"]      # the band's backward is one call (PR 48)
        for r in rows.values():
            assert r["key"] == repr((512, 512, 64, True, False, 200))
            assert (r["live"], r["total"], r["masked"]) == (9, 16, 9)
        dispatch.reset_dispatch_counters()
        names = [e.params["name"] for e in _pallas_eqns(jax.make_jaxpr(jax.grad(f))(q).jaxpr)]
        assert sorted(names) == ["flash_attention_window_dqkv_blocks", "flash_attention_window_fwd"]

    def test_errors(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), B=1, H=1, S=128)
        with pytest.raises(ValueError, match="causal=True"):
            A.flash_attention(q, k, v, window=64)
        with pytest.raises(ValueError, match="at least"):
            A.flash_attention(q, k, v, causal=True, window=0)


def _pallas_eqns(jaxpr):
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(e)
            continue
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    out += _pallas_eqns(getattr(sub, "jaxpr", sub))
    return out


class TestNoWindowIsTheParentsCall:
    """``window=None`` (and a window that holds the whole sequence) plans,
    counts, names and keys a call as the commit before the window did."""

    @pytest.mark.parametrize("key,block,tile,counts", TestTilePlan.TABLE,
                             ids=[f"S{k[0]}x{k[1]}-D{k[2]}-{'causal' if k[3] else 'full'}"
                                  for k, *_ in TestTilePlan.TABLE])
    def test_plan_and_counts(self, key, block, tile, counts):
        plan = A._tile_plan(*key)
        assert plan == A._tile_plan(*key, None) and plan.window is None
        assert tuple(plan)[:7] == (key[0], key[1], *block, *tile, key[3])
        live, total, masked = counts
        assert plan.counts(False) == {"total": total, "live": live, "masked": masked}

    @pytest.mark.parametrize("window", [None, 512, 4096], ids=["none", "W=S", "W>S"])
    def test_probe_key_tile_key_grid_and_names(self, window, monkeypatch):
        from beforeholiday_tpu import monitor
        from beforeholiday_tpu.guard import dispatch as gd

        monkeypatch.setattr(A, "_resolve_impl", lambda impl: "pallas")
        q, k, v = _qkv(jax.random.PRNGKey(5), B=1, H=2, S=512, D=64)
        gd.clear_probe_cache("flash_attention")
        gd.reset_dispatch_counters()
        f = lambda q: jnp.sum(A.flash_attention(q, k, v, causal=True, window=window))
        jaxpr = jax.make_jaxpr(jax.grad(f))(q).jaxpr
        keys = [key for key in gd.dispatch_counters() if key[0] == "flash_attention"]
        sig = ((2, 512, 64), "float32")
        assert keys == [("flash_attention", "cpu", (sig, sig, sig, "None", ((1,), "int32")),
                         (("causal", "True"), ("rate", "0.0"), ("scale", "0.125")), ())]
        assert {r["key"] for r in monitor.tile_records()} == {repr((512, 512, 64, True, False))}
        calls = _pallas_eqns(jaxpr)
        assert [e.params["grid_mapping"].grid for e in calls] == [(2, 1, 1)] * 2   # fwd, dqkv
        assert all(e.params["name"] is None for e in calls)    # the scope names them
        gd.reset_dispatch_counters()

    def test_a_windowed_call_has_its_own_probe_key(self, monkeypatch):
        from beforeholiday_tpu.guard import dispatch as gd

        monkeypatch.setattr(A, "_resolve_impl", lambda impl: "pallas")
        q, k, v = _qkv(jax.random.PRNGKey(5), B=1, H=1, S=512, D=64)
        gd.clear_probe_cache("flash_attention")
        gd.reset_dispatch_counters()
        A.flash_attention(q, k, v, causal=True, window=200)
        (key,) = [key for key in gd.dispatch_counters() if key[0] == "flash_attention"]
        assert key[3] == (("causal", "True"), ("rate", "0.0"), ("scale", "0.125"), ("window", "200"))
        gd.reset_dispatch_counters()


# ---------------------------------------------------------------------------------
# one backward kernel where a head is one block (PR 41)
# ---------------------------------------------------------------------------------


def _hashed_keep(seed_ref, b, i, j, nq, nk, shape, keep_prob):
    """``A._keep_mask`` for the interpreter, which has no PRNG: the same
    contract (a tile's mask is a function of the seed and the tile's id in the
    whole square, whichever kernel and panel draws it) from integer hashing."""
    tile = (b * nq + i) * nk + j
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    h = (r * 7919 + c * 104729 + tile * 31337 + seed_ref[0]) % 1009
    return h < int(keep_prob * 1009)


# (S, D): the GPT cells' call; one tile a block; the widest head at its largest
# one-block S; D = 128 in two strips
FUSED_SHAPES = [(1024, 64), (256, 128), (512, 256), (128, 64)]


class TestFusedBackward:
    """``plan.one_pass``: dq, dk and dv from ONE kernel (``_fa_bwd_fused``),
    against the oracle at ``TestTiledParity``'s tolerances and against the two
    kernels every other plan takes (``_fa_bwd_two_calls``, called here on the
    same one-block plan)."""

    BH = 2

    def _inputs(self, S, D, seed=21):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        q, k, v, w = (jax.random.normal(kk, (self.BH, S, D), jnp.float32) for kk in ks[:4])
        return q, k, v, w, jax.random.normal(ks[4], (self.BH, S), jnp.float32)

    @staticmethod
    def _lens(case, S, D):
        t = A._tile_plan(S, S, D, True).tk
        return {"no-lens": None,
                "inside-a-tile": (S - t // 2 - 5, S),
                "on-a-tile-edge": (t * max(1, S // t // 2), S - t if S > t else S),
                "zero": (0, t // 2 + 2)}[case]

    @pytest.mark.parametrize("case", ["no-lens", "inside-a-tile", "on-a-tile-edge", "zero"])
    @pytest.mark.parametrize("S,D", FUSED_SHAPES, ids=lambda x: str(x))
    def test_matches_oracle(self, S, D, case):
        q, k, v, w, _ = self._inputs(S, D)
        scale = 1.0 / np.sqrt(D)
        lens = self._lens(case, S, D)
        kv = None if lens is None else jnp.asarray(lens, jnp.float32)
        seed = jnp.zeros((1,), jnp.int32)
        assert A._tile_plan(S, S, D, True).one_pass

        def loss(q, k, v):
            return jnp.sum(A._flash3(q, k, v, kv, seed, True, scale, 0.0) * w)

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        full = jnp.full((self.BH,), float(S)) if kv is None else kv
        want = _oracle_grads(q, k, v, full, True, scale, w)[1:]
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            assert not np.any(np.isnan(np.asarray(a))), name
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)

    @pytest.mark.parametrize("S,D", FUSED_SHAPES, ids=lambda x: str(x))
    def test_lse_variant_with_dlse(self, S, D):
        q, k, v, w, wl = self._inputs(S, D, 22)
        scale = 1.0 / np.sqrt(D)
        lens = jnp.asarray(self._lens("inside-a-tile", S, D), jnp.float32)

        def loss(q, k, v):
            o, lse = A.flash_attention_with_lse(q, k, v, causal=True, scale=scale, kv_lens=lens)
            return jnp.sum(o * w) + jnp.sum(lse * wl)

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = _oracle_grads(q, k, v, lens, True, scale, w, wl)[1:]
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)

    @pytest.mark.parametrize("S,D", FUSED_SHAPES, ids=lambda x: str(x))
    def test_dropout_is_the_two_call_backwards(self, S, D, monkeypatch):
        """Fixed seed, the forward's draw: dq is the dq kernel's bit for bit
        (same panels, same product), dk and dv are the same float32 terms
        summed strip by strip instead of inside one product."""
        monkeypatch.setattr(A, "_keep_mask", _hashed_keep)
        q, k, v, w, _ = self._inputs(S, D, 23)
        scale, rate = 1.0 / np.sqrt(D), 0.25
        seed = jnp.asarray([12345], jnp.int32)
        plan = A._tile_plan(S, S, D, True)
        o, lse = A._fa_fwd_pallas(q, k, v, None, True, scale, True, rate, seed)
        plain, _ = A._fa_fwd_pallas(q, k, v, None, True, scale, True)
        assert float(jnp.max(jnp.abs(o - plain))) > 1e-2          # the mask bites
        args = (plan, q, k, v, w, o, lse, None, None, scale, True, rate, seed)
        one, two = A._fa_bwd_fused(*args), A._fa_bwd_two_calls(*args)
        np.testing.assert_array_equal(one[0], two[0])
        for name, a, b in zip(("dk", "dv"), one[1:], two[1:]):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)

    @staticmethod
    def _grad_fn(with_lse):
        seed = jnp.zeros((1,), jnp.int32)

        def loss(q, k, v):
            if with_lse:
                o, lse = A._flash3_lse(q, k, v, None, True, 0.125)
                return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse)
            return jnp.sum(A._flash3(q, k, v, None, seed, True, 0.125, 0.0).astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1, 2))

    @pytest.mark.parametrize("with_lse", [False, True], ids=["plain", "lse"])
    @pytest.mark.parametrize("S,D", FUSED_SHAPES + [(512, 64), (1024, 128)], ids=lambda x: str(x))
    def test_one_block_a_head_is_one_backward_call(self, S, D, with_lse):
        x = jax.ShapeDtypeStruct((2, S, D), jnp.bfloat16)
        calls = _pallas_eqns(jax.make_jaxpr(self._grad_fn(with_lse))(x, x, x).jaxpr)
        assert [e.params["grid_mapping"].grid for e in calls] == [(2, 1, 1)] * 2
        assert [len(e.params["out_avals"]) for e in calls] == [2, 3]     # (o, lse); (dq, dk, dv)
        assert all(e.params["name"] is None for e in calls)    # ``%flash_attention.N`` on the chip

    # a 2 x 2-block causal call (S=1024 at D=256): the three kernels of the
    # commit before the fused backward (6707be1), operation for operation
    FWD = {"add": 25, "broadcast_in_dim": 24, "concatenate": 3, "cond": 4,
           "convert_element_type": 14, "div": 1, "dot_general": 13, "eq": 3, "exp": 10,
           "get": 40, "gt": 5, "iota": 8, "log": 1, "lt": 1, "max": 5, "mul": 26,
           "program_id": 3, "reduce_max": 5, "reduce_sum": 5, "select_n": 8, "slice": 10,
           "sub": 10, "swap": 20}
    # since PR 44 that forward's grid is its three live blocks: the same body
    # with two ids where three were, each step's blocks read from the two tables
    FWD_LIVE = {**FWD, "program_id": 2, "get": 42}
    DQ = {"add": 24, "broadcast_in_dim": 10, "concatenate": 3, "cond": 4,
          "convert_element_type": 20, "dot_general": 18, "eq": 3, "exp": 5, "get": 47, "gt": 4,
          "iota": 8, "lt": 1, "mul": 31, "program_id": 3, "reduce_sum": 5, "select_n": 4,
          "slice": 5, "sub": 10, "swap": 7}
    DKV = {"add": 29, "broadcast_in_dim": 8, "concatenate": 3, "cond": 4,
           "convert_element_type": 20, "dot_general": 23, "eq": 3, "exp": 5, "get": 52, "gt": 4,
           "iota": 8, "lt": 1, "mul": 28, "program_id": 3, "reduce_sum": 2, "select_n": 4,
           "slice": 8, "sub": 10, "swap": 14}

    @pytest.mark.parametrize("with_lse", [False, True], ids=["plain", "lse"])
    @pytest.mark.parametrize("case", ["non-causal", "dq-over-the-budget"])
    def test_several_blocks_a_head_keep_their_two_calls_and_bodies(self, case, with_lse,
                                                                   monkeypatch):
        """What the fused call of several blocks does not take (PR 43) keeps the
        dq + dkv pair and the bodies it had: a non-causal call (dq is final only
        after the LAST key block; ``TestNonCausalBodyUnchanged``'s bodies), and a
        causal head whose float32 dq is over the VMEM budget (the 2 x 2-block
        bodies of 6707be1 above)."""
        import collections

        x = jax.ShapeDtypeStruct((1, 1024, 256), jnp.bfloat16)
        causal = case == "dq-over-the-budget"
        assert A._tile_plan(1024, 1024, 256, causal).nq == 2
        if causal:
            monkeypatch.setattr(A, "_HEAD_DQ_BYTES", 1024 * 256 * 4 - 1)
            grad, want = self._grad_fn(with_lse), (self.FWD_LIVE, self.DQ, self.DKV)
            dlse = {"get": 5, "slice": 5} if with_lse else {}    # a read a strip
        else:
            lens, seed = jnp.full((1,), 1024.0), jnp.zeros((1,), jnp.int32)

            def loss(q, k, v):
                if with_lse:
                    o, lse = A._flash3_lse(q, k, v, lens, False, 0.125)
                    return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse)
                return jnp.sum(A._flash3(q, k, v, lens, seed, False, 0.125, 0.0)
                               .astype(jnp.float32))

            grad = jax.grad(loss, argnums=(0, 1, 2))
            body = TestNonCausalBodyUnchanged
            want = (body.FWD, body.DQ, body.DKV)
            dlse = {"get": 1, "slice": 1} if with_lse else {}
        calls = _pallas_eqns(jax.make_jaxpr(grad)(x, x, x).jaxpr)
        grids = [e.params["grid_mapping"].grid for e in calls]
        assert grids == [(1, 3) if causal else (1, 2, 2)] + [(1, 2, 2)] * 2
        fwd, dq, dkv = (collections.Counter(p) for p in _kernel_primitives(grad, x, x, x))
        assert fwd == want[0]
        assert dq == collections.Counter(want[1]) + collections.Counter(dlse)
        assert dkv == collections.Counter(want[2]) + collections.Counter(dlse)

    @pytest.mark.parametrize("with_lse", [False, True], ids=["plain", "lse"])
    def test_the_fused_body_of_several_blocks_is_smaller_than_the_two_it_replaces(self, with_lse):
        """A causal 2 x 2-block head (S=1024 at D=256) is ONE backward call on the
        dkv kernel's grid, and its body is the dkv body + one product a panel
        (dq) + the head's dq zeroed and written out: 5 products a strip and one
        ``exp`` a strip where the two bodies (``DQ`` + ``DKV`` above: 212 + 229
        operations, 41 products, 10 ``exp``) had 7 and 2."""
        x = jax.ShapeDtypeStruct((1, 1024, 256), jnp.bfloat16)
        calls = _pallas_eqns(jax.make_jaxpr(self._grad_fn(with_lse))(x, x, x).jaxpr)
        assert [e.params["grid_mapping"].grid for e in calls] == [(1, 3), (1, 2, 2)]
        assert [len(e.params["out_avals"]) for e in calls] == [2, 3]     # (o, lse); (dq, dk, dv)
        assert all(e.params["name"] is None for e in calls)    # ``%flash_attention.N`` on the chip
        params = calls[1].params["compiler_params"]["mosaic_tpu"]
        assert tuple(params.dimension_semantics) == ("parallel", "arbitrary", "arbitrary")
        assert params.vmem_limit_bytes == A._blocks_vmem_bytes(
            A._tile_plan(1024, 1024, 256, True), 256, 256, 2)
        fwd, body = _kernel_primitives(self._grad_fn(with_lse), x, x, x)
        import collections
        assert collections.Counter(fwd) == self.FWD_LIVE
        assert len(body) <= 265 + (10 if with_lse else 0), len(body)
        # the DKV body's 23 products + dq's: one a strip of the diagonal's walk, one a block below
        assert body.count("dot_general") == 23 + 4 + 1
        assert body.count("exp") == 5

    def test_the_fused_body_is_smaller_than_the_two_it_replaces(self):
        """The body is traced at every start of a program (set-up time): at the
        GPT cells' call the two bodies were 167 + 169 operations (6707be1)."""
        x = jax.ShapeDtypeStruct((2, 1024, 64), jnp.bfloat16)
        _, dqkv = _kernel_primitives(self._grad_fn(False), x, x, x)
        assert len(dqkv) <= 250, len(dqkv)
        # s a piece (the run left of the diagonal, the tile on it), then a strip: dp, dq, dk, dv
        assert dqkv.count("dot_general") == 7 + 4 * 4
        assert dqkv.count("exp") == 4 and "cond" not in dqkv

    @pytest.mark.parametrize("key,kernels,counts", [
        ((1024, 1024, 64), ["dqkv", "fwd"], (10, 16, 4)),                       # the GPT cells
        ((8192, 8192, 256), ["dqkv_blocks", "fwd"], (2080, 4096, 64)),          # the Qwen cell
        ((8192, 8192, (192, 128)), ["dqkv_blocks", "fwd"], (528, 1024, 32)),    # the Kanana cell
    ], ids=["gpt_cells", "qwen_cell", "kanana_cell"])
    def test_tile_records_say_which_backward_was_traced(self, key, kernels, counts):
        from beforeholiday_tpu import monitor
        from beforeholiday_tpu.guard import dispatch

        dispatch.reset_dispatch_counters()
        Dk, Dv = key[2] if isinstance(key[2], tuple) else (key[2], key[2])
        x, v = (jax.ShapeDtypeStruct((1, key[0], D), jnp.bfloat16) for D in (Dk, Dv))
        jax.make_jaxpr(self._grad_fn(False))(x, x, v)
        rows = {r["kernel"]: r for r in monitor.tile_records() if r["op"] == "flash_attention"}
        assert sorted(rows) == kernels
        for r in rows.values():
            assert r["key"] == repr((*key, True, False))
            assert (r["live"], r["total"], r["masked"]) == counts
        dispatch.reset_dispatch_counters()

    def test_a_windowed_block_takes_the_fused_call_under_its_own_name(self):
        """One block a head with a window inside it: the band's walk of that
        block (both edges), the kernel named as the windowed ones are."""
        S, W, D = 512, 300, 64
        plan = A._tile_plan(S, S, D, True, W)
        assert plan.one_pass and plan.band == 1
        q, k, v, w, _ = self._inputs(S, D, 24)
        kv = jnp.asarray((200.0, 512.0))
        seed = jnp.zeros((1,), jnp.int32)
        flash = lambda q, k, v: jnp.sum(A._flash3(q, k, v, kv, seed, True, 0.125, 0.0, W) * w)
        want = jax.grad(lambda q, k, v: jnp.sum(_window_oracle(q, k, v, W, 0.125, kv) * w),
                        argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(jax.grad(flash, argnums=(0, 1, 2))(q, k, v), want):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)
        names = [e.params["name"] for e in _pallas_eqns(
            jax.make_jaxpr(jax.grad(flash, argnums=(0, 1, 2)))(q, k, v).jaxpr)]
        assert names == ["flash_attention_window_fwd", "flash_attention_window_dqkv"]


# -- one backward call where a causal head is several blocks (PR 43) ------------------

# (Dk, Dv, S): 5 x 5 blocks of 256 (two strips of 128 on the diagonal) at D <= 128
# and at the latent-attention widths
BLOCKS_SHAPES = [(64, 64, 1280), (128, 128, 1280), (192, 128, 1280), (64, 128, 1280)]


def _swaps_on(jaxpr, ref):
    """``swap`` operations of a kernel body that store to ``ref`` (one of the
    body's own variables), followed into the branches of its ``cond``s."""
    n = 0
    for e in jaxpr.eqns:
        if e.primitive.name == "swap" and e.invars[0] is ref:
            n += 1
        elif e.primitive.name == "cond":
            for at, v in enumerate(e.invars[1:]):
                if v is ref:
                    n += max(_swaps_on(br.jaxpr, br.jaxpr.invars[at]) for br in e.params["branches"])
    return n


class TestFusedBackwardOfSeveralBlocks:
    """A causal, un-windowed head of several blocks whose float32 dq fits the
    VMEM budget: dq, dk and dv from ONE call (``_fa_bwd_blocks``) against the
    oracle and against the dq + dkv pair (``_fa_bwd_two_calls``) on the same
    residuals. dk and dv are the dkv kernel's bit for bit (its body); dq sums the
    same float32 terms, the block ON the diagonal by key strip and not by row
    strip."""

    BH = 2

    def _inputs(self, Dk, Dv, S, seed=41):
        return TestTwoWidths._inputs(self, Dk, Dv, S, seed)

    @pytest.mark.parametrize("variant", ["plain", "kv_lens", "dlse", "dropout"])
    @pytest.mark.parametrize("Dk,Dv,S", BLOCKS_SHAPES, ids=lambda x: str(x))
    def test_matches_the_oracle_and_the_two_calls(self, Dk, Dv, S, variant, monkeypatch):
        monkeypatch.setattr(A, "_keep_mask", _hashed_keep)
        q, k, v, w, wl = self._inputs(Dk, Dv, S)
        scale = Dk ** -0.5
        plan = A._tile_plan(S, S, Dk, True, None, Dv)
        assert (plan.nq, plan.bq) == (5, 256) and A._bwd_of(plan, Dk) is A._fa_bwd_blocks
        lens = jnp.asarray((S - 70, S - 256), jnp.float32) if variant in ("kv_lens", "dlse") else None
        rate = 0.25 if variant == "dropout" else 0.0
        seed = jnp.asarray([4321], jnp.int32)
        dlse = jnp.broadcast_to(wl[..., None], (self.BH, S, 128)) if variant == "dlse" else None
        o, lse = A._fa_fwd_pallas(q, k, v, lens, True, scale, True, rate, seed)
        args = (plan, q, k, v, w, o, lse, dlse, lens, scale, True, rate, seed)
        one, two = A._fa_bwd_blocks(*args), A._fa_bwd_two_calls(*args)
        assert [t.shape for t in one] == [q.shape, k.shape, v.shape]
        for name, a, b in zip(("dq", "dk", "dv"), one, two):
            assert not np.any(np.isnan(np.asarray(a))), name
            if name == "dq":
                np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-6, err_msg=name)
            else:
                np.testing.assert_array_equal(a, b, err_msg=name)
        if rate == 0.0:       # the oracle draws another mask
            full = jnp.full((self.BH,), float(S)) if lens is None else lens
            want = _oracle_grads(q, k, v, full, True, scale, w, wl if variant == "dlse" else None)[1:]
            for name, a, b in zip(("dq", "dk", "dv"), one, want):
                np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)

    @pytest.mark.parametrize("Dk,Dv,S", BLOCKS_SHAPES, ids=lambda x: str(x))
    def test_bfloat16_results_are_the_two_calls_to_a_rounding(self, Dk, Dv, S):
        q, k, v, w, _ = (t.astype(jnp.bfloat16) for t in self._inputs(Dk, Dv, S, 42))
        plan = A._tile_plan(S, S, Dk, True, None, Dv)
        o, lse = A._fa_fwd_pallas(q, k, v, None, True, Dk ** -0.5, True)
        args = (plan, q, k, v, w, o, lse, None, None, Dk ** -0.5, True, 0.0, None)
        one, two = A._fa_bwd_blocks(*args), A._fa_bwd_two_calls(*args)
        np.testing.assert_array_equal(one[1], two[1])
        np.testing.assert_array_equal(one[2], two[2])
        a, b = (np.asarray(t, np.float32) for t in (one[0], two[0]))
        assert one[0].dtype == jnp.bfloat16 and np.mean(a != b) < 1e-3
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=2 ** -6)    # a bfloat16 step or two

    @pytest.mark.parametrize("with_lse", [False, True], ids=["plain", "lse"])
    def test_every_query_blocks_dq_is_written_exactly_once(self, with_lse):
        """The body stores to the dq output in ONE place, under the diagonal
        step's predicate — so a head's grid (key block outer) writes query block
        ``j`` at step ``(j, j)`` and at no other — and the query side's index
        maps name, at the steps above the diagonal, the block the diagonal step
        takes: nothing is copied for them, and dq's block leaves when ``j``
        moves on with every key block before it summed in."""
        x = jax.ShapeDtypeStruct((1, 1024, 256), jnp.bfloat16)
        grad = TestFusedBackward._grad_fn(with_lse)
        call = _pallas_eqns(jax.make_jaxpr(grad)(x, x, x).jaxpr)[1]
        body, gm = call.params["jaxpr"], call.params["grid_mapping"]
        n_in = 7 if with_lse else 6
        dq_ref, dk_ref, dv_ref = body.invars[n_in:n_in + 3]
        assert [_swaps_on(body, r) for r in (dq_ref, dk_ref, dv_ref)] == [1, 1, 1]
        maps = [bm.index_map_jaxpr for bm in gm.block_mappings]
        at = lambda m, j, i: tuple(int(t) for t in jax.core.eval_jaxpr(m.jaxpr, m.consts, 0, j, i))
        for j in range(2):
            for i in range(2):
                blocks = [at(m, j, i)[1] for m in maps]
                # q, k, v, do, o, lse [, dlse]; dq, dk, dv
                want = [max(i, j), j, j] + [max(i, j)] * (n_in - 3) + [j, j, j]
                assert blocks == want, (j, i, blocks)

    @pytest.mark.parametrize("case", ["non-causal", "windowed", "dq-over-the-budget",
                                      "windowed-dq-over-the-budget", "one-block"])
    def test_what_it_does_not_take_keeps_the_calls_it_had(self, case, monkeypatch):
        """The rule is a function of ``(Sq, Sk, Dk, Dv, causal, window)`` alone. A
        windowed head of several blocks is on the fused side since PR 48, under the
        same budget: over it, it keeps the pair as the un-windowed head does."""
        S, D = 1280, 64
        if case.endswith("dq-over-the-budget"):
            assert A._HEAD_DQ_BYTES == 8 * 2 ** 20      # S = 8192 at D = 256; 16,384 at 128
            for s, d, fits in ((8192, 256, True), (16384, 128, True), (16384, 192, False),
                               (32768, 64, True), (32768, 128, False)):
                for window in (None, 1024):
                    plan = A._tile_plan(s, s, d, True, window)
                    assert (A._bwd_of(plan, d) is A._fa_bwd_blocks) == fits, (s, d, window)
            monkeypatch.setattr(A, "_HEAD_DQ_BYTES", S * D * 4 - 1)
        causal, window = case != "non-causal", 300 if case.startswith("windowed") else None
        S = 256 if case == "one-block" else S
        plan = A._tile_plan(S, S, D, causal, window)
        want = {"one-block": A._fa_bwd_fused, "windowed": A._fa_bwd_blocks}.get(
            case, A._fa_bwd_two_calls)
        assert A._bwd_of(plan, D) is want
        q, k, v, w, _ = self._inputs(D, D, S, 43)
        lens = None if causal else jnp.full((self.BH,), float(S))
        seed = jnp.zeros((1,), jnp.int32)
        grad = jax.grad(lambda *a: jnp.sum(A._flash3(*a, lens, seed, causal, 0.125, 0.0, window) * w),
                        argnums=(0, 1, 2))
        calls = _pallas_eqns(jax.make_jaxpr(grad)(q, k, v).jaxpr)
        assert len(calls) == (3 if want is A._fa_bwd_two_calls else 2)
        for e in calls[1:]:
            params = e.params["compiler_params"]["mosaic_tpu"]
            if want is A._fa_bwd_blocks:
                assert tuple(params.dimension_semantics) == ("parallel", "arbitrary", "arbitrary")
                assert params.vmem_limit_bytes == A._blocks_vmem_bytes(plan, D, D, 4)
                continue
            assert tuple(params.dimension_semantics) == ("parallel", "parallel", "arbitrary")
            assert params.vmem_limit_bytes is None

    def test_through_the_public_call_and_ring_attentions_chunk(self):
        """``flash_attention`` and ``flash_attention_with_lse`` (a causal chunk
        of several blocks, ``dlse`` from the merge) reach the one call."""
        from beforeholiday_tpu import monitor
        from beforeholiday_tpu.guard import dispatch

        dispatch.reset_dispatch_counters()
        q, k, v, w, wl = self._inputs(64, 64, 640, 44)
        f = lambda *a: jnp.sum(A.flash_attention(*(t[None] for t in a), causal=True,
                                                 impl="pallas")[0] * w)

        def g(q, k, v):
            o, lse = A.flash_attention_with_lse(q, k, v, causal=True, scale=0.125)
            return jnp.sum(o * w) + jnp.sum(lse * wl)

        full = jnp.full((self.BH,), 640.0)
        for fn, want in ((f, _oracle_grads(q, k, v, full, True, 0.125, w)[1:]),
                         (g, _oracle_grads(q, k, v, full, True, 0.125, w, wl)[1:])):
            for a, b in zip(jax.grad(fn, argnums=(0, 1, 2))(q, k, v), want):
                np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
        kernels = {r["kernel"] for r in monitor.tile_records() if r["op"] == "flash_attention"}
        assert kernels == {"fwd", "dqkv_blocks"}
        dispatch.reset_dispatch_counters()


# -- one backward call where a windowed head is several blocks: the band (PR 48) --------

# the TestWindow shapes whose head is several blocks: the edge inside a block and
# on a block boundary (band == nq == 2), a band of 2 of 4 blocks, of 4 of 5, and
# the diagonal alone (band 1); + a band of 3 of 5 blocks, two strips a block
BAND_SHAPES = {**{c: WINDOWED[c] for c in WINDOWED if c != "one-block-a-head"},
               "band-of-3": (1280, 256 + 130, 64)}


class TestFusedBackwardOfTheBand:
    """A causal, WINDOWED head of several blocks whose float32 dq fits the VMEM
    budget: dq, dk and dv from ONE call (``_fa_bwd_blocks`` on the band's dkv
    grid ``(BH, nk, band)``) against ``_window_oracle`` and against the dq + dkv
    pair (``_fa_bwd_two_calls``) on the same residuals. As without a window, dk
    and dv are the dkv kernel's bit for bit (its body, its order) and dq sums the
    same float32 terms key block by key block as the dq kernel does, the blocks
    an edge crosses by key strip and not by row strip: equal to a float32
    rounding, and bit for bit where a block is one tile."""

    BH = 2

    def _inputs(self, S, D, seed=61):
        return TestFusedBackward._inputs(self, S, D, seed)

    def test_the_shapes_are_what_the_cases_say(self):
        got = {c: (p.nq, p.band, A._bwd_of(p, D) is A._fa_bwd_blocks)
               for c, (S, W, D) in BAND_SHAPES.items()
               for p in [A._tile_plan(S, S, D, True, W)]}
        assert got == {
            "edge-inside-a-block": (2, 2, True), "edge-inside-a-tile-D128": (2, 2, True),
            "edge-on-a-block-boundary": (2, 2, True), "window-is-one-tile": (4, 2, True),
            "across-several-blocks": (5, 4, True), "own-key-only": (2, 1, True),
            "band-of-3": (5, 3, True)}

    @pytest.mark.parametrize("variant", ["plain", "kv_lens", "dlse", "dropout"])
    @pytest.mark.parametrize("case", BAND_SHAPES)
    def test_matches_the_oracle_and_the_two_calls(self, case, variant, monkeypatch):
        monkeypatch.setattr(A, "_keep_mask", _hashed_keep)
        S, W, D = BAND_SHAPES[case]
        q, k, v, w, wl = self._inputs(S, D)
        scale = D ** -0.5
        plan = A._tile_plan(S, S, D, True, W)
        # one length inside the last block, one that empties it
        lens = jnp.asarray((S - 70, S - plan.bq), jnp.float32) if variant in ("kv_lens", "dlse") else None
        rate = 0.25 if variant == "dropout" else 0.0
        seed = jnp.asarray([4321], jnp.int32)
        dlse = jnp.broadcast_to(wl[..., None], (self.BH, S, 128)) if variant == "dlse" else None
        o, lse = A._fa_fwd_pallas(q, k, v, lens, True, scale, True, rate, seed, W)
        args = (plan, q, k, v, w, o, lse, dlse, lens, scale, True, rate, seed)
        one, two = A._fa_bwd_blocks(*args), A._fa_bwd_two_calls(*args)
        assert [t.shape for t in one] == [q.shape, k.shape, v.shape]
        for name, a, b in zip(("dq", "dk", "dv"), one, two):
            assert not np.any(np.isnan(np.asarray(a))), name
            if name == "dq" and plan.bq > plan.tq:
                np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-6, err_msg=name)
            else:
                np.testing.assert_array_equal(a, b, err_msg=name)
        if rate == 0.0 and dlse is None:       # the oracle draws another mask, and has no lse
            want = jax.grad(lambda q, k, v: jnp.sum(_window_oracle(q, k, v, W, scale, lens) * w),
                            argnums=(0, 1, 2))(q, k, v)
            for name, a, b in zip(("dq", "dk", "dv"), one, want):
                np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)

    @pytest.mark.parametrize("case", ["edge-inside-a-block", "across-several-blocks"])
    def test_the_lse_variants_dlse_against_autodiff_of_the_mask(self, case):
        """``flash_attention_with_lse(window=)``: the cotangent of the exposed lse
        through the one call, against the materialised mask's own logsumexp."""
        S, W, D = BAND_SHAPES[case]
        q, k, v, w, wl = self._inputs(S, D, 62)
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        keep = (j <= i) & (j > i - W)

        def flash(q, k, v):
            o, lse = A.flash_attention_with_lse(q, k, v, causal=True, scale=0.125, window=W)
            return jnp.sum(o * w) + jnp.sum(lse * wl)

        def oracle(q, k, v):
            s = jnp.where(keep, jnp.einsum("bqd,bkd->bqk", q, k) * 0.125, -jnp.inf)
            return (jnp.sum(_window_oracle(q, k, v, W, 0.125) * w)
                    + jnp.sum(jax.nn.logsumexp(s, -1) * wl))

        for name, a, b in zip(("dq", "dk", "dv"), jax.grad(flash, (0, 1, 2))(q, k, v),
                              jax.grad(oracle, (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)

    @pytest.mark.parametrize("case", ["edge-inside-a-tile-D128", "across-several-blocks"])
    def test_bfloat16_results_are_the_two_calls_to_a_rounding(self, case):
        S, W, D = BAND_SHAPES[case]
        q, k, v, w, _ = (t.astype(jnp.bfloat16) for t in self._inputs(S, D, 63))
        plan = A._tile_plan(S, S, D, True, W)
        o, lse = A._fa_fwd_pallas(q, k, v, None, True, D ** -0.5, True, window=W)
        args = (plan, q, k, v, w, o, lse, None, None, D ** -0.5, True, 0.0, None)
        one, two = A._fa_bwd_blocks(*args), A._fa_bwd_two_calls(*args)
        np.testing.assert_array_equal(one[1], two[1])
        np.testing.assert_array_equal(one[2], two[2])
        a, b = (np.asarray(t, np.float32) for t in (one[0], two[0]))
        assert one[0].dtype == jnp.bfloat16 and np.mean(a != b) < 1e-3
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=2 ** -6)    # a bfloat16 step or two

    @pytest.mark.parametrize("with_lse", [False, True], ids=["plain", "lse"])
    def test_one_call_on_the_bands_grid_under_its_own_name(self, with_lse):
        """At the Mellum cell's call: forward and ONE backward ``pallas_call``,
        ``flash_attention_window_dqkv_blocks`` (``^%flash_attention_window`` reads
        it), grid ``(BH, nk, band)``, the query side under the band's dkv maps —
        query block ``j + s`` clamped onto the last — and dq, dk, dv under the key
        block's; the dq output is stored in one place, the diagonal step's."""
        S, W, D = 8192, 1024, 128
        x = jax.ShapeDtypeStruct((2, S, D), jnp.bfloat16)
        seed = jnp.zeros((1,), jnp.int32)

        def loss(q, k, v):
            if with_lse:
                o, lse = A._flash3_lse(q, k, v, None, True, 0.125, W)
                return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse)
            return jnp.sum(A._flash3(q, k, v, None, seed, True, 0.125, 0.0, W).astype(jnp.float32))

        calls = _pallas_eqns(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x).jaxpr)
        assert [e.params["name"] for e in calls] == [
            "flash_attention_window_fwd", "flash_attention_window_dqkv_blocks"]
        assert [e.params["grid_mapping"].grid for e in calls] == [(2, 8, 2)] * 2
        assert len(calls[1].params["out_avals"]) == 3
        plan = A._tile_plan(S, S, D, True, W)
        params = calls[1].params["compiler_params"]["mosaic_tpu"]
        assert tuple(params.dimension_semantics) == ("parallel", "arbitrary", "arbitrary")
        assert params.vmem_limit_bytes == A._blocks_vmem_bytes(plan, D, D, 2) < 48 * 2 ** 20
        body, gm = calls[1].params["jaxpr"], calls[1].params["grid_mapping"]
        n_in = 7 if with_lse else 6
        dq_ref, dk_ref, dv_ref = body.invars[n_in:n_in + 3]
        assert [_swaps_on(body, r) for r in (dq_ref, dk_ref, dv_ref)] == [1, 1, 1]
        maps = [bm.index_map_jaxpr for bm in gm.block_mappings]
        at = lambda m, j, s: tuple(int(t) for t in jax.core.eval_jaxpr(m.jaxpr, m.consts, 0, j, s))
        for j in range(plan.nk):
            for s in range(plan.band):
                i = min(j + s, plan.nq - 1)
                # q, k, v, do, o, lse [, dlse]; dq, dk, dv
                assert [at(m, j, s)[1] for m in maps] == [i, j, j] + [i] * (n_in - 3) + [j, j, j]

    def test_the_body_is_smaller_than_the_two_it_replaces(self):
        """Set-up time: the band's one body holds its two walks once (5 products
        and one ``exp`` a strip) where the dq and dkv bodies held them twice."""
        S, W, D = 8192, 1024, 128
        x = jax.ShapeDtypeStruct((1, S, D), jnp.bfloat16)
        plan = A._tile_plan(S, S, D, True, W)
        args = (plan, x, x, x, x, x, jax.ShapeDtypeStruct((1, S, 128), jnp.float32),
                None, None, 0.125, True, 0.0, None)
        ops = lambda fn: _kernel_primitives(lambda *a: fn(plan, *a, *args[7:]), *args[1:7])
        (one,), (dq, dkv) = ops(A._fa_bwd_blocks), ops(A._fa_bwd_two_calls)
        assert len(one) < 0.62 * (len(dq) + len(dkv)), (len(one), len(dq), len(dkv))
        assert one.count("exp") == dkv.count("exp") == dq.count("exp")
        assert one.count("dot_general") == dkv.count("dot_general") + dkv.count("exp")

    def test_tile_records_and_the_public_call(self):
        """``flash_attention(window=)`` reaches the one call: ``fwd`` +
        ``dqkv_blocks`` booked, no ``dq`` / ``dkv``, with the band's counts."""
        from beforeholiday_tpu import monitor
        from beforeholiday_tpu.guard import dispatch

        dispatch.reset_dispatch_counters()
        x = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16)
        f = lambda q, k, v: jnp.sum(A.flash_attention(
            q, k, v, causal=True, window=1024, impl="pallas").astype(jnp.float32))
        jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(x, x, x)
        rows = {r["kernel"]: r for r in monitor.tile_records() if r["op"] == "flash_attention"}
        assert sorted(rows) == ["dqkv_blocks", "fwd"]
        for r in rows.values():
            assert r["key"] == repr((8192, 8192, 128, True, False, 1024))
            assert (r["live"], r["total"], r["masked"]) == (150, 1024, 60)
        dispatch.reset_dispatch_counters()


# -- queries and keys of one width, values of another (latent attention; PR 42) ------

# (Dk, Dv, S): a head that is one block (the fused backward) and one of several
# (the fused backward of several blocks: 5 x 5 blocks of 256) at each pair of widths
TWO_WIDTH_SHAPES = [(192, 128, 256), (192, 128, 1280), (64, 128, 256), (64, 128, 1280)]


class TestTwoWidths:
    """``q, k (.., Dk)`` on ``v (.., Dv)``: every output and cotangent of the
    kernels (interpret mode) against the jnp oracle, which takes the two widths
    by itself; scores are ``Dk`` deep and default to ``Dk^-1/2``, values and the
    result are ``Dv`` wide, ``dq`` / ``dk`` come back ``Dk`` wide and ``dv``
    ``Dv`` wide, and nothing is padded to a common width."""

    BH = 2

    def _inputs(self, Dk, Dv, S, seed=31):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        q, k = (jax.random.normal(kk, (self.BH, S, Dk), jnp.float32) for kk in ks[:2])
        v, w = (jax.random.normal(kk, (self.BH, S, Dv), jnp.float32) for kk in ks[2:4])
        return q, k, v, w, jax.random.normal(ks[4], (self.BH, S), jnp.float32)

    @pytest.mark.parametrize("lens", [None, "inside-a-tile"])
    @pytest.mark.parametrize("Dk,Dv,S", TWO_WIDTH_SHAPES, ids=lambda x: str(x))
    def test_causal_matches_oracle(self, Dk, Dv, S, lens):
        q, k, v, w, _ = self._inputs(Dk, Dv, S)
        scale = 1.0 / np.sqrt(Dk)
        plan = A._tile_plan(S, S, Dk, True, None, Dv)
        assert plan.one_pass == (S == 256) and plan.bq == 256
        kv = None if lens is None else jnp.asarray((S - 70, S), jnp.float32)
        seed = jnp.zeros((1,), jnp.int32)
        flash = lambda q, k, v: A._flash3(q, k, v, kv, seed, True, scale, 0.0)
        got = (flash(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
        full = jnp.full((self.BH,), float(S)) if kv is None else kv
        want = _oracle_grads(q, k, v, full, True, scale, w)
        assert [a.shape[-1] for a in got] == [Dv, Dk, Dk, Dv]
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            assert not np.any(np.isnan(np.asarray(a))), name
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)

    @pytest.mark.parametrize("Dk,Dv,S", TWO_WIDTH_SHAPES[:3], ids=lambda x: str(x))
    def test_lse_variant_with_dlse(self, Dk, Dv, S):
        q, k, v, w, wl = self._inputs(Dk, Dv, S, 32)
        scale = 1.0 / np.sqrt(Dk)
        lens = jnp.asarray((S - 70, S), jnp.float32)

        def loss(q, k, v):
            o, lse = A.flash_attention_with_lse(q, k, v, causal=True, scale=scale, kv_lens=lens)
            assert o.shape == (self.BH, S, Dv) and lse.shape == (self.BH, S)
            return jnp.sum(o * w) + jnp.sum(lse * wl)

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = _oracle_grads(q, k, v, lens, True, scale, w, wl)[1:]
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)

    @pytest.mark.parametrize("Dk,Dv", [(192, 128), (64, 128)])
    def test_non_causal_with_kv_lens_through_the_public_call(self, Dk, Dv):
        """(B, H, S, D) operands, more keys than queries, a key length inside a
        block; the default scale is ``Dk^-1/2``."""
        ks = jax.random.split(jax.random.PRNGKey(33), 4)
        q = jax.random.normal(ks[0], (2, 2, 256, Dk), jnp.float32)
        k = jax.random.normal(ks[1], (2, 2, 384, Dk), jnp.float32)
        v = jax.random.normal(ks[2], (2, 2, 384, Dv), jnp.float32)
        w = jax.random.normal(ks[3], (2, 2, 256, Dv), jnp.float32)
        lens = jnp.asarray([300, 384])

        def grads(impl, **kw):
            f = lambda *a: A.flash_attention(*a, kv_lens=lens, impl=impl, **kw)
            return (f(q, k, v),) + jax.grad(lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(q, k, v)

        got, want = grads("pallas"), grads("jnp", scale=Dk ** -0.5)
        assert got[0].shape == (2, 2, 256, Dv)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)

    def test_bfloat16_at_the_latent_attention_widths(self):
        q, k, v, w, _ = (t.astype(jnp.bfloat16) if t.ndim == 3 else t
                         for t in self._inputs(192, 128, 512, 34))
        got = A.flash_attention(q[None], k[None], v[None], causal=True, impl="pallas")
        want = A.flash_attention(q[None], k[None], v[None], causal=True, impl="jnp")
        assert got.dtype == jnp.bfloat16 and got.shape == (1, self.BH, 512, 128)
        np.testing.assert_allclose(got.astype(np.float32), want.astype(np.float32),
                                   atol=2e-2, rtol=2e-2)

    def test_the_plan_takes_both_widths(self):
        """Blocks of 1024 where the values are one lane tile wide and the keys at
        most two: 192 / 128 (measured on the chip, PR 42) as every one-width call
        at D <= 128; a one-width call at 192 or 256 keeps 512. A head of 192 /
        128 at S = 8192 is 8 x 8 blocks and its backward the fused call of several
        blocks (PR 43); both widths are in the tiles' key."""
        from beforeholiday_tpu import monitor
        from beforeholiday_tpu.guard import dispatch

        plan = A._tile_plan(8192, 8192, 192, True, None, 128)
        assert (plan.bq, plan.nq, plan.tq, plan.one_pass) == (1024, 8, 256, False)
        assert A._tile_plan(8192, 8192, 64, True, None, 128) == A._tile_plan(8192, 8192, 128, True)
        for one_width in (192, 256):
            assert A._tile_plan(8192, 8192, one_width, True).bq == 512
        assert A._tile_plan(8192, 8192, 128, True, None, 192).bq == 512      # wide values
        assert A._tile_plan(8192, 8192, 128, True, None, 128) == A._tile_plan(8192, 8192, 128, True)
        key = (2048, 2048, (192, 128), True, False)
        for kernel in ("fwd", "dqkv_blocks"):
            dispatch._TILES.pop(("flash_attention", kernel, key), None)
        q, k, v, w, _ = self._inputs(192, 128, 2048)
        jax.grad(lambda *a: jnp.sum(A._flash3(
            *a, None, jnp.zeros((1,), jnp.int32), True, 0.1, 0.0)))(q, k, v)
        rows = {r["kernel"]: r for r in monitor.tile_records() if r["key"] == repr(key)}
        assert sorted(rows) == ["dqkv_blocks", "fwd"]
        assert all((r["total"], r["live"], r["masked"]) == (64, 36, 8) for r in rows.values())

    def test_one_width_books_the_tiles_it_booked(self):
        """A ``Dv == Dk`` call's key holds the one width as an int, as before
        the kernels knew two (``monitor.tile_records()`` rows keep their keys)."""
        from beforeholiday_tpu.guard import dispatch

        before = set(dispatch.tile_counters())
        q, k, v = (t[0] for t in _qkv(jax.random.PRNGKey(35), B=1, H=2, S=384, D=64))
        jax.grad(lambda *a: jnp.sum(A._flash3(
            *a, None, jnp.zeros((1,), jnp.int32), True, 0.1, 0.0)))(q, k, v)
        new = set(dispatch.tile_counters()) - before
        assert new == {("flash_attention", kernel, (384, 384, 64, True, False))
                       for kernel in ("fwd", "dqkv_blocks")}

    def test_no_operand_is_padded_to_a_common_width(self):
        """The kernels' operands are the caller's arrays at their own widths:
        no ``pad`` / ``concatenate`` before a ``pallas_call``, whose operands are
        192 and 128 wide and whose results are 192 (dq, dk) and 128 (o, dv)."""
        q, k, v, w, _ = self._inputs(192, 128, 2048)
        flash = lambda *a: A.flash_attention(*(t[None] for t in a), causal=True, impl="pallas")
        jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2)))(q, k, v)
        calls = _pallas_eqns(jaxpr.jaxpr)
        assert len(calls) == 2          # fwd, the fused backward of several blocks
        # (the seed and the forward's two step tables are scalars: one dimension)
        widths = lambda vs: sorted({x.aval.shape[-1] for x in vs if x.aval.ndim > 1})
        for eqn in calls:
            assert widths(eqn.invars) == [128, 192]
        assert [x.aval.shape[-1] for x in calls[-1].outvars] == [192, 192, 128]
        names = {e.primitive.name for e in jaxpr.jaxpr.eqns}
        assert not names & {"pad", "concatenate"}

    def test_shape_errors_name_both_widths(self):
        q, k, v = _qkv(jax.random.PRNGKey(36), S=128, D=64)
        with pytest.raises(ValueError, match=r"q \(B, H, S, Dk\), k \(B, H, Sk, Dk\), v \(B, H, Sk, Dv\)"):
            A.flash_attention(q, k[..., :32], v)
        with pytest.raises(ValueError, match=r"q \(B, H, S, Dk\)"):
            A.flash_attention(q, k, v[:, :, :64])
        with pytest.raises(ValueError, match=r"head dims 64 \(q, k\) / 4 \(v\)"):
            A.flash_attention(q, k, v[..., :4], impl="pallas")
        assert A.is_flash_available(128, 192, 128) and not A.is_flash_available(128, 192, 4)
        assert A.is_flash_available(128, 64) and not A.is_flash_available(100, 64, 64)


# -- the causal forward names live blocks only; dq and dkv clamp onto the diagonal (PR 44) --


def _parents_maps(monkeypatch):
    """``ops.attention`` with the index maps of the commit before: every plan
    without a window steps over the whole square, the forward too, and names the
    block of its step — the plain ``(b, s, 0)``."""
    own = lambda b, o, s, *_: (b, o, 0)
    other = lambda b, o, s, *_: (b, s, 0)
    maps = A._block_maps
    monkeypatch.setattr(A.TilePlan, "live_axis", property(lambda self: False))
    monkeypatch.setattr(A, "_block_maps",
                        lambda plan: (own, other, other) if plan.window is None else maps(plan))


# (Sq, Sk, D, causal, window): blocks a side, block, and the forward's (copies, steps) a head
GRIDS = {
    "S2048-b512": ((2048, 2048, 256, True, None), 4, 512, (10, 10)),
    "S8192-b1024": ((8192, 8192, 128, True, None), 8, 1024, (36, 36)),
    "S8192-b512": ((8192, 8192, 256, True, None), 16, 512, (136, 136)),
    "windowed": ((8192, 8192, 128, True, 1024), 8, 1024, (15, 16)),     # the Mellum cell's band of 2
    "non-causal": ((2048, 2048, 64, False, None), 2, 1024, (4, 4)),
    "one-block": ((1024, 1024, 64, True, None), 1, 1024, (1, 1)),       # the GPT cells
}


class TestLiveBlocks:
    """A causal head of several blocks without a window: the forward's grid is
    ``(BH, live blocks)`` — no step and no copy above the diagonal — and the dq
    and dkv kernels, which keep the square's grid, name the diagonal's block at
    the steps above it. No kernel body changes: every output is the parent's
    maps' bit for bit."""

    BH = 2

    @staticmethod
    def _walks(plan):
        """``(fwd, dq, dkv)``: the (query block, key block) each kernel's index
        maps name at every grid step of one head, in the grid's order."""
        own, keys, queries = A._block_maps(plan)
        at = lambda m, *ids: int(m(0, *ids)[1])
        square = lambda steps: [(o, s) for o in range(plan.nq) for s in range(steps)]
        dq = [(at(own, o, s), at(keys, o, s)) for o, s in square(A._steps(plan))]
        dkv = [(at(queries, o, s), at(own, o, s)) for o, s in square(A._steps(plan, True))]
        if not plan.live_axis:
            return dq, dq, dkv
        (BH, steps), tables, own, keys = A._live_grid(plan, 3)
        tables = [np.asarray(t) for t in tables]
        assert BH == 3 and all(t.shape == (steps,) and t.dtype == np.int32 for t in tables)
        return [(at(own, s, *tables), at(keys, s, *tables)) for s in range(steps)], dq, dkv

    @pytest.mark.parametrize("case", GRIDS)
    def test_index_maps_name_the_live_blocks_and_no_other(self, case):
        key, n, block, (copies, steps) = GRIDS[case]
        plan = A._tile_plan(*key)
        assert (plan.nq, plan.nk, plan.bq, plan.bk) == (n, n, block, block)
        causal, window = key[3], key[4]
        assert plan.live_axis == (causal and window is None and n > 1)
        fwd, dq, dkv = self._walks(plan)
        assert fwd == plan.fwd_steps() and len(fwd) == steps
        named_anew = lambda walk: sum(a != b for a, b in zip([None] + walk, walk))
        lower = [(i, j) for i in range(n) for j in range(i + 1)]
        if plan.live_axis or plan.one_pass:
            # the lower triangle and nothing else: the forward steps on each block
            # once, row by row from key block 0 to the diagonal; dq and dkv step
            # over the square and name, above the diagonal, the diagonal's block
            assert fwd == lower
            assert dq == [(i, min(j, i)) for i in range(n) for j in range(n)]
            assert dkv == [(max(i, j), j) for j in range(n) for i in range(n)]
            assert set(dq) == set(dkv) == set(lower)
            assert [named_anew(w) for w in (fwd, dq, dkv)] == [n * (n + 1) // 2] * 3
        elif window is not None:            # the parent's band, clamped into the sequence
            back = plan.band - 1
            assert fwd == dq == [(i, max(i + s - back, 0)) for i in range(n) for s in range(plan.band)]
            assert dkv == [(min(j + s, n - 1), j) for j in range(n) for s in range(plan.band)]
        else:                               # the parent's square, each block once
            assert fwd == dq == [(i, j) for i in range(n) for j in range(n)]
            assert dkv == [(i, j) for j in range(n) for i in range(n)]
        assert named_anew(fwd) == copies

    @pytest.mark.parametrize("case", GRIDS)
    def test_counts_book_the_forwards_copies_and_steps(self, case):
        key, n, _, (copies, steps) = GRIDS[case]
        plan = A._tile_plan(*key)
        tiles = plan.counts(False)
        assert sorted(tiles) == ["live", "masked", "total"]         # the backward kernels' rows
        assert plan.counts(False, fwd=True) == {**tiles, "copies": copies, "steps": steps}
        assert plan.counts(True, fwd=True)["copies"] == copies
        if plan.live_axis:      # what the parent's maps took: the whole square, each block copied
            assert (copies, steps) == (n * (n + 1) // 2,) * 2 and n * n > steps

    def test_tile_records_hold_them_on_the_forwards_row(self):
        from beforeholiday_tpu import monitor
        from beforeholiday_tpu.guard import dispatch

        key = (384, 384, 64, True, False)
        for kernel in ("fwd", "dqkv_blocks"):
            dispatch._TILES.pop(("flash_attention", kernel, key), None)
        q, k, v = (t[0] for t in _qkv(jax.random.PRNGKey(45), B=1, H=2, S=384, D=64))
        jax.grad(lambda *a: jnp.sum(A._flash3(
            *a, None, jnp.zeros((1,), jnp.int32), True, 0.1, 0.0)))(q, k, v)
        rows = {r["kernel"]: r for r in monitor.tile_records() if r["key"] == repr(key)}
        assert (rows["fwd"]["copies"], rows["fwd"]["steps"]) == (6, 6)      # 3 x 3 blocks of 128
        assert "copies" not in rows["dqkv_blocks"] and "steps" not in rows["dqkv_blocks"]
        assert all((r["total"], r["live"], r["masked"]) == (9, 6, 3) for r in rows.values())

    def _inputs(self, Dk, Dv, S, seed=51):
        return TestTwoWidths._inputs(self, Dk, Dv, S, seed)

    def _all_five(self, Dk, Dv, S, variant, two_calls):
        q, k, v, w, wl = self._inputs(Dk, Dv, S)
        scale = Dk ** -0.5
        plan = A._tile_plan(S, S, Dk, True, None, Dv)
        lens = jnp.asarray((S - 70, S - 128), jnp.float32) if variant in ("kv_lens", "dlse") else None
        rate = 0.25 if variant == "dropout" else 0.0
        seed = jnp.asarray([4321], jnp.int32)
        dlse = jnp.broadcast_to(wl[..., None], (self.BH, S, 128)) if variant == "dlse" else None
        fwd = lambda q, k, v: A._fa_fwd_pallas(q, k, v, lens, True, scale, True, rate, seed)
        (call,) = _pallas_eqns(jax.make_jaxpr(fwd)(q, k, v).jaxpr)
        o, lse = fwd(q, k, v)
        bwd = A._fa_bwd_two_calls if two_calls else A._fa_bwd_blocks
        return call.params["grid_mapping"].grid, (o, lse) + tuple(
            bwd(plan, q, k, v, w, o, lse, dlse, lens, scale, True, rate, seed))

    @pytest.mark.parametrize("backward", ["one_call", "two_calls"])
    @pytest.mark.parametrize("variant", ["plain", "kv_lens", "dropout", "dlse"])
    @pytest.mark.parametrize("Dk,Dv", [(192, 128), (64, 64), (256, 256)], ids=lambda x: str(x))
    def test_every_output_is_the_parents_maps_bit_for_bit(self, Dk, Dv, variant, backward,
                                                          monkeypatch):
        """3 x 3 blocks of 128 in the interpreter: ``o``, ``lse``, ``dq``, ``dk``
        and ``dv`` under the live axis and the clamp against the same kernels
        under the plain ``(b, s, 0)`` maps on the whole square — the one backward
        call (its query side) and the dq + dkv pair (both sides)."""
        monkeypatch.setattr(A, "_keep_mask", _hashed_keep)
        S, two = 384, backward == "two_calls"
        plan = A._tile_plan(S, S, Dk, True, None, Dv)
        assert (plan.nq, plan.bq, plan.live_axis) == (3, 128, True)
        grid, got = self._all_five(Dk, Dv, S, variant, two)
        with monkeypatch.context() as parent:
            _parents_maps(parent)
            square, want = self._all_five(Dk, Dv, S, variant, two)
        assert plan.live_axis and (grid, square) == ((self.BH, 6), (self.BH, 3, 3))
        for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
            assert a.shape == b.shape and not np.any(np.isnan(np.asarray(a))), name
            np.testing.assert_array_equal(a, b, err_msg=name)

    @pytest.mark.parametrize("variant", ["plain", "kv_lens", "dropout"])
    def test_the_forwards_grid_is_the_live_blocks_and_the_parents_is_the_square(
            self, variant, monkeypatch):
        """The call's own grid and operands: ``(BH, 6)`` with the two tables after
        the other scalars where the parent's maps give ``(BH, 3, 3)``; the
        backward keeps the square and its operands."""
        q, k, v, w, _ = self._inputs(64, 64, 384)
        lens = jnp.asarray((300.0, 256.0)) if variant == "kv_lens" else None
        rate = 0.25 if variant == "dropout" else 0.0
        seed = jnp.zeros((1,), jnp.int32)

        def calls():    # a fresh function a trace: ``_flash3`` keeps the forward it traced
            def both(q, k, v):
                o, lse = A._fa_fwd_pallas(q, k, v, lens, True, 0.125, True, rate, seed)
                return A._fa_bwd_pallas(q, k, v, w, o, lse, None, lens, True, 0.125, True,
                                        rate, seed)
            return _pallas_eqns(jax.make_jaxpr(both)(q, k, v).jaxpr)

        scalars = (lens is not None) + (rate > 0.0)
        fwd, bwd = calls()
        assert fwd.params["grid_mapping"].grid == (self.BH, 6)
        assert bwd.params["grid_mapping"].grid == (self.BH, 3, 3)
        assert fwd.params["grid_mapping"].num_index_operands == scalars + 2
        assert bwd.params["grid_mapping"].num_index_operands == scalars
        tables = [x.aval for x in fwd.invars[scalars:scalars + 2]]
        assert [(t.shape, t.dtype) for t in tables] == [((6,), jnp.int32)] * 2
        semantics = lambda e: tuple(e.params["compiler_params"]["mosaic_tpu"].dimension_semantics)
        assert semantics(fwd) == ("parallel", "arbitrary")
        with monkeypatch.context() as parent:
            _parents_maps(parent)
            fwd, bwd = calls()
            assert fwd.params["grid_mapping"].grid == bwd.params["grid_mapping"].grid == (self.BH, 3, 3)
            assert fwd.params["grid_mapping"].num_index_operands == scalars
            assert semantics(fwd) == ("parallel", "parallel", "arbitrary")

    def test_the_fused_backward_has_no_index_map_of_its_own(self):
        import inspect

        assert "lambda" not in inspect.getsource(A._fa_bwd_blocks).replace("spec = lambda", "")
        assert "_block_maps(plan)" in inspect.getsource(A._fa_bwd_blocks)


# ---------------------------------------------------------------------------------
# the selected-keys form: a set of keys per query that arrives at run time (PR 46)
# ---------------------------------------------------------------------------------

def _selection(key, B, S, keep):
    """A seeded selection ``(B, S, S)`` int8: query ``t`` keeps ``min(t + 1,
    keep)`` keys ``s <= t``, scattered (the largest of a random score), so that
    whole rows of a block can be empty and a query may not keep its own key."""
    scores = jax.random.normal(key, (B, S, S))
    causal = jnp.tril(jnp.ones((S, S), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(keep, S))
    sel = jnp.zeros((B, S, S), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None], idx].set(True)
    return (sel & causal).astype(jnp.int8)


def _selected_oracle(q, k, v, sel, scale):
    """Materialised scores in float32, the softmax over the kept keys only."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    p = jax.nn.softmax(jnp.where(sel[:, None] != 0, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _all_four(f, q, k, v, w):
    o, pull = jax.vjp(f, q, k, v)
    return (o,) + pull(w)


class TestSelected:
    """``flash_attention(selected=)``: forward, the fused backward of one block,
    the fused backward of several blocks and the dq + dkv pair, in the interpreter,
    against the jnp oracle and a materialised softmax over the kept keys."""

    # (S, D, keep, which backward the plan takes)
    SHAPES = ((256, 64, 40, "dqkv"), (2048, 64, 300, "dqkv_blocks"), (384, 128, 1, "dqkv_blocks"))

    @pytest.mark.parametrize("S,D,keep,kernel", SHAPES,
                             ids=[f"S{s}-D{d}-keep{n}" for s, d, n, _ in SHAPES])
    def test_o_dq_dk_dv_against_the_oracles(self, S, D, keep, kernel):
        from beforeholiday_tpu import monitor
        from beforeholiday_tpu.guard import dispatch as gd

        B, H = (1, 2) if S > 1024 else (2, 2)
        q, k, v = _qkv(jax.random.PRNGKey(S), B=B, H=H, S=S, D=D)
        w = jax.random.normal(jax.random.PRNGKey(1), q.shape)
        sel = _selection(jax.random.PRNGKey(2), B, S, keep)
        scale = D ** -0.5
        gd.reset_dispatch_counters()
        run = lambda impl: _all_four(lambda q, k, v: A.flash_attention(
            q, k, v, causal=True, selected=sel, impl=impl), q, k, v, w)
        got, want = run("pallas"), run("jnp")
        plain = _all_four(lambda q, k, v: _selected_oracle(q, k, v, sel, scale), q, k, v, w)
        for name, a, b, c in zip(("o", "dq", "dk", "dv"), got, want, plain):
            # one kept key: dq and dk are zero in exact arithmetic; the inputs' scale is 1
            size = max(float(jnp.max(jnp.abs(c))), 1.0)
            assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * size, name
            assert float(jnp.max(jnp.abs(a - c))) <= 2e-5 * size, name
        booked = {r["kernel"] for r in monitor.tile_records() if "selected" in r["key"]}
        assert booked == {"fwd", kernel}, booked
        gd.reset_dispatch_counters()

    def test_the_two_call_backward_takes_the_operand_too(self, monkeypatch):
        """A head whose float32 dq does not fit the fused kernel's VMEM budget
        takes dq + dkv, each under the block of kept keys its own maps name."""
        S, D = 512, 64
        monkeypatch.setattr(A, "_block_size", lambda s, *w: 128)
        monkeypatch.setattr(A, "_HEAD_DQ_BYTES", S * D * 4 - 1)
        A._tile_plan.cache_clear()
        try:
            q, k, v = _qkv(jax.random.PRNGKey(3), B=1, H=2, S=S, D=D)
            w = jax.random.normal(jax.random.PRNGKey(1), q.shape)
            sel = _selection(jax.random.PRNGKey(2), 1, S, 70)
            f = lambda q, k, v: A.flash_attention(q, k, v, causal=True, selected=sel,
                                                  impl="pallas")
            names = [e.params["name"] for e in _pallas_eqns(
                jax.make_jaxpr(lambda *a: _all_four(f, *a, w))(q, k, v).jaxpr)]
            assert names == [f"flash_attention_sparse_{n}" for n in ("fwd", "dq", "dkv")]
            got = _all_four(f, q, k, v, w)
            want = _all_four(lambda q, k, v: _selected_oracle(q, k, v, sel, D ** -0.5), q, k, v, w)
            for a, b in zip(got, want):
                assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(jnp.max(jnp.abs(b)))
        finally:
            A._tile_plan.cache_clear()

    @pytest.mark.parametrize("S", (256, 2048), ids=("one-block", "several-blocks"))
    def test_a_selection_of_all_causal_keys_is_the_plain_causal_call_bit_for_bit(self, S):
        B, H = 1, 2
        q, k, v = _qkv(jax.random.PRNGKey(S + 1), B=B, H=H, S=S, D=64)
        w = jax.random.normal(jax.random.PRNGKey(1), q.shape)
        everything = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), jnp.int8)), (B, S, S))
        run = lambda sel: _all_four(lambda q, k, v: A.flash_attention(
            q, k, v, causal=True, selected=sel, impl="pallas"), q, k, v, w)
        for name, a, b in zip(("o", "dq", "dk", "dv"), run(everything), run(None)):
            assert bool(jnp.array_equal(a, b)), name

    def test_the_heads_of_a_batch_row_share_its_selection_and_rows_differ(self):
        """``sel[b]`` serves every head of row ``b`` and no other row."""
        B, H, S = 2, 3, 256
        q, k, v = _qkv(jax.random.PRNGKey(9), B=B, H=H, S=S, D=64)
        sel = _selection(jax.random.PRNGKey(2), B, S, 30)
        assert not bool(jnp.array_equal(sel[0], sel[1]))
        got = A.flash_attention(q, k, v, causal=True, selected=sel, impl="pallas")
        for b in range(B):
            one = A.flash_attention(q[b:b + 1], k[b:b + 1], v[b:b + 1], causal=True,
                                    selected=sel[b:b + 1], impl="pallas")
            assert bool(jnp.array_equal(got[b], one[0]))

    def test_plan_names_fill_and_counts(self):
        plain = A._tile_plan(8192, 8192, 128, True, None, 128)
        plan = A._tile_plan(8192, 8192, 128, True, None, 128, True)
        assert plan == plain._replace(selected=True) and plan.live_axis and not plain.selected
        assert A._bwd_of(plan, 128) is A._fa_bwd_blocks
        assert [A._kernel_name(plan, n) for n in ("fwd", "dqkv_blocks")] == [
            "flash_attention_sparse_fwd", "flash_attention_sparse_dqkv_blocks"]
        assert A._kernel_name(plain, "fwd") is None
        assert A._fill(plan) == 2 * A._NEG and A._fill(plain) == A._NEG
        # every causal tile is walked, and every one of them through the operand's mask
        counts, base = plan.counts(False, True), plain.counts(False, True)
        assert {k: counts[k] for k in ("total", "live", "steps", "copies")} == \
            {k: base[k] for k in ("total", "live", "steps", "copies")}
        assert counts["masked"] == counts["live"] == 528 and base["masked"] == 32
        # the kept keys' block and its int32 copy are in what the fused backward asks for
        extra = A._blocks_vmem_bytes(plan, 128, 128, 2) - A._blocks_vmem_bytes(plain, 128, 128, 2)
        assert extra == 2 * 1024 * 1024 + 4 * 1024 * 1024

    def test_a_call_without_a_selection_traces_what_it_traced(self, monkeypatch):
        """``selected=None`` hands the kernels no operand: the same operands, grid
        and (scope-given) names as a call that does not name the argument."""
        monkeypatch.setattr(A, "_resolve_impl", lambda impl: "pallas")
        q, k, v = _qkv(jax.random.PRNGKey(5), B=1, H=2, S=512, D=64)
        f = lambda kw: jax.make_jaxpr(jax.grad(
            lambda q: jnp.sum(A.flash_attention(q, k, v, causal=True, **kw))))(q)
        a, b = f({}), f({"selected": None})
        assert str(a) == str(b)
        assert all(e.params["name"] is None for e in _pallas_eqns(a.jaxpr))

    def test_what_a_selected_call_refuses(self):
        q, k, v = _qkv(jax.random.PRNGKey(5), B=1, H=1, S=128, D=64)
        sel = jnp.ones((1, 128, 128), jnp.int8)
        for kw in ({"causal": False}, {"causal": True, "window": 64},
                   {"causal": True, "kv_lens": jnp.asarray([100])},
                   {"causal": True, "dropout_rate": 0.1, "dropout_key": jax.random.PRNGKey(0)}):
            with pytest.raises(ValueError, match="selected="):
                A.flash_attention(q, k, v, selected=sel, **kw)
        with pytest.raises(ValueError, match=r"\(B, S, Sk\)"):
            A.flash_attention(q, k, v, causal=True, selected=sel[:, :64])

    def test_the_guard_probes_a_selected_call_under_its_own_key(self, monkeypatch):
        from beforeholiday_tpu.guard import dispatch as gd

        monkeypatch.setattr(A, "_resolve_impl", lambda impl: "pallas")
        q, k, v = _qkv(jax.random.PRNGKey(5), B=1, H=2, S=256, D=64)
        sel = _selection(jax.random.PRNGKey(2), 1, 256, 30)
        gd.clear_probe_cache("flash_attention")
        gd.reset_dispatch_counters()
        A.flash_attention(q, k, v, causal=True, selected=sel)
        (key,) = [key for key in gd.dispatch_counters() if key[0] == "flash_attention"]
        sig = ((2, 256, 64), "float32")
        assert key[2] == (sig, sig, sig, ((1, 256, 256), "int8")) and key[3] == (("scale", "0.125"),)
        assert gd.dispatch_counters()[key]["pallas"] == 1
        gd.reset_dispatch_counters()
