"""chip_smoke.py's phases at a toy GPT on virtual CPU devices, its refusal to
run without the chip, its dispatch assertion, and the compile-cache helper it
calls first. Cheap by construction: both phases are built and compiled once
per module."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

_REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, _REPO)
import chip_smoke  # noqa: E402

from beforeholiday_tpu.guard import dispatch  # noqa: E402
from beforeholiday_tpu.ops import attention, normalization  # noqa: E402
from beforeholiday_tpu.testing import faults, gpt  # noqa: E402
from beforeholiday_tpu.utils import compile_cache  # noqa: E402


def _pallas_by_default(monkeypatch):
    """Resolve the default dispatch to the Pallas kernels, as the chip does
    (here they run in the interpreter), so the guard's counters book what
    they book there."""
    pick = lambda impl: "pallas" if impl is None else impl  # noqa: E731
    monkeypatch.setattr(attention, "_resolve_impl", pick)
    monkeypatch.setattr(normalization, "_resolve_impl", pick)


@pytest.fixture(scope="module")
def phases():
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    cfg = gpt.GPTConfig(vocab_size=64, seq_len=128, d_model=32, n_heads=2,
                        n_layers=1, dtype=jnp.bfloat16)
    batch = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
    with pytest.MonkeyPatch.context() as mp:
        _pallas_by_default(mp)
        one = chip_smoke.train_1chip(cfg, batch, devs, 5)
        four = chip_smoke.train_4chip(cfg, batch, devs[:4], 5,
                                      expect_losses=one["losses"])
    dispatch.reset_dispatch_counters()
    dispatch.clear_probe_cache()
    return one, four


class TestPhases:
    def test_one_device_trains(self, phases):
        one, _ = phases
        assert one["ok"], one["errors"]
        assert one["steps"] == 5 and len(one["step_s"]) == 4
        assert one["losses"][-1] < one["losses"][0]
        assert one["found_inf_last"] is False
        for op in ("flash_attention", "layer_norm"):
            assert one["dispatch"][op]["pallas"] > 0
            assert one["dispatch"][op]["jnp"] == 0
        json.dumps(one)  # the phase line must serialize as is

    def test_dp4_reproduces_the_one_device_trajectory(self, phases):
        one, four = phases
        assert four["ok"], four["errors"]
        assert four["devices"] == 4
        # every chip is fed the one-device batch: same mean loss, same
        # averaged gradients, step for step
        assert four["losses"] == pytest.approx(
            one["losses"], rel=chip_smoke.DP4_LOSS_RTOL)
        assert four["dp_vs_1chip_max_rel"] <= chip_smoke.DP4_LOSS_RTOL
        assert four["dispatch"]["flash_attention"]["pallas"] > 0
        json.dumps(four)

    def test_dp4_flags_a_foreign_trajectory(self, phases):
        """The comparison is live: the same run held against another
        trajectory fails the phase."""
        one, _ = phases
        cfg = gpt.GPTConfig(vocab_size=64, seq_len=16, d_model=16, n_heads=2,
                            n_layers=1, use_flash_attention=False)
        batch = gpt.synthetic_batch(jax.random.PRNGKey(1), cfg, 2)
        res = chip_smoke.train_4chip(
            cfg, batch, jax.devices()[:4], 2,
            expect_losses=[2 * x for x in one["losses"][:2]])
        assert not res["ok"]
        assert any("one-chip trajectory" in e for e in res["errors"])


class TestDispatchAssertion:
    KEY = ("flash_attention", "tpu", (), (), ())
    LN = ("layer_norm", "tpu", (), (), ())

    def test_clean_counters_pass(self):
        counters = {self.KEY: {"pallas": 2, "jnp": 0, "probes": 1},
                    self.LN: {"pallas": 3, "jnp": 0, "probes": 1}}
        assert chip_smoke.dispatch_errors(counters, {}) == []

    def test_jnp_dispatch_fails(self):
        counters = {self.KEY: {"pallas": 2, "jnp": 1, "probes": 1},
                    self.LN: {"pallas": 3, "jnp": 0, "probes": 1}}
        errs = chip_smoke.dispatch_errors(counters, {})
        assert len(errs) == 1 and "flash_attention" in errs[0]

    def test_missing_op_fails(self):
        """resolve_impl stepping aside books NOTHING (checked_impl is never
        reached) — silence is a failure too."""
        errs = chip_smoke.dispatch_errors({}, {})
        assert len(errs) == 2 and all("no pallas dispatch" in e for e in errs)

    def test_forced_probe_failure_fails(self, monkeypatch):
        _pallas_by_default(monkeypatch)
        dispatch.reset_dispatch_counters()
        q = jnp.ones((1, 2, 128, 16), jnp.bfloat16)
        try:
            with faults.force_probe_failure("flash_attention"):
                attention.flash_attention(q, q, q, causal=True)
                errs = chip_smoke.dispatch_errors(
                    dispatch.dispatch_counters(), dispatch.probe_failures())
        finally:
            dispatch.reset_dispatch_counters()
        assert any("degraded to jnp" in e for e in errs)
        assert any("probe failed for flash_attention" in e for e in errs)


class TestMainRefusesWithoutTheChip:
    def test_exits_nonzero_before_compiling(self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode not in (0, 2, 3)  # 2/3 are the chip tool's own
        assert "'cpu'" in out.stderr and "tpu" in out.stderr
        assert out.stdout.strip() == ""  # no result line
        assert not (tmp_path / "cache").exists()  # nothing was compiled


class TestVerdictLine:
    """The driver reads the LAST stdout line: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``), whatever else the run printed."""

    @staticmethod
    def _canned(name, ok):
        return {"phase": name, "ok": ok, "compile_s": 1.0, "step_s": [0.1, 0.1],
                "tokens_per_s": 1.0, "losses": [2.0, 1.0],
                "errors": [] if ok else ["boom"]}

    @pytest.mark.parametrize("ok", [True, False])
    def test_last_line_is_the_contract_object(self, monkeypatch, capsys, ok):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(
            chip_smoke, "train_1chip",
            lambda *a, **k: self._canned("train_1chip", True))
        monkeypatch.setattr(
            chip_smoke, "train_4chip",
            lambda *a, **k: self._canned("train_4chip", ok))
        rc = chip_smoke.main([])
        assert (rc == 0) is ok
        lines = capsys.readouterr().out.strip().splitlines()
        verdict = json.loads(lines[-1])
        dev = jax.devices()
        assert verdict == {"ok": ok, "device": {
            "platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}}
        assert type(verdict["device"]["count"]) is int
        summary = json.loads(lines[-2])["summary"]
        assert summary["claim"] is None and summary["ok"] is ok


class TestCompileCacheHelper:
    @pytest.fixture
    def restore_cache_dir(self):
        prev = jax.config.jax_compilation_cache_dir
        prev_key = jax.config.jax_compilation_cache_include_metadata_in_key
        yield
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_compilation_cache_include_metadata_in_key", prev_key)

    def test_env_placement_is_left_alone(self, monkeypatch, restore_cache_dir):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == "/placed/from/outside"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_a_fixed_path_in_the_checkout(
            self, monkeypatch, restore_cache_dir):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = compile_cache.enable_compile_cache()
        second = compile_cache.enable_compile_cache()
        assert first == second == os.path.join(
            os.path.realpath(_REPO), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
