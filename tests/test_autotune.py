"""Autotuner tests: knob space, signatures, manifest, search, resolution.

Pins the contracts ISSUE 20 ships:

* the :class:`KnobSpace` constraint algebra (requires / requires_context,
  sanitize-to-fixpoint so stale manifest entries revert instead of raise);
* signature stability — same (model, mesh, chip) → same digest, any change
  → a different one;
* manifest durability (atomic write, corrupt file degrades to empty) and
  THE cache-hit pin: a second ``tune()`` under the same key runs ZERO
  trials;
* ledger-costed pruning (peak_temp_bytes over budget, compute-bound and
  already slower) and the ``max_trials`` bound;
* per-trial isolation: ``trial_scope`` scope-resets the trial's own
  ``track_compiles`` entry so repeated lowers across trials fire no
  spurious recompile warn-once and trip no strict ``BucketGateError``;
* resolution precedence through ``amp.initialize(tuned=True)`` and the
  DDP/ZeRO-2/ZeRO-3 constructors: explicit kwargs > manifest > defaults,
  with ONE structured warning per site on a manifest miss.
"""

import contextlib
import json
import logging
import os

import jax
import jax.numpy as jnp
import pytest

from beforeholiday_tpu import tune
from beforeholiday_tpu.tune import space as space_mod
from beforeholiday_tpu.utils.logging import reset_warn_once

pytestmark = pytest.mark.autotune

MiB = 1 << 20


class _Capture(logging.Handler):
    """The repo loggers set propagate=False (utils/logging.py), so caplog
    never sees warn_once records — capture with a direct handler."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records = []

    def emit(self, record):
        self.records.append(record)


@contextlib.contextmanager
def _captured_warnings():
    lg = logging.getLogger("beforeholiday_tpu")
    h = _Capture()
    lg.addHandler(h)
    try:
        yield h
    finally:
        lg.removeHandler(h)


def _small_space():
    return tune.KnobSpace([
        tune.Knob("a", ("x", "y", "z"), "x", layer="test"),
        tune.Knob("b", (False, True), False, layer="test"),
    ])


# ===================================================================== space
class TestKnobSpace:
    def test_defaults_and_names(self):
        sp = _small_space()
        assert sp.defaults() == {"a": "x", "b": False}
        assert sp.names() == ["a", "b"]
        assert "a" in sp and "missing" not in sp
        assert len(sp) == 2

    def test_default_must_be_legal(self):
        with pytest.raises(ValueError, match="not among"):
            tune.Knob("k", (1, 2), 3, layer="test")

    def test_duplicate_knob_rejected(self):
        k = tune.Knob("k", (1, 2), 1, layer="test")
        with pytest.raises(ValueError, match="duplicate"):
            tune.KnobSpace([k, k])

    def test_violations_flag_unknown_and_illegal(self):
        sp = _small_space()
        bad = sp.violations({"a": "w", "nope": 1})
        assert any("not among legal values" in v for v in bad)
        assert any("unknown knob" in v for v in bad)
        with pytest.raises(tune.KnobConstraintError):
            sp.validate({"a": "w"})
        assert sp.is_legal({"a": "y", "b": True})

    def test_requires_constraint_bucket_bytes_dcn(self):
        sp = tune.shipped_space()
        ctx = {"two_level": True}
        # active DCN bucket without hierarchical=True is illegal
        assert not sp.is_legal({"bucket_bytes_dcn": 4 * MiB}, ctx)
        assert sp.is_legal(
            {"bucket_bytes_dcn": 4 * MiB, "hierarchical": True}, ctx
        )

    def test_requires_context_collective_matmul(self):
        sp = tune.shipped_space()
        cfg = {"collective_matmul": True}
        assert not sp.is_legal(cfg)
        assert not sp.is_legal(cfg, {"sequence_parallel": False})
        assert sp.is_legal(cfg, {"sequence_parallel": True})

    def test_unknown_requires_target_rejected_at_construction(self):
        with pytest.raises(ValueError, match="unknown knob"):
            tune.KnobSpace([
                tune.Knob("k", (False, True), False, layer="t",
                          requires=(("ghost", True),)),
            ])

    def test_sanitize_drops_illegal_and_dependents_to_fixpoint(self):
        sp = tune.shipped_space()
        # a manifest entry recorded on a two-level mesh, resolved on a flat
        # one: hierarchical reverts (missing context), and THEN
        # bucket_bytes_dcn loses its footing and reverts too
        clean, dropped = sp.sanitize(
            {"hierarchical": True, "bucket_bytes_dcn": 4 * MiB,
             "compress": True},
            context={},
        )
        assert clean["hierarchical"] is False
        assert clean["bucket_bytes_dcn"] is None
        assert clean["compress"] is True  # unconstrained knob survives
        assert "hierarchical" in dropped and "bucket_bytes_dcn" in dropped
        assert not sp.violations(clean, {})

    def test_sanitize_base_restricts_to_owned_knobs(self):
        sp = tune.shipped_space()
        clean, dropped = sp.sanitize(
            {"bucket_bytes": 4 * MiB, "compress": True, "prefetch": 2},
            base={"bucket_bytes": None, "compress": False},
        )
        assert clean == {"bucket_bytes": 4 * MiB, "compress": True}
        assert "prefetch" in dropped  # not owned by this consumer

    def test_sanitize_drops_out_of_range_value(self):
        sp = _small_space()
        clean, dropped = sp.sanitize({"a": "w", "b": True})
        assert clean == {"a": "x", "b": True}
        assert dropped == ["a"]

    def test_single_knob_configs_respect_context(self):
        sp = tune.shipped_space()
        flat = sp.single_knob_configs()
        names = {n for n, _, _ in flat}
        # context-gated knobs stay out without their context...
        assert "collective_matmul" not in names
        assert "hierarchical" not in names
        # ...and every emitted config is legal
        for _, _, cfg in flat:
            assert sp.is_legal(cfg)
        rich = sp.single_knob_configs(
            {"sequence_parallel": True, "two_level": True}
        )
        rich_names = {n for n, _, _ in rich}
        assert "collective_matmul" in rich_names
        assert "hierarchical" in rich_names

    def test_subset(self):
        sp = tune.shipped_space()
        sub = sp.subset(["compress", "bucket_bytes"])
        assert sub.names() == ["compress", "bucket_bytes"]
        with pytest.raises(KeyError):
            sp.subset(["ghost"])
        # a subset that strands a requires target must fail loudly
        with pytest.raises(ValueError, match="unknown knob"):
            sp.subset(["bucket_bytes_dcn"])

    def test_unset_sentinel(self):
        assert not tune.UNSET
        assert repr(tune.UNSET) == "UNSET"
        assert space_mod._Unset() is tune.UNSET  # singleton


# ================================================================= signature
class TestSignature:
    def test_pytree_key_stable_and_shape_sensitive(self):
        p1 = {"w": jnp.zeros((4, 8)), "b": jnp.zeros((8,))}
        p2 = {"w": jnp.zeros((4, 8)), "b": jnp.zeros((8,))}
        p3 = {"w": jnp.zeros((4, 16)), "b": jnp.zeros((16,))}
        k1 = tune.tuning_key(p1)
        k2 = tune.tuning_key(p2)
        k3 = tune.tuning_key(p3)
        assert k1 == k2 and k1.digest == k2.digest
        assert k1.digest != k3.digest

    def test_callable_key_uses_abstract_signature(self):
        calls = []

        def f(x):
            calls.append(1)
            return x @ x.T

        x = jnp.zeros((3, 5))
        k1 = tune.tuning_key(f, (x,))
        k2 = tune.tuning_key(f, (jnp.ones((3, 5)),))  # same shapes
        assert k1.digest == k2.digest
        assert "out:" in k1.model  # eval_shape captured the output too

    def test_mesh_and_chip_move_the_digest(self):
        p = {"w": jnp.zeros((2, 2))}
        base = tune.tuning_key(p, mesh={"data": 1})
        other_mesh = tune.tuning_key(p, mesh={"data": 8})
        other_chip = tune.tuning_key(
            p, mesh={"data": 1}, chip="TPU v5 lite"
        )
        assert base.digest != other_mesh.digest
        assert base.digest != other_chip.digest
        d = base.describe()
        assert d["digest"] == base.digest
        assert ("data", 1) in base.mesh

    def test_digest_is_short_hex(self):
        k = tune.tuning_key({"w": jnp.zeros((1,))})
        assert len(k.digest) == 16
        int(k.digest, 16)  # hex


# ================================================================== manifest
class TestManifest:
    def test_roundtrip_and_coercion(self, tmp_path):
        path = tmp_path / "m.json"
        key = tune.tuning_key({"w": jnp.zeros((2,))})
        man = tune.TuningManifest(str(path))
        man.store(key, {"compress": True}, cost_s=0.25, trials=5)
        fresh = tune.TuningManifest(str(path))
        hit = fresh.lookup(key)
        assert hit["config"] == {"compress": True}
        assert isinstance(hit["best_cost_s"], float)
        assert isinstance(hit["trials"], int)
        assert hit["signature"]["digest"] == key.digest
        doc = json.loads(path.read_text())
        assert doc["schema"] == tune.SCHEMA

    def test_corrupt_and_wrong_schema_degrade_to_empty(self, tmp_path):
        path = tmp_path / "m.json"
        path.write_text("{ not json")
        assert tune.TuningManifest(str(path)).entries() == {}
        path.write_text(json.dumps({"schema": "other-v9", "entries": {
            "d": {"config": {"a": 1}},
        }}))
        assert tune.TuningManifest(str(path)).entries() == {}
        # missing file too
        assert tune.TuningManifest(str(tmp_path / "no.json")).entries() == {}

    def test_atomic_write_leaves_no_temp_droppings(self, tmp_path):
        path = tmp_path / "m.json"
        man = tune.TuningManifest(str(path))
        man.store("digest0", {"a": 1})
        leftovers = [p for p in os.listdir(tmp_path) if p != "m.json"]
        assert leftovers == []

    def test_lookup_returns_copy(self, tmp_path):
        man = tune.TuningManifest(str(tmp_path / "m.json"))
        man.store("d", {"a": 1})
        man.lookup("d")["config"]["a"] = 999
        assert man.lookup("d")["config"]["a"] == 1

    def test_bad_key_type(self, tmp_path):
        man = tune.TuningManifest(str(tmp_path / "m.json"))
        with pytest.raises(TypeError):
            man.lookup(42)

    def test_env_var_default_path(self, monkeypatch, tmp_path):
        monkeypatch.setenv(
            "BEFOREHOLIDAY_TUNE_MANIFEST", str(tmp_path / "env.json")
        )
        assert tune.default_path() == str(tmp_path / "env.json")
        assert tune.TuningManifest().path == str(tmp_path / "env.json")


# ==================================================================== search
class _CostedTrials:
    """Synthetic trial_fn: per-step cost looked up by config, linear in
    steps, with call accounting."""

    def __init__(self, costs):
        self.costs = costs  # {(sorted items): per-step seconds}
        self.calls = []

    def __call__(self, config, steps, entry):
        self.calls.append((dict(config), steps, entry))
        return self.costs[tuple(sorted(config.items()))] * steps


def _costs(space, best_cfg, best=0.01, other=0.05):
    out = {}
    for cfg in [space.defaults()] + [
        c for _, _, c in space.single_knob_configs()
    ]:
        k = tuple(sorted(cfg.items()))
        out[k] = best if cfg == best_cfg else other
    return out


class TestSearch:
    def test_finds_best_and_second_run_is_cache_hit_zero_trials(
        self, tmp_path
    ):
        sp = _small_space()
        winner = {"a": "z", "b": False}
        trials = _CostedTrials(_costs(sp, winner))
        key = tune.tuning_key({"w": jnp.zeros((3,))})
        manifest = str(tmp_path / "m.json")
        res = tune.tune(trials, sp, key, manifest=manifest,
                        max_trials=8, steps_per_trial=2, iters=1)
        assert res.config == winner
        assert not res.cache_hit
        assert 1 <= res.trials <= 8
        assert res.cost_s == pytest.approx(0.01)
        n_calls = len(trials.calls)

        # THE PIN: same signature again → manifest hit, ZERO trials, and
        # the trial_fn is never invoked
        rerun = tune.tune(trials, sp, key, manifest=manifest,
                          max_trials=8, steps_per_trial=2, iters=1)
        assert rerun.cache_hit
        assert rerun.trials == 0
        assert rerun.records == []
        assert rerun.config == winner
        assert len(trials.calls) == n_calls

    def test_max_trials_bounds_invocations(self):
        sp = _small_space()
        trials = _CostedTrials(_costs(sp, sp.defaults()))
        res = tune.tune(trials, sp, max_trials=2, steps_per_trial=1, iters=1)
        assert res.trials == 2
        assert len(trials.calls) == 2
        with pytest.raises(ValueError, match="max_trials"):
            tune.tune(trials, sp, max_trials=0)

    def test_trial_entries_are_distinct_and_prefixed(self):
        sp = _small_space()
        trials = _CostedTrials(_costs(sp, sp.defaults()))
        res = tune.tune(trials, sp, max_trials=4, steps_per_trial=1, iters=1)
        entries = [r.entry for r in res.records]
        assert len(set(entries)) == len(entries)
        assert all(e.startswith("tune.trial") for e in entries)

    def test_halving_promotes_survivors_to_longer_horizons(self):
        sp = _small_space()
        winner = {"a": "y", "b": False}
        trials = _CostedTrials(_costs(sp, winner))
        tune.tune(trials, sp, max_trials=16, steps_per_trial=2, iters=1,
                  eta=2)
        steps_seen = sorted({s for _, s, _ in trials.calls})
        assert steps_seen[0] == 2
        assert steps_seen[-1] > 2  # at least one promotion rung ran

    def test_illegal_candidate_rejected_upfront(self):
        sp = _small_space()
        trials = _CostedTrials({})
        with pytest.raises(tune.KnobConstraintError):
            tune.tune(trials, sp, candidates=[{"a": "bogus"}])
        assert trials.calls == []

    def test_memory_budget_prunes_hungry_config(self, monkeypatch):
        from beforeholiday_tpu.tune import search as search_mod

        sp = _small_space()
        hungry = {"a": "y", "b": False}
        # the hungry config is also the fastest — only the memory ledger
        # can veto it
        trials = _CostedTrials(_costs(sp, hungry, best=0.01, other=0.02))
        entry_cfg = {}

        def spying(config, steps, entry):
            entry_cfg[entry] = dict(config)
            return trials(config, steps, entry)

        monkeypatch.setattr(
            search_mod, "_entry_peak_temp_bytes",
            lambda entry: 10_000 if entry_cfg[entry] == hungry else 100,
        )
        res = tune.tune(spying, sp, max_trials=8, steps_per_trial=1,
                        iters=1, memory_budget_bytes=1_000)
        assert res.config != hungry
        reasons = {r.pruned for r in res.records if r.pruned}
        assert reasons == {"peak_temp_bytes_over_budget"}
        pruned = [r for r in res.records if r.pruned]
        assert all(r.cost_s is None for r in pruned)
        assert all(
            r.evidence["peak_temp_bytes"] == 10_000 for r in pruned
        )

    def test_compute_bound_and_slower_is_pruned(self, monkeypatch):
        from beforeholiday_tpu.tune import search as search_mod

        sp = _small_space()
        fast = sp.defaults()  # runs first, sets the incumbent
        trials = _CostedTrials(_costs(sp, fast, best=0.01, other=0.5))
        monkeypatch.setattr(
            search_mod, "_entry_bound", lambda entry, chip=None: "compute"
        )
        res = tune.tune(trials, sp, max_trials=8, steps_per_trial=1, iters=2)
        assert res.config == fast
        slow_recs = [r for r in res.records if r.config != fast]
        assert slow_recs
        assert all(
            r.pruned == "compute_bound_and_slower" for r in slow_recs
        )
        # pruning cut the trial short: slow configs ran 1 iter, not 2
        slow_keys = {tuple(sorted(r.config.items())) for r in slow_recs}
        from collections import Counter

        per_cfg = Counter(
            tuple(sorted(c.items())) for c, _, _ in trials.calls
        )
        assert all(per_cfg[k] == len(
            [r for r in slow_recs
             if tuple(sorted(r.config.items())) == k]
        ) for k in slow_keys)

    def test_memory_bound_config_survives_being_slower(self, monkeypatch):
        from beforeholiday_tpu.tune import search as search_mod

        sp = _small_space()
        fast = sp.defaults()
        trials = _CostedTrials(_costs(sp, fast, best=0.01, other=0.05))
        monkeypatch.setattr(
            search_mod, "_entry_bound", lambda entry, chip=None: "memory"
        )
        res = tune.tune(trials, sp, max_trials=8, steps_per_trial=1, iters=1)
        # slower but memory-bound: overlap might still save it at a longer
        # horizon, so nothing is pruned
        assert not any(r.pruned for r in res.records)
        assert res.config == fast

    def test_all_pruned_falls_back_to_first_candidate_and_no_store(
        self, monkeypatch, tmp_path
    ):
        from beforeholiday_tpu.tune import search as search_mod

        sp = _small_space()
        trials = _CostedTrials(_costs(sp, sp.defaults()))
        monkeypatch.setattr(
            search_mod, "_entry_peak_temp_bytes", lambda entry: 10_000
        )
        key = tune.tuning_key({"w": jnp.zeros((2,))})
        manifest = str(tmp_path / "m.json")
        res = tune.tune(trials, sp, key, manifest=manifest,
                        max_trials=4, steps_per_trial=1, iters=1,
                        memory_budget_bytes=1)
        assert res.cost_s is None
        assert res.config == sp.defaults()
        # an all-pruned search must NOT poison the manifest
        assert tune.TuningManifest(manifest).lookup(key) is None

    def test_real_wall_time_lands_in_the_roofline_ledger(self):
        # no monkeypatching: a real (tiny) trial_fn, real ledger entries
        from beforeholiday_tpu.monitor import roofline_summary

        sp = tune.KnobSpace([
            tune.Knob("k", (False, True), False, layer="test"),
        ])

        def trial_fn(config, steps, entry):
            return 1e-3 * steps

        res = tune.tune(trial_fn, sp, max_trials=2, steps_per_trial=2,
                        iters=1)
        assert res.trials == 2
        entries = {row["entry"] for row in roofline_summary()}
        assert any(e.startswith("tune.trial") for e in entries)


# ================================================================= isolation
class TestTrialIsolation:
    def test_trial_scope_resets_only_its_own_entry(self):
        from beforeholiday_tpu.monitor.compile import (
            compile_counts,
            reset_compile_counts,
            track_compiles,
        )

        reset_compile_counts()
        try:
            @track_compiles("tune.trial0")
            def f(x):
                return x + 1

            @track_compiles("other.entry")
            def g(x):
                return x * 2

            with tune.trial_scope("tune.trial0"):
                f(jnp.zeros((2,)))
                f(jnp.zeros((3,)))
            g(jnp.zeros((2,)))
            counts = compile_counts()
            assert "tune.trial0" not in counts  # scoped reset on exit
            assert counts["other.entry"]["signatures"] == 1  # untouched
        finally:
            reset_compile_counts()

    def test_repeated_trial_lowers_trip_no_strict_gate(self):
        """A strict bucket-gated entry lowered afresh each trial: without
        the scoped reset the second trial's new signature would be the
        (N+1)-th and raise BucketGateError — with it, every trial starts
        from a clean budget."""
        from beforeholiday_tpu.monitor.compile import (
            reset_compile_counts,
            track_compiles,
        )

        reset_compile_counts()
        try:
            entry = "tune.trial.gate"
            for trial, dim in enumerate((2, 3, 4)):
                with tune.trial_scope(entry):
                    @track_compiles(entry, strict=True, max_signatures=1)
                    def step(x):
                        return x.sum()

                    step(jnp.zeros((dim,)))  # would raise on trial > 0
        finally:
            reset_compile_counts()

    def test_repeated_trial_lowers_fire_no_spurious_warn_once(self, caplog):
        from beforeholiday_tpu.monitor.compile import (
            reset_compile_counts,
            track_compiles,
        )

        reset_compile_counts()
        try:
            entry = "tune.trial.warn"
            with caplog.at_level(logging.WARNING):
                for dim in (2, 3, 4):
                    with tune.trial_scope(entry):
                        @track_compiles(entry)
                        def step(x):
                            return x.sum()

                        step(jnp.zeros((dim,)))
            assert not [
                r for r in caplog.records if "recompile sentinel" in r.message
            ]
        finally:
            reset_compile_counts()

    def test_trial_scope_clears_probe_cache_on_entry_and_exit(
        self, monkeypatch
    ):
        import beforeholiday_tpu.guard as guard

        calls = []
        monkeypatch.setattr(
            guard, "clear_probe_cache",
            lambda op_name=None: calls.append(op_name),
        )
        with tune.trial_scope("tune.trial9"):
            assert calls == [None]  # fresh cache going in
        assert calls == [None, None]  # and cleared coming out


# ================================================================ resolution
class TestResolution:
    def test_untuned_is_pure_overlay(self):
        cfg, source = tune.resolve_knobs(
            "site", {"a": 1, "b": 2}, {"a": 5, "b": tune.UNSET},
        )
        assert cfg == {"a": 5, "b": 2}
        assert source == "explicit"

    def test_tuned_hit_then_explicit_wins(self, tmp_path):
        manifest = tune.TuningManifest(str(tmp_path / "m.json"))
        key = tune.tuning_key({"w": jnp.zeros((2,))})
        manifest.store(key, {"compress": True, "bucket_bytes": 4 * MiB})
        defaults = {"compress": False, "bucket_bytes": None}
        cfg, source = tune.resolve_knobs(
            "site", defaults, {"compress": tune.UNSET,
                               "bucket_bytes": tune.UNSET},
            tuned=True, key=key, manifest=manifest,
        )
        assert source == "manifest"
        assert cfg == {"compress": True, "bucket_bytes": 4 * MiB}
        # explicit compress=False restates the default — it STILL beats
        # the manifest
        cfg, source = tune.resolve_knobs(
            "site", defaults, {"compress": False,
                               "bucket_bytes": tune.UNSET},
            tuned=True, key=key, manifest=manifest,
        )
        assert cfg == {"compress": False, "bucket_bytes": 4 * MiB}

    def test_tuned_miss_warns_once_per_site(self, tmp_path):
        reset_warn_once(("tune.resolve", "site-a"))
        reset_warn_once(("tune.resolve", "site-b"))
        manifest = str(tmp_path / "empty.json")
        key = tune.tuning_key({"w": jnp.zeros((2,))})
        with _captured_warnings() as h:
            for _ in range(3):
                cfg, source = tune.resolve_knobs(
                    "site-a", {"compress": False}, tuned=True, key=key,
                    manifest=manifest,
                )
            tune.resolve_knobs(
                "site-b", {"compress": False}, tuned=True, key=key,
                manifest=manifest,
            )
        assert cfg == {"compress": False}
        assert source == "defaults"
        misses = [r for r in h.records
                  if "no manifest entry" in r.getMessage()]
        assert len(misses) == 2  # one per site, not one per call
        assert any("site-a" in r.getMessage() for r in misses)
        assert any("site-b" in r.getMessage() for r in misses)

    def test_tuned_hit_sanitizes_stale_entry(self, tmp_path):
        manifest = tune.TuningManifest(str(tmp_path / "m.json"))
        key = tune.tuning_key({"w": jnp.zeros((2,))})
        manifest.store(key, {"hierarchical": True, "compress": True})
        cfg, source = tune.resolve_knobs(
            "ddp", {"hierarchical": False, "compress": False},
            tuned=True, key=key, manifest=manifest,
            context={"two_level": False},
        )
        assert source == "manifest"
        assert cfg == {"hierarchical": False, "compress": True}


class TestTunedConstructors:
    def _store(self, tmp_path, key, config):
        manifest = tune.TuningManifest(str(tmp_path / "m.json"))
        manifest.store(key, config)
        return manifest

    def test_amp_initialize_resolves_opt_level(self, tmp_path):
        from beforeholiday_tpu import amp
        from beforeholiday_tpu.optimizers import FusedAdam

        params = {"w": jnp.zeros((4, 4), jnp.float32)}
        key = tune.tuning_key(params)
        manifest = self._store(tmp_path, key, {"opt_level": "O6"})
        reset_warn_once()
        m = amp.initialize(
            lambda p, x: x @ p["w"], params, FusedAdam(lr=1e-3), None,
            tuned=True, tuning_key=key, tuning_manifest=manifest,
        )
        assert m.policy.opt_level == "O6"
        # explicit opt_level wins over the manifest's O6
        m = amp.initialize(
            lambda p, x: x @ p["w"], params, FusedAdam(lr=1e-3), "O5",
            tuned=True, tuning_key=key, tuning_manifest=manifest,
        )
        assert m.policy.opt_level == "O5"

    def test_amp_initialize_miss_defaults_to_o5(self, tmp_path):
        from beforeholiday_tpu import amp
        from beforeholiday_tpu.optimizers import FusedAdam

        reset_warn_once(("tune.resolve", "amp.initialize"))
        params = {"w": jnp.zeros((4, 4), jnp.float32)}
        with _captured_warnings() as h:
            m = amp.initialize(
                lambda p, x: x @ p["w"], params, FusedAdam(lr=1e-3),
                tuned=True, tuning_manifest=str(tmp_path / "empty.json"),
            )
        assert m.policy.opt_level == "O5"
        assert [r for r in h.records
                if "no manifest entry" in r.getMessage()]

    def test_ddp_resolves_and_explicit_wins(self, tmp_path):
        from beforeholiday_tpu.parallel import DistributedDataParallel

        key = tune.tuning_key({"w": jnp.zeros((2,))})
        manifest = self._store(
            tmp_path, key,
            {"bucket_bytes": 4 * MiB, "compress": True,
             "overlap_backward": True},
        )
        ddp = DistributedDataParallel(
            tuned=True, tuning_key=key, tuning_manifest=manifest,
        )
        assert ddp.bucket_bytes == 4 * MiB
        assert ddp.compress is True
        assert ddp.overlap_backward is True
        ddp = DistributedDataParallel(
            compress=False,
            tuned=True, tuning_key=key, tuning_manifest=manifest,
        )
        assert ddp.compress is False  # explicit beats manifest
        assert ddp.bucket_bytes == 4 * MiB  # omitted knobs still tuned

    def test_ddp_stale_hierarchical_entry_degrades_not_raises(
        self, tmp_path
    ):
        from beforeholiday_tpu.parallel import DistributedDataParallel

        key = tune.tuning_key({"w": jnp.zeros((2,))})
        manifest = self._store(
            tmp_path, key, {"hierarchical": True, "compress": True},
        )
        # flat data axis: hierarchical=True from the manifest must revert to
        # the default, not detonate the constructor's axis check
        ddp = DistributedDataParallel(
            tuned=True, tuning_key=key, tuning_manifest=manifest,
        )
        assert ddp.hierarchical is False
        assert ddp.compress is True

    def test_zero2_and_zero3_resolve_their_own_knobs(self, tmp_path):
        from beforeholiday_tpu.optimizers import (
            DistributedFusedAdam,
            ZeRO3FusedAdam,
        )

        key = tune.tuning_key({"w": jnp.zeros((2,))})
        manifest = self._store(
            tmp_path, key, {"bucket_bytes": 4 * MiB, "prefetch": 2},
        )
        z2 = DistributedFusedAdam(
            lr=1e-2, impl="jnp",
            tuned=True, tuning_key=key, tuning_manifest=manifest,
        )
        assert z2.bucket_bytes == 4 * MiB
        z3 = ZeRO3FusedAdam(
            lr=1e-2, impl="jnp",
            tuned=True, tuning_key=key, tuning_manifest=manifest,
        )
        assert z3.bucket_bytes == 4 * MiB
        assert z3.prefetch == 2  # zero3-only knob rode the same entry
        z3 = ZeRO3FusedAdam(
            lr=1e-2, impl="jnp", prefetch=0,
            tuned=True, tuning_key=key, tuning_manifest=manifest,
        )
        assert z3.prefetch == 0  # explicit beats manifest

    def test_untuned_constructors_unchanged(self):
        from beforeholiday_tpu.optimizers import ZeRO3FusedAdam
        from beforeholiday_tpu.parallel import DistributedDataParallel

        ddp = DistributedDataParallel()
        assert ddp.bucket_bytes is None
        assert ddp.compress is False
        assert ddp.hierarchical is False
        z3 = ZeRO3FusedAdam(lr=1e-2, impl="jnp")
        assert z3.prefetch == 1
