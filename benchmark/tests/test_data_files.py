"""Every data file loads, names only what exists, and agrees with BENCHMARK.json."""

import glob
import importlib
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _files(kind, fixtures=False):
    base = os.path.join(BENCH, "tests", "fixtures") if fixtures else BENCH
    return sorted(glob.glob(os.path.join(base, kind, "*.json")))


def _load(path):
    with open(path) as f:
        return json.load(f)


def _listed(kind):
    """The files of ``kind`` that BENCHMARK.json lists; one kept for a later PR
    says ``"listed": false`` and why."""
    out = {}
    for path in _files(kind):
        data = _load(path)
        if data.get("listed", True):
            out[data["name"]] = data
        else:
            assert len(data["not_listed_because"]) > 20
    return out


@pytest.fixture(scope="module")
def manifest():
    return _load(os.path.join(REPO, "BENCHMARK.json"))


ALL = [p for k in ("configs", "workloads", "layer_metrics") for p in _files(k)] + \
      [p for k in ("configs", "workloads") for p in _files(k, fixtures=True)]


@pytest.mark.parametrize("path", ALL, ids=lambda p: os.path.relpath(p, BENCH))
def test_file_loads_and_is_named_for_what_it_holds(path):
    data = _load(path)
    assert data["name"] == os.path.basename(path)[:-5]
    assert NAME.match(data["name"])
    assert re.match(r"^[A-Za-z0-9_.\-/]+$", os.path.relpath(path, REPO))


@pytest.mark.parametrize("path", _files("configs") + _files("configs", True), ids=os.path.basename)
def test_config_names_a_family_with_its_reference(path):
    cfg = _load(path)
    family = importlib.import_module(f"benchmark.families.{cfg['family']}")
    for attr in ("weights", "batch", "Program", "model_flops_per_item", "reference",
                 "reference_optimizer", "ITEMS_PER_ROW", "GUARDED_OPS"):
        assert hasattr(family, attr), attr
    assert hasattr(family.reference, "loss")
    assert len(cfg["source"]) <= 200 and isinstance(cfg["reduced"], list)
    assert NAME.match(cfg["throughput_metric"]["name"]) and UNIT.match(cfg["throughput_metric"]["unit"])


@pytest.mark.parametrize("path", _files("workloads") + _files("workloads", True), ids=os.path.basename)
def test_cell_names_a_known_config_and_layout(path):
    from benchmark import run

    cell = _load(path)
    run.load("configs", cell["config"])
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] in (1, 4) and cell["layout"] in ("single", "dp")
    assert (cell["layout"] == "dp") == (cell["chips"] > 1)
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert set(cell["limits"]) == {"loss_gap", "first_grad_norm_gap", "update_norm_gap"}
    assert bool(cell.get("rehearsal")) == ("fixtures" in path)


@pytest.mark.parametrize("path", _files("layer_metrics"), ids=os.path.basename)
def test_layer_metric_names_a_reduction(path, manifest):
    spec = _load(path)
    reduction = importlib.import_module(f"benchmark.reductions.{spec['reduction']}")
    assert callable(reduction.reduce)
    assert UNIT.match(spec["unit"]) and spec["better"] in ("lower", "higher")
    entry = [m for m in manifest["per_layer"] if m["name"] == spec["name"]]
    assert len(entry) == (1 if spec.get("listed", True) else 0)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert not entry or entry[0][key] == spec[key], key
    if entry:
        assert spec["moves"] in {m["name"] for m in manifest["end_to_end"]}


def test_manifest_agrees_with_the_files(manifest):
    cells = _listed("workloads")
    assert {w["name"] for w in manifest["workloads"]} == set(cells)
    for w in manifest["workloads"]:
        for key in ("config", "traffic", "chips", "why"):
            assert w[key] == cells[w["name"]][key], (w["name"], key)
    for c in manifest["configs"]:
        cfg = _load(os.path.join(REPO, c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    assert {c["name"] for c in manifest["configs"]} == set(_listed("configs"))
    assert {m["name"] for m in manifest["per_layer"]} == set(_listed("layer_metrics"))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    assert manifest["paths"] == ["benchmark"] and manifest["command"][-1] == "benchmark/run.py"
    throughput = {c["throughput_metric"]["name"] for c in _listed("configs").values()}
    assert throughput <= {m["name"] for m in manifest["end_to_end"]}


def test_peaks_name_their_source_and_refuse_an_unknown_kind():
    from benchmark import run

    assert run.peak_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in _load(os.path.join(BENCH, "peaks.json"))["source"]
    with pytest.raises(KeyError):
        run.peak_of("TPU v9 imaginary")
