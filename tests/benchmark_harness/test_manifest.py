"""BENCHMARK.json keeps to the contract, and a cell, a configuration, a
traffic mix and a metric are each added as files: no code names any of them."""
import json
import os
import re

import pytest

from benchmark import manifest

from bh_tiny import REPO, make_root


@pytest.fixture(scope="module")
def m():
    return manifest.load_manifest(REPO)


def test_manifest_keeps_the_contract(m):
    manifest.check_manifest(m, REPO)
    assert len(json.dumps(m)) < 64 * 1024


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_and_units_hold_only_allowed_characters(m, kind):
    for x in m[kind]:
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", x["name"]), x
        if "unit" in x:
            assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", x["unit"]), x
        for key in ("config", "traffic"):
            if key in x:
                assert manifest.NAME_RE.match(x[key])


def test_every_file_under_paths_has_a_plain_name(m):
    for p in m["paths"]:
        for d, _, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_every_cell_has_mfu_and_a_roofline_moving_its_metric(m):
    for w in m["workloads"]:
        mine = [x for x in m["per_layer"] if w["name"] in x.get("workloads", [w["name"]])]
        mfu = {x["moves"] for x in mine if "mfu" in x["name"]}
        roof = {x["moves"] for x in mine if x["name"].split(".")[0].endswith("_roofline")}
        assert mfu and roof and mfu & roof, w["name"]


@pytest.mark.parametrize("breach", [
    {"run_seconds": 52}, {"run_seconds": 10.5},
    {"configs": [{"name": "a b", "source": "s", "file": "benchmark/x", "reduced": [], "why": "w"}]},
])
def test_breaches_are_refused(m, breach):
    with pytest.raises(manifest.ManifestError):
        manifest.check_manifest({**m, **breach})


def test_reduced_may_not_name_a_width(m):
    bad = json.loads(json.dumps(m))
    bad["configs"][0]["reduced"] = ["hidden_size"]
    with pytest.raises(manifest.ManifestError, match="width"):
        manifest.check_manifest(bad)


def test_a_cell_and_a_metric_added_as_files_are_found(tmp_path):
    root = make_root(tmp_path)
    # one more per-layer metric, as a file and one manifest entry
    with open(os.path.join(root, "benchmark", "metrics", "admissions.tiny.json"), "w") as f:
        json.dump({"reader": "delta_ratio",
                   "num": {"registry": "llm_admissions_total"}, "den": None}, f)
    mm = manifest.load_manifest(root)
    mm["per_layer"].append({"name": "admissions.tiny", "unit": "count",
                            "better": "higher", "source": "program_counter",
                            "layer": "scheduler and admission",
                            "moves": "ttft_p95_ms", "workloads": ["tiny-chat"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(mm, f)
    manifest.check_manifest(manifest.load_manifest(root), root)
    cell = manifest.load_cell(root, "tiny-chat")
    assert cell["config"]["hidden_size"] == 64
    assert cell["traffic"]["loop"] == "open"
    names = [e["name"] for e, _ in cell["per_layer"]]
    assert "admissions.tiny" in names and "mfu.chat" in names
    obs = {"before": {"registry": {"llm_admissions_total": {"value": 3.0}}, "stats": {}},
           "after": {"registry": {"llm_admissions_total": {"value": 10.0}}, "stats": {}}}
    got = manifest.read_metrics([p for p in cell["per_layer"]
                                 if p[0]["name"] == "admissions.tiny"], obs)
    assert got == {"admissions.tiny": {"value": 7.0, "unit": "count"}}


def test_unknown_cell_and_unknown_chip_are_errors(tmp_path):
    with pytest.raises(manifest.ManifestError, match="no cell"):
        manifest.load_cell(REPO, "nonesuch")
    peaks = manifest.load_cell(REPO, manifest.load_manifest(REPO)["workloads"][0]["name"])["peaks"]
    assert manifest.peaks_for(peaks, "TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(manifest.ManifestError, match="no device_kind"):
        manifest.peaks_for(peaks, "TPU v9 imaginary")
